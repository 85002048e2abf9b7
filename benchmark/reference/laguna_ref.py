"""Plain Laguna: a full forward pass over a whole sequence in jax.numpy,
float32 at ``highest``.

Written from the equations of ISSUE 37 / the source's config
(poolside/Laguna-XS.2, ``model_type`` ``laguna``): pre-norm RMSNorm
blocks, ``h = x + Attn_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``.
``Attn_l`` with ``H_l`` query heads (48 on a full layer, 64 on a sliding
one) of 128 over 8 key/value heads (query head ``j`` reads key/value head
``j // (H_l / 8)``): ``q = x W_q``, ``k = x W_k``, ``v = x W_v``; ``q``
and ``k`` rotated at the token's position in halves ``(x[i], x[i + r/2])``
over the first ``r`` values of a head (a full layer: ``r`` = 64, the YaRN
frequencies, cos and sin times ``attention_factor``, the other 64 values
untouched; a sliding layer: ``r`` = 128, ``theta^(-2i/128)``); scores
``q . k 128^-1/2``, causal, and on a sliding layer the keys at ``p - 511
.. p`` only (a mask over the whole sequence); softmax; each head's output
times ``sigmoid(x W_g)`` (one value a head); ``W_o``. Sparse layer:
sigmoid scores in float32, the ``top_k`` largest of ``s + b``, weights
``s_i / sum(chosen s) x scaling``, a loop over the experts with dense
masks, plus the shared expert. Layer 0: a dense SwiGLU. No cache, no
batching, no kernel, and no code shared with ``ray_tpu/models``,
``ray_tpu/ops`` or ``ray_tpu/parallel/moe.py``.

It reads the weights as the program stores them (bfloat16, the program's
names: that is the whole of what the two sides share) and lifts them to
float32 a layer at a time, because two copies do not fit the chip.
Attention goes a head and ``Q_BLOCK`` queries at a time and a wide SwiGLU
``FF_BLOCK`` columns at a time, so that a sequence of 9,216 tokens fits
beside the program's weights and pools.

``sizes`` (``sizes_of``) is a hashable tuple of pairs. Controls: ``quant``
rounds both operands of every matrix product (``fp8``); ``whole_context``
lets the sliding layers see every earlier position.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
FF_BLOCK = 2048
Q_BLOCK = 1024
FULL, SLIDING = "full_attention", "sliding_attention"


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> 448)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _pairs(x):
    if isinstance(x, dict):
        return tuple(sorted((k, _pairs(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_pairs(v) for v in x)
    return x


def sizes_of(cfg) -> tuple:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    return tuple(sorted({
        "layer_types": tuple(get("layer_types")),
        "mlp_layer_types": tuple(get("mlp_layer_types")),
        "heads": tuple(get("num_attention_heads_per_layer")),
        "kv_heads": get("num_key_value_heads"), "head_dim": get("head_dim"),
        "window": get("sliding_window"), "gating": bool(get("gating")),
        "rope": _pairs(dict(get("rope_parameters"))),
        "top_k": get("num_experts_per_tok"),
        "scaling": get("moe_routed_scaling_factor"),
        "eps": get("rms_norm_eps")}.items()))


# ------------------------------------------------------------- rotary

def rope_frequencies(dim, p):
    """The ``dim / 2`` angular frequencies (plain Python floats) of one
    layer type's ``rope_parameters`` entry ``p``. ``default``: ``f_i =
    theta^(-2i/dim)``. ``yarn``: ``f_i`` kept where the original context
    holds more than ``beta_fast`` turns, ``f_i / factor`` where it holds
    fewer than ``beta_slow``, a linear ramp over the pair indices between
    (the ends rounded outwards to whole indices)."""
    theta = float(p["rope_theta"])
    f = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if p.get("rope_type", "default") != "yarn":
        return f

    def index_of(turns):        # where the original context holds `turns`
        return dim * math.log(p["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(index_of(p["beta_fast"])), 0)
    high = min(math.ceil(index_of(p["beta_slow"])), dim - 1)
    out = []
    for i, fi in enumerate(f):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(fi * (1.0 - ramp) + fi / p["factor"] * ramp)
    return out


def rotate(x, freqs, scale=1.0):
    """x [S, ..., d] at positions 0 .. S-1: the first ``r = 2
    len(freqs)`` values of the last axis are turned in halves, ``(x[i],
    x[i + r/2])`` by the angle ``t f_i`` (cos and sin times ``scale``);
    the values from ``r`` on pass."""
    S, half = x.shape[0], len(freqs)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None, :]          # [S, r/2]
    shape = (S,) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = (jnp.cos(angle) * scale).reshape(shape), \
        (jnp.sin(angle) * scale).reshape(shape)
    a, b = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., 2 * half:]], axis=-1)


# -------------------------------------------------------------- layers

def _mm(quant):
    q_ = quant if quant is not None else (lambda t: t)

    def mm(a, b):
        return jnp.matmul(q_(a.astype(jnp.float32)),
                          q_(b.astype(jnp.float32)), precision=_HI)
    return mm


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _swiglu(mm, x, p):
    """``W_d (SiLU(W_g x) * W_u x)``, ``FF_BLOCK`` columns of the width
    at a time (a sum over blocks of the width: the same mathematics)."""
    d, ff = p["gate"].shape
    fb = FF_BLOCK if ff % FF_BLOCK == 0 else ff

    def cols(w):                # [d, ff] -> [ff / fb, d, fb]
        return w.reshape(d, ff // fb, fb).transpose(1, 0, 2)

    def block(y, w):
        gate, up, down = w
        return y + mm(_silu(mm(x, gate)) * mm(x, up), down), None
    y, _ = jax.lax.scan(block, jnp.zeros_like(x), (
        cols(p["gate"]), cols(p["up"]), p["down"].reshape(ff // fb, fb, d)))
    return y


def attention(p, x, z, mm, kind, H, window):
    """x [S, D] (normed) -> ([S, D], K rotated [S, kv_heads x d], V
    [S, kv_heads x d]: the rows a cache would hold). One query head and
    ``Q_BLOCK`` queries at a time, so that one [Q_BLOCK, S] of scores is
    held. ``window`` None: every earlier position is seen."""
    S = x.shape[0]
    Hkv, d = z["kv_heads"], z["head_dim"]
    G = H // Hkv
    rp = dict(dict(z["rope"])[kind])
    freqs = rope_frequencies(
        int(d * rp.get("partial_rotary_factor", 1.0)), rp)
    factor = float(rp.get("attention_factor", 1.0))
    k = rotate(mm(x, p["k_proj"]).reshape(S, Hkv, d), freqs, factor)
    v = mm(x, p["v_proj"]).reshape(S, Hkv, d)
    gate = jax.nn.sigmoid(mm(x, p["g_proj"])) if z["gating"] \
        else jnp.ones((S, H), jnp.float32)
    qb = Q_BLOCK if S % Q_BLOCK == 0 else S
    t = jnp.arange(S)

    def head(y, w):
        j, w_q, w_o = w             # [D, d], [d, D]
        q = rotate(mm(x, w_q), freqs, factor)
        k_h, v_h = k[:, j // G], v[:, j // G]

        def block(i):
            at = i * qb + jnp.arange(qb)
            s = mm(jax.lax.dynamic_slice_in_dim(q, i * qb, qb), k_h.T) \
                * d ** -0.5
            seen = t[None, :] <= at[:, None]
            if window is not None:
                seen = seen & (t[None, :] > at[:, None] - window)
            return mm(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1),
                      v_h)
        o = jax.lax.map(block, jnp.arange(S // qb)).reshape(S, d)
        return y + mm(o * gate[:, j][:, None], w_o), None

    y, _ = jax.lax.scan(head, jnp.zeros_like(x), (
        jnp.arange(H),
        p["q_proj"].reshape(-1, H, d).transpose(1, 0, 2),
        p["o_proj"].reshape(H, d, -1)))
    return y, k.reshape(S, Hkv * d), v.reshape(S, Hkv * d)


def routed_experts(p, x, z, mm):
    f32 = jnp.float32
    scores = jax.nn.sigmoid(jnp.matmul(
        x, p["router"].astype(f32), precision=_HI))         # never rounded
    _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(f32),
                              z["top_k"])
    w = jnp.take_along_axis(scores, chosen, axis=1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * z["scaling"]

    def expert(y, e):
        i, weights = e
        mask = jnp.sum(jnp.where(chosen == i, w, 0.0), axis=1)
        return y + mask[:, None] * _swiglu(mm, x, weights), None
    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (jnp.arange(p["w_gate"].shape[0]),
         {"gate": p["w_gate"], "up": p["w_up"], "down": p["w_down"]}))
    return y + _swiglu(mm, x, p["shared"])


@functools.lru_cache(maxsize=None)
def _layer_fn(kind, heads, sparse, window, sizes, quant):
    z = dict(sizes)
    mm = _mm(quant)

    def layer(p, x):
        f32 = jnp.float32
        h = _rms(x, p["attn_norm"]["scale"].astype(f32), z["eps"])
        y, k, v = attention(p["attn"], h, z, mm, kind, heads, window)
        x = x + y
        h = _rms(x, p["ffn_norm"]["scale"].astype(f32), z["eps"])
        if sparse:
            return x + routed_experts(p["moe"], h, z, mm), k, v
        return x + _swiglu(mm, h, p["mlp"]), k, v
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(quant, eps):
    mm = _mm(quant)
    return jax.jit(lambda p, x, rows: mm(
        _rms(x[rows], p["final_norm"]["scale"].astype(jnp.float32), eps),
        p["lm_head"]))


def forward(params, ids, sizes, quant=None, rows=None, keep_kv=False,
            whole_context=False):
    """ids [S] int (one sequence) -> logits [S, V] float32 (or the rows
    ``rows`` of it). ``params`` is the program's stored tree (its
    ``"params"`` entry); ``sizes`` from ``sizes_of``. With ``keep_kv``
    also what a cache would hold, every layer in order: (logits, [(K
    rotated, V)] each [S, kv_heads x head_dim])."""
    z = dict(sizes)
    ids = jnp.asarray(ids, jnp.int32)
    x = params["embed"][ids].astype(jnp.float32)
    kept = []
    for i, kind in enumerate(z["layer_types"]):
        window = z["window"] if kind == SLIDING and not whole_context \
            else None
        x, k, v = _layer_fn(kind, z["heads"][i],
                            z["mlp_layer_types"][i] == "sparse", window,
                            sizes, quant)(params[f"layers_{i}"], x)
        if keep_kv:
            kept.append((k, v))
    rows = jnp.arange(ids.shape[0]) if rows is None else jnp.asarray(rows)
    logits = _head_fn(quant, z["eps"])(params, x, rows)
    return (logits, kept) if keep_kv else logits


KV_TAIL = 256       # a full layer's cached rows compared: the last written


def ring_rows(fed: int, n_prompt: int, window: int, block_size: int):
    """What a sequence's ring holds once ``fed`` tokens were written (a
    prompt of ``n_prompt``, then one token a step): position ``p`` lies
    in ring page ``(p // block_size) % ring``, ``ring = window /
    block_size + 1``, so ring page ``s`` holds the newest logical page
    ``lp <= (fed - 1) // block_size`` with ``lp % ring == s``. Returns
    (positions [ring x block_size], written [ring x block_size] bool): a
    row counts where its position was written by this sequence and never
    overwritten: below ``fed``, and at or past ``n_prompt - window`` (a
    prompt writes its last ``window`` rows only)."""
    import numpy as np
    ring = window // block_size + 1
    newest = (fed - 1) // block_size
    s = np.arange(ring)
    page = newest - (newest - s) % ring
    pos = (page[:, None] * block_size + np.arange(block_size)[None, :]
           ).reshape(-1)
    return pos, (pos >= max(n_prompt - window, 0)) & (pos < fed) & (pos >= 0)


def served_token_gaps(params, prompt, served, sizes, pad_to: int,
                      block_size: int, controls=()):
    """Teacher-forced check of one served request: run prompt + served
    tokens (padded to ``pad_to``; causality keeps the padding out of the
    rows read) and return, for each served token, how far its reference
    logit lies under its row's maximum, and what the caches would hold
    once the request ended (the last served token was sampled and never
    fed): ``full`` [full layers, 2, n, row], K and V of the last ``n`` =
    min(KV_TAIL, fed) positions; ``ring`` [sliding layers, 2, ring x
    block_size, row], each ring row's position by ``ring_rows`` (rows
    not ``ring_written`` are zeros). For each name in ``controls``
    (``"fp8"``, ``"whole_context"``): ``control_<name>_gaps``, the same
    figure for the tokens the reference computed that way would have
    picked instead, and ``control_<name>_full`` / ``_ring``, its rows."""
    import numpy as np
    z = dict(sizes)
    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n_p + n_s] = list(prompt) + list(served)
    at = np.arange(n_p - 1, n_p - 1 + n_s)
    fed = n_p + n_s - 1
    tail = np.arange(max(fed - KV_TAIL, 0), fed)
    pos, written = ring_rows(fed, n_p, z["window"], block_size)

    def caches(kept):
        full = jnp.stack([jnp.stack([k[tail], v[tail]])
                          for (k, v), t in zip(kept, z["layer_types"])
                          if t == FULL])
        take, keep = np.clip(pos, 0, pad_to - 1), written[:, None]
        ring = jnp.stack([jnp.stack([jnp.where(keep, k[take], 0.0),
                                     jnp.where(keep, v[take], 0.0)])
                          for (k, v), t in zip(kept, z["layer_types"])
                          if t == SLIDING])
        return np.asarray(full), np.asarray(ring)

    rows, kept = forward(params, ids, sizes, rows=at, keep_kv=True)
    tok = jnp.asarray(np.asarray(served, np.int32))
    top = jnp.max(rows, axis=-1)
    full, ring = caches(kept)
    del kept
    out = {"gaps": np.asarray(top - rows[jnp.arange(n_s), tok]),
           "logit_std": float(jnp.std(rows[0])),
           "argmax_equal": int(jnp.sum(jnp.argmax(rows, -1) == tok)),
           "full": full, "ring": ring, "ring_written": written}
    for name in controls:
        low, kept = forward(
            params, ids, sizes, fp8 if name == "fp8" else None, rows=at,
            keep_kv=True, whole_context=name == "whole_context")
        pick = jnp.argmax(low, axis=-1)
        out[f"control_{name}_gaps"] = np.asarray(
            top - rows[jnp.arange(n_s), pick])
        out[f"control_{name}_full"], out[f"control_{name}_ring"] = \
            caches(kept)
        del kept
    return out


CONTROLS = ("fp8", "whole_context")
