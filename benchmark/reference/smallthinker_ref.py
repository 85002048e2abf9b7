"""Plain SmallThinker: a full forward pass over a whole sequence in
jax.numpy, float32 at ``highest``.

Written from the equations of ISSUE 43 / the source's config
(PowerInfer/SmallThinker-21BA3B-Instruct, ``model_name``
``smallthinker_21b_instruct``), in the published order:

  n   = RMSNorm(x)
  r   = n W_r                        64 logits, float32, never rounded:
                                     the router reads the attention's input
  h   = x + Attn_l(n)                q = n W_q (28 heads of 128), k, v = n
                                     W_k, n W_v (4 heads of 128), query head
                                     j reads key/value head j // 7; layout 0:
                                     every earlier position, NO rotary;
                                     layout 1: positions p - 4095 .. p (a
                                     mask over the whole sequence), q and k
                                     rotated in halves (x[i], x[i + 64]) by
                                     p theta^(-2i/128) over the whole head;
                                     causal softmax, scale 128^-1/2; W_o
  m   = RMSNorm(h)
  top = the 6 largest of r;  w = softmax(r[top])     (top-k, THEN softmax)
  y   = h + sum_{i in top} w_i W_d,i (relu(W_g,i m) * (W_u,i m))

then a final RMSNorm and the untied head. No cache, no batching, no
kernel, and no code shared with ``ray_tpu/models``, ``ray_tpu/ops`` or
``ray_tpu/parallel/moe.py``.

It reads the weights as the program stores them (bfloat16, the program's
names: that is the whole of what the two sides share) and lifts them to
float32 an expert, a head or a block of the vocabulary at a time, because
two copies do not fit the chip. Attention goes a head and ``Q_BLOCK``
queries at a time, the experts one at a time with dense masks, the head
``V_BLOCKS`` blocks of columns, so that a sequence of 9,216 tokens at the
published widths fits beside the served weights and pools.

``sizes`` (``sizes_of``) is a hashable tuple of pairs. Controls: ``fp8``
rounds both operands of every matrix product (the next coarser precision
under the served bfloat16); ``whole_context`` lets the window layers see
every earlier position.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 1024
V_BLOCKS = 8
KV_TAIL = 256       # cached rows compared: the last written, and (a window
#                     layer) the first of the window


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> 448)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def sizes_of(cfg) -> tuple:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    n = get("num_hidden_layers")
    return tuple(sorted({
        "window_layout": tuple(get("sliding_window_layout"))[:n],
        "rope_layout": tuple(get("rope_layout"))[:n],
        "heads": get("num_attention_heads"),
        "kv_heads": get("num_key_value_heads"), "head_dim": get("head_dim"),
        "window": get("sliding_window_size"),
        "theta": float(get("rope_theta")),
        "top_k": get("moe_num_active_primary_experts"),
        "eps": get("rms_norm_eps")}.items()))


def rotate(x, theta):
    """x [S, ..., d] at positions 0 .. S-1, turned in halves over the
    whole last axis: ``(x[i], x[i + d/2])`` by the angle ``t
    theta^(-2i/d)``."""
    S, d = x.shape[0], x.shape[-1]
    freqs = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)],
                        jnp.float32)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    shape = (S,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mm(quant):
    q_ = quant if quant is not None else (lambda t: t)

    def mm(a, b):
        return jnp.matmul(q_(a.astype(jnp.float32)),
                          q_(b.astype(jnp.float32)), precision=_HI)
    return mm


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def attention(p, x, z, mm, rotary, window):
    """x [S, D] (normed) -> ([S, D], K (rotated where the layer has
    rotary) [S, kv_heads x d], V [S, kv_heads x d]: the rows a cache would
    hold). One query head and ``Q_BLOCK`` queries at a time. ``window``
    None: every earlier position is seen."""
    S = x.shape[0]
    H, Hkv, d = z["heads"], z["kv_heads"], z["head_dim"]
    G = H // Hkv
    k = mm(x, p["k_proj"]).reshape(S, Hkv, d)
    if rotary:
        k = rotate(k, z["theta"])
    v = mm(x, p["v_proj"]).reshape(S, Hkv, d)
    qb = Q_BLOCK if S % Q_BLOCK == 0 else S
    t = jnp.arange(S)

    def head(y, w):
        j, w_q, w_o = w             # [D, d], [d, D]
        q = mm(x, w_q)
        if rotary:
            q = rotate(q, z["theta"])
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
        return y + mm(o, w_o), None

    y, _ = jax.lax.scan(head, jnp.zeros_like(x), (
        jnp.arange(H),
        p["q_proj"].reshape(-1, H, d).transpose(1, 0, 2),
        p["o_proj"].reshape(H, d, -1)))
    return y, k.reshape(S, Hkv * d), v.reshape(S, Hkv * d)


def routed_experts(p, logits, m, z, mm):
    """``logits`` [S, E] the router's (from the attention's input), ``m``
    [S, D] what the experts multiply: the ``top_k`` largest logits, a
    softmax over them, a loop over the experts with dense masks."""
    top, chosen = jax.lax.top_k(logits, z["top_k"])
    w = jax.nn.softmax(top, axis=-1)

    def expert(y, e):
        i, gate, up, down = e
        mask = jnp.sum(jnp.where(chosen == i, w, 0.0), axis=1)
        act = jnp.maximum(mm(m, gate), 0.0) * mm(m, up)
        return y + mask[:, None] * mm(act, down), None
    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(m),
        (jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
         p["w_down"]))
    return y


@functools.lru_cache(maxsize=None)
def _layer_fn(rotary, window, sizes, quant):
    z = dict(sizes)
    mm = _mm(quant)

    def layer(p, x):
        f32 = jnp.float32
        n = _rms(x, p["attn_norm"]["scale"].astype(f32), z["eps"])
        logits = jnp.matmul(n, p["moe"]["router"].astype(f32),
                            precision=_HI)              # never rounded
        y, k, v = attention(p["attn"], n, z, mm, rotary, window)
        h = x + y
        m = _rms(h, p["ffn_norm"]["scale"].astype(f32), z["eps"])
        return h + routed_experts(p["moe"], logits, m, z, mm), k, v
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _norm_fn(eps):
    return jax.jit(lambda scale, x, rows: _rms(
        x[rows], scale.astype(jnp.float32), eps))


@functools.lru_cache(maxsize=None)
def _head_fn(quant):
    return jax.jit(_mm(quant))


def forward(params, ids, sizes, quant=None, rows=None, keep_kv=False,
            whole_context=False):
    """ids [S] int (one sequence) -> logits [S, V] float32 (or the rows
    ``rows`` of it). ``params`` is the program's stored tree (its
    ``"params"`` entry); ``sizes`` from ``sizes_of``. With ``keep_kv``
    also what a cache would hold, every layer in order: (logits, [(K, V)]
    each [S, kv_heads x head_dim])."""
    z = dict(sizes)
    ids = jnp.asarray(ids, jnp.int32)
    x = params["embed"][ids].astype(jnp.float32)
    kept = []
    for i, windowed in enumerate(z["window_layout"]):
        window = z["window"] if windowed and not whole_context else None
        x, k, v = _layer_fn(bool(z["rope_layout"][i]), window, sizes,
                            quant)(params[f"layers_{i}"], x)
        if keep_kv:
            kept.append((k, v))
    rows = jnp.arange(ids.shape[0]) if rows is None else jnp.asarray(rows)
    x = _norm_fn(z["eps"])(params["final_norm"]["scale"], x, rows)
    head = params["lm_head"]
    V = head.shape[1]
    step = V // V_BLOCKS if V % V_BLOCKS == 0 else V
    logits = jnp.concatenate(
        [_head_fn(quant)(x, head[:, c:c + step]) for c in range(0, V, step)],
        axis=1)
    return (logits, kept) if keep_kv else logits


def probe_positions(fed: int, window: int):
    """The positions whose cached rows a finished request is compared at,
    once ``fed`` tokens were written. ``tail`` [KV_TAIL]: the last
    ``KV_TAIL`` (the full group). ``ringed`` [2 x KV_TAIL] (the window
    group): the last ``KV_TAIL``, then the first ``KV_TAIL`` of what a
    window layer still reads (``fed - window`` on: where a ring indexed
    wrongly, or overwritten too early, shows), none of them behind the
    window, whose rows a ring no longer holds. Clipped to 0 .. fed - 1 at
    the ends: a short sequence repeats a row."""
    import numpy as np
    oldest = max(fed - window, 0)
    tail = np.clip(np.arange(fed - KV_TAIL, fed), 0, fed - 1)
    ringed = np.concatenate([np.maximum(tail, oldest),
                             np.minimum(np.arange(KV_TAIL) + oldest,
                                        fed - 1)])
    return tail, ringed


def served_token_gaps(params, prompt, served, sizes, pad_to: int,
                      controls=()):
    """Teacher-forced check of one served request: run prompt + served
    tokens (padded to ``pad_to``; causality keeps the padding out of the
    rows read) and return, for each served token, how far its reference
    logit lies under its row's maximum, and what the caches would hold
    once the request ended (the last served token was sampled and never
    fed): ``full`` [full layers, 2, KV_TAIL, row], K and V at the ``tail``
    positions; ``window`` [window layers, 2, 2 x KV_TAIL, row], at
    ``probe_positions``' ``ringed``. For each name in
    ``controls`` (``"fp8"``, ``"whole_context"``): ``control_<name>_gaps``,
    the same figure for the tokens the reference computed that way would
    have picked instead, and ``control_<name>_full`` / ``_window``, its
    rows."""
    import numpy as np
    z = dict(sizes)
    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n_p + n_s] = list(prompt) + list(served)
    at = np.arange(n_p - 1, n_p - 1 + n_s)
    fed = n_p + n_s - 1
    tail, both = probe_positions(fed, z["window"])

    def caches(kept):
        full = jnp.stack([jnp.stack([k[tail], v[tail]])
                          for (k, v), w in zip(kept, z["window_layout"])
                          if not w])
        win = jnp.stack([jnp.stack([k[both], v[both]])
                         for (k, v), w in zip(kept, z["window_layout"])
                         if w])
        return np.asarray(full), np.asarray(win)

    rows, kept = forward(params, ids, sizes, rows=at, keep_kv=True)
    tok = jnp.asarray(np.asarray(served, np.int32))
    top = jnp.max(rows, axis=-1)
    full, win = caches(kept)
    del kept
    out = {"gaps": np.asarray(top - rows[jnp.arange(n_s), tok]),
           "logit_std": float(jnp.std(rows[0])),
           "argmax_equal": int(jnp.sum(jnp.argmax(rows, -1) == tok)),
           "fed": fed, "full": full, "window": win}
    for name in controls:
        low, kept = forward(
            params, ids, sizes, fp8 if name == "fp8" else None, rows=at,
            keep_kv=True, whole_context=name == "whole_context")
        pick = jnp.argmax(low, axis=-1)
        out[f"control_{name}_gaps"] = np.asarray(
            top - rows[jnp.arange(n_s), pick])
        out[f"control_{name}_full"], out[f"control_{name}_window"] = \
            caches(kept)
        del kept, low
    return out


CONTROLS = ("fp8", "whole_context")
