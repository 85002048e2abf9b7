"""Plain Kimi-K2: a full forward pass over a whole sequence in jax.numpy,
float32 at ``highest``.

Written from the equations of ISSUE 35 / the source's config
(moonshotai/Kimi-K2.7-Code, ``model_type`` ``kimi_k2``): pre-norm
RMSNorm blocks, ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.
MLA: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads of ``[q_n |
q_r]``; ``[c_kv | k_r] = x W_kva``; ``c = RMSNorm(c_kv)``; ``q_r`` and
``k_r`` rotated at the token's position with the YaRN frequencies (``k_r``
one head, shared by all); ``[k_n | v] = c W_kvb`` per head; scores ``(q_n
. k_n + q_r . k_r) (nope + rope)^-1/2 m^2``; causal softmax; ``W_o``.
Routed layer: sigmoid scores in float32, the ``top_k`` largest of ``s +
b``, weights ``s_i / sum(chosen s) x scaling``, a loop over the experts
held with dense masks, plus the shared expert. No cache, no batching, no
kernel, and no code shared with ``ray_tpu/models``, ``ray_tpu/ops`` or
``ray_tpu/parallel/moe.py``. The share is the program's: experts ``held``
of the router's width, the vocabulary slice that the weights have; what
the absent experts would add is left out.

It reads the weights as the program stores them (bfloat16, the program's
names: that is the whole of what the two sides share) and lifts them to
float32 a layer at a time, because two copies do not fit the chip.
Attention goes a head at a time and a wide SwiGLU ``FF_BLOCK`` columns at
a time, so that a sequence of 9,216 tokens fits beside the program's
weights and pool.

``sizes`` (``sizes_of``) is a hashable tuple of pairs: layers,
first_k_dense, heads, q_rank, rank, nope, rope, v, theta, yarn (a tuple
of pairs or None), top_k, held (first, count), scaling, renormalize, eps.
Control: ``quant`` rounds both operands of every matrix product
(``fp8``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
FF_BLOCK = 2048


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> 448)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def sizes_of(cfg) -> tuple:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    held = get("experts_held") or (0, get("n_routed_experts"))
    yarn = get("rope_scaling")
    return tuple(sorted({
        "layers": get("num_hidden_layers"),
        "first_k_dense": get("first_k_dense_replace"),
        "heads": get("num_attention_heads"), "q_rank": get("q_lora_rank"),
        "rank": get("kv_lora_rank"), "nope": get("qk_nope_head_dim"),
        "rope": get("qk_rope_head_dim"), "v": get("v_head_dim"),
        "theta": float(get("rope_theta")),
        "yarn": tuple(sorted(dict(yarn).items())) if yarn else None,
        "top_k": get("num_experts_per_tok"), "held": tuple(held),
        "scaling": get("routed_scaling_factor"),
        "renormalize": bool(get("norm_topk_prob")),
        "eps": get("rms_norm_eps")}.items()))


# ------------------------------------------------------------- rotary

def yarn_frequencies(dim, theta, yarn):
    """The ``dim / 2`` angular frequencies (plain Python floats). With
    ``yarn``: ``f_i = theta^(-2i/dim)`` kept where the original context
    holds more than ``beta_fast`` turns, ``f_i / factor`` where it holds
    fewer than ``beta_slow``, a linear ramp over the pair indices
    between (the ends rounded outwards to whole indices)."""
    f = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if not yarn:
        return f
    y = dict(yarn)

    def index_of(turns):        # where the original context holds `turns`
        return dim * math.log(y["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(index_of(y["beta_fast"])), 0)
    high = min(math.ceil(index_of(y["beta_slow"])), dim - 1)
    out = []
    for i, fi in enumerate(f):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(fi * (1.0 - ramp) + fi / y["factor"] * ramp)
    return out


def softmax_mscale(yarn) -> float:
    """``m = 0.1 mscale_all_dim ln(factor) + 1`` (1 without YaRN); the
    softmax scale takes ``m^2``. cos and sin take ``m(mscale) /
    m(mscale_all_dim)``, which ``cos_sin_scale`` gives."""
    if not yarn:
        return 1.0
    y = dict(yarn)
    return _m(y["factor"], y.get("mscale_all_dim", 0.0))


def _m(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def cos_sin_scale(yarn) -> float:
    if not yarn:
        return 1.0
    y = dict(yarn)
    return _m(y["factor"], y.get("mscale", 1.0)) \
        / _m(y["factor"], y.get("mscale_all_dim", 0.0))


def rotate(x, freqs, scale=1.0):
    """x [S, ..., dim] at positions 0 .. S-1: pair (x[2i], x[2i+1]) is
    turned by the angle ``t f_i`` (the interleaved pairing)."""
    S, dim = x.shape[0], x.shape[-1]
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freqs, jnp.float32)[None, :]          # [S, dim/2]
    shape = (S,) + (1,) * (x.ndim - 2) + (dim // 2,)
    cos, sin = (jnp.cos(angle) * scale).reshape(shape), \
        (jnp.sin(angle) * scale).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


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


def mla_mixer(p, x, z, mm):
    """x [S, D] (normed) -> ([S, D], the rows a cache would hold
    [S, rank + rope]: ``(c, RoPE(k_r))``). One head at a time: its
    queries, keys and values are built from the latents inside the loop,
    so that nothing of [S, heads, ...] and only one [S, S] of scores is
    held."""
    S = x.shape[0]
    H, R, dn, dr, dv = z["heads"], z["rank"], z["nope"], z["rope"], z["v"]
    f32 = jnp.float32
    if z["q_rank"]:
        q_in = _rms(mm(x, p["q_a"]), p["q_norm"]["scale"].astype(f32),
                    z["eps"])
        w_q = p["q_b"]
    else:
        q_in, w_q = x, p["q_proj"]
    kv = mm(x, p["kv_a"])
    c = _rms(kv[:, :R], p["kv_norm"]["scale"].astype(f32), z["eps"])
    freqs = yarn_frequencies(dr, z["theta"], z["yarn"])
    cs = cos_sin_scale(z["yarn"])
    k_r = rotate(kv[:, R:], freqs, cs)                      # [S, dr]
    scale = (dn + dr) ** -0.5 * softmax_mscale(z["yarn"]) ** 2
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(y, w):
        w_qh, w_kvh, w_oh = w       # [.., dn+dr], [R, dn+dv], [dv, D]
        q = mm(q_in, w_qh)
        q = jnp.concatenate([q[:, :dn], rotate(q[:, dn:], freqs, cs)], -1)
        up = mm(c, w_kvh)
        k = jnp.concatenate([up[:, :dn], k_r], -1)
        s = jnp.where(causal, mm(q, k.T) * scale, -jnp.inf)
        return y + mm(mm(jax.nn.softmax(s, axis=-1), up[:, dn:]), w_oh), None

    def heads_of(w, width):     # [in, H * width] -> [H, in, width]
        return w.reshape(w.shape[0], H, width).transpose(1, 0, 2)
    y, _ = jax.lax.scan(head, jnp.zeros_like(x), (
        heads_of(w_q, dn + dr), heads_of(p["kv_b"], dn + dv),
        p["o_proj"].reshape(H, dv, -1)))
    return y, jnp.concatenate([c, k_r], -1)


def routed_experts(p, x, z, mm):
    f32 = jnp.float32
    first, count = z["held"]
    scores = jax.nn.sigmoid(jnp.matmul(
        x, p["router"].astype(f32), precision=_HI))         # never rounded
    _, chosen = jax.lax.top_k(scores + p["router_bias"].astype(f32),
                              z["top_k"])
    w = jnp.take_along_axis(scores, chosen, axis=1)
    if z["renormalize"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * z["scaling"]

    def expert(y, e):
        i, weights = e
        mask = jnp.sum(jnp.where(chosen == first + i, w, 0.0), axis=1)
        return y + mask[:, None] * _swiglu(mm, x, weights), None
    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(x),
        (jnp.arange(count), {"gate": p["w_gate"], "up": p["w_up"],
                             "down": p["w_down"]}))
    if "shared" in p:
        y = y + _swiglu(mm, x, p["shared"])
    return y


@functools.lru_cache(maxsize=None)
def _layer_fn(routed, sizes, quant):
    z = dict(sizes)
    mm = _mm(quant)

    def layer(p, x):
        f32 = jnp.float32
        h = _rms(x, p["attn_norm"]["scale"].astype(f32), z["eps"])
        y, rows = mla_mixer(p["mla"], h, z, mm)
        x = x + y
        h = _rms(x, p["ffn_norm"]["scale"].astype(f32), z["eps"])
        if routed:
            return x + routed_experts(p["moe"], h, z, mm), rows
        return x + _swiglu(mm, h, p["mlp"]), rows
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(quant, eps):
    mm = _mm(quant)
    return jax.jit(lambda p, x, rows: mm(
        _rms(x[rows], p["final_norm"]["scale"].astype(jnp.float32), eps),
        p["lm_head"]))


def forward(params, ids, sizes, quant=None, rows=None, latents_at=None):
    """ids [S] int (one sequence) -> logits [S, V] float32 (or the rows
    ``rows`` of it). ``params`` is the program's stored tree (its
    ``"params"`` entry); ``sizes`` from ``sizes_of``. With ``latents_at``
    (positions) also what a cache would hold of them, every layer:
    (logits, [layers, len(latents_at), rank + rope])."""
    z = dict(sizes)
    ids = jnp.asarray(ids, jnp.int32)
    x = params["embed"][ids].astype(jnp.float32)
    kept = []
    for i in range(z["layers"]):
        x, latent = _layer_fn(i >= z["first_k_dense"], sizes, quant)(
            params[f"layers_{i}"], x)
        if latents_at is not None:
            kept.append(latent[jnp.asarray(latents_at)])
    rows = jnp.arange(ids.shape[0]) if rows is None else jnp.asarray(rows)
    logits = _head_fn(quant, z["eps"])(params, x, rows)
    return logits if latents_at is None else (logits, jnp.stack(kept))


LATENT_TAIL = 256       # cached rows compared, the last a request wrote


def served_token_gaps(params, prompt, served, sizes, pad_to: int,
                      control=None):
    """Teacher-forced check of one served request: run prompt + served
    tokens (padded to ``pad_to``; causality keeps the padding out of the
    rows read) and return, for each served token, how far its reference
    logit lies under its row's maximum, and ``latents``: the rows
    [layers, n, rank + rope] a cache would hold of the last ``n`` =
    min(LATENT_TAIL, tokens fed) positions the request wrote (the last
    served token was sampled and never fed). With ``control`` (a
    rounding, e.g. ``fp8``): the same figure for the tokens the reference
    computed that way would have picked instead, and its latents."""
    import numpy as np
    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n_p + n_s] = list(prompt) + list(served)
    at = np.arange(n_p - 1, n_p - 1 + n_s)
    fed = n_p + n_s - 1
    tail = np.arange(max(fed - LATENT_TAIL, 0), fed)
    rows, latents = forward(params, ids, sizes, rows=at, latents_at=tail)
    tok = jnp.asarray(np.asarray(served, np.int32))
    top = jnp.max(rows, axis=-1)
    out = {"gaps": np.asarray(top - rows[jnp.arange(n_s), tok]),
           "logit_std": float(jnp.std(rows[0])),
           "argmax_equal": int(jnp.sum(jnp.argmax(rows, -1) == tok)),
           "latents": latents, "latents_from": int(tail[0])}
    if control is not None:
        low, low_latents = forward(params, ids, sizes, control, rows=at,
                                   latents_at=tail)
        pick = jnp.argmax(low, axis=-1)
        out["control_gaps"] = np.asarray(top - rows[jnp.arange(n_s), pick])
        out["control_latents"] = low_latents
    return out
