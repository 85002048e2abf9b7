"""Plain LongCat-Flash: a full forward pass over a whole sequence in
jax.numpy, float32 at ``highest``.

Written from the source's modelling file (``transformers`` 4.57
``models/longcat_flash/modular_longcat_flash.py``, ``model_type``
``longcat_flash``; tests/test_longcat_flash.py holds this file to that
module, weight for weight). One logical layer, ``N`` = RMSNorm with a
gain (``LongcatFlashDecoderLayer.forward``):

  h1 = x  + MLA_0(N(x));   u = N(h1);   m = MoE(u)
  h2 = h1 + MLP_0(u)
  h3 = h2 + MLA_1(N(h2))
  y  = h3 + MLP_1(N(h3)) + m

MLA (``LongcatFlashMLA``; its two inner norms at eps 1e-6, the class's
default, not ``rms_norm_eps``): ``q = RMSNorm(x W_qa) W_qb`` -> heads of ``[q_n
| q_r]``, both parts times ``(hidden / q_rank)^1/2``; ``[c_kv | k_r] = x
W_kva``; ``c = RMSNorm(c_kv)`` times ``(hidden / kv_rank)^1/2``; ``[k_n |
v] = c W_kvb`` per head; ``q_r`` and ``k_r`` rotated at the token's
position in interleaved pairs, ``f_i = theta^(-2i/rope)``, ``k_r`` one
head shared by all and not scaled; scores ``(q_n . k_n + q_r . k_r) (nope
+ rope)^-1/2``; causal softmax; ``W_o``. MoE (``LongcatFlashTopkRouter``,
``LongcatFlashMoE``): ``p = softmax(u W_r)`` in float32 over ``real +
zero`` outputs; the ``top_k`` largest of ``p + b``; weights ``p_i x
scaling``, not renormalised; an output below ``real`` is a SwiGLU expert,
one above is ``nn.Identity``: ``w u``. No cache, no batching, no kernel,
and no code shared with ``ray_tpu/models``, ``ray_tpu/ops`` or
``ray_tpu/parallel/moe.py``. The share is the program's: experts ``held``
of the real ones, the vocabulary slice that the weights have; what the
absent experts would add is left out, and the identity experts' part is
whole (the token's own chip computes it).

It reads the weights as the program stores them (bfloat16, the program's
names: that is the whole of what the two sides share) and lifts them to
float32 a layer at a time, because two copies do not fit the chip.
Attention goes a head at a time and a wide SwiGLU ``FF_BLOCK`` columns at
a time, so that a sequence of 3,072 tokens fits beside the program's
weights and pool.

``sizes`` (``sizes_of``) is a hashable tuple of pairs. Control: ``quant``
rounds both operands of every matrix product (``fp8``).
"""

from __future__ import annotations

import functools

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
    hidden = get("hidden_size")
    return tuple(sorted({
        "layers": get("num_layers"), "heads": get("num_attention_heads"),
        "q_rank": get("q_lora_rank"), "rank": get("kv_lora_rank"),
        "nope": get("qk_nope_head_dim"), "rope": get("qk_rope_head_dim"),
        "v": get("v_head_dim"), "theta": float(get("rope_theta")),
        # the two LoRA scales, from the flags and the widths
        "q_scale": (hidden / get("q_lora_rank")) ** 0.5
        if get("mla_scale_q_lora") else 1.0,
        "kv_scale": (hidden / get("kv_lora_rank")) ** 0.5
        if get("mla_scale_kv_lora") else 1.0,
        "top_k": get("moe_topk"), "real": get("n_routed_experts"),
        "zero": get("zero_expert_num") or 0, "held": tuple(held),
        "scaling": float(get("routed_scaling_factor")),
        "eps": get("rms_norm_eps"),
        # q_a_layernorm and kv_a_layernorm are built without an eps: the
        # class's default
        "latent_eps": 1e-6}.items()))


def rotate(x, dim, theta):
    """x [S, ..., dim] at positions 0 .. S-1: pair (x[2i], x[2i+1]) is
    turned by the angle ``t theta^(-2i/dim)`` (the source de-interleaves,
    rotates halves and leaves them so: the same pairs in another order,
    alike for q and k, so every dot product is the same)."""
    S = x.shape[0]
    freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    shape = (S,) + (1,) * (x.ndim - 2) + (dim // 2,)
    cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def _mm(quant):
    q_ = quant if quant is not None else (lambda t: t)

    def mm(a, b):
        return jnp.matmul(q_(a.astype(jnp.float32)),
                          q_(b.astype(jnp.float32)), precision=_HI)
    return mm


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * p["scale"].astype(jnp.float32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mlp(mm, x, p):
    """``down(SiLU(gate x) * up x)``, ``FF_BLOCK`` columns of the width
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


def mla(p, x, z, mm):
    """x [S, D] (normed) -> ([S, D], the rows a cache would hold [S, rank
    + rope]: the scaled latent ``kv_scale c`` and ``RoPE(k_r)``). One
    head at a time, so that only one [S, S] of scores is held."""
    S = x.shape[0]
    H, R, dn, dr, dv = z["heads"], z["rank"], z["nope"], z["rope"], z["v"]
    q_in = _rms(mm(x, p["q_a"]), p["q_norm"], z["latent_eps"])
    kv = mm(x, p["kv_a"])
    c = _rms(kv[:, :R], p["kv_norm"], z["latent_eps"]) * z["kv_scale"]
    k_r = rotate(kv[:, R:], dr, z["theta"])                 # [S, dr]
    scale = (dn + dr) ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(y, w):
        w_qh, w_kvh, w_oh = w       # [q_rank, dn+dr], [R, dn+dv], [dv, D]
        q = mm(q_in, w_qh) * z["q_scale"]
        q = jnp.concatenate(
            [q[:, :dn], rotate(q[:, dn:], dr, z["theta"])], -1)
        up = mm(c, w_kvh)
        k = jnp.concatenate([up[:, :dn], k_r], -1)
        s = jnp.where(causal, mm(q, k.T) * scale, -jnp.inf)
        return y + mm(mm(jax.nn.softmax(s, axis=-1), up[:, dn:]), w_oh), None

    def heads_of(w, width):     # [in, H * width] -> [H, in, width]
        return w.reshape(w.shape[0], H, width).transpose(1, 0, 2)
    y, _ = jax.lax.scan(head, jnp.zeros_like(x), (
        heads_of(p["q_b"], dn + dr), heads_of(p["kv_b"], dn + dv),
        p["o_proj"].reshape(H, dv, -1)))
    return y, jnp.concatenate([c, k_r], -1)


def route(p, u, z):
    """-> (chosen [S, top_k] router outputs, their weights)."""
    f32 = jnp.float32
    prob = jax.nn.softmax(jnp.matmul(
        u, p["router"].astype(f32), precision=_HI), axis=-1)  # never rounded
    _, chosen = jax.lax.top_k(prob + p["router_bias"].astype(f32),
                              z["top_k"])
    return chosen, jnp.take_along_axis(prob, chosen, axis=1) * z["scaling"]


def moe(p, u, z, mm):
    """The routed product of the experts held, and the identity experts'
    part whole."""
    first, count = z["held"]
    chosen, w = route(p, u, z)

    def expert(y, e):
        i, weights = e
        mask = jnp.sum(jnp.where(chosen == first + i, w, 0.0), axis=1)
        return y + mask[:, None] * _mlp(mm, u, weights), None
    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(u),
        (jnp.arange(count), {"gate": p["w_gate"], "up": p["w_up"],
                             "down": p["w_down"]}))
    identity = jnp.sum(jnp.where(chosen >= z["real"], w, 0.0), axis=1)
    return y + identity[:, None] * u


@functools.lru_cache(maxsize=None)
def _layer_fn(sizes, quant):
    z = dict(sizes)
    mm = _mm(quant)

    def layer(p, x):
        rows, shortcut = [], None
        for j in (0, 1):
            y, cached = mla(p[f"mla_{j}"],
                            _rms(x, p[f"attn_norm_{j}"], z["eps"]), z, mm)
            rows.append(cached)
            x = x + y
            u = _rms(x, p[f"ffn_norm_{j}"], z["eps"])
            if j == 0:
                shortcut = moe(p["moe"], u, z, mm)
            x = x + _mlp(mm, u, p[f"mlp_{j}"])
        return x + shortcut, jnp.stack(rows)
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(quant, eps):
    mm = _mm(quant)
    return jax.jit(lambda p, x, rows: mm(
        _rms(x[rows], p["final_norm"], eps), p["lm_head"]))


def forward(params, ids, sizes, quant=None, rows=None, latents_at=None):
    """ids [S] int (one sequence) -> logits [S, V] float32 (or the rows
    ``rows`` of it). ``params`` is the program's stored tree (its
    ``"params"`` entry); ``sizes`` from ``sizes_of``. With ``latents_at``
    (positions) also what a cache would hold of them, every attention
    sublayer in the source's order ``2 i + j``: (logits, [2 * layers,
    len(latents_at), rank + rope])."""
    z = dict(sizes)
    ids = jnp.asarray(ids, jnp.int32)
    x = params["embed"][ids].astype(jnp.float32)
    kept = []
    for i in range(z["layers"]):
        x, latent = _layer_fn(sizes, quant)(params[f"layers_{i}"], x)
        if latents_at is not None:
            kept.append(latent[:, jnp.asarray(latents_at)])
    rows = jnp.arange(ids.shape[0]) if rows is None else jnp.asarray(rows)
    logits = _head_fn(quant, z["eps"])(params, x, rows)
    return logits if latents_at is None \
        else (logits, jnp.concatenate(kept, axis=0))


def routing(params, ids, sizes):
    """What the routers chose over a whole sequence, for a count by
    hand: [layers, S, top_k] router outputs (float32 forward)."""
    z = dict(sizes)
    mm = _mm(None)
    x = params["embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    out = []
    for i in range(z["layers"]):
        p = params[f"layers_{i}"]
        y, _ = mla(p["mla_0"], _rms(x, p["attn_norm_0"], z["eps"]), z, mm)
        u = _rms(x + y, p["ffn_norm_0"], z["eps"])
        out.append(route(p["moe"], u, z)[0])
        x, _ = _layer_fn(sizes, None)(p, x)
    return jnp.stack(out)


LATENT_TAIL = 256       # cached rows compared, the last a request wrote


def served_token_gaps(params, prompt, served, sizes, pad_to: int,
                      control=None):
    """Teacher-forced check of one served request: run prompt + served
    tokens (padded to ``pad_to``; causality keeps the padding out of the
    rows read) and return, for each served token, how far its reference
    logit lies under its row's maximum, and ``latents``: the rows [2 *
    layers, n, rank + rope] a cache would hold of the last ``n`` =
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
