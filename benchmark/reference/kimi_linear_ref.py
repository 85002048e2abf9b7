"""Plain Kimi-Linear: a full forward pass over a whole sequence in
jax.numpy, float32 at ``highest``.

Written from the equations of ISSUE 28 / the source's config
(moonshotai/Kimi-Linear-48B-A3B-Instruct): pre-norm RMSNorm blocks with a
residual round the mixer and round the feed-forward, no position
encoding. KDA is a ``lax.scan`` over tokens, its short convolution is
explicit shifts, MLA builds every head's keys and values, the routed
experts are a loop over those held with dense masks. No cache, no
batching, no kernel, no chunking, and no code shared with
``ray_tpu/models/kimi_linear.py``, ``ray_tpu/ops`` or
``ray_tpu/parallel/moe.py``. The share is the program's: experts
``held`` of the router's width, the vocabulary slice that the weights
have; what the absent experts would add is left out.

It reads the weights as the program stores them (bfloat16, the program's
names: that is the whole of what the two sides share) and lifts them to
float32 a layer at a time, because two copies do not fit the chip.

``sizes`` is a plain dict: kinds (tuple of "kda"/"mla"), first_k_dense,
H, d (KDA heads and head size), heads, rank, nope, rope, v (MLA), top_k,
held (first, count), scaling, renormalize, eps. Controls: ``quant``
rounds both operands of every matrix product (``fp8``), ``state_dtype``
keeps the KDA state in a lower precision between tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> 448)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def sizes_of(cfg) -> tuple:
    """The sizes above from an object or dict with the source's key
    names, as a hashable tuple of pairs (``dict()`` gives the dict)."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    n = get("num_hidden_layers")
    kda = set(get("kda_layers"))
    held = get("experts_held") or (0, get("num_experts"))
    return tuple(sorted({
        "kinds": tuple("kda" if i in kda else "mla"
                       for i in range(1, n + 1)),
        "first_k_dense": get("first_k_dense_replace"),
        "H": get("kda_num_heads"), "d": get("kda_head_dim"),
        "heads": get("num_attention_heads"), "rank": get("kv_lora_rank"),
        "nope": get("qk_nope_head_dim"), "rope": get("qk_rope_head_dim"),
        "v": get("v_head_dim"), "top_k": get("num_experts_per_token"),
        "held": tuple(held), "scaling": get("routed_scaling_factor"),
        "renormalize": bool(get("moe_renormalize")),
        "eps": get("rms_norm_eps")}.items()))


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
    return mm(_silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])


def kda_mixer(p, x, z, mm, state_dtype=jnp.float32, last=None):
    """x [S, D] (normed) -> ([S, D], the state [H, dk, dv] after token
    ``last``), one sequence from a zero state. Tokens after ``last``
    (padding) leave the state as it is."""
    S = x.shape[0]
    H, d = z["H"], z["d"]
    f32 = jnp.float32
    w = p["qkv_conv"].astype(f32)                           # [K, C]
    K = w.shape[0]
    pre = mm(x, p["qkv_proj"])                              # [S, 3Hd]
    # y_t = sum_j w[j] pre[t - (K-1) + j]: explicit shifts
    conv = jnp.zeros_like(pre)
    for j in range(K):
        back = K - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((back, pre.shape[1]), f32), pre[:S - back]], axis=0)
        conv = conv + shifted * w[j]
    q, k, v = (t.reshape(S, H, d) for t in jnp.split(_silu(conv), 3, -1))

    def unit(t):
        return t * jax.lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) * d ** -0.5, unit(k)
    f = mm(mm(x, p["f_a"]), p["f_b"]) + p["dt_bias"].astype(f32)
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] \
        * jax.nn.softplus(f).reshape(S, H, d)
    beta = jax.nn.sigmoid(mm(x, p["b_proj"]))               # [S, H]
    if last is not None:
        live = jnp.arange(S) <= last
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)

    def token(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        s = jnp.exp(g_t)[:, :, None] * state.astype(f32)    # [H, dk, dv]
        r = v_t - jnp.einsum("hkv,hk->hv", s, k_t, precision=_HI)
        s = s + b_t[:, None, None] * k_t[:, :, None] * r[:, None, :]
        s = s.astype(state_dtype)
        return s, jnp.einsum("hkv,hk->hv", s.astype(f32), q_t,
                             precision=_HI)
    state, o = jax.lax.scan(token, jnp.zeros((H, d, d), state_dtype),
                            (q, k, v, g, beta))
    o = _rms(o, p["o_norm"]["scale"].astype(f32), z["eps"])
    gate = jax.nn.sigmoid(mm(mm(x, p["g_a"]), p["g_b"]))
    return mm(o.reshape(S, H * d) * gate, p["o_proj"]), state


def mla_mixer(p, x, z, mm):
    S = x.shape[0]
    H, R, dn, dr, dv = z["heads"], z["rank"], z["nope"], z["rope"], z["v"]
    f32 = jnp.float32
    q = mm(x, p["q_proj"]).reshape(S, H, dn + dr)
    kv = mm(x, p["kv_a"])
    c = _rms(kv[:, :R], p["kv_norm"]["scale"].astype(f32), z["eps"])
    k_rope = kv[:, R:]                                      # no rotation
    up = mm(c, p["kv_b"]).reshape(S, H, dn + dv)
    k = jnp.concatenate(
        [up[..., :dn], jnp.broadcast_to(k_rope[:, None, :], (S, H, dr))], -1)
    v = up[..., dn:]
    s = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) * (dn + dr) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    y = mm(jax.nn.softmax(s, axis=-1), v.transpose(1, 0, 2))  # [H, S, dv]
    return mm(y.transpose(1, 0, 2).reshape(S, H * dv), p["o_proj"])


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
def _layer_fn(kind, routed, sizes, quant, state_dtype):
    z = dict(sizes)
    mm = _mm(quant)

    def layer(p, x, last):
        f32 = jnp.float32
        state = None
        h = _rms(x, p["attn_norm"]["scale"].astype(f32), z["eps"])
        if kind == "kda":
            y, state = kda_mixer(p["kda"], h, z, mm, state_dtype, last)
            x = x + y
        else:
            x = x + mla_mixer(p["mla"], h, z, mm)
        h = _rms(x, p["ffn_norm"]["scale"].astype(f32), z["eps"])
        if routed:
            return x + routed_experts(p["moe"], h, z, mm), state
        return x + _swiglu(mm, h, p["mlp"]), state
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(quant, eps):
    mm = _mm(quant)
    return jax.jit(lambda p, x, rows: mm(
        _rms(x[rows], p["final_norm"]["scale"].astype(jnp.float32), eps),
        p["lm_head"]))


def forward(params, ids, sizes, quant=None, state_dtype=jnp.float32,
            rows=None, state_after=None):
    """ids [S] int (one sequence) -> logits [S, V] float32 (or the rows
    ``rows`` of it). ``params`` is the program's stored tree (its
    ``"params"`` entry); ``sizes`` from ``sizes_of``. With
    ``state_after`` (a token's index) also the KDA layers' states
    [n_kda, H, dk, dv] after that token: (logits, states)."""
    z = dict(sizes)
    ids = jnp.asarray(ids, jnp.int32)
    last = jnp.int32(ids.shape[0] - 1 if state_after is None
                     else state_after)
    x = params["embed"][ids].astype(jnp.float32)
    states = []
    for i, kind in enumerate(z["kinds"]):
        x, state = _layer_fn(kind, i >= z["first_k_dense"], sizes, quant,
                             state_dtype)(params[f"layers_{i}"], x, last)
        if state is not None:
            states.append(state)
    rows = jnp.arange(ids.shape[0]) if rows is None else jnp.asarray(rows)
    logits = _head_fn(quant, z["eps"])(params, x, rows)
    return logits if state_after is None else (logits, jnp.stack(states))


def served_token_gaps(params, prompt, served, sizes, pad_to: int,
                      controls=()):
    """Teacher-forced check of one served request: run prompt + served
    tokens (padded to ``pad_to``; causality keeps the padding out of the
    rows read) and return, for each served token, how far its reference
    logit lies under its row's maximum, and ``state``: the KDA layers'
    states [n_kda, H, dk, dv] after the last token the request's slot
    took in (the last served token was sampled and never fed).
    ``controls`` is a tuple of (name, quant, state_dtype): for each, the
    same figure for the tokens that the reference computed that way
    would have picked instead, and the states it would have left."""
    import numpy as np
    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n_p + n_s] = list(prompt) + list(served)
    at = np.arange(n_p - 1, n_p - 1 + n_s)
    fed = n_p + n_s - 2
    rows, state = forward(params, ids, sizes, rows=at, state_after=fed)
    tok = jnp.asarray(np.asarray(served, np.int32))
    top = jnp.max(rows, axis=-1)
    out = {"gaps": np.asarray(top - rows[jnp.arange(n_s), tok]),
           "logit_std": float(jnp.std(rows[0])),
           "argmax_equal": int(jnp.sum(jnp.argmax(rows, -1) == tok)),
           "state": state}
    for name, quant, state_dtype in controls:
        low, low_state = forward(params, ids, sizes, quant, state_dtype,
                                 rows=at, state_after=fed)
        pick = jnp.argmax(low, axis=-1)
        out[f"control_{name}_gaps"] = np.asarray(
            top - rows[jnp.arange(n_s), pick])
        out[f"control_{name}_state"] = low_state
    return out


CONTROLS = (("fp8", fp8, jnp.float32),
            ("bf16_state", None, jnp.bfloat16))
