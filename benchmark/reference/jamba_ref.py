"""Plain Jamba (the family's dense members): a full forward pass over a
whole sequence in jax.numpy, float32 at ``highest``.

Written from the equations of ISSUE 48 / the source's config
(ai21labs/AI21-Jamba2-3B, ``model_type`` ``jamba``): every layer is ``x
<- x + Mixer(RMSNorm(x))`` then ``x <- x + SwiGLU(RMSNorm(x))``, the
mixer attention where ``i % attn_layer_period == attn_layer_offset`` and
Mamba-1 otherwise, then a final norm and the token table as the head.
The Mamba recurrence is a ``lax.scan`` over single tokens on the state in
its natural shape ``[d_in, N]``, exactly as the equations read (``h <-
exp(dt A) h + dt B u``, ``y = h C + D u``); its convolution is four
explicit shifts plus the bias; ``dt``, ``B`` and ``C`` go under their own
RMSNorms; attention builds every head's scores over the whole sequence
against the ONE key/value head, with no position encoding. No cache, no
batching, no kernel, no chunking, and no code shared with
``ray_tpu/models/jamba.py`` or ``ray_tpu/ops``.

Departures: none from the equations. It reads the weights as the program
stores them (bfloat16, under the program's names, the Mamba layers of a
run stacked on a leading axis, ``A_log`` channels-minor ``[N, d_in]``:
the whole of what the two sides share) and lifts them to float32 a layer
at a time, each layer a jitted function of its own, and takes the head a
block of the vocabulary at a time: the check at the published widths then
holds one layer's float32 weights (0.4 GB) and one block of the table
beside the replica, and only the rows of the logits that are read.

``sizes`` is a hashable tuple of pairs (``dict()`` gives the dict): kinds
(a layer's ``mamba`` | ``attention``), N, R, K, heads, kv_heads,
head_dim, eps. Controls: ``quant`` rounds both operands of every matrix
product (``fp8``), ``state_dtype`` keeps the Mamba state in a lower
precision between tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
VOCAB_BLOCK = 8192


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> 448)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def sizes_of(cfg) -> tuple:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    n, period, offset = (get("num_hidden_layers"), get("attn_layer_period"),
                         get("attn_layer_offset"))
    heads = get("num_attention_heads")
    return tuple(sorted({
        "kinds": tuple("attention" if i % period == offset else "mamba"
                       for i in range(n)),
        "N": get("mamba_d_state"), "R": get("mamba_dt_rank"),
        "K": get("mamba_d_conv"), "heads": heads,
        "kv_heads": get("num_key_value_heads"),
        "head_dim": get("head_dim") or get("hidden_size") // heads,
        "eps": get("rms_norm_eps")}.items()))


def _mm(quant):
    q_ = quant if quant is not None else (lambda t: t)

    def mm(a, b):
        return jnp.matmul(q_(a.astype(jnp.float32)),
                          q_(b.astype(jnp.float32)), precision=_HI)
    return mm


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def mamba_mixer(p, x, z, mm, state_dtype=jnp.float32, last=None):
    """x [S, D] (normed) -> ([S, D], the state [d_in, N] after token
    ``last``), one sequence from a zero state. Tokens after ``last``
    (padding) leave the state as it is."""
    S = x.shape[0]
    N, R, K, f32 = z["N"], z["R"], z["K"], jnp.float32
    uz = mm(x, p["in_proj"])
    d_in = uz.shape[1] // 2
    pre, gate = uz[:, :d_in], uz[:, d_in:]
    w = p["conv"].astype(f32)                               # [K, d_in]
    # conv_t = b + sum_j w[j] pre[t - (K - 1) + j]: explicit shifts
    conv = jnp.zeros_like(pre) + p["conv_bias"].astype(f32)
    for j in range(K):
        back = K - 1 - j
        conv = conv + w[j] * jnp.concatenate(
            [jnp.zeros((back, d_in), f32), pre[:S - back]], axis=0)
    u = _silu(conv)
    tbc = mm(u, p["x_proj"])
    t = _rms(tbc[:, :R], p["dt_norm"]["scale"], z["eps"])
    B = _rms(tbc[:, R:R + N], p["b_norm"]["scale"], z["eps"])
    C = _rms(tbc[:, R + N:], p["c_norm"]["scale"], z["eps"])
    dt = jax.nn.softplus(mm(t, p["dt_proj"]) + p["dt_bias"].astype(f32))
    if last is not None:
        dt = jnp.where((jnp.arange(S) <= last)[:, None], dt, 0.0)
    A = -jnp.exp(p["A_log"].astype(f32).T)                  # [d_in, N]

    def token(h, xs):
        u_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * A) * h.astype(f32) \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        h = h.astype(state_dtype)
        return h, jnp.sum(h.astype(f32) * c_t[None, :], axis=1)
    h, y = jax.lax.scan(token, jnp.zeros((d_in, N), state_dtype),
                        (u, dt, B, C))
    y = y + p["D"].astype(f32) * u
    return mm(y * _silu(gate), p["out_proj"]), h


def attention(p, x, z, mm):
    """Causal softmax attention over the whole sequence, no position
    encoding; query head j reads key/value head j // (heads / kv)."""
    S = x.shape[0]
    Hq, Hkv, d = z["heads"], z["kv_heads"], z["head_dim"]
    q = mm(x, p["q_proj"]).reshape(S, Hq, d).transpose(1, 0, 2)
    k = mm(x, p["k_proj"]).reshape(S, Hkv, d).transpose(1, 0, 2)
    v = mm(x, p["v_proj"]).reshape(S, Hkv, d).transpose(1, 0, 2)
    of = jnp.arange(Hq) // (Hq // Hkv)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(_, qh):
        q_h, g = qh
        s = jnp.where(causal, mm(q_h, k[g].T) * d ** -0.5, -jnp.inf)
        return None, mm(jax.nn.softmax(s, axis=-1), v[g])
    _, y = jax.lax.scan(head, None, (q, of))                # [Hq, S, d]
    return mm(y.transpose(1, 0, 2).reshape(S, Hq * d), p["o_proj"])


@functools.lru_cache(maxsize=None)
def _layer_fn(kind, sizes, quant, state_dtype):
    z = dict(sizes)
    mm = _mm(quant)

    def layer(p, x, last):
        n = _rms(x, p["mixer_norm"]["scale"], z["eps"])
        if kind == "mamba":
            y, state = mamba_mixer(p["mixer"], n, z, mm, state_dtype, last)
        else:
            y, state = attention(p["attn"], n, z, mm), None
        x = x + y
        n = _rms(x, p["ffn_norm"]["scale"], z["eps"])
        f = p["mlp"]
        return x + mm(_silu(mm(n, f["gate"])) * mm(n, f["up"]),
                      f["down"]), state
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(quant, eps):
    mm = _mm(quant)
    return jax.jit(lambda scale, table, x: mm(_rms(x, scale, eps), table.T))


def layers_of(params, kinds):
    """The program's tree a layer at a time: (kind, that layer's own
    entries). A run of Mamba layers is stored stacked (``mamba_<run>``),
    an attention layer as ``attn_<i>``."""
    run, n_attn, at = -1, 0, 0
    for i, kind in enumerate(kinds):
        if kind == "attention":
            yield kind, params[f"attn_{n_attn}"]
            n_attn += 1
            continue
        if i == 0 or kinds[i - 1] != "mamba":       # a new run starts
            run, at = run + 1, 0
        yield kind, jax.tree_util.tree_map(
            lambda a, j=at: a[j], params[f"mamba_{run}"])
        at += 1


def forward(params, ids, sizes, quant=None, state_dtype=jnp.float32,
            rows=None, state_after=None):
    """ids [S] int (one sequence) -> logits [S, V] float32 (or the rows
    ``rows`` of it). ``params`` is the program's stored tree (its
    ``"params"`` entry); ``sizes`` from ``sizes_of``. With
    ``state_after`` (a token's index) also the Mamba layers' states
    [n_mamba, d_in, N] after that token: (logits, states)."""
    z = dict(sizes)
    ids = jnp.asarray(ids, jnp.int32)
    last = jnp.int32(ids.shape[0] - 1 if state_after is None
                     else state_after)
    x = params["embed"][ids].astype(jnp.float32)
    states = []
    for kind, p in layers_of(params, z["kinds"]):
        x, state = _layer_fn(kind, sizes, quant, state_dtype)(p, x, last)
        if state is not None:
            states.append(state)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    table = params["embed"]                 # the head is the token table
    head = _head_fn(quant, z["eps"])
    logits = jnp.concatenate([
        head(params["final_norm"]["scale"], table[v:v + VOCAB_BLOCK], x)
        for v in range(0, table.shape[0], VOCAB_BLOCK)], axis=1)
    return logits if state_after is None else (logits, jnp.stack(states))


def served_token_gaps(params, prompt, served, sizes, pad_to: int,
                      controls=()):
    """Teacher-forced check of one served request: run prompt + served
    tokens (padded to ``pad_to``; causality keeps the padding out of the
    rows read) and return, for each served token, how far its reference
    logit lies under its row's maximum, and ``state``: the Mamba layers'
    states [n_mamba, d_in, N] after the last token the request's slot
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
