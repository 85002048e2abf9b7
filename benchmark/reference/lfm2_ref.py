"""Plain LFM2-MoE: a full forward pass over a whole sequence in
jax.numpy, float32 at ``highest``.

Written from the equations of ISSUE 53 / the source's config
(LiquidAI/LFM2-8B-A1B, ``model_type`` ``lfm2_moe``). Every layer is ``x <-
x + Operator_l(RMSNorm(x))`` then ``x <- x + FeedForward_l(RMSNorm(x))``
(eps 1e-5, gain times the normalised value, no bias anywhere), one
RMSNorm after the last layer, the token table as the head. With ``n`` the
operator's normed input and ``m`` the feed-forward part's:

  conv       [B | C | u] = n W_in;  g = B * u;
             c_t = w_0 g_(t-2) + w_1 g_(t-1) + w_2 g_t  (explicit shifts
             over the whole sequence, zeros before its start);
             out = (C * c) W_out
  attention  q = n W_q (heads x d), k, v = n W_k, n W_v (kv_heads x d);
             q and k under an RMSNorm over the d of a head (one gain of d
             each) BEFORE the rotary; rotary over the whole head in halves
             (x[i], x[i + d/2]) by p theta^(-2i/d); query head j reads
             key/value head j // (heads / kv_heads); causal softmax over
             the whole sequence, scale d^-1/2; W_o
  dense      (silu(m W_1) * (m W_3)) W_2
  routed     s = sigmoid(m W_r), never rounded; chosen = the top_k largest
             of s + b (b selects and never weighs); w = s / (sum over the
             chosen of s + 1e-6) times the scaling factor; y = sum over
             the chosen of w_e (silu(m W_1,e) * (m W_3,e)) W_2,e

No cache, no tail, no batching, no kernel, and no code shared with
``ray_tpu/models``, ``ray_tpu/ops`` or ``ray_tpu/parallel/moe.py``.

Departures: every expert multiplies every token of the sequence and a
mask keeps those that chose it (a product's rows are independent: the
same numbers as multiplying the chosen tokens alone). It reads the weights
as the program stores them (bfloat16, under the program's names: the
whole of what the two sides share) and lifts them to float32 a layer, an expert, a head
or a block of the vocabulary at a time, each layer a jitted function of
its own: the check at the published widths then holds one expert's
float32 weights (44 MB) and one block of the table beside the replica,
and only the rows of the logits that are read.

``sizes`` (``sizes_of``) is a hashable tuple of pairs. Options, each a
control or a test's wrong program: ``quant`` rounds both operands of every
matrix product but the router's (``fp8``: the next coarser precision
under the served bfloat16; ``bf16``); ``bias_weighs`` weighs by ``s + b``;
``renorm_eps`` 0 leaves the 1e-6 out; ``norm_after_rotary``.
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


def bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def sizes_of(cfg) -> tuple:
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    n, heads = get("num_hidden_layers"), get("num_attention_heads")
    return tuple(sorted({
        "operators": tuple(get("layer_types"))[:n],
        "dense": get("num_dense_layers"),
        "K": get("conv_L_cache"), "heads": heads,
        "kv_heads": get("num_key_value_heads"),
        "head_dim": get("head_dim") or get("hidden_size") // heads,
        "theta": float(get("rope_theta")),
        "top_k": get("num_experts_per_tok"),
        "scaling": float(get("routed_scaling_factor")),
        "eps": get("norm_eps")}.items()))


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


def rotate(x, theta):
    """x [S, H, d] at positions 0 .. S-1, turned in halves over the whole
    last axis: ``(x[i], x[i + d/2])`` by the angle ``t theta^(-2i/d)``."""
    S, d = x.shape[0], x.shape[-1]
    freqs = jnp.asarray([theta ** (-2.0 * i / d) for i in range(d // 2)],
                        jnp.float32)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(p, x, z, mm, last):
    """x [S, D] (normed) -> ([S, D], the tail [K - 1, D]: the rows of
    ``g`` at tokens ``last - (K - 2) .. last``, zeros before the
    sequence's start)."""
    S, D = x.shape
    K = z["K"]
    bcu = mm(x, p["in_proj"])
    gate_b, gate_c, u = bcu[:, :D], bcu[:, D:2 * D], bcu[:, 2 * D:]
    g = gate_b * u
    w = p["conv"].astype(jnp.float32)                       # [K, D]
    before = jnp.concatenate([jnp.zeros((K - 1, D), jnp.float32), g])
    c = sum(w[j] * before[j:j + S] for j in range(K))
    # g at token t lies at ``before[t + K - 1]``
    tail = jax.lax.dynamic_slice_in_dim(before, last + 1, K - 1)
    return mm(gate_c * c, p["out_proj"]), tail


def attention(p, x, z, mm, norm_after_rotary=False):
    """Causal softmax attention over the whole sequence, a query head at
    a time. Returns ([S, D], K as attended (normed, rotated) [S, kv_heads
    x d], V [S, kv_heads x d]: the rows a cache would hold)."""
    S = x.shape[0]
    H, Hkv, d = z["heads"], z["kv_heads"], z["head_dim"]
    q = mm(x, p["q_proj"]).reshape(S, H, d)
    k = mm(x, p["k_proj"]).reshape(S, Hkv, d)
    v = mm(x, p["v_proj"]).reshape(S, Hkv, d)
    norm_q = lambda t: _rms(t, p["q_norm"]["scale"], z["eps"])  # noqa: E731
    norm_k = lambda t: _rms(t, p["k_norm"]["scale"], z["eps"])  # noqa: E731
    if norm_after_rotary:
        q, k = norm_q(rotate(q, z["theta"])), norm_k(rotate(k, z["theta"]))
    else:
        q, k = rotate(norm_q(q), z["theta"]), rotate(norm_k(k), z["theta"])
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(_, qh):
        q_h, j = qh
        g = j // (H // Hkv)
        s = jnp.where(causal, mm(q_h, k[:, g].T) * d ** -0.5, -jnp.inf)
        return None, mm(jax.nn.softmax(s, axis=-1), v[:, g])
    _, y = jax.lax.scan(head, None, (q.transpose(1, 0, 2), jnp.arange(H)))
    return mm(y.transpose(1, 0, 2).reshape(S, H * d), p["o_proj"]), \
        k.reshape(S, Hkv * d), v.reshape(S, Hkv * d)


def routed_experts(p, m, z, mm, bias_weighs=False, renorm_eps=1e-6):
    """m [S, D] -> [S, D]: the router in the published order, then a loop
    over the experts with dense masks."""
    s = jax.nn.sigmoid(jnp.matmul(m, p["router"].astype(jnp.float32),
                                  precision=_HI))           # never rounded
    biased = s + p["router_bias"].astype(jnp.float32)
    _, chosen = jax.lax.top_k(biased, z["top_k"])
    w = jnp.take_along_axis(biased if bias_weighs else s, chosen, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + renorm_eps) * z["scaling"]

    def expert(y, e):
        i, gate, up, down = e
        mask = jnp.sum(jnp.where(chosen == i, w, 0.0), axis=1)
        act = _silu(mm(m, gate)) * mm(m, up)
        return y + mask[:, None] * mm(act, down), None
    y, _ = jax.lax.scan(
        expert, jnp.zeros_like(m),
        (jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
         p["w_down"]))
    return y


@functools.lru_cache(maxsize=None)
def _layer_fn(operator, dense, sizes, quant, options):
    z = dict(sizes)
    mm = _mm(quant)
    opt = dict(options)

    def layer(p, x, last, kv_at):
        n = _rms(x, p["operator_norm"]["scale"], z["eps"])
        if operator == "conv":
            y, kept = short_conv(p["conv"], n, z, mm, last)
        else:
            y, k, v = attention(p["attn"], n, z, mm,
                                opt.get("norm_after_rotary", False))
            kept = jnp.stack([k[kv_at], v[kv_at]])
        x = x + y
        m = _rms(x, p["ffn_norm"]["scale"], z["eps"])
        if dense:
            f = p["mlp"]
            y = mm(_silu(mm(m, f["gate"])) * mm(m, f["up"]), f["down"])
        else:
            y = routed_experts(p["moe"], m, z, mm,
                               opt.get("bias_weighs", False),
                               opt.get("renorm_eps", 1e-6))
        return x + y, kept
    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(quant, eps):
    mm = _mm(quant)
    return jax.jit(lambda scale, table, x: mm(_rms(x, scale, eps), table.T))


def layers_of(params, z):
    """The program's tree a layer at a time: (operator, dense?, the
    entries of ``layers_<i>``)."""
    for i, op in enumerate(z["operators"]):
        yield op, i < z["dense"], params[f"layers_{i}"]


def forward(params, ids, sizes, quant=None, rows=None, state_after=None,
            kv_at=None, **options):
    """ids [S] int (one sequence) -> logits [S, V] float32 (or the rows
    ``rows`` of it). ``params`` is the program's stored tree (its
    ``"params"`` entry); ``sizes`` from ``sizes_of``. With
    ``state_after`` (a token's index) also the convolution layers' tails
    [n_conv, K - 1, D] after that token: (logits, tails); with ``kv_at``
    (positions) also what a cache would hold of them, [n_attn, 2, len,
    kv_heads x d] (K as attended, V): (logits, tails, rows)."""
    z = dict(sizes)
    ids = jnp.asarray(ids, jnp.int32)
    last = jnp.int32(ids.shape[0] - 1 if state_after is None
                     else state_after)
    x = params["embed"][ids].astype(jnp.float32)
    tails, cached = [], []
    opts = tuple(sorted(options.items()))
    at = jnp.zeros((1,), jnp.int32) if kv_at is None \
        else jnp.asarray(kv_at, jnp.int32)
    for op, dense, p in layers_of(params, z):
        x, kept = _layer_fn(op, dense, sizes, quant, opts)(p, x, last, at)
        (tails if op == "conv" else cached).append(kept)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    table = params["embed"]                 # the head is the token table
    head = _head_fn(quant, z["eps"])
    logits = jnp.concatenate([
        head(params["embedding_norm"]["scale"], table[v:v + VOCAB_BLOCK], x)
        for v in range(0, table.shape[0], VOCAB_BLOCK)], axis=1)
    if state_after is None:
        return logits
    if kv_at is None:
        return logits, jnp.stack(tails)
    return logits, jnp.stack(tails), jnp.stack(cached)


# (name, quant, options): what `--control` computes in the program's place
CONTROLS = (("fp8", fp8, ()),
            ("bias_weighs", None, (("bias_weighs", True),)))


KV_TAIL = 256      # cached rows compared: the last written


def probe_positions(fed: int):
    """The positions whose cached K and V rows a finished request is
    compared at, once ``fed`` tokens were written: the last ``KV_TAIL``,
    clipped to 0 .. fed - 1 (a short sequence repeats its first row)."""
    import numpy as np
    return np.clip(np.arange(fed - KV_TAIL, fed), 0, fed - 1)


def served_token_gaps(params, prompt, served, sizes, pad_to: int,
                      controls=()):
    """Teacher-forced check of one served request: run prompt + served
    tokens (padded to ``pad_to``; causality keeps the padding out of the
    rows read) and return, for each served token, how far its reference
    logit lies under its row's maximum (``gaps``); ``tail``: the
    convolution layers' tails [n_conv, K - 1, D] after the last token the
    request's slot took in (the last served token was sampled and never
    fed); ``kv``: what the attention layers' pages would hold at
    ``probe_positions`` [n_attn, 2, KV_TAIL, row]. ``controls`` is a
    tuple of (name, quant, options): for each, the same figures for the
    tokens that the reference computed that way would have picked
    instead, and the tails and rows it would have left."""
    import numpy as np
    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((pad_to,), np.int32)
    ids[:n_p + n_s] = list(prompt) + list(served)
    at = np.arange(n_p - 1, n_p - 1 + n_s)
    fed = n_p + n_s - 1         # tokens written; the last one's index + 1
    where = probe_positions(fed)
    rows, tail, kv = forward(params, ids, sizes, rows=at,
                             state_after=fed - 1, kv_at=where)
    tok = jnp.asarray(np.asarray(served, np.int32))
    top = jnp.max(rows, axis=-1)
    out = {"gaps": np.asarray(top - rows[jnp.arange(n_s), tok]),
           "logit_std": float(jnp.std(rows[0])),
           "argmax_equal": int(jnp.sum(jnp.argmax(rows, -1) == tok)),
           "fed": fed, "tail": np.asarray(tail), "kv": np.asarray(kv)}
    for name, quant, options in controls:
        low, low_tail, low_kv = forward(
            params, ids, sizes, quant, rows=at, state_after=fed - 1,
            kv_at=where, **dict(options))
        pick = jnp.argmax(low, axis=-1)
        out[f"control_{name}_gaps"] = np.asarray(
            top - rows[jnp.arange(n_s), pick])
        out[f"control_{name}_tail"] = np.asarray(low_tail)
        out[f"control_{name}_kv"] = np.asarray(low_kv)
    return out
