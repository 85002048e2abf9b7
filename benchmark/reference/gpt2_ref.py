"""Plain GPT-2: forward, loss and gradient in jax.numpy, float32.

Written from the published equations (Radford et al. 2019; the layout of
openai-community/gpt2): learned token and position tables, pre-LayerNorm
blocks of causal multi-head attention and a 4x GELU(tanh) MLP, a final
LayerNorm and a head tied to the token table. No kernel, no cache, no
batching tricks, and no code shared with ``ray_tpu/models/gpt2.py``.
Matrix products run under ``default_matmul_precision("highest")``, or,
for the control, through ``fp8`` below.

Weights come from a seed in one jitted call (``init_params``): every
matrix, bias and table N(0, 0.02), every gain 1 + N(0, 0.02), so that no
term of the equations can be dropped unnoticed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5      # openai-community/gpt2 config.json layer_norm_epsilon;
                   # a configuration file may state another (``ln_eps``)


def _init(key, n_layer, n_embd, vocab_size, n_positions, dtype):
    """One random draw per kind of tensor, all layers of a kind stacked
    in it: a dozen draws, not a dozen a layer, so the program is small
    enough to load from the compile cache in a second or two (432 draws
    made a 55 MB executable that took 45 s to load: my chip run, PR
    24)."""
    E, L = n_embd, n_layer
    counter = [0]

    def normal(shape, mean=0.0):
        counter[0] += 1
        k = jax.random.fold_in(key, counter[0])
        return (mean + 0.02 * jax.random.normal(k, shape, jnp.float32)
                ).astype(dtype)

    def ln(lead=()):
        return {"g": normal(lead + (E,), 1.0), "b": normal(lead + (E,))}

    def dense(i, o):
        return {"w": normal((L, i, o)), "b": normal((L, o))}

    stacked = {"ln_1": ln((L,)), "attn": dense(E, 3 * E),
               "proj": dense(E, E), "ln_2": ln((L,)),
               "fc": dense(E, 4 * E), "fc_proj": dense(4 * E, E)}
    return {
        "wte": normal((vocab_size, E)),
        "wpe": normal((n_positions, E)),
        "h": [jax.tree_util.tree_map(lambda x: x[i], stacked)
              for i in range(L)],
        "ln_f": ln(),
    }


def init_params(seed: int, *, n_layer: int, n_embd: int, vocab_size: int,
                n_positions: int, dtype=jnp.float32):
    """Every weight from ``seed``, on the default device, in one jitted
    call. ``seed`` may be any whole number; it is folded to 32 bits."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    return jax.jit(functools.partial(
        _init, n_layer=n_layer, n_embd=n_embd, vocab_size=vocab_size,
        n_positions=n_positions, dtype=dtype))(key)


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax -> 448), the
    usual fp8 recipe; the control's matrix products see both operands
    through this."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(params, ids, n_head: int, quant=None, ln_eps: float = LN_EPS):
    """ids [B, S] int -> logits [B, S, V] float32. ``quant`` rounds both
    operands of every matrix product (None: float32 at 'highest')."""
    q_ = quant if quant is not None else (lambda t: t)

    def mm(a, b):
        return jnp.matmul(q_(a.astype(jnp.float32)),
                          q_(b.astype(jnp.float32)),
                          precision=jax.lax.Precision.HIGHEST)

    B, S = ids.shape
    E = params["wte"].shape[1]
    D = E // n_head
    x = (params["wte"][ids] + params["wpe"][jnp.arange(S)][None]
         ).astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))
    for h in params["h"]:
        a = _layer_norm(x, h["ln_1"], ln_eps)
        qkv = mm(a, h["attn"]["w"]) + h["attn"]["b"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q, k, v = (t.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        s = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(D)
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        y = mm(p, v).transpose(0, 2, 1, 3).reshape(B, S, E)
        x = x + mm(y, h["proj"]["w"]) + h["proj"]["b"]
        m = _layer_norm(x, h["ln_2"], ln_eps)
        m = _gelu_tanh(mm(m, h["fc"]["w"]) + h["fc"]["b"])
        x = x + mm(m, h["fc_proj"]["w"]) + h["fc_proj"]["b"]
    x = _layer_norm(x, params["ln_f"], ln_eps)
    return mm(x, params["wte"].T)


def nll_sum(params, ids, n_head: int, quant=None, ln_eps: float = LN_EPS):
    """Sum over rows and positions of -log p(ids[t+1] | ids[:t+1]), and
    the number of predicted tokens."""
    logits = forward(params, ids, n_head, quant, ln_eps)[:, :-1]
    labels = ids[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - ll), labels.size


def loss_and_grad_norm(params, ids, n_head: int, quant=None,
                       rows_per_call: int = 2, ln_eps: float = LN_EPS):
    """Mean next-token loss over all of ``ids`` and the global L2 norm of
    its gradient, computed a few rows at a time so that float32 logits of
    the whole batch never exist at once."""
    n_rows = ids.shape[0]

    def chunk_loss(p, chunk):
        total, _ = nll_sum(p, chunk, n_head, quant, ln_eps)
        return total

    vg = jax.jit(jax.value_and_grad(chunk_loss))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    total, grads = 0.0, None
    for i in range(0, n_rows, rows_per_call):
        t, g = vg(params, ids[i:i + rows_per_call])
        total += float(t)
        grads = g if grads is None else add(grads, g)
    count = n_rows * (ids.shape[1] - 1)
    sq = jax.jit(lambda g: sum(jnp.sum(jnp.square(x)) for x in
                               jax.tree_util.tree_leaves(g)))(grads)
    return total / count, math.sqrt(float(sq)) / count


def served_token_gaps(params, prompt, served, n_head: int, pad_to: int,
                      with_control=None, ln_eps: float = LN_EPS):
    """Teacher-forced check of one served request: run prompt + served
    tokens (padded to ``pad_to`` positions; causality keeps the padding
    out of the rows read) and return, for each served token, how far its
    reference logit lies under its row's maximum. With ``with_control``
    (a quant function) also return the same figure for the tokens that
    the reference rounded that way would have picked in the program's
    place."""
    import numpy as np
    n_p, n_s = len(prompt), len(served)
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :n_p + n_s] = list(prompt) + list(served)
    ids = jnp.asarray(ids)
    fwd = _jitted_forward(n_head, None, ln_eps)
    rows = fwd(params, ids)[0, n_p - 1:n_p - 1 + n_s]           # [n, V]
    tok = jnp.asarray(np.asarray(served, np.int32))
    top = jnp.max(rows, axis=-1)
    gaps = top - rows[jnp.arange(n_s), tok]
    out = {"gaps": np.asarray(gaps), "logit_std": float(jnp.std(rows[0])),
           "argmax_equal": int(jnp.sum(jnp.argmax(rows, -1) == tok))}
    if with_control is not None:
        low = _jitted_forward(n_head, with_control, ln_eps)(params, ids)[
            0, n_p - 1:n_p - 1 + n_s]
        pick = jnp.argmax(low, axis=-1)
        out["control_gaps"] = np.asarray(top - rows[jnp.arange(n_s), pick])
    return out


@functools.lru_cache(maxsize=None)
def _jitted_forward(n_head, quant, ln_eps):
    return jax.jit(lambda p, ids: forward(p, ids, n_head, quant, ln_eps))
