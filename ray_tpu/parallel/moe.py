"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

TPU-native GShard/Switch formulation (reference repo has no MoE engine —
SURVEY.md §2.6 marks EP absent; the design bar here is the public GShard/
Switch-Transformer dispatch): routing and dispatch are dense einsums over a
[tokens, experts, capacity] one-hot — no gather/scatter, fully static
shapes, so XLA tiles everything onto the MXU and inserts the all-to-alls
over ICI when the expert dimension is sharded P("ep", ...).

  gates    [S, E]     router softmax
  dispatch [S, E, C]  one-hot token->(expert, slot), capacity-dropped
  combine  [S, E, C]  dispatch * gate
  xin      = einsum('sec,sd->ecd', dispatch, x)     (all_to_all over ep)
  h        = act(einsum('ecd,edf->ecf', xin, w1))   (expert-sharded)
  out      = einsum('ecf,efd->ecd', h, w2)
  y        = einsum('sec,ecd->sd', combine, out)    (all_to_all back)

Top-1 (Switch) routing with the standard load-balance auxiliary loss.

``RoutedExperts`` beside it is the layer the served models use: sigmoid
scores over all experts, top-k, renormalised, no capacity and no drop,
SwiGLU experts, computed for the experts one chip holds (docs/
LLM_SERVING.md, "Routed experts"). Its product multiplies the groups
that hold a token and reads no other expert's weights: whole rows
through the touched experts for few tokens (``ops/routed_experts.py``),
sorted row blocks for many.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ray_tpu.ops.routed_experts import touched_experts


class MoE(nn.Module):
    """Switch-style top-1 MoE feed-forward layer.

    Returns (y, aux_loss). Partition the expert params over ``ep`` via
    ``expert_sharding_rule`` (leading expert axis).
    """
    num_experts: int
    d_ff: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32
    act: Callable = nn.gelu
    router_noise: float = 0.0

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        *lead, d = x.shape
        s = 1
        for n in lead:
            s *= n
        e = self.num_experts
        c = max(1, int(self.capacity_factor * s / e))
        xf = x.reshape(s, d)

        # ---- router (f32 for numerics, as in every public MoE impl)
        logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            xf.astype(jnp.float32))
        if self.router_noise > 0.0 and not deterministic:
            rng = self.make_rng("router")
            logits = logits + jax.random.uniform(
                rng, logits.shape, minval=1.0 - self.router_noise,
                maxval=1.0 + self.router_noise)
        gates = jax.nn.softmax(logits, axis=-1)            # [S, E]
        expert_idx = jnp.argmax(gates, axis=-1)            # [S]
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)

        # load-balance aux loss (Switch eq. 4): E * sum(frac_tokens * prob)
        density = onehot.mean(axis=0)
        prob_mean = gates.mean(axis=0)
        aux = e * jnp.sum(density * prob_mean)

        # position of each token within its expert (capacity slots)
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # [S, E]
        slot = pos.sum(axis=-1)                            # [S]
        keep = slot < c
        gate_val = (gates * onehot).sum(-1) * keep         # [S]
        dispatch = (onehot * keep[:, None])[:, :, None] * \
            jax.nn.one_hot(jnp.clip(slot, 0, c - 1), c,
                           dtype=jnp.float32)[:, None, :]  # [S, E, C]
        combine = dispatch * gate_val[:, None, None]

        # ---- expert computation, sharded over ep on the leading dim
        w1 = self.param(
            "experts_w1", nn.initializers.lecun_normal(), (e, d, self.d_ff),
            jnp.float32)
        w2 = self.param(
            "experts_w2", nn.initializers.lecun_normal(), (e, self.d_ff, d),
            jnp.float32)
        xin = jnp.einsum("sec,sd->ecd", dispatch.astype(self.dtype),
                         xf.astype(self.dtype))
        h = self.act(jnp.einsum("ecd,edf->ecf", xin, w1.astype(self.dtype)))
        out = jnp.einsum("ecf,efd->ecd", h, w2.astype(self.dtype))
        y = jnp.einsum("sec,ecd->sd", combine.astype(self.dtype), out)
        return y.reshape(*lead, d).astype(x.dtype), aux


class SwiGLU(nn.Module):
    """``W_d (SiLU(W_g x) * W_u x)``: a dense feed-forward, and the
    shared expert of ``RoutedExperts``. Weights are stored in
    ``dtype``."""
    d_ff: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        init = nn.initializers.normal(0.02)
        gate = self.param("gate", init, (d, self.d_ff), self.dtype)
        up = self.param("up", init, (d, self.d_ff), self.dtype)
        down = self.param("down", init, (self.d_ff, d), self.dtype)
        x = x.astype(self.dtype)
        return jnp.matmul(nn.silu(x @ gate) * (x @ up), down,
                          preferred_element_type=jnp.float32)


# RoutedExperts: the most tokens that go as whole rows through the touched
# experts, and the rows of a sorted product's block. Up to WHOLE_ROWS_BELOW
# an expert's weights (read once) outweigh the rows it did not get; above,
# rows are worth sorting. What is served lies far to either side (a decode
# step has at most 64 tokens, a prefill at least 512), so the two were set
# by that and not by a sweep: nothing between was measured.
WHOLE_ROWS_BELOW = 256
BLOCK_ROWS = 256
# The sorted product holds its rows for the worst case (every assignment
# landing on the experts held here) in float32: [T * k + E * BLOCK_ROWS,
# d]. Up to this many bytes of them it holds them (4,096 tokens at
# Kimi-Linear's width: 453 MB). Above (8,192 tokens at Kimi-K2's width:
# 1.97 GB of rows beside 0.98 of their inputs, refused by the compiler
# for the described v5e, PR 35) it holds index arrays only and moves a
# block's rows inside the loop: ``_experts_by_block``.
SORTED_ROWS_BYTES = 640 << 20


class RoutedExperts(nn.Module):
    """A routed-experts layer that drops no token, for the experts held
    HERE (the share of one chip under expert parallelism).

    The router scores every token against all ``num_experts`` (sigmoid,
    float32), chooses the ``top_k`` largest of ``score + bias``, and
    weighs the chosen by ``score / sum(chosen scores)`` (if
    ``renormalize``) times ``scaling``. Of the chosen, this layer
    computes those in ``held = (first, count)``: the part of the result
    that its own SwiGLU experts give, plus ``shared_d_ff`` wide shared
    expert(s) that every chip computes alike. What the absent experts
    would add is left out; nothing stands in for them. Returns ``(y,
    counts)``, counts [count] int32 the real tokens sent to each held
    expert this call.

    One algorithm, "multiply the groups that hold a token", in two
    forms by the number of tokens (static). Up to ``WHOLE_ROWS_BELOW``
    tokens (a decode step) each expert that got a token multiplies all
    the rows, the rows that did not choose it weighed zero, and an expert
    without a token is not read at all: what such a step costs is the
    weights it reads, so its time follows ``counts > 0`` (the step's
    ``experts_touched``) and its result does not. Forward only
    (``ops/routed_experts.py``). Above it the assignments are sorted by
    expert into row blocks of ``BLOCK_ROWS`` that each belong to one
    expert, and a loop multiplies the blocks that hold a token (rows for
    the worst case, every assignment landing here, so the load changes
    the time and never the result).
    """
    num_experts: int
    d_ff: int
    top_k: int
    held: Optional[Tuple[int, int]] = None       # None: all of them
    scaling: float = 1.0
    renormalize: bool = True
    shared_d_ff: int = 0
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, valid=None):
        *lead, d = x.shape
        xf = x.reshape(-1, d)
        T = xf.shape[0]
        first, count = self.held or (0, self.num_experts)
        real = jnp.ones((T,), bool) if valid is None else valid.reshape(T)

        with jax.named_scope("moe/router"):
            w_r = self.param("router", nn.initializers.normal(0.02),
                             (d, self.num_experts), jnp.float32)
            bias = self.param("router_bias", nn.initializers.zeros,
                              (self.num_experts,), jnp.float32)
            scores = jax.nn.sigmoid(jnp.matmul(
                xf.astype(jnp.float32), w_r,
                precision=jax.lax.Precision.HIGHEST))          # [T, E]
            _, chosen = jax.lax.top_k(scores + bias, self.top_k)
            w = jnp.take_along_axis(scores, chosen, axis=1)    # [T, k]
            if self.renormalize:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
            w = w * self.scaling
            here = (chosen >= first) & (chosen < first + count) \
                & real[:, None]
            # the held experts are 0..count-1 here; `count` means "not
            # this chip's"
            local = jnp.where(here, chosen - first, count)
            counts = jnp.sum(jax.nn.one_hot(local, count + 1,
                                            dtype=jnp.int32),
                             axis=(0, 1))[:count]

        init = nn.initializers.normal(0.02)
        w_gate = self.param("w_gate", init, (count, d, self.d_ff),
                            self.dtype)
        w_up = self.param("w_up", init, (count, d, self.d_ff), self.dtype)
        w_down = self.param("w_down", init, (count, self.d_ff, d),
                            self.dtype)
        xb = xf.astype(self.dtype)
        with jax.named_scope("moe/experts"):
            if T <= WHOLE_ROWS_BELOW:
                combine = jnp.sum(
                    jax.nn.one_hot(local, count, dtype=jnp.float32)
                    * w[..., None], axis=1)                    # [T, count]
                y = touched_experts(xb, combine, counts, w_gate, w_up,
                                    w_down)
            else:
                rows = T * self.top_k + count * BLOCK_ROWS
                product = _experts_by_block \
                    if rows * d * 4 > SORTED_ROWS_BYTES else _experts_sorted
                y = product(xb, local, w, w_gate, w_up, w_down)
        if self.shared_d_ff:
            with jax.named_scope("moe/shared"):
                y = y + SwiGLU(self.shared_d_ff, self.dtype,
                               name="shared")(xb)
        return y.reshape(*lead, d), counts


def _sorted_rows(local, E: int):
    """The assignments sorted by expert, each expert's group padded to
    whole blocks of ``BLOCK_ROWS`` rows: ``order`` (the assignments in
    sorted order), ``dest`` (each one's row; ``rows`` for an assignment
    that is not this chip's), ``rows`` (rows for the worst case), and
    for each block where it starts, which expert's it is, and ``ends``
    (where each expert's group ends: the last is the live rows)."""
    A, bm = local.size, BLOCK_ROWS
    e = local.reshape(A)                                   # E: not here
    sizes = jnp.sum(jax.nn.one_hot(e, E + 1, dtype=jnp.int32), axis=0)
    padded = -(-sizes[:E] // bm) * bm
    ends = jnp.cumsum(padded)                              # [E]
    order = jnp.argsort(e, stable=True)
    sorted_e = e[order]
    rank = jnp.arange(A) - (jnp.cumsum(sizes) - sizes)[sorted_e]
    rows = -(-A // bm) * bm + E * bm                       # worst case
    dest = jnp.where(sorted_e < E,
                     (ends - padded)[jnp.minimum(sorted_e, E - 1)] + rank,
                     rows)
    return e, order, dest, rows, ends


def _experts_sorted(x, local, w, w_gate, w_up, w_down):
    """Grouped products: the assignments sorted by expert, each expert's
    group padded to whole blocks of ``BLOCK_ROWS`` rows, one SwiGLU a
    block with that block's expert, blocks without a token skipped."""
    T, d = x.shape
    E, k, bm = w_gate.shape[0], local.shape[1], BLOCK_ROWS
    A = T * k
    e, order, dest, rows, ends = _sorted_rows(local, E)
    xs = jnp.zeros((rows, d), x.dtype).at[dest].set(
        x[order // k], mode="drop")
    n_blocks = rows // bm
    starts = jnp.arange(n_blocks) * bm
    block_expert = jnp.minimum(
        jnp.searchsorted(ends, starts, side="right"), E - 1)

    def block(_, b):
        def run():
            xb = jax.lax.dynamic_slice_in_dim(xs, b * bm, bm)
            i = block_expert[b]
            return jnp.matmul(nn.silu(xb @ w_gate[i]) * (xb @ w_up[i]),
                              w_down[i], preferred_element_type=jnp.float32)
        return None, jax.lax.cond(
            starts[b] < ends[-1], run,
            lambda: jnp.zeros((bm, d), jnp.float32))

    _, ys = jax.lax.scan(block, None, jnp.arange(n_blocks))
    ys = ys.reshape(rows, d)
    back = jnp.zeros((A,), jnp.int32).at[order].set(
        jnp.minimum(dest, rows - 1).astype(jnp.int32))
    weight = jnp.where(e < E, w.reshape(A), 0.0)
    y = ys[back] * weight[:, None]
    return jnp.sum(y.reshape(T, k, d), axis=1)


def _experts_by_block(x, local, w, w_gate, w_up, w_down):
    """The same grouped products with nothing of the worst case's size
    but index arrays: which token and what weight each sorted row has.
    A loop over the LIVE blocks gathers a block's rows from ``x``,
    multiplies them by the block's expert and adds the weighed result to
    its tokens' rows of the output, so what moves is the assignments
    that landed here (12 experts of 384 get a 32nd of them) and not all
    ``T * k``."""
    T, d = x.shape
    E, k, bm = w_gate.shape[0], local.shape[1], BLOCK_ROWS
    _, order, dest, rows, ends = _sorted_rows(local, E)
    token = jnp.full((rows,), T, jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")       # T: no token
    weight = jnp.zeros((rows,), jnp.float32).at[dest].set(
        w.reshape(T * k)[order], mode="drop")
    block_expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(rows // bm) * bm, side="right"), E - 1)

    def block(b, y):
        tok = jax.lax.dynamic_slice_in_dim(token, b * bm, bm)
        xb = x[jnp.minimum(tok, T - 1)]
        i = block_expert[b]
        out = jnp.matmul(nn.silu(xb @ w_gate[i]) * (xb @ w_up[i]),
                         w_down[i], preferred_element_type=jnp.float32)
        out = out * jax.lax.dynamic_slice_in_dim(weight, b * bm, bm)[:, None]
        return y.at[tok].add(out, mode="drop")

    return jax.lax.fori_loop(0, ends[-1] // bm, block,
                             jnp.zeros((T, d), jnp.float32))


def expert_sharding_rule(mesh, path: Tuple[str, ...], shape, spec):
    """Param-sharding hook: leaves named experts_* shard P("ep", ...) on the
    expert axis (compose with the default rules for other leaves)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    name = "/".join(str(p) for p in path)
    if "experts_" in name and spec.ep > 1 and shape and \
            shape[0] % spec.ep == 0:
        return NamedSharding(mesh, P("ep", *([None] * (len(shape) - 1))))
    return None
