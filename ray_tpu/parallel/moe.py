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
or softmax scores over the router's whole width, top-k, renormalised or
not, no capacity and no drop, gated experts (``act``: SwiGLU's SiLU, or
ReGLU's ReLU, in both kernels and the shared expert), computed for the
experts one chip holds (docs/LLM_SERVING.md, "Routed experts"). The
router may read another tensor than the experts multiply (``router_x``: a
layer that routes on its attention's input, so that the choice and the
sort do not wait for the attention). Its product
multiplies the groups that hold a token and reads no other expert's
weights: whole rows through the touched experts for few tokens, sorted
row blocks for many (both kernels: ``ops/routed_experts.py``). A router
may have ``zero_experts`` outputs beyond its real experts (LongCat-Flash's
zero-compute experts): a choice of one gives the token itself, takes no
row in either product and reads no weight.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.routed_experts import ACTS, ROW_TILE, grouped_experts, \
    touched_experts


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
    shared expert of ``RoutedExperts`` (``act="relu"``: ReGLU). Weights
    are stored in ``dtype``."""
    d_ff: int
    dtype: Any = jnp.bfloat16
    act: str = "silu"

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        init = nn.initializers.normal(0.02)
        gate = self.param("gate", init, (d, self.d_ff), self.dtype)
        up = self.param("up", init, (d, self.d_ff), self.dtype)
        down = self.param("down", init, (self.d_ff, d), self.dtype)
        x = x.astype(self.dtype)
        return jnp.matmul(ACTS[self.act](x @ gate) * (x @ up), down,
                          preferred_element_type=jnp.float32)


# RoutedExperts: the most tokens that go as whole rows through the touched
# experts. Up to WHOLE_ROWS_BELOW an expert's weights (read once) outweigh
# the rows it did not get; above, rows are worth sorting. A touched expert
# multiplies ALL the rows, 2 FLOPs a row for each 2-byte weight it reads,
# so its product stands on the chip's ridge where the rows are the peak
# over the bandwidth (a v5e: 197 TFLOP/s over 819 GB/s = 240 rows) and is
# bound by the weights below it: 256 is that ridge in whole row tiles.
# Measured AT it (my chip run, PR 53: LFM2-8B-A1B, 32 experts of 2048 x
# 1792, 4 a token, all held, 256 rows a step, so an expert expects 32
# tokens and the touched form multiplies 8 times the routed pairs, the
# sorted form at blocks of 128 rows 4 times; at least 28 experts touched):
# a layer alone 0.967 ms touched (its 705 MB of weights take 0.86 at the
# bandwidth) against 1.054 sorted into blocks of 128 rows, 1.111 of 64,
# 1.505 of 32, 1.248 of 256; with 160 or 129 of the 256 rows real the same
# order (0.969 / 1.054 and 0.942 / 1.022 / 0.999 of 64); inside the 14
# routed layers of the b256 decode program 19.41 ms a step touched against
# 20.46 (blocks of 128) and 21.73 (of 64), and 18.45 against 19.45 with 160
# rows real: the touched form wins by 5% of a step, because both read the
# same weights and the sorted one pays its sort, its gathers and 32 more
# grid steps. Whatever an expert expects (``T * top_k`` over the router's
# width: 32 there, the most of any model served here; fewer pairs an expert
# leave the sorted form less to save), a bucket of 256 rows goes whole.
# Above 256 rows and below 512 no program exists (a bucket is a power of
# two) and nothing was measured.
WHOLE_ROWS_BELOW = 256
# The grouped product's sorted rows (bfloat16 in, float32 out) for the
# worst case (every assignment landing on the experts held here) are held
# whole up to ROWS_BYTES: one gather, one kernel call, and each token
# gathers its results back (a Laguna prompt: 1.5 GiB of rows beside 12.1
# GiB of weights and pools; tests/test_chip_compile.py holds that it
# fits). Above (Kimi-K2's prompt: 2.75 GiB, refused by the compiler) a loop
# over the live chunks of CHUNK_BYTES of them holds a chunk at a time and
# adds its results to their tokens' rows: that scatter-add costs a Laguna
# layer 5.5 ms where the gather back costs 2.7, and a Kimi-K2 layer more
# the larger the chunk (PERF.md, PR 40: 2-4 blocks of 256 rows read best
# there). Index arrays alone always have the worst case's length.
# The loop also takes the rows where the worst case is more than
# ROWS_OVER_EXPECTED times what even routing sends here (groups padded):
# whole, the rows gathered and each assignment's result gathered back are
# the worst case's whatever landed. LongCat-Flash's share (16 of a router's
# 768 outputs held: 26,624 rows for the ~2,560 of a 2,048-token prompt,
# 10.4 times) cost 10.4 ms a layer so and 3.8 by the loop (PERF.md, PR
# 41); Kimi-Linear's (64 of 256: twice) and Laguna's (all: once) stay
# whole. Nothing between 2 and 10.4 was measured.
ROWS_BYTES = 2 << 30
CHUNK_BYTES = 32 << 20
ROWS_OVER_EXPECTED = 8


class ExpertProduct(NamedTuple):
    """Which product ``RoutedExperts`` runs for ``T`` tokens, and the
    rows of its blocks: what ``expert_product`` chose from the shapes."""
    name: str           # "touched_kernel" | "grouped_kernel"
    block_rows: int     # touched: all the (padded) rows; grouped: a block's
    chunk_rows: int     # grouped: sorted rows held at a time

    def rows_multiplied(self, counts) -> int:
        """The rows that went through an expert's three products, from
        the per-expert token counts [..., experts] of a call: the rows of
        the live blocks (whole rows for every touched expert)."""
        counts = np.asarray(counts)
        if self.name == "touched_kernel":
            return int((counts > 0).sum()) * self.block_rows
        return int((-(-counts // self.block_rows)).sum()) * self.block_rows


def expert_product(T: int, top_k: int, num_experts: int, held: int, d: int,
                   itemsize: int = 2, zero_experts: int = 0) -> ExpertProduct:
    """The product for ``T`` tokens of width ``d``, each sent to
    ``top_k`` of the router's ``num_experts + zero_experts`` outputs,
    ``held`` of the real ones here. Above ``WHOLE_ROWS_BELOW`` tokens the
    block is 128 rows where an expert expects no more (``T * top_k`` over
    the router's width: a zero-compute output takes its share of the
    assignments and no row), else 256 (the kernel
    moves bytes, not FLOPs: a block's rows and its expert's weights in 16
    us at 256 rows a block and 6.3 MB an expert, two blocks of 128 in 21,
    a block of 512 in 25 while it pads twice as much: PERF.md, PR 40);
    the rows held at a time are the worst case's where they fit
    ``ROWS_BYTES`` and are at most ``ROWS_OVER_EXPECTED`` times what even
    routing sends here, else the whole blocks that fit ``CHUNK_BYTES``."""
    if T <= WHOLE_ROWS_BELOW:
        rows = -(-T // ROW_TILE) * ROW_TILE
        return ExpertProduct("touched_kernel", rows, rows)
    width = num_experts + zero_experts
    bm = 128 if T * top_k <= 128 * width else 256
    rows = _worst_rows(T * top_k, held, bm)
    expected = _worst_rows(-(-T * top_k * held // width), held, bm)
    if rows * d * (itemsize + 4) > ROWS_BYTES \
            or rows > ROWS_OVER_EXPECTED * expected:
        rows = max(1, CHUNK_BYTES // (bm * d * (itemsize + 4))) * bm
    return ExpertProduct("grouped_kernel", bm, rows)


class RoutedExperts(nn.Module):
    """A routed-experts layer that drops no token, for the experts held
    HERE (the share of one chip under expert parallelism).

    The router scores every token against its ``num_experts +
    zero_experts`` outputs (``score``: ``sigmoid``, or ``softmax`` over
    the whole width; float32), chooses the ``top_k`` largest of ``score +
    bias``, and weighs the chosen by ``score / (sum(chosen scores) +
    renormalize_eps)`` (if ``renormalize``; else the score as it is)
    times ``scaling``. Of the
    chosen, this layer computes those in ``held = (first, count)``: the
    part of the result that its own gated experts give (``act``: the
    gate's activation, ``"silu"`` or ``"relu"``), plus
    ``shared_d_ff`` wide shared expert(s) that every chip computes alike.
    ``router_x`` (the shape of ``x``): what the router scores in place of
    ``x``, which the experts still multiply.
    What the absent experts would add is left out; nothing stands in for
    them. Returns ``(y, counts)``, counts [count] int32 the real tokens
    sent to each held expert this call.

    An output ``>= num_experts`` is a zero-compute expert: it gives the
    token itself, so a token's choices of them add ``x * sum(their
    weights)`` (scope ``moe/zero``), computed for every real token HERE,
    on the token's own chip, as a shared expert would be. Such a choice
    is "not this chip's" to both products: no row, no weight read, and
    not in ``counts``. With ``zero_experts`` the layer returns ``(y,
    counts, zero_tokens)``, zero_tokens [] int32 the real tokens'
    assignments to a zero-compute expert this call.

    One algorithm, "multiply the groups that hold a token", in two
    forms by the number of tokens (static; ``expert_product`` chooses).
    Up to ``WHOLE_ROWS_BELOW`` tokens (a decode step) each expert that
    got a token multiplies all the rows, the rows that did not choose it
    weighed zero, and an expert without a token is not read at all: what
    such a step costs is the weights it reads, so its time follows
    ``counts > 0`` (the step's ``experts_touched``) and its result does
    not. Above it the assignments are sorted by expert into row blocks
    that each belong to one expert, and one kernel multiplies the blocks
    that hold a token (index arrays for the worst case, every assignment
    landing here, so the load changes the time and never the result).
    Both forward only (``ops/routed_experts.py``).
    """
    num_experts: int
    d_ff: int
    top_k: int
    held: Optional[Tuple[int, int]] = None       # None: all of them
    scaling: float = 1.0
    renormalize: bool = True
    shared_d_ff: int = 0
    dtype: Any = jnp.bfloat16
    score: str = "sigmoid"                       # | "softmax"
    zero_experts: int = 0           # router outputs beyond the real experts
    act: str = "silu"                            # | "relu"
    # added to the chosen scores' sum before the division (LFM2's 1e-6)
    renormalize_eps: float = 0.0

    @nn.compact
    def __call__(self, x, valid=None, router_x=None):
        *lead, d = x.shape
        xf = x.reshape(-1, d)
        rf = xf if router_x is None else router_x.reshape(-1, d)
        T = xf.shape[0]
        first, count = self.held or (0, self.num_experts)
        real = jnp.ones((T,), bool) if valid is None else valid.reshape(T)
        width = self.num_experts + self.zero_experts
        squash = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[
            self.score]

        with jax.named_scope("moe/router"):
            w_r = self.param("router", nn.initializers.normal(0.02),
                             (d, width), jnp.float32)
            bias = self.param("router_bias", nn.initializers.zeros,
                              (width,), jnp.float32)
            scores = squash(jnp.matmul(
                rf.astype(jnp.float32), w_r,
                precision=jax.lax.Precision.HIGHEST))          # [T, E]
            _, chosen = jax.lax.top_k(scores + bias, self.top_k)
            w = jnp.take_along_axis(scores, chosen, axis=1)    # [T, k]
            if self.renormalize:
                total = jnp.sum(w, axis=-1, keepdims=True)
                w = w / (total + self.renormalize_eps
                         if self.renormalize_eps else total)
            w = w * self.scaling
            here = (chosen >= first) & (chosen < first + count) \
                & real[:, None]
            # the held experts are 0..count-1 here; `count` means "not
            # this chip's"
            local = jnp.where(here, chosen - first, count)

        init = nn.initializers.normal(0.02)
        w_gate = self.param("w_gate", init, (count, d, self.d_ff),
                            self.dtype)
        w_up = self.param("w_up", init, (count, d, self.d_ff), self.dtype)
        w_down = self.param("w_down", init, (count, self.d_ff, d),
                            self.dtype)
        xb = xf.astype(self.dtype)
        plan = expert_product(T, self.top_k, self.num_experts, count, d,
                              jnp.dtype(self.dtype).itemsize,
                              self.zero_experts)
        with jax.named_scope("moe/experts"):
            if plan.name == "touched_kernel":
                counts = jnp.sum(jax.nn.one_hot(local, count + 1,
                                                dtype=jnp.int32),
                                 axis=(0, 1))[:count]
                combine = jnp.sum(
                    jax.nn.one_hot(local, count, dtype=jnp.float32)
                    * w[..., None], axis=1)                    # [T, count]
                y = touched_experts(xb, combine, counts, w_gate, w_up,
                                    w_down, self.act)
            else:
                y, counts = _experts_grouped(xb, local, w, w_gate, w_up,
                                             w_down, plan, self.act)
        if self.shared_d_ff:
            with jax.named_scope("moe/shared"):
                y = y + SwiGLU(self.shared_d_ff, self.dtype, self.act,
                               name="shared")(xb)
        if not self.zero_experts:
            return y.reshape(*lead, d), counts
        with jax.named_scope("moe/zero"):
            zero = (chosen >= self.num_experts) & real[:, None]
            y = y + xf.astype(jnp.float32) * jnp.sum(
                jnp.where(zero, w, 0.0), axis=1, keepdims=True)
        return y.reshape(*lead, d), counts, jnp.sum(zero, dtype=jnp.int32)


def _worst_rows(A: int, E: int, bm: int) -> int:
    """The rows ``A`` assignments take when every one lands on the ``E``
    experts held here, each group padded to whole blocks of ``bm``."""
    return (-(-A // bm) + E) * bm


def _sorted_rows(local, w, E: int, bm: int):
    """The assignments sorted by expert (``local`` is ``E`` for one that
    is not this chip's), each expert's group padded to whole blocks of
    ``bm`` rows. For each of the worst case's ``rows`` rows its token
    (``T`` for a group's padding and past the live rows) and, where ``w``
    is given, its weight; for each block its expert; the live blocks'
    count; ``sizes`` (the assignments each expert got); and ``order``
    (the assignments in sorted order) with ``dest`` (each one's row;
    ``rows`` for one that is not this chip's). One sort (it carries the
    weights), arithmetic over the sorted keys and ONE scatter: a scalar
    gather or scatter of ``T * k`` elements, or a one-hot count of them,
    costs the chip as much as the sort (PERF.md, PR 40)."""
    T, k = local.shape
    A = T * k
    keys = (local.reshape(A), jnp.arange(A, dtype=jnp.int32))
    sorted_e, order, *sorted_w = jax.lax.sort(
        keys if w is None else keys + (w.reshape(A).astype(jnp.float32),),
        num_keys=1, is_stable=True)
    first = jnp.searchsorted(sorted_e, jnp.arange(E + 1)).astype(jnp.int32)
    sizes = first[1:] - first[:-1]
    padded = -(-sizes // bm) * bm
    ends = jnp.cumsum(padded)
    shift = ends - padded - first[:-1]      # padding before a group
    rows = _worst_rows(A, E, bm)
    dest = jnp.where(
        sorted_e < E,
        jnp.arange(A) + jnp.sum(jnp.where(
            sorted_e[:, None] == jnp.arange(E)[None, :], shift[None, :], 0),
            axis=1), rows)
    # a row's token (T: none) and the bits of its weight (zero), placed
    # together
    columns = [order // k] + [jax.lax.bitcast_convert_type(v, jnp.int32)
                              for v in sorted_w]
    placed = jnp.broadcast_to(
        jnp.array([T, 0][:len(columns)], jnp.int32),
        (rows, len(columns))).at[dest].set(jnp.stack(columns, axis=1),
                                           mode="drop")
    token = placed[:, 0]
    weight = jax.lax.bitcast_convert_type(placed[:, 1], jnp.float32) \
        if sorted_w else None
    block_expert = jnp.minimum(jnp.searchsorted(
        ends, jnp.arange(rows // bm) * bm, side="right"),
        E - 1).astype(jnp.int32)
    return token, weight, block_expert, (ends[-1] // bm).astype(jnp.int32), \
        sizes, order, dest


def _experts_grouped(x, local, w, w_gate, w_up, w_down, plan,
                     act: str = "silu"):
    """Grouped products: the assignments sorted by expert, each expert's
    group padded to whole blocks of ``plan.block_rows`` rows, and
    ``grouped_experts`` (one kernel) over the blocks that hold a token.
    Index arrays have the worst case's length (every assignment landing
    here); rows are held ``plan.chunk_rows`` at a time. Where that is
    the worst case's rows, they are gathered once, multiplied once, and
    each token gathers its ``k`` results back and weighs them. Where it
    is not (an 8,192-token prompt at Kimi-K2's width), a loop over the
    LIVE chunks gathers a chunk's rows, multiplies them and adds the
    weighed results to their tokens' rows, so what is held is a chunk's
    and what moves is the assignments that landed here. Returns ``(y,
    sizes)``, sizes [E] the assignments each held expert got."""
    T, d = x.shape
    E, k = w_gate.shape[0], local.shape[1]
    A = T * k
    bm, C = plan.block_rows, plan.chunk_rows
    rows = _worst_rows(A, E, bm)

    if C >= rows:
        token, _, block_expert, live, sizes, order, dest = _sorted_rows(
            local, None, E, bm)
        ys = grouped_experts(x[jnp.minimum(token, T - 1)],
                             (token < T).astype(jnp.float32), block_expert,
                             live.reshape(1), w_gate, w_up, w_down, bm, act)
        # each assignment's row (0 for one that is not this chip's); the
        # rows of a block past the live ones were NOT WRITTEN
        back = jnp.zeros((A,), jnp.int32).at[order].set(
            jnp.where(dest < rows, dest, 0).astype(jnp.int32))
        here = local.reshape(A) < E
        y = jnp.where(here[:, None], ys[back] * w.reshape(A, 1), 0.0)
        return jnp.sum(y.reshape(T, k, d), axis=1), sizes

    token, weight, block_expert, live, sizes, _, _ = _sorted_rows(
        local, w, E, bm)
    pad = -rows % C
    token = jnp.pad(token, (0, pad), constant_values=T)
    weight = jnp.pad(weight, (0, pad))
    block_expert = jnp.pad(block_expert, (0, pad // bm))

    def chunk(c, y):
        tok = jax.lax.dynamic_slice_in_dim(token, c * C, C)
        ys = grouped_experts(
            x[jnp.minimum(tok, T - 1)],
            jax.lax.dynamic_slice_in_dim(weight, c * C, C),
            jax.lax.dynamic_slice_in_dim(block_expert, c * (C // bm),
                                         C // bm),
            (live - c * (C // bm)).reshape(1), w_gate, w_up, w_down, bm,
            act)
        # the rows of a block past the live ones were NOT WRITTEN and
        # have no token: dropped
        return y.at[tok].add(ys, mode="drop")

    return jax.lax.fori_loop(0, -(-live * bm // C), chunk,
                             jnp.zeros((T, d), jnp.float32)), sizes


def expert_sharding_rule(mesh, path: Tuple[str, ...], shape, spec):
    """Param-sharding hook: leaves named experts_* shard P("ep", ...) on the
    expert axis (compose with the default rules for other leaves)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    name = "/".join(str(p) for p in path)
    if "experts_" in name and spec.ep > 1 and shape and \
            shape[0] % spec.ep == 0:
        return NamedSharding(mesh, P("ep", *([None] * (len(shape) - 1))))
    return None
