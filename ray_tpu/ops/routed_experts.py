"""The routed experts' products: for few tokens every row through the
experts that got a token, and through no other (``touched_experts``);
for many the rows sorted by expert, a block of them at a time through
its one expert (``grouped_experts``, at the end of this file).

A decode step has a few dozen rows and holds dozens of experts of some
ten megabytes each, so what it costs is the weights it reads. The rows
stay whole (an expert multiplies all ``T`` of them and the rows that did
not choose it are weighed zero); the expert axis is what is skipped: the
touched experts are listed first, and the kernel walks that list.

One Pallas kernel, grid ``(experts, d_ff tiles)``. The list and its
length ride scalar prefetch, so a grid step's ``index_map`` names the
weights of ``order[min(i, n - 1)]``: steps past the list name the block
that is already resident, which issues no DMA, and compute nothing
(``pl.when``). The pipeline fetches the next expert's tiles under the
current product. The result [T, d] float32 stays resident and is written
once. Off the chip the same kernel runs interpreted (``interpret=True``),
so the CPU tests see the same walk. Forward only: a ``pallas_call`` has
no derivative, and nothing differentiates through the served step.
Traced under ``attention_mesh(mesh)`` for a mesh of more than one device
the kernel runs inside ``shard_map``, every device on the whole (small)
input and its own copy of the weights, as ``flash_attention`` does
(GSPMD cannot partition a Mosaic kernel).

Measured on a TPU v5e at the Kimi-Linear widths (64 rows, 64 experts of
3 x 2304 x 1024 bfloat16): PERF.md, PR 29.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as A

ROW_TILE = 16       # rows are padded to whole bfloat16 sublane tiles
# the gate's activation, a field of the layer (``RoutedExperts.act``):
# SwiGLU's, or ReGLU's
ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
# The widest d_ff tile whose three weight blocks, twice (the pipeline's
# two buffers), stay under this much VMEM (a v5e core has 128 MiB). At
# the Kimi-Linear widths that is a whole expert a grid step (28 MB), which
# read 1-8% faster than half an expert a step (PERF.md, PR 29).
_FF_TILES = (1024, 512, 256, 128)
_WEIGHT_VMEM = 32 << 20


def ff_tile(d: int, d_ff: int, item: int) -> int:
    """The widest ``d_ff`` tile under ``_WEIGHT_VMEM`` (else all of it)."""
    return next((t for t in _FF_TILES if d_ff % t == 0
                 and 2 * 3 * d * t * item <= _WEIGHT_VMEM), d_ff)


def _on_the_mesh(call, n_args: int):
    """``call`` inside ``shard_map`` where the trace runs on a mesh of
    more than one device: every device on the whole input and its own
    copy of the weights (GSPMD cannot partition a Mosaic kernel)."""
    mesh = getattr(A._TRACE_MESH, "mesh", None)
    if mesh is None or mesh.size <= 1:
        return call
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.jax_compat import shard_map
    return shard_map(call, mesh=mesh, in_specs=(P(),) * n_args,
                     out_specs=P(), check_vma=False)


def touched_first(counts):
    """``(order, n)``: the experts that got a token first, in their own
    order, then the others; ``n`` how many got one."""
    untouched = counts == 0
    order = jnp.argsort(untouched, stable=True).astype(jnp.int32)
    return order, jnp.sum(~untouched, dtype=jnp.int32).reshape(1)


def live_block(i, j, order, n, last_tile):
    """The (expert, d_ff tile) grid step ``(i, j)`` works on. Past the
    list it is the list's last block again (no new DMA); an empty list
    names ``order[0]``'s last tile, fetched once and never used."""
    return live_rows(i, j, order, n, last_tile)[1:]


def _kernel(order_ref, n_ref, x_ref, cw_ref, gate_ref, up_ref, down_ref,
            y_ref, *, act):
    from jax.experimental import pallas as pl
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
        h = ACTS[act](g) * u * cw_ref[...]
        y_ref[...] += jnp.dot(h.astype(x.dtype), down_ref[...],
                              preferred_element_type=jnp.float32)


def touched_experts(x, combine, counts, w_gate, w_up, w_down,
                    act: str = "silu"):
    """``sum_e combine[:, e] * GLU_e(x)`` (the gate through ``act``: SiLU
    or ReLU) over the experts with
    ``counts[e] > 0``. x [T, d] in the weights' dtype, combine [T, E]
    float32 (zero where a row did not choose the expert; it must be zero
    in every column whose count is zero), counts [E], w_gate and w_up
    [E, d, d_ff], w_down [E, d_ff, d]. Returns [T, d] float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, d = x.shape
    E, _, d_ff = w_gate.shape
    item = jnp.dtype(w_gate.dtype).itemsize
    tf = ff_tile(d, d_ff, item)
    tiles = d_ff // tf
    rows = -(-T // ROW_TILE) * ROW_TILE
    order, n = touched_first(counts)
    cw = jnp.pad(combine.T, ((0, 0), (0, rows - T)))[..., None]  # [E, rows, 1]
    x = jnp.pad(x, ((0, rows - T), (0, 0)))

    block = functools.partial(live_block, last_tile=tiles - 1)

    def up_map(i, j, order, n):
        e, tile = block(i, j, order, n)
        return e, 0, tile

    def down_map(i, j, order, n):
        e, tile = block(i, j, order, n)
        return e, tile, 0

    def whole(i, j, order, n):
        return 0, 0

    # two buffers of each block, and the resident rows
    need = 2 * (3 * d * tf * item + rows * 128 * 4) \
        + 2 * rows * d * (item + 4)
    call = pl.pallas_call(
        functools.partial(_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(E, tiles),
            in_specs=[
                pl.BlockSpec((rows, d), whole),
                pl.BlockSpec((None, rows, 1),
                             lambda i, j, order, n:
                             (block(i, j, order, n)[0], 0, 0)),
                pl.BlockSpec((None, d, tf), up_map),
                pl.BlockSpec((None, d, tf), up_map),
                pl.BlockSpec((None, tf, d), down_map),
            ],
            out_specs=pl.BlockSpec((rows, d), whole)),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(need * 1.25) + (8 << 20)),
        interpret=not A._use_pallas(),
        name="routed_experts_touched")
    return _on_the_mesh(call, 7)(order, n, x, cw, w_gate, w_up,
                                 w_down)[:T]


def live_rows(i, j, block_expert, n, last_tile):
    """The (row block, expert, d_ff tile) grid step ``(i, j)`` works on.
    Past the last live block it is that block's last tile again (no new
    DMA); with no live block, block 0's, fetched once and never used."""
    b = jnp.maximum(jnp.minimum(i, n[0] - 1), 0)
    return b, block_expert[b], jnp.where(i < n[0], j, last_tile)


def _grouped_kernel(expert_ref, n_ref, x_ref, w_ref, gate_ref, up_ref,
                    down_ref, y_ref, *, act):
    from jax.experimental import pallas as pl
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, gate_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
        h = (ACTS[act](g) * u).astype(x.dtype)
        y = jnp.dot(h, down_ref[...],
                    preferred_element_type=jnp.float32) * w_ref[...]

        @pl.when(j == 0)
        def _():
            y_ref[...] = y

        @pl.when(j > 0)
        def _():
            y_ref[...] += y


def grouped_experts(xs, weight, block_expert, n, w_gate, w_up, w_down,
                    block_rows: int, act: str = "silu"):
    """``weight[r] * GLU_e(xs[r])`` (the gate through ``act``: SiLU or
    ReLU) for the rows of the first ``n[0]``
    blocks of ``block_rows`` rows, ``e = block_expert[r // block_rows]``:
    the rows sorted by expert, each expert's group padded to whole
    blocks. xs [R, d] in the weights' dtype, weight [R] float32 (zero on
    a group's padding), block_expert [R // block_rows] and n [1] int32,
    w_gate and w_up [E, d, d_ff], w_down [E, d_ff, d]. Returns [R, d]
    float32; THE ROWS OF THE BLOCKS PAST ``n[0]`` ARE NOT WRITTEN (what
    the buffer held), so nothing may read them.

    One Pallas kernel, grid ``(row blocks, d_ff tiles)``; the blocks'
    experts and their count ride scalar prefetch. Consecutive blocks of
    one expert name the same weights, which stay resident (where an
    expert is one tile); the next expert's tiles are fetched under the
    current block's products; steps past the last live block name what
    is resident and compute nothing. Gate, up, the activation and down run on a
    block in VMEM: bfloat16 operands, float32 sums, ``h`` rounded once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, d = xs.shape
    d_ff = w_gate.shape[2]
    bm = block_rows
    item = jnp.dtype(w_gate.dtype).itemsize
    tf = ff_tile(d, d_ff, item)
    tiles = d_ff // tf
    block = functools.partial(live_rows, last_tile=tiles - 1)

    def rows_map(i, j, e, n):
        return block(i, j, e, n)[0], 0

    def up_map(i, j, e, n):
        _, expert, tile = block(i, j, e, n)
        return expert, 0, tile

    def down_map(i, j, e, n):
        _, expert, tile = block(i, j, e, n)
        return expert, tile, 0

    # two buffers of each block, and the products' float32 results
    need = 2 * (3 * d * tf * item + bm * d * (item + 4) + bm * 128 * 4) \
        + bm * (3 * tf + d) * 4
    call = pl.pallas_call(
        functools.partial(_grouped_kernel, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R // bm, tiles),
            in_specs=[
                pl.BlockSpec((bm, d), rows_map),
                pl.BlockSpec((bm, 1), rows_map),
                pl.BlockSpec((None, d, tf), up_map),
                pl.BlockSpec((None, d, tf), up_map),
                pl.BlockSpec((None, tf, d), down_map),
            ],
            out_specs=pl.BlockSpec((bm, d), rows_map)),
        out_shape=jax.ShapeDtypeStruct((R, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(need * 1.25) + (8 << 20)),
        interpret=not A._use_pallas(),
        name="routed_experts_grouped")
    return _on_the_mesh(call, 7)(block_expert, n, xs, weight[:, None],
                                 w_gate, w_up, w_down)
