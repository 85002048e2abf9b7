"""A layer's matrix product over weights stacked on a leading layer axis
and stored in float32: ``x @ w[layer] + b[layer]`` with ``x`` and the
weight's values in the activation's dtype (bfloat16) and the sums in
float32.

A served model loops ONE block's program over its layers
(``models.gpt2.GPT2(stacked=True)``) and keeps its parameters as they are
stored. Written in XLA (``w[layer].astype(bfloat16)`` inside the loop) the
TPU compiler moves the cast out of the loop and over the whole stack: every
program then reads each stack in float32, writes a bfloat16 copy of it to
HBM and reads that copy back, 5.66 GB a step for GPT-2 large's 2.83 GB of
matrices, and holds the copy among its temporaries (PERF.md, PR 50).
``stacked_linear`` on the chip is a call the compiler cannot look into:
ONE Pallas kernel that takes the stack ``[L, K, N]`` where it lies, the
layer by scalar prefetch into the index maps (as ``mamba_recurrence`` and
``paged_attention_decode`` take theirs). A grid step's DMA brings a
float32 tile ``[tk, tn]`` of the layer's matrix, the tile is rounded to
bfloat16 in VMEM on its way into the matrix unit, and the rows' product
with it is summed in float32; the bias is added to the float32 sum and the
result written once, in the activation's dtype. A weight is read once a
call, in the precision it is stored in, and no copy of it is written.

``stacked_linear_path`` says which form runs, from what it can observe;
``"xla"`` is the product as it was and stays the reference. The two
multiply the same bfloat16 values and sum in float32; they differ in one
rounding: the XLA form rounds the sum to bfloat16 and adds the bias in
bfloat16 (as ``flax.linen.Dense`` does), the kernel adds the float32 bias
to the float32 sum and rounds once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as A

ROW_TILE = 16       # rows are padded to whole bfloat16 sublane tiles
# the most rows the kernel takes, all of them in every grid step: the
# longest program a cell runs and the chip has timed (PERF.md, PR 50)
_ROWS = 1024
# a float32 weight tile [tk, tn] holds at most this many bytes (the
# pipeline keeps two, and the kernel a bfloat16 copy of the one it
# multiplies). GPT-2 large's four products on a v5e, 16 rows: every tile of
# 1 to 8 MB read within 3% of every other, 81-85% of 819 GB/s (PERF.md,
# PR 50)
_TILE_BYTES = 4 << 20
_K_TILES = (1280, 1024, 512, 256, 128)
_N_TILES = (1280, 1024, 768, 640, 512, 384, 256, 128)


def _tiles(K: int, N: int):
    """``(tk, tn)`` of the weight tile: the most rows of ``_K_TILES`` that
    divide ``K`` (all of GPT-2 large's 1,280: the rows are then fetched
    once and no sum is carried between grid steps) and the widest lane
    tile that then fits ``_TILE_BYTES``. None: no whole tiles."""
    if K % 128 or N % 128:
        return None
    tk = next(t for t in _K_TILES if K % t == 0)
    return tk, next(t for t in _N_TILES
                    if N % t == 0 and tk * t * 4 <= _TILE_BYTES)


def stacked_linear_path(x, w) -> str:
    """Which product ``stacked_linear`` runs for rows ``x`` [..., K] and
    the stack ``w`` [L, K, N], from what it can observe: ``"kernel"`` on a
    TPU where the rows are bfloat16 and the stack float32 (there is a cast
    to keep out of HBM, and both forms multiply the same rounded values),
    ``K`` and ``N`` are whole tiles, the rows at most ``_ROWS``, and no
    mesh of several devices is being traced for (a bare Mosaic call is
    refused there); ``"xla"`` for everything else: the CPU, weights that
    are bfloat16 already, float32 rows, a longer prompt."""
    if not A._use_pallas() or w.ndim != 3 or w.dtype != jnp.float32 \
            or x.dtype != jnp.bfloat16:
        return "xla"
    mesh = getattr(A._TRACE_MESH, "mesh", None)
    fits = (x.shape[-1] == w.shape[1] and _tiles(*w.shape[1:]) is not None
            and x.size // x.shape[-1] <= _ROWS
            and (mesh is None or mesh.size == 1))
    return "kernel" if fits else "xla"


def _linear_kernel(layer_ref, x_ref, w_ref, b_ref, o_ref, *acc, nk):
    """One tile of the result: the rows [M, tk] against the layer's weight
    tile [tk, tn], float32 as stored and rounded here. With ``K`` in
    several tiles the float32 sum is carried in ``acc`` over the last
    grid axis."""
    from jax.experimental import pallas as pl
    part = jnp.dot(x_ref[...], w_ref[...].astype(x_ref.dtype),
                   preferred_element_type=jnp.float32)
    if nk == 1:
        o_ref[...] = (part + b_ref[...]).astype(o_ref.dtype)
        return
    acc_ref, = acc
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = part

    @pl.when(k > 0)
    def _():
        acc_ref[...] += part

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = (acc_ref[...] + b_ref[...]).astype(o_ref.dtype)


# (a function of its own under ``jit``, the layer an argument: a model's
# layers are the same call, as ``ssm._mamba_step_call``)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _linear_call(layer, x, w, b, *, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    L, _, N = w.shape
    tk, tn = _tiles(K, N)
    nk = K // tk
    item = x.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_linear_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(N // tn, nk),
            in_specs=[
                pl.BlockSpec((M, tk), lambda j, k, layer: (0, k)),
                pl.BlockSpec((None, tk, tn),
                             lambda j, k, layer: (layer[0], k, j)),
                pl.BlockSpec((None, 1, tn),
                             lambda j, k, layer: (layer[0], 0, j))],
            out_specs=pl.BlockSpec((M, tn), lambda j, k, layer: (0, j)),
            scratch_shapes=[pltpu.VMEM((M, tn), jnp.float32)] * (nk > 1)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the weight tile twice and its rounded copy, the rows and
            # the result twice, the carried sum and the product
            vmem_limit_bytes=tk * tn * (2 * 4 + item) + 2 * M * tk * item
            + M * tn * (2 * item + 8) + (16 << 20)),
        interpret=interpret,
        name="stacked_linear",
    )(layer, x, w, b.reshape(L, 1, N))


def stacked_linear_kernel(x, w, b, layer, *, interpret: bool = False):
    """The Pallas form alone: ``x`` [M, K] (the activation's dtype), ``w``
    [L, K, N] and ``b`` [L, N] float32, ``layer`` an int or a traced
    scalar. Returns [M, N] in ``x``'s dtype. Rows are padded to whole
    sublane tiles (a decode bucket of fewer than 16 rows). Off the chip:
    ``interpret=True`` (tests)."""
    M = x.shape[0]
    rows = -(-M // ROW_TILE) * ROW_TILE
    if rows != M:
        x = jnp.pad(x, ((0, rows - M), (0, 0)))
    out = _linear_call(jnp.asarray(layer, jnp.int32).reshape(1), x, w,
                       b.astype(jnp.float32), interpret=interpret)
    return out[:M]


def stacked_linear(x, w, b, layer):
    """``x @ w[layer] + b[layer]`` for rows ``x`` [..., K] in the
    activation's dtype, the stack ``w`` [L, K, N] and ``b`` [L, N] as
    stored, ``layer`` an int or a traced scalar (a loop's counter).
    Returns [..., N] in ``x``'s dtype. Runs what ``stacked_linear_path``
    names: the kernel over the stack where it lies, or the layer's slice
    cast to ``x``'s dtype and multiplied in XLA, as ``flax.linen.Dense``
    does."""
    if stacked_linear_path(x, w) == "kernel":
        out = stacked_linear_kernel(x.reshape(-1, x.shape[-1]), w, b, layer)
        return out.reshape(*x.shape[:-1], w.shape[-1])
    y = jax.lax.dot_general(
        x, w[layer].astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())))
    return y + b[layer].astype(x.dtype)
