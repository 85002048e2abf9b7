"""Mamba-1, the selective state-space recurrence: a state a sequence of
``N`` values a channel, each with a decay of its own that the token sets.

Per channel ``c`` (of ``d_in``) and state ``n`` (of ``N``), with the
token's time step ``dt_t,c >= 0``, its ``B_t``, ``C_t`` in R^N and its
input ``u_t,c``; ``A_c,n < 0`` and the skip ``D_c`` are weights:

    h_c,n <- exp(dt_t,c A_c,n) h_c,n + dt_t,c B_t,n u_t,c
    y_t,c  = sum_n h_c,n C_t,n + D_c u_t,c

A file of its own beside ``ops/linear_attention.py``: that one is the
delta rule on matrix states (a write that first reads the state, a
triangular solve a chunk); here no matrix product touches the state, a
decay differs by channel AND state, and there is no matrix form of a
chunk. Only the causal short convolution before the recurrence is shared
(``linear_attention.short_conv``, which the model calls).

The state is held channels-minor, ``[N, d_in]`` a sequence: the
recurrence's natural ``[d_in, N]`` would put 16 values on lanes of 128,
so a tile would be an eighth full; ``[16, 5120]`` is whole tiles, ``dt``
and ``u`` broadcast over its sublanes and ``B`` / ``C`` over its lanes.
``A`` is kept ``[N, d_in]`` for the same reason. Three forms:

* ``mamba_step`` - one token a row in XLA: the off-chip path and the
  kernel's yardstick.
* ``mamba_step_in_place`` - the same step as ONE Pallas kernel
  (``mamba_recurrence``) over the state POOL ``[layers, slots, N, d_in]``
  where it lies: a row's state is loaded once, updated in float32 on the
  vector unit and written back to the slot it came from; the pool is
  aliased in and out. ``mamba_decode_step`` is a served decode step's one
  entry and runs whichever ``mamba_decode_path`` names.
* ``mamba_scan`` - a prompt's recurrence from a given state, in XLA: a
  loop over the positions in chunks of ``chunk`` (``lax.scan`` unrolled
  by ``chunk``), the state carried; never more than a position's ``[R, N,
  d_in]`` is held, whatever the padded length.

``dt = 0`` at a position is the recurrence's own "no token here" (decay
``exp(0) = 1``, input 0): the state passes through bit for bit, which is
how right-padded positions and padding rows are kept out of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as A_

# lanes of one pass of the kernel over a row's state: [N, _LANES] float32
# is 8 vector registers at N = 16, and the pass's temporaries stay in them
_LANES = (1024, 512, 256, 128)
# rows whose u, dt and y share one block of the kernel (whole sublanes):
# the block is fetched once for these rows and written once after them
_ROWS = 8


def mamba_step(u, dt, B, C, A, D, state):
    """One token a row. u, dt [R, d_in] (``dt`` after the softplus; 0: no
    token); B, C [R, N]; A [N, d_in] (< 0); D [d_in]; state [R, N, d_in]
    float32. Returns (y [R, d_in] float32, new state)."""
    f32 = jnp.float32
    u, dt, B, C = (t.astype(f32) for t in (u, dt, B, C))
    new = jnp.exp(dt[:, None, :] * A.astype(f32)) * state \
        + (dt * u)[:, None, :] * B[:, :, None]
    y = jnp.sum(new * C[:, :, None], axis=1)
    return y + D.astype(f32) * u, new


def mamba_decode_path(state_pool, S: int) -> str:
    """Which recurrence ``models.jamba`` runs over the state pool
    [layers, slots, N, d_in], from what it can observe: ``"mamba_kernel"``
    (``mamba_step_in_place``) for one new token a row (``S == 1``) on a
    TPU, where the pool is float32, ``N`` whole sublane tiles and ``d_in``
    whole lane tiles, and no mesh of several devices is being traced for
    (a bare Mosaic call is refused there); ``"xla"`` (the rows' states
    read, ``mamba_step`` or ``mamba_scan``, written back) for everything
    else: a prompt, no pool, the CPU."""
    if state_pool is None or S != 1 or not A_._use_pallas():
        return "xla"
    mesh = getattr(A_._TRACE_MESH, "mesh", None)
    fits = (state_pool.ndim == 4 and state_pool.dtype == jnp.float32
            and state_pool.shape[2] % 8 == 0
            and state_pool.shape[3] % 128 == 0
            and (mesh is None or mesh.size == 1))
    return "mamba_kernel" if fits else "xla"


def _mamba_step_kernel(layer_ref, slot_ref, u_ref, dt_ref, bt_ref, ct_ref,
                       a_ref, s_ref, y_ref, new_ref, *, rows, lanes):
    """One row's state [N, d_in], in passes of ``lanes`` channels. ``u``,
    ``dt`` and ``y`` are blocks of ``rows`` rows [rows, d_in], this row's
    one sublane of them; ``B`` and ``C`` come transposed and whole,
    [N, R]: this row's column is picked by a compare and a sum over the
    lanes, which leaves it [N, 1], ready to broadcast over the channels."""
    from jax.experimental import pallas as pl
    r = pl.program_id(0)
    i = r % rows
    col = jax.lax.broadcasted_iota(jnp.int32, bt_ref.shape, 1) == r
    b = jnp.sum(jnp.where(col, bt_ref[...], 0.0), axis=1, keepdims=True)
    c = jnp.sum(jnp.where(col, ct_ref[...], 0.0), axis=1, keepdims=True)
    for j in range(s_ref.shape[-1] // lanes):
        at = slice(j * lanes, (j + 1) * lanes)
        u, dt = u_ref[pl.ds(i, 1), at], dt_ref[pl.ds(i, 1), at]  # [1, lanes]
        new = jnp.exp(dt * a_ref[:, at]) * s_ref[:, at] + (dt * u) * b
        new_ref[:, at] = new
        y_ref[pl.ds(i, 1), at] = jnp.sum(new * c, axis=0, keepdims=True)


# (a function of its own under ``jit``, the layer an argument: a model's
# Mamba layers are the same call, and a decode program traces the kernel
# and lowers it to Mosaic once, as ``linear_attention._kda_step_call``)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _mamba_step_call(layer, slots, u, dt, B, C, A, pool, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, d_in = u.shape
    N = pool.shape[2]
    rows = min(R, _ROWS)
    lanes = next((n for n in _LANES if d_in % n == 0), d_in)
    vec = pl.BlockSpec((rows, d_in), lambda r, *_: (r // rows, 0))
    whole = pl.BlockSpec((N, R), lambda r, *_: (0, 0))
    tile = pl.BlockSpec((None, None, N, d_in),
                        lambda r, layer, slots: (layer[0], slots[r], 0, 0))
    return pl.pallas_call(
        functools.partial(_mamba_step_kernel, rows=rows, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R,),
            in_specs=[vec, vec, whole, whole,
                      pl.BlockSpec((N, d_in), lambda r, *_: (0, 0)), tile],
            out_specs=[vec, tile]),
        out_shape=[jax.ShapeDtypeStruct((R, d_in), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is its own output: slots no row names are not touched
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=6 * N * d_in * 4 + 6 * rows * d_in * 4
            + (16 << 20)),
        interpret=interpret,
        name="mamba_recurrence",
    )(layer, slots, u, dt, B.T, C.T, A, pool)


def mamba_step_in_place(u, dt, B, C, A, D, state_pool, layer, slots,
                        interpret: bool = False):
    """``mamba_step`` over the pool: row r's state is ``state_pool[layer,
    slots[r]]``. ``layer`` an int or a traced scalar; ``slots`` [R] int.
    Returns (y [R, d_in] float32, the pool with the rows' slots of that
    layer updated): the pool is aliased in and out, so under a jit that
    donates it nothing is copied, and a slot no row names is bit for bit
    what it was. Rows must name distinct slots, but for rows with ``dt =
    0``, which leave their slot as it is and may share one (the null slot
    of the padding rows). The decays are taken INSIDE the kernel (outside
    they would be an array of the state's size, written and read again:
    twice the kernel's bytes); the skip ``D u`` does not touch the state
    and is added here. Off the chip: ``interpret=True`` (tests)."""
    f32 = jnp.float32
    u, dt, B, C = (t.astype(f32) for t in (u, dt, B, C))
    y, pool = _mamba_step_call(
        jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
        u, dt, B, C, A.astype(f32), state_pool, interpret=interpret)
    return y + D.astype(f32) * u, pool


def mamba_decode_step(u, dt, B, C, A, D, state_pool, layer, slots=None):
    """A served decode step's recurrence, one token a row, over the pool
    [layers, slots, N, d_in]: row r's state is ``state_pool[layer,
    slots[r]]``, and without ``slots`` row r is slot r + 1 (a full decode
    batch: a contiguous slice). Returns (y, the pool with those slots
    updated). Runs what ``mamba_decode_path`` names: the kernel over the
    pool where it lies, or the rows' states read, ``mamba_step``, and
    written back."""
    R = u.shape[0]
    if mamba_decode_path(state_pool, 1) == "mamba_kernel":
        return mamba_step_in_place(
            u, dt, B, C, A, D, state_pool, layer,
            1 + jnp.arange(R) if slots is None else slots)
    at = slice(1, 1 + R) if slots is None else slots
    y, new = mamba_step(u, dt, B, C, A, D, state_pool[layer, at])
    return y, state_pool.at[layer, at].set(new)


def mamba_scan(u, dt, B, C, A, D, state, chunk: int = 8):
    """A sequence a row from a given state. u, dt [R, S, d_in] (``dt`` 0
    at an empty position); B, C [R, S, N]; A [N, d_in]; D [d_in]; state
    [R, N, d_in] float32 (the state before the first position). Returns
    (y [R, S, d_in] float32, final state). A loop over the positions, the
    state carried, ``chunk`` positions a trip of the loop (8: a layer of
    a (4, 512) prompt read 3.00 ms at 1, 1.15 at 2, 1.07 at 8 and 16 and
    1.10 at 32, (8, 256) and (1, 512) alike: my chip run, PR 48)."""
    f32 = jnp.float32
    u, dt, B, C = (jnp.moveaxis(t.astype(f32), 1, 0) for t in (u, dt, B, C))
    A, D = A.astype(f32), D.astype(f32)

    def position(h, xs):
        y, h = mamba_step(*xs, A, D, h)
        return h, y

    S = u.shape[0]
    final, y = jax.lax.scan(position, state.astype(f32), (u, dt, B, C),
                            unroll=max(1, min(chunk, S)))
    return jnp.moveaxis(y, 0, 1), final
