"""Flash attention for TPU (Pallas) with an XLA fallback.

No reference analogue — the reference delegates all kernel work to
torch/CUDA (SURVEY.md §2.6: TP/SP absent, math lives inside train_func).
For a TPU-native framework the fused attention kernel is a core op: it keeps
the S×S score matrix out of HBM (block-online softmax in VMEM), which is what
makes long-context training possible at all.

Algorithm: standard flash attention v2 tiling.
  forward: for each q block, stream kv blocks; online softmax keeps running
  max m and normalizer l; out = acc / l; LSE saved for backward.
  backward: two kernels — dkv (grid over kv blocks, loop q) and dq (grid over
  q blocks, loop kv) — recompute p from saved LSE.

Shapes: [batch, heads, seq, head_dim]; block sizes default 128 (MXU tile).
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BLOCK_Q = 512   # measured on v5e: 512 halves per-program overhead
DEFAULT_BLOCK_K = 512   # vs 128 at s=1024 (2.1ms -> sub-ms fwd per op)
_NEG_INF = -1e30


def _use_pallas() -> bool:
    try:
        return jax.devices()[0].platform == "tpu"
    except Exception:
        return False


# ---------------------------------------------------------------------------
# Reference (XLA) implementation — correctness baseline + CPU path


def attention_reference(q, k, v, *, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        segment_ids=None):
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    seq_q, seq_k = q.shape[2], k.shape[2]
    if causal:
        qi = jnp.arange(seq_q)[:, None] + (seq_k - seq_q)
        ki = jnp.arange(seq_k)[None, :]
        logits = jnp.where(ki <= qi, logits, _NEG_INF)
    if segment_ids is not None:
        q_seg, k_seg = segment_ids
        mask = q_seg[:, None, :, None] == k_seg[:, None, None, :]
        logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Whole-kv kernels (short sequences)
#
# For self-attention at s <= _WHOLE_KV_MAX_S the entire kv fits VMEM, so
# the fastest structure on v5e is fully static code Mosaic can pipeline:
# no running maximum, no loop whose bounds the program computes. Without
# ``causal`` that is one [bq, d] x [s, d]T dot, one exp, one [bq, s] x
# [s, d] dot a program (``_whole_fwd_kernel`` / ``_whole_bwd_kernel``).
#
# Under ``causal`` one program a (batch, head) walks the query blocks in
# a STATIC Python loop and multiplies block ``i`` against the keys and
# values ``[0, (i + 1) * bq)`` only (``_causal_fwd_kernel`` /
# ``_causal_bwd_kernel``): ``n`` blocks visit ``n (n + 1) / 2`` of the
# ``n^2`` squares of the score matrix, and with no running maximum a row's
# sum and its weighted values are plain sums over what was visited.
# Read on a v5e (PR 45, device times of the kernels alone, b16 h12 s1024
# d64 bf16, forward + backward a layer): the one-block form that masked
# the finished 1,024 x 1,024 square 0.557 + 1.351 ms (93-97% of what the
# matrix units give the FULL square at a 64-wide head, which fills half
# of a 128 x 128 unit); this form at bq 128 / 256 / 512: 0.381 + 1.044 /
# 0.390 + 0.945 / 0.428 + 1.129 ms; the streaming flash loop 1.5x the
# one-block form. At 256 the backward stands at 87% of the matrix units'
# bound for the blocks it visits. What did NOT pay: the compare on the
# diagonal block alone (two products a query block where one does:
# backward 1.073 ms), square tiles (1.00+), key blocks against the
# queries after them (the forward's float32 read-add-write of its sums:
# 0.558), bq 512 at s 2,048 (out of VMEM inside a 12-layer program).
# The backward takes ``delta = sum(o * do)`` itself, from blocks it
# holds: as an XLA reduction its [b h, s, 1] float32 result is laid out
# 128 lanes a value (100 MB a layer written and read back: 0.267 ms a
# layer in XLA against +0.010 ms in the kernel). ``lse`` leaves the
# forward as a ROW [b h, 1, s] for the same reason (a column is 100 MB a
# layer kept for the backward: 1.19 GB of the step's peak), turned by
# one [bq, 128] transpose a block; alone the pair costs what it did
# (0.381 + 0.971 ms), inside the train step the forward no longer waits
# for its own output (0.432 -> 0.384 ms a layer). Indexing the column
# out as a 1-D value in place of the transpose cost +0.18 ms a layer.
#
# Key trick — no running max: softmax is shift-invariant, so a static
# shift with an overflow cap replaces the max/subtract/rescale passes
# (exp(min(s, _CAP_HI) - _CAP_SHIFT); exact as long as pre-scaled logits
# stay under _CAP_HI, which trained-LM logits do; rows whose logits ALL
# sit below _CAP_SHIFT - 87 underflow — out of scope for this path, the
# streaming kernel keeps the exact running max).
# (A ones-column-in-v MXU row-sum was tried and reverted: lane-unaligned
# 65-wide v blocks are catastrophic, and padding v to 128 lanes in XLA
# costs 1-5 ms/layer of HBM concatenate traffic.)

_WHOLE_KV_MAX_S = 2048     # s*s*4B score block stays well inside VMEM
_CAP_HI = 50.0             # logit cap: exp(50-25)=7e10 << f32 max
_CAP_SHIFT = 25.0
_CAUSAL_BLOCK_Q = 256      # see the sweep above

_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_NN = (((1,), (0,)), ((), ()))     # a @ b
_TN = (((0,), (0,)), ((), ()))     # a.T @ b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _whole_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref):
    e = jnp.exp(jnp.minimum(_dot(q_ref[:], k_ref[:], _NT), _CAP_HI)
                - _CAP_SHIFT)
    # row-sum on the VPU: cheaper than padding v with a ones column in
    # XLA (the concatenate cost ~1-5 ms/layer of HBM traffic per step)
    l = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
    acc = _dot(e.astype(v_ref.dtype), v_ref[:], _NN)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[:] = jnp.log(l) + _CAP_SHIFT


def _whole_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    qq = q_ref[:]
    kk = k_ref[:]
    dd = do_ref[:]
    # same _CAP_HI clamp as the forward: without it, a logit above the
    # cap makes p here disagree with the clamped forward and the
    # gradient silently explodes instead of saturating
    p = jnp.exp(jnp.minimum(_dot(qq, kk, _NT), _CAP_HI) - lse_ref[:])
    ds = (p * (_dot(dd, v_ref[:], _NT) - delta_ref[:])).astype(qq.dtype)
    dq_ref[:] = _dot(ds, kk, _NN).astype(dq_ref.dtype)
    dkc = _dot(ds, qq, _TN).astype(dk_ref.dtype)
    dvc = _dot(p.astype(dd.dtype), dd, _TN).astype(dv_ref.dtype)
    # dk/dv accumulate across the q-block grid dimension: their output
    # block index is constant in qi, so Mosaic keeps them VMEM-resident
    @pl.when(qi == 0)
    def _():
        dk_ref[:] = dkc
        dv_ref[:] = dvc

    @pl.when(qi > 0)
    def _():
        dk_ref[:] = dk_ref[:] + dkc
        dv_ref[:] = dv_ref[:] + dvc


def _causal_strip(lo, hi):
    # the live entries of query rows [lo, hi) against keys [0, hi)
    q_pos = lo + jax.lax.broadcasted_iota(jnp.int32, (hi - lo, hi), 0)
    return jax.lax.broadcasted_iota(jnp.int32, (hi - lo, hi), 1) <= q_pos


def _column_to_row(col):
    # [n, 1] -> [1, n] through one [n, 128] transpose
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], 128)))[0:1, :]


def _row_to_column(row):
    # [1, n] -> [n, 1]
    return jnp.transpose(jnp.broadcast_to(row, (128, row.shape[1])))[:, 0:1]


def _causal_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q):
    # refs hold one (batch, head) whole: q/k/v/o [s, d]; lse [1, s], a
    # row (see the header: a column lies 128 lanes a value in HBM)
    for lo in range(0, q_ref.shape[0], block_q):
        hi = lo + block_q
        s_ = _dot(q_ref[lo:hi, :], k_ref[0:hi, :], _NT)
        e = jnp.where(_causal_strip(lo, hi),
                      jnp.exp(jnp.minimum(s_, _CAP_HI) - _CAP_SHIFT), 0.0)
        l = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        acc = _dot(e.astype(v_ref.dtype), v_ref[0:hi, :], _NN)
        o_ref[lo:hi, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[:, lo:hi] = _column_to_row(jnp.log(l) + _CAP_SHIFT)


def _causal_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
                       dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, block_q):
    # dq of a query block from the keys at or before it; dk, dv of a key
    # from the query blocks at or after it, summed in the float32
    # scratch [s, d] and cast once at the end
    dt = q_ref.dtype
    for lo in range(0, q_ref.shape[0], block_q):
        hi = lo + block_q
        qq, dd = q_ref[lo:hi, :], do_ref[lo:hi, :]
        kk, vv = k_ref[0:hi, :], v_ref[0:hi, :]
        delta = jnp.sum(o_ref[lo:hi, :].astype(jnp.float32)
                        * dd.astype(jnp.float32), axis=-1, keepdims=True)
        # same _CAP_HI clamp as the forward (see _whole_bwd_kernel)
        p = jnp.exp(jnp.minimum(_dot(qq, kk, _NT), _CAP_HI)
                    - _row_to_column(lse_ref[:, lo:hi]))
        p = jnp.where(_causal_strip(lo, hi), p, 0.0)
        ds = (p * (_dot(dd, vv, _NT) - delta)).astype(dt)
        dq_ref[lo:hi, :] = _dot(ds, kk, _NN).astype(dq_ref.dtype)
        dk, dv = _dot(ds, qq, _TN), _dot(p.astype(dt), dd, _TN)
        if lo:
            dk_acc[0:lo, :] += dk[0:lo]
            dv_acc[0:lo, :] += dv[0:lo]
        # the diagonal block is the first to reach these keys
        dk_acc[lo:hi, :] = dk[lo:hi]
        dv_acc[lo:hi, :] = dv[lo:hi]
    dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _whole_block_q(s: int) -> int:
    # the one-block form (no ``causal``): score block [bq, s] f32 capped
    # at ~4 MiB so several pipeline buffers coexist in VMEM: s itself up
    # to 1,024, 512 at 2,048
    bq = max(128, min(s, (4 << 20) // (4 * s) // 128 * 128))
    while s % bq:
        bq //= 2
    return max(bq, 128)


def _attn_exact() -> bool:
    # RTPU_ATTN_EXACT=1 forces the streaming flash kernels (exact
    # running-max softmax) for workloads whose logits may exceed the
    # whole-kv path's static cap (see _CAP_HI note above). Prefer the
    # explicit ``flash_attention(..., exact=True)`` kwarg — this env
    # var is the global fallback for code that can't reach the call
    # site, and is baked in at TRACE time (set it before the first jit
    # of the attention shape; toggling afterwards does not retrace
    # cached programs).
    import os
    return bool(os.environ.get("RTPU_ATTN_EXACT"))


def _attn_debug() -> bool:
    import os
    return bool(os.environ.get("RTPU_ATTN_DEBUG"))


def _use_whole_kv(sq: int, sk: int, d: int,
                  exact: Optional[bool] = None) -> bool:
    if _attn_exact() if exact is None else exact:
        return False
    return (sq == sk and sk <= _WHOLE_KV_MAX_S and d <= 128
            and sk % 128 == 0 and sq % _whole_block_q(sq) == 0)


def flash_plan(sq: int, sk: int, d: int, causal: bool,
               exact: Optional[bool] = None,
               block_q: int = DEFAULT_BLOCK_Q,
               block_k: int = DEFAULT_BLOCK_K) -> dict:
    """What ``flash_attention``'s kernels do at these shapes: ``path``
    (``"whole_kv_causal"``: a static loop over query blocks, each against
    the keys at or before it; ``"whole_kv"``: query blocks against all
    keys; ``"streaming"``: the running-maximum kernels over key blocks of
    ``block_k``), ``block_q``, and how many blocks of the score matrix
    are multiplied, of how many (squares of ``block_q`` on the whole-kv
    paths, ``block_q x block_k`` on the streaming one). A pure function
    of the shapes; the whole-kv wrappers take their ``block_q`` from it
    and nowhere else."""
    if not _use_whole_kv(sq, sk, d, exact):
        bq, bk = min(block_q, sq), min(block_k, sk)
        nq, nk = -(-sq // bq), -(-sk // bk)
        visited = sum(min(-(-(i + 1) * bq // bk), nk) for i in range(nq)) \
            if causal else nq * nk
        return {"path": "streaming", "block_q": bq,
                "blocks_visited": visited, "blocks_total": nq * nk}
    if not causal:
        bq = _whole_block_q(sq)
        return {"path": "whole_kv", "block_q": bq,
                "blocks_visited": (sq // bq) ** 2,
                "blocks_total": (sq // bq) ** 2}
    bq = min(sq, _CAUSAL_BLOCK_Q if sq % _CAUSAL_BLOCK_Q == 0 else 128)
    n = sq // bq
    return {"path": "whole_kv_causal", "block_q": bq,
            "blocks_visited": n * (n + 1) // 2, "blocks_total": n * n}


def _debug_check_logits(q_scaled, k):
    """Debug-mode finite-range assert for the whole-kv fast path: the
    static-shift softmax is exact only while every pre-softmax logit
    stays under ``_CAP_HI`` — beyond it the clamp silently flattens the
    distribution (and saturates gradients). With ``RTPU_ATTN_DEBUG=1``
    (or ``flash_attention(..., debug=True)``) an out-of-range logit
    fails loudly instead. Materializes the full score matrix — debug
    cost, never on the production path."""
    s_max = jnp.max(jax.lax.dot_general(
        q_scaled, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32))

    def _raise(m):
        m = float(m)
        if m > _CAP_HI:
            raise FloatingPointError(
                f"flash_attention whole-kv fast path: max scaled logit "
                f"{m:.3f} exceeds the static softmax cap "
                f"_CAP_HI={_CAP_HI} — the clamp would silently distort "
                f"the distribution. Pass exact=True (or set "
                f"RTPU_ATTN_EXACT=1) to use the exact streaming "
                f"kernel, or rescale the logits.")

    if isinstance(s_max, jax.core.Tracer):
        # under jit the check runs at execution time via callback (the
        # failure surfaces as a runtime callback error)
        jax.debug.callback(_raise, s_max)
    else:
        _raise(s_max)


def _head_spec(rows, width, blocked=False):
    """A (batch, head)'s [rows, width]: all of it or, ``blocked``, the
    block of rows that the grid's second axis names."""
    from jax.experimental import pallas as pl

    if blocked:
        return pl.BlockSpec((None, rows, width), lambda i, j: (i, j, 0))
    return pl.BlockSpec((None, rows, width), lambda i, *_: (i, 0, 0))


# (functions of their own under ``jit``, as ``_paged_decode_call``: a step
# program lowers the unrolled kernels once a shape, not once a layer.
# Read in the train cell, PR 45: the first step 15.8-16.2 s without,
# 11.5-11.8 s with, the one-block form's 13.0)
@functools.partial(jax.jit, static_argnums=(3, 4))
def _whole_forward(q, k, v, causal, interpret=False):
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = flash_plan(sq, sk, d, causal, exact=False)["block_q"]
    if causal:
        # a program holds its head whole and walks the query blocks
        kernel = functools.partial(_causal_fwd_kernel, block_q=bq)
        grid, lse_shape = (b * h,), (1, sq)
        q_spec, lse_spec = _head_spec(sq, d), _head_spec(1, sq)
    else:
        kernel, grid, lse_shape = _whole_fwd_kernel, (b * h, sq // bq), (sq, 1)
        q_spec, lse_spec = _head_spec(bq, d, True), _head_spec(bq, 1, True)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, _head_spec(sk, d), _head_spec(sk, d)],
        out_specs=[q_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, *lse_shape), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )
    with jax.named_scope("flash_fwd"):
        out, lse = call(q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
                        v.reshape(b * h, sk, d))
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def _whole_backward(res, g, *, causal, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, out, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = flash_plan(sq, sk, d, causal, exact=False)["block_q"]
    if causal:
        # the kernel takes delta = sum(o * do) itself, from o
        kernel = functools.partial(_causal_bwd_kernel, block_q=bq)
        grid = (b * h,)
        lse, last = lse.reshape(b * h, 1, sq), out.reshape(b * h, sq, d)
        q_spec = last_spec = _head_spec(sq, d)
        lse_spec = _head_spec(1, sq)
        scratch = [pltpu.VMEM((sk, d), jnp.float32)] * 2     # dk, dv
    else:
        kernel, grid, scratch = _whole_bwd_kernel, (b * h, sq // bq), []
        lse = lse.reshape(b * h, sq, 1)
        last = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                       axis=-1).reshape(b * h, sq, 1)        # delta
        q_spec = _head_spec(bq, d, True)
        lse_spec = last_spec = _head_spec(bq, 1, True)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, _head_spec(sk, d), _head_spec(sk, d), q_spec,
                  lse_spec, last_spec],
        out_specs=[q_spec, _head_spec(sk, d), _head_spec(sk, d)],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_bwd",
    )
    with jax.named_scope("flash_bwd"):
        dq, dk, dv = call(q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
                          v.reshape(b * h, sk, d), g.reshape(b * h, sq, d),
                          lse, last)
    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# ---------------------------------------------------------------------------
# Packed whole-kv causal kernels: the projection where it lies
#
# The same static loop over query blocks as ``_causal_fwd_kernel`` /
# ``_causal_bwd_kernel``, on blocks cut from the token-major arrays by the
# index maps: a program holds ``128 // head_dim`` heads (at GPT-2's 64, a
# pair) as ONE [s, 128] column block of the projection's [B, S, 3E] output
# (passed three times: q at column block ``j``, k at ``E / 128 + j``, v at
# ``2 E / 128 + j``) and writes one [s, 128] column block of [B, S, E], so
# no array is laid out anew for the kernels and every block is whole lanes.
# Inside a program the heads are told apart by LANE, never by slicing: a
# matrix unit is 128 deep and 128 wide, so ``q`` with the other heads'
# lanes zeroed against the whole [hi, 128] key block is this head's scores
# in the passes a 64-deep product takes, ``e @ v[.., 128]`` holds this
# head's values in its own lanes (the rest go in a select), and the
# products onto ``dk`` / ``dv`` (``ds.T @ q``, ``p.T @ do``, their right
# operands zeroed outside the head) land in the head's lanes and add. The
# softmax scale goes into q with the same multiply that zeroes the lanes.
# ``dq``, ``dk`` and ``dv`` leave the backward as the three column blocks
# of ONE [B, S, 3E] array, which ``c_attn``'s backward takes as it lies: a
# BlockSpec gives an output one block a program, so the array stays in HBM
# (``pl.ANY``) and a program copies its three [s, 128] blocks out of VMEM
# itself, waiting for them a program later (Mosaic took it as written; the
# fallback, three [B, S, E] outputs and a concatenate, was never needed).
#
# Which layout runs is read from the shapes (``packed_heads``): this one
# where the plan is ``whole_kv_causal``, heads fill whole 128-lane blocks,
# q, k and v are one projection's output with as many kv heads as q heads
# and no mesh cuts the heads; the [b h, s, d] kernels above for every
# other caller (grouped heads that a model lays out and rotates itself,
# ``models/llama.py``; a ``tp`` mesh, whose ``shard_map`` cuts heads;
# ``causal=False``). Read on a v5e (my chip run, PR 51: 12 layers of
# forward + backward in one program, device times of the kernels from a
# trace, ms a layer; [the [b h, s, d] kernels on the same values, split,
# ``heads`` and the transpose back traced round them]): [16, 1024, 12 x
# 64] bf16, GPT-2 small's, 0.372 + 0.906 [0.382 + 0.972, beside 0.729 ms
# of copies and transposes that the packed path does not have: the whole
# program 1.58 ms a layer against 2.67], results equal to the last bit;
# [4, 2048, 12 x 64] 0.328 + 0.797 [0.310 + 0.796; copies 0.336]; one
# 128-wide head a program, [16, 1024, 8 x 128]: 0.266 + 0.642 [0.252 +
# 0.609; copies 0.585] and [2, 2048, 8 x 128] 0.114 + 0.280 [0.102 +
# 0.254; 0.017]: a column block's DMA is rows of 256 bytes a tile where a
# head's own array is one run, ~5% of a kernel that a pair of 64-wide
# heads wins back by filling its lanes, and that the copies it saves
# outweigh wherever there are copies to save.


def _lanes_of_heads(width, head_dim):
    """A [1, width] bool a head of a column block: the head's lanes
    (``None`` where the block is one head)."""
    if head_dim == width:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return [(lane >= h * head_dim) & (lane < (h + 1) * head_dim)
            for h in range(width // head_dim)]


def _only(lanes, x):
    return x if lanes is None else jnp.where(lanes, x, 0.0)


def _packed_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q,
                       head_dim, scale):
    # refs hold a batch row's [s, 128] column block: q/k/v of [B, S, 3E],
    # o of [B, S, E]; lse [heads, s], a row a head (see the header)
    dt = q_ref.dtype
    heads = _lanes_of_heads(q_ref.shape[1], head_dim)
    for lo in range(0, q_ref.shape[0], block_q):
        hi = lo + block_q
        q = q_ref[lo:hi, :].astype(jnp.float32) * scale
        kk, vv = k_ref[0:hi, :], v_ref[0:hi, :]
        live = _causal_strip(lo, hi)
        acc = total = None
        for h, lanes in enumerate(heads):
            s_ = _dot(_only(lanes, q).astype(dt), kk, _NT)
            e = jnp.where(live,
                          jnp.exp(jnp.minimum(s_, _CAP_HI) - _CAP_SHIFT), 0.0)
            l = jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
            mine = _dot(e.astype(dt), vv, _NN)
            lse_ref[h:h + 1, lo:hi] = _column_to_row(jnp.log(l) + _CAP_SHIFT)
            acc = mine if acc is None else jnp.where(lanes, mine, acc)
            total = l if total is None else jnp.where(lanes, l, total)
        o_ref[lo:hi, :] = (acc / total).astype(o_ref.dtype)


def _packed_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, dqkv_ref,
                       buf, sem, dk_acc, dv_acc, *, block_q, head_dim, scale):
    # as ``_causal_bwd_kernel``, the heads of the block by lane. dq, dk
    # and dv go to their column blocks of ``dqkv_ref`` [B, S, 3E] in HBM
    # from ``buf`` [2, 3, s, 128], a half of it a program (see the header)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dt = q_ref.dtype
    i, j, cols = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    n = i * cols + j
    slot = n % 2
    heads = _lanes_of_heads(q_ref.shape[1], head_dim)
    for lo in range(0, q_ref.shape[0], block_q):
        hi = lo + block_q
        q = q_ref[lo:hi, :].astype(jnp.float32) * scale
        dd = do_ref[lo:hi, :].astype(jnp.float32)
        kk, vv = k_ref[0:hi, :], v_ref[0:hi, :]
        live = _causal_strip(lo, hi)
        o_do = o_ref[lo:hi, :].astype(jnp.float32) * dd
        dq = dk = dv = None
        for h, lanes in enumerate(heads):
            qq, mine = _only(lanes, q).astype(dt), _only(lanes, dd).astype(dt)
            delta = jnp.sum(_only(lanes, o_do), axis=-1, keepdims=True)
            # same _CAP_HI clamp as the forward (see _whole_bwd_kernel)
            p = jnp.exp(jnp.minimum(_dot(qq, kk, _NT), _CAP_HI)
                        - _row_to_column(lse_ref[h:h + 1, lo:hi]))
            p = jnp.where(live, p, 0.0)
            ds = (p * (_dot(mine, vv, _NT) - delta)).astype(dt)
            dq_h = _dot(ds, kk, _NN)
            dk_h, dv_h = _dot(ds, qq, _TN), _dot(p.astype(dt), mine, _TN)
            dq = dq_h if dq is None else jnp.where(lanes, dq_h, dq)
            dk = dk_h if dk is None else dk + dk_h
            dv = dv_h if dv is None else dv + dv_h
        buf[slot, 0, lo:hi, :] = (dq * scale).astype(dt)
        if lo:
            dk_acc[0:lo, :] += dk[0:lo]
            dv_acc[0:lo, :] += dv[0:lo]
        # the diagonal block is the first to reach these keys
        dk_acc[lo:hi, :] = dk[lo:hi]
        dv_acc[lo:hi, :] = dv[lo:hi]
    buf[slot, 1] = dk_acc[:].astype(dt)
    buf[slot, 2] = dv_acc[:].astype(dt)

    def copies(slot, i, j):
        return [pltpu.make_async_copy(
            buf.at[slot, part],
            dqkv_ref.at[i, :, pl.ds(pl.multiple_of(
                (part * cols + j) * 128, 128), 128)],
            sem.at[slot, part]) for part in range(3)]

    for copy in copies(slot, i, j):
        copy.start()

    @pl.when(n > 0)
    def _():
        # the program before this one: its half of ``buf`` is written
        # next (a wait takes its bytes from the shapes, not the place)
        for copy in copies(1 - slot, i, j):
            copy.wait()

    @pl.when(n == pl.num_programs(0) * cols - 1)
    def _():
        for copy in copies(slot, i, j):
            copy.wait()


def _column_spec(rows, first):
    """A batch row's [rows, 128] column block of a token-major [B, S,
    width] array: block ``first + j`` along the last axis."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((None, rows, 128), lambda i, j: (i, 0, first + j))


def _lse_spec(heads, rows):
    """A program's rows of ``lse`` [B, column blocks, heads, S]."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((None, None, heads, rows), lambda i, j: (i, j, 0, 0))


def _packed_layout(qkv, n_head):
    b, s, width = qkv.shape
    e = width // 3
    d = e // n_head
    # (rows, head_dim, heads a program, column blocks a part, block_q)
    return s, d, 128 // d, e // 128, flash_plan(
        s, s, d, True, exact=False)["block_q"]


@functools.partial(jax.jit, static_argnames=("n_head", "scale", "interpret"))
def _packed_forward(qkv, *, n_head, scale, interpret=False):
    from jax.experimental import pallas as pl

    s, d, per, cols, bq = _packed_layout(qkv, n_head)
    b = qkv.shape[0]
    call = pl.pallas_call(
        functools.partial(_packed_fwd_kernel, block_q=bq, head_dim=d,
                          scale=scale),
        grid=(b, cols),
        in_specs=[_column_spec(s, part * cols) for part in range(3)],
        out_specs=[_column_spec(s, 0), _lse_spec(per, s)],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, cols * 128), qkv.dtype),
            jax.ShapeDtypeStruct((b, cols, per, s), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )
    with jax.named_scope("flash_fwd"):
        return call(qkv, qkv, qkv)


@functools.partial(jax.jit, static_argnames=("n_head", "scale", "interpret"))
def _packed_backward(qkv, out, lse, g, *, n_head, scale, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, d, per, cols, bq = _packed_layout(qkv, n_head)
    b, item = qkv.shape[0], qkv.dtype.itemsize
    call = pl.pallas_call(
        functools.partial(_packed_bwd_kernel, block_q=bq, head_dim=d,
                          scale=scale),
        grid=(b, cols),
        in_specs=[*(_column_spec(s, part * cols) for part in range(3)),
                  _column_spec(s, 0), _lse_spec(per, s), _column_spec(s, 0)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
        scratch_shapes=[pltpu.VMEM((2, 3, s, 128), qkv.dtype),
                        pltpu.SemaphoreType.DMA((2, 3)),
                        pltpu.VMEM((s, 128), jnp.float32),      # dk
                        pltpu.VMEM((s, 128), jnp.float32)],     # dv
        # (``buf``'s halves and their copies go from a program to the next)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the five inputs twice, ``buf``, the two sums, and room for
            # a head's [block_q, s] float32 strips
            vmem_limit_bytes=16 * s * 128 * item + 2 * s * 128 * 4
            + 12 * bq * s * 4 + (8 << 20)),
        interpret=interpret,
        name="flash_bwd",
    )
    with jax.named_scope("flash_bwd"):
        return call(qkv, qkv, qkv, g, lse, out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _flash_packed(qkv, n_head, scale, interpret):
    return _packed_forward(qkv, n_head=n_head, scale=scale,
                           interpret=interpret)[0]


def _flash_packed_fwd_rule(qkv, n_head, scale, interpret):
    out, lse = _packed_forward(qkv, n_head=n_head, scale=scale,
                               interpret=interpret)
    return out, (qkv, out, lse)


def _flash_packed_bwd_rule(n_head, scale, interpret, res, g):
    return (_packed_backward(*res, g, n_head=n_head, scale=scale,
                             interpret=interpret),)


_flash_packed.defvjp(_flash_packed_fwd_rule, _flash_packed_bwd_rule)


# ---------------------------------------------------------------------------
# Pallas forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                block_k, seq_k):
    # refs: q [bq, d]; k/v [seq_k, d]; o [bq, d]; lse [bq, 1]
    # (lse keeps a trailing lane dim — TPU blocks must be >=2D tiles)
    #
    # VPU economy (the measured bottleneck at d=64 on v5e — the softmax
    # passes cost as much as all the MXU work):
    #   - dots take NATIVE (bf16) inputs with f32 accumulation; an f32
    #     upcast first would force f32 MXU matmuls (~4x slower)
    #   - sm_scale is pre-folded into q by the wrapper (sm_scale == 1.0
    #     here), deleting a full [bq, block_k] multiply per kv block
    #   - the kv loop is SPLIT: blocks strictly below the diagonal skip
    #     the iota/compare/select masking entirely; only the ragged
    #     diagonal blocks pay for it
    from jax.experimental import pallas as pl

    bq, d = q_ref.shape
    q = q_ref[:]
    qi = pl.program_id(1)

    m = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    num_kv = seq_k // block_k

    def body(j, carry, masked):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if sm_scale != 1.0:
            s = s * sm_scale
        if masked:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    if causal:
        # [0, clean): fully below the diagonal — unmasked.
        # [clean, needed): intersect the diagonal — masked.
        clean = (qi * bq) // block_k
        needed = jnp.minimum(pl.cdiv((qi + 1) * bq, block_k), num_kv)
        carry = jax.lax.fori_loop(
            0, clean, lambda j, c: body(j, c, False), (m, l, acc))
        m, l, acc = jax.lax.fori_loop(
            clean, needed, lambda j, c: body(j, c, True), carry)
    else:
        m, l, acc = jax.lax.fori_loop(
            0, num_kv, lambda j, c: body(j, c, False), (m, l, acc))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log(l)


def _flash_forward(q, k, v, sm_scale, causal, block_q, block_k,
                   interpret=False):
    from jax.experimental import pallas as pl

    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (
        f"seq lengths must be multiples of block sizes ({sq}%{bq}, {sk}%{bk})"
        " — pad to tile boundaries (fixed shapes keep XLA from recompiling)")
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_k=bk, seq_k=sk)
    call = pl.pallas_call(
        kernel,
        grid=(b * h, sq // bq),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, bq, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd_blocked",
    )
    with jax.named_scope("flash_fwd_blocked"):
        out, lse = call(qf, kf, vf)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


# ---------------------------------------------------------------------------
# Pallas backward


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, sm_scale, causal, block_q, seq_q):
    from jax.experimental import pallas as pl

    bk, d = k_ref.shape
    kj = pl.program_id(1)
    # native-dtype (bf16) dot inputs, f32 accumulation, pre-scaled q,
    # split masked/clean loops — see _fwd_kernel
    k = k_ref[:]
    v = v_ref[:]
    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)

    num_q = seq_q // block_q

    def body(i, carry, masked):
        dk, dv = carry
        q = q_ref[pl.ds(i * block_q, block_q), :]
        do = do_ref[pl.ds(i * block_q, block_q), :]
        lse = lse_ref[pl.ds(i * block_q, block_q), :]      # [bq, 1]
        delta = delta_ref[pl.ds(i * block_q, block_q), :]  # [bq, 1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if sm_scale != 1.0:
            s = s * sm_scale
        if masked:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            k_pos = kj * bk + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        pc = p.astype(do.dtype)
        dv = dv + jax.lax.dot_general(pc, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype) if sm_scale == 1.0 else \
            (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # [start_q, diag_end): intersect the diagonal — masked.
        # [diag_end, num_q): fully below — unmasked.
        start_q = (kj * bk) // block_q
        diag_end = jnp.minimum(pl.cdiv((kj + 1) * bk, block_q), num_q)
        carry = jax.lax.fori_loop(
            start_q, diag_end, lambda i, c: body(i, c, True), (dk, dv))
        dk, dv = jax.lax.fori_loop(
            diag_end, num_q, lambda i, c: body(i, c, False), carry)
    else:
        dk, dv = jax.lax.fori_loop(
            0, num_q, lambda i, c: body(i, c, False), (dk, dv))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, *, sm_scale, causal, block_k, seq_k):
    from jax.experimental import pallas as pl

    bq, d = q_ref.shape
    qi = pl.program_id(1)
    # native-dtype (bf16) dot inputs, f32 accumulation, pre-scaled q,
    # split masked/clean loops — see _fwd_kernel
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[:]      # [bq, 1]
    delta = delta_ref[:]  # [bq, 1]
    dq = jnp.zeros((bq, d), jnp.float32)

    num_kv = seq_k // block_k

    def body(j, dq, masked):
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if sm_scale != 1.0:
            s = s * sm_scale
        if masked:
            q_pos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype) if sm_scale == 1.0 else \
            (p * (dp - delta) * sm_scale).astype(k.dtype)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    if causal:
        clean = (qi * bq) // block_k
        needed = jnp.minimum(pl.cdiv((qi + 1) * bq, block_k), num_kv)
        dq = jax.lax.fori_loop(
            0, clean, lambda j, c: body(j, c, False), dq)
        dq = jax.lax.fori_loop(
            clean, needed, lambda j, c: body(j, c, True), dq)
    else:
        dq = jax.lax.fori_loop(
            0, num_kv, lambda j, c: body(j, c, False), dq)
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _flash_backward(res, g, *, sm_scale, causal, block_q, block_k,
                    interpret=False):
    from jax.experimental import pallas as pl

    q, k, v, out, lse = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    delta = jnp.sum(out.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1)  # [b,h,sq]
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    dof = g.reshape(b * h, sq, d)
    lsef = lse.reshape(b * h, sq, 1)
    deltaf = delta.reshape(b * h, sq, 1)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                                   causal=causal, block_q=bq, seq_q=sq)
    call = pl.pallas_call(
        dkv_kernel,
        grid=(b * h, sk // bk),
        in_specs=[
            pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sq, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sq, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sq, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = call(qf, kf, vf, dof, lsef, deltaf)

    dq_kernel = functools.partial(_bwd_dq_kernel, sm_scale=sm_scale,
                                  causal=causal, block_k=bk, seq_k=sk)
    call = pl.pallas_call(
        dq_kernel,
        grid=(b * h, sq // bq),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, bq, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, bq, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )
    with jax.named_scope("flash_bwd_dq"):
        dq = call(qf, kf, vf, dof, lsef, deltaf)

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# ---------------------------------------------------------------------------
# Public op with custom VJP


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                     exact):
    out, _ = _dispatch_forward(q, k, v, sm_scale, causal, block_q, block_k,
                               interpret, exact)
    return out


def _dispatch_forward(q, k, v, sm_scale, causal, block_q, block_k,
                      interpret, exact=None):
    if sm_scale == 1.0 and _use_whole_kv(q.shape[2], k.shape[2],
                                         q.shape[3], exact):
        return _whole_forward(q, k, v, causal, interpret)
    return _flash_forward(q, k, v, sm_scale, causal, block_q, block_k,
                          interpret)


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k, interpret,
                    exact):
    out, lse = _dispatch_forward(q, k, v, sm_scale, causal, block_q,
                                 block_k, interpret, exact)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, interpret, exact,
                    res, g):
    q, k, v, out, lse = res
    if sm_scale == 1.0 and _use_whole_kv(q.shape[2], k.shape[2],
                                         q.shape[3], exact):
        return _whole_backward(res, g, causal=causal, interpret=interpret)
    return _flash_backward(res, g, sm_scale=sm_scale, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


_TRACE_MESH = threading.local()
_BATCH_AXES = ("dp", "fsdp")    # the mesh axes that shard a batch's rows


def _batch_axes(mesh):
    return tuple(a for a in _BATCH_AXES if a in mesh.axis_names) or None


@contextlib.contextmanager
def attention_mesh(mesh):
    """Name the mesh a step is being traced for. ``flash_attention``
    has no other way to learn it (tracers under jit carry no sharding),
    and needs it to wrap its Pallas calls in ``shard_map``; the SPMD
    trainers (train/spmd.py) enter this around the model's trace."""
    prev = getattr(_TRACE_MESH, "mesh", None)
    _TRACE_MESH.mesh = mesh
    try:
        yield
    finally:
        _TRACE_MESH.mesh = prev


def flash_attention(q, k, v, *, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    force_pallas: Optional[bool] = None,
                    interpret: bool = False,
                    exact: Optional[bool] = None,
                    debug: Optional[bool] = None):
    """Fused attention. [b, h, s, d] → [b, h, s, d].

    On TPU runs the Pallas kernel; elsewhere falls back to the XLA reference
    (still fused reasonably by XLA on CPU for tests).

    The head-major layout: a caller whose q, k and v are one projection's
    [B, S, 3E] output calls ``flash_attention_packed`` instead, which cuts
    the kernels' blocks from that array where the shapes allow (GPT-2's
    train step: no [B, H, S, D] copy, 9.5 ms of a 113 ms step; PR 51) and
    comes here where they do not. What stays here by design: heads a model
    lays out itself (``models/llama.py`` rotates and repeats them), every
    mesh that cuts heads, ``causal=False`` and the streaming lengths.

    Traced under ``attention_mesh(mesh)`` for a mesh of more than one
    device, the kernel runs inside ``shard_map`` on each device's batch
    and head shard (GSPMD cannot partition a Mosaic kernel).

    ``exact`` picks the softmax numerics explicitly: ``True`` forces
    the streaming flash kernels (exact running-max softmax — use for
    workloads whose scaled logits may exceed the whole-kv path's
    static ``_CAP_HI`` cap), ``False`` allows the whole-kv fast path
    wherever its shape constraints hold, and ``None`` (default) defers
    to the ``RTPU_ATTN_EXACT`` env var. Per-call and trace-stable,
    unlike the env var, which only applies at first trace.

    ``debug`` (default: env ``RTPU_ATTN_DEBUG``) adds a finite-range
    assert when the whole-kv path is taken: any pre-softmax logit
    above ``_CAP_HI`` raises ``FloatingPointError`` instead of being
    silently clamped. Costs a full score-matrix pass — debugging only.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    use = _use_pallas() if force_pallas is None else force_pallas
    # Auto mode falls back to XLA for shapes the kernel can't tile: seq not
    # divisible by the (clamped) block sizes, or blocks under the TPU
    # sublane minimum (16 covers bf16's (16,128) tile). An explicit
    # force_pallas=True is honored — the kernel's own asserts surface.
    sq, sk = q.shape[2], k.shape[2]
    if force_pallas is None and use:
        # clamp blocks to a divisor of the sequence before giving up —
        # e.g. s=3840 doesn't divide by the 512 default but does by 256,
        # and the XLA fallback would materialize the full S x S scores
        def _fit(block, s):
            b = min(block, s)
            while b >= 16 and s % b:
                b //= 2
            return b
        bq, bk = _fit(block_q, sq), _fit(block_k, sk)
        if (bq < 16 or bk < 16 or sq % bq or sk % bk
                or bq % 16 or bk % 16):
            use = False
        else:
            block_q, block_k = bq, bk
    if not use and not interpret:
        return attention_reference(q, k, v, causal=causal, sm_scale=sm_scale)
    # what the program being traced holds (static: once a trace, never a
    # step), under the span open on this thread or in the module's ring
    from ray_tpu._private import tracing
    tracing.step_event("attention.flash_plan", 0.0, **flash_plan(
        sq, sk, q.shape[3], causal, exact, block_q, block_k),
        packed=False, heads_per_program=1)

    def local(q, k, v):
        # Fold the softmax scale into q OUTSIDE the kernel (one [b,h,s,d]
        # multiply, and autodiff routes the matching dq scale through it)
        # so the kernels skip a full [bq, block_k] multiply per kv block.
        q = (q * sm_scale).astype(q.dtype)
        if (debug if debug is not None else _attn_debug()) and \
                _use_whole_kv(sq, sk, q.shape[3], exact):
            _debug_check_logits(q, k)
        return _flash_attention(q, k, v, 1.0, causal, block_q, block_k,
                                interpret, exact)

    mesh = getattr(_TRACE_MESH, "mesh", None)
    if mesh is not None and mesh.size > 1:
        # Mosaic kernels cannot be partitioned by GSPMD: on a multi-
        # device mesh each device runs the kernel on its own batch and
        # head shard (attention mixes neither), the sequence whole.
        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel.jax_compat import shard_map
        head = "tp" if "tp" in mesh.axis_names else None
        spec = P(_batch_axes(mesh), head, None, None)
        local = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                          out_specs=spec, check_vma=False)
    return local(q, k, v)


def packed_heads(s: int, n_head: int, n_kv_head: int, head_dim: int,
                 causal: bool, exact: Optional[bool] = None,
                 mesh=None) -> int:
    """How many heads a program of the packed kernels holds at these
    shapes, 0 where ``flash_attention_packed`` goes through
    ``flash_attention``'s [b, h, s, d] layout instead. A pure function of
    the shapes and the mesh: packed where the plan is ``whole_kv_causal``,
    whole heads fill a 128-lane block (``128 % head_dim == 0``) and the
    blocks the heads (``n_head % (128 // head_dim) == 0``), there are as
    many kv heads as q heads, and the mesh is one device or shards the
    batch alone (a ``tp`` axis cuts the heads of the projection)."""
    if not causal or n_kv_head != n_head or head_dim < 1 or 128 % head_dim:
        return 0
    if n_head % (128 // head_dim) or flash_plan(
            s, s, head_dim, True, exact)["path"] != "whole_kv_causal":
        return 0
    if mesh is not None and any(
            size > 1 and axis not in _BATCH_AXES
            for axis, size in mesh.shape.items()):
        return 0
    return 128 // head_dim


def flash_attention_packed(qkv, n_head: int, *, n_kv_head: Optional[int] = None,
                           causal: bool = False,
                           sm_scale: Optional[float] = None,
                           force_pallas: Optional[bool] = None,
                           interpret: bool = False,
                           exact: Optional[bool] = None,
                           debug: Optional[bool] = None):
    """Fused self-attention of a projection's output where it lies:
    ``qkv`` [B, S, (n_head + 2 n_kv_head) head_dim], the queries', keys'
    and values' heads side by side as ``c_attn`` leaves them, ->
    [B, S, n_head head_dim], as ``c_proj`` takes it.

    Where ``packed_heads`` says so (and a Pallas kernel runs at all: on a
    TPU, or ``interpret``), the packed whole-kv causal kernels cut their
    blocks from ``qkv`` itself, ``128 // head_dim`` heads a program, and
    the gradient is one [B, S, 3E] array: no split, no [B, H, S, D] copy
    and no transpose back is traced. Everywhere else (no ``causal``,
    ``exact``, ``debug``, grouped kv heads, a head that does not fill
    whole lanes, the streaming lengths, a mesh that cuts heads) this is
    split + heads + ``flash_attention`` + the transpose back, with the
    same arguments: one algorithm, two layouts."""
    b, s, width = qkv.shape
    n_kv = n_head if n_kv_head is None else n_kv_head
    d = width // (n_head + 2 * n_kv)
    if sm_scale is None:
        sm_scale = d ** -0.5
    use = _use_pallas() if force_pallas is None else force_pallas
    mesh = getattr(_TRACE_MESH, "mesh", None)
    per = 0
    if (use or interpret) and not (debug if debug is not None
                                   else _attn_debug()):
        per = packed_heads(s, n_head, n_kv, d, causal, exact, mesh)
    if not per:
        q, k, v = jnp.split(qkv, [n_head * d, (n_head + n_kv) * d], axis=-1)

        def heads(t, n):  # [B,S,n*D] -> [B,n,S,D]
            return t.reshape(b, s, n, d).transpose(0, 2, 1, 3)

        y = flash_attention(
            heads(q, n_head), _repeat_kv(heads(k, n_kv), n_head // n_kv),
            _repeat_kv(heads(v, n_kv), n_head // n_kv), causal=causal,
            sm_scale=sm_scale, force_pallas=force_pallas,
            interpret=interpret, exact=exact, debug=debug)
        return y.transpose(0, 2, 1, 3).reshape(b, s, n_head * d)
    from ray_tpu._private import tracing
    tracing.step_event("attention.flash_plan", 0.0,
                       **flash_plan(s, s, d, True, exact),
                       packed=True, heads_per_program=per)
    # the scale as q's dtype holds it: what ``(q * sm_scale).astype`` of
    # the other layout multiplies by
    scale = float(np.asarray(sm_scale, dtype=qkv.dtype))

    def local(qkv):
        return _flash_packed(qkv, n_head, scale, interpret)

    if mesh is not None and mesh.size > 1:
        # (as in ``flash_attention``: each device its batch shard)
        from jax.sharding import PartitionSpec as P

        from ray_tpu.parallel.jax_compat import shard_map
        spec = P(_batch_axes(mesh), None, None)
        local = shard_map(local, mesh=mesh, in_specs=(spec,),
                          out_specs=spec, check_vma=False)
    return local(qkv)


# ---------------------------------------------------------------------------
# Incremental decode + paged KV cache (LLM serving, docs/LLM_SERVING.md)
#
# Training attention above recomputes every key/value each step; online
# inference must not. The serve LLM engine keeps KV in fixed-size BLOCKS
# (a paged cache, vLLM-style): per sequence a block table maps logical
# token positions to physical pages, so sequences grow without
# contiguous reallocation and freed pages are reusable immediately.
#
# Layouts (chosen so a scatter/gather is one advanced-index op):
#   contiguous cache   k/v: [B, S_max, Hkv, D]
#   paged cache        k/v pages: [P, bs, Hkv, D]; block_tables [B, NB]
#   serving pool       all layers' pages, lane-dense: [L, P, bs, Hkv*D]
#   lengths            [B] int32 — valid cache entries per sequence
#
# Three compute paths, all numerically equivalent (tier-1 gated in
# tests/test_llm_kernels_decode.py):
#   decode_attention            contiguous masked reference (XLA, CPU ok)
#   paged_attention_reference   gather pages -> decode_attention
#   paged_attention_decode      Pallas kernel over the serving pool: the
#                               block tables ride scalar prefetch, each
#                               row's live pages are copied from HBM a
#                               chunk at a time under a flash-style
#                               online softmax — the cache is never
#                               materialized contiguously (interpret=True
#                               on CPU). A served decode step (one token
#                               a row) runs it on the chip:
#                               ``cached_attention``


def _repeat_kv(k, rep: int, axis: int = 1):
    """Broadcast each kv head over its query group (GQA)."""
    return k if rep == 1 else jnp.repeat(k, rep, axis=axis)


def decode_attention(q, k_cache, v_cache, lengths, *,
                     sm_scale: Optional[float] = None,
                     q_positions=None):
    """Attention of new-token queries against a (padded) KV cache.

    q: [B, H, S_new, D] — the S_new newest tokens' queries; the cache
    already contains their keys/values (positions
    ``lengths - S_new .. lengths - 1``).
    k_cache/v_cache: [B, S_max, Hkv, D]; lengths: [B] int32 — valid
    entries INCLUDING the new tokens. Causal within the new tokens,
    full visibility over the prefix, masked past ``lengths``. GQA when
    Hkv < H (H must be a multiple of Hkv). ``q_positions`` ([B, S_new]
    int32, optional) overrides each query row's absolute position —
    right-padded prefill passes the real positions (and -1 for padding
    rows, whose output is discarded). Returns [B, H, S_new, D].
    """
    B, H, S_new, D = q.shape
    Hkv = k_cache.shape[2]
    if sm_scale is None:
        sm_scale = D ** -0.5
    k = _repeat_kv(k_cache.transpose(0, 2, 1, 3), H // Hkv)  # [B,H,S,D]
    v = _repeat_kv(v_cache.transpose(0, 2, 1, 3), H // Hkv)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    S_max = k_cache.shape[1]
    # query i (0-based among the new tokens) sits at absolute position
    # lengths - S_new + i and may attend to absolute positions <= its own
    if q_positions is None:
        q_positions = (lengths[:, None] - S_new) + \
            jnp.arange(S_new)[None, :]                     # [B,S_new]
    q_pos = q_positions[..., None]                         # [B,S_new,1]
    k_pos = jnp.arange(S_max)[None, None, :]               # [1,1,S_max]
    mask = (k_pos <= q_pos)[:, None]                       # [B,1,S_new,S_max]
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def append_kv_pages(k_new, v_new, k_pages, v_pages, block_tables,
                    lengths, valid=None, layer=None, ring: bool = False):
    """Scatter new keys/values into their pages.

    k_new/v_new: [B, S, Hkv, D] written at logical positions
    ``lengths .. lengths + S - 1`` of each sequence; ``valid`` ([B, S]
    bool, optional) routes padding tokens to the reserved null page 0
    instead (batch/length bucketing for jit). The pages are one layer's
    [P, bs, Hkv, D] or, with ``layer``, that layer of the serving pool
    [L, P, bs, Hkv*D]: B*S rows of it are written, the rest is not
    touched. Returns updated (k_pages, v_pages). Distinct sequences own
    distinct pages, so the scatter indices never collide except in the
    null page (scratch). ``ring``: the tables are the rows' rings (a
    windowed page group): logical page ``lp`` is ring page ``lp % NB``,
    and the caller writes no two positions a ring apart in one call.
    """
    B, S = k_new.shape[:2]
    lead = () if layer is None else (layer,)
    bs, row = k_pages.shape[len(lead) + 1], k_pages.shape[len(lead) + 2:]
    pos = lengths[:, None] + jnp.arange(S)[None, :]        # [B, S]
    page = jnp.take_along_axis(
        block_tables, (pos // bs) % block_tables.shape[1] if ring
        else pos // bs, axis=1)
    slot = pos % bs
    if valid is not None:
        page = jnp.where(valid, page, 0)
        slot = jnp.where(valid, slot, 0)
    at = (*lead, page, slot)
    return (k_pages.at[at].set(k_new.reshape(B, S, *row)),
            v_pages.at[at].set(v_new.reshape(B, S, *row)))


def paged_gather(pages, block_tables, layer=None):
    """Pages -> per-sequence (padded) contiguous cache:
    [P, bs, Hkv, D] + [B, NB] -> [B, NB*bs, Hkv, D]; with ``layer``,
    [L, P, bs, Hkv*D] -> that layer's [B, NB*bs, Hkv*D]. Every row's
    whole table is read, whatever the row holds: what prompts, verify
    windows and every step off the chip attend to. A decode step on the
    chip reads its live pages in place instead
    (``paged_attention_decode``, ``latent_attention_decode``)."""
    B, NB = block_tables.shape
    out = pages[block_tables] if layer is None \
        else pages[layer, block_tables]                    # [B,NB,bs,...]
    return out.reshape(B, NB * out.shape[2], *out.shape[3:])


def append_latent_pages(rows, pages, block_tables, lengths, valid=None,
                        layer=None):
    """``append_kv_pages`` for a model that caches ONE row a token (a
    latent, with no V pool beside it): rows [B, S, W] go to logical
    positions ``lengths .. lengths + S - 1`` of pages [P, bs, W] or,
    with ``layer``, of that layer of [L, P, bs, W]; padding tokens go to
    the null page."""
    B, S = rows.shape[:2]
    lead = () if layer is None else (layer,)
    bs = pages.shape[len(lead) + 1]
    pos = lengths[:, None] + jnp.arange(S)[None, :]
    page = jnp.take_along_axis(block_tables, pos // bs, axis=1)
    slot = pos % bs
    if valid is not None:
        page = jnp.where(valid, page, 0)
        slot = jnp.where(valid, slot, 0)
    return pages.at[(*lead, page, slot)].set(rows.astype(pages.dtype))


# latent_attention: the most float32 logits [B, H, q_block, T] of one
# query block against the whole context (four prompts of 1,024 tokens
# over 3,072 positions and 32 heads, the largest that Kimi-Linear's cell
# runs, hold 0.8 GB at 512 a block). Above it the logits stay inside the
# core: ``latent_prefill_attention``.
LATENT_LOGITS_BYTES = 1 << 30
_KEY_BLOCKS = (512, 256, 128)


def _latent_prefill_kernel(last_ref, *refs, block_k, window=None):
    # refs: q [bq, d]; k [T, d]; v [T, dv]; pos [bq, 1]; o [bq, dv];
    # last [B, S / bq] (scalar prefetch): the last key block a query of
    # the block may see (-1: a block of padding, which visits none); with
    # ``window``, first [B, S / bq] before them: the first it may see
    from jax.experimental import pallas as pl

    first_ref = None
    if window is not None:
        first_ref, *refs = refs
    q_ref, k_ref, v_ref, pos_ref, o_ref = refs
    bq = q_ref.shape[0]
    q, pos = q_ref[:], pos_ref[:]
    row = pl.program_id(0) // (pl.num_programs(0) // last_ref.shape[0])

    def body(j, carry):
        m, total, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        seen = k_pos <= pos
        if window is not None:
            seen = seen & (k_pos > pos - window)
        s = jnp.where(seen, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if window is not None:
            # (a query whose first keys all lie behind its window: its
            # running maximum is still the floor, and exp(0) is not 0)
            p = jnp.where(seen, p, 0.0)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, total * alpha + jnp.sum(p, axis=-1, keepdims=True), acc

    _, total, acc = jax.lax.fori_loop(
        0 if first_ref is None else first_ref[row, pl.program_id(1)],
        last_ref[row, pl.program_id(1)] + 1, body, (
            jnp.full((bq, 1), _NEG_INF, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32),
            jnp.zeros((bq, v_ref.shape[1]), jnp.float32)))
    o_ref[:] = (acc / jnp.maximum(total, 1e-30)).astype(o_ref.dtype)


def latent_prefill_attention(q, keys, values, q_positions, sm_scale,
                             block_q: int = 512, block_k: int = 512,
                             window: Optional[int] = None,
                             name: str = "latent_prefill_attention"):
    """Softmax attention of many queries over materialised keys and
    values, as one Pallas kernel: a grid step holds one head's keys and
    values whole and one block of its queries, walks the key blocks with
    a running maximum, sum and output (the same mathematics as one
    softmax over all of them), and stops at the last key block that holds
    a position some query of the block may see; a block of padding
    queries (positions -1) walks none and returns zeros. No logits leave
    the core. q [B, S, H, d], keys [B, T, Hkv, d], values [B, T, Hkv, dv],
    q_positions [B, S] -> [B, S, H, dv]. ``H`` is ``Hkv`` or a multiple
    of it (grouped heads: query head ``h`` reads key/value head ``h //
    (H / Hkv)`` where it lies, nothing is repeated). With ``window`` a
    query at position ``p`` sees the keys at ``p - window + 1 .. p``
    only, and the walk starts at the first key block some query of the
    block may see. ``S`` and ``T`` are whole blocks. Off the chip the
    same kernel runs interpreted. Forward only."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, d = q.shape
    T, Hkv, dv = keys.shape[1], keys.shape[2], values.shape[-1]
    G = H // Hkv
    bq, bk = min(block_q, S), min(block_k, T)
    assert S % bq == 0 and T % bk == 0 and H == G * Hkv, (S, bq, T, bk, H)

    def heads_first(t):             # [B, N, H, w] -> [B * H, N, w]
        return jnp.moveaxis(t, 2, 1).reshape(-1, t.shape[1], t.shape[3])
    blocks = q_positions.reshape(B, S // bq, bq)
    last = jnp.minimum(jnp.max(blocks, axis=-1)
                       // bk, T // bk - 1).astype(jnp.int32)
    scalars = [last]
    if window is not None:
        lowest = jnp.min(jnp.where(blocks >= 0, blocks, T), axis=-1)
        scalars.append((jnp.maximum(lowest - window + 1, 0) // bk
                        ).astype(jnp.int32))
    pos = jnp.broadcast_to(q_positions[:, None, :, None].astype(jnp.int32),
                           (B, H, S, 1)).reshape(B * H, S, 1)
    item = jnp.dtype(keys.dtype).itemsize
    need = 2 * T * (d + dv) * item + 6 * bq * bk * 4 + 4 * bq * (d + dv) * 4

    def kv_head(i, j, *_):          # query head i of the grid: its keys
        return (i if G == 1 else i // G, 0, 0)

    def own(i, j, *_):
        return (i, j, 0)
    call = pl.pallas_call(
        functools.partial(_latent_prefill_kernel, block_k=bk, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(B * H, S // bq),
            in_specs=[
                pl.BlockSpec((None, bq, d), own),
                pl.BlockSpec((None, T, d), kv_head),
                pl.BlockSpec((None, T, dv), kv_head),
                pl.BlockSpec((None, bq, 1), own),
            ],
            out_specs=pl.BlockSpec((None, bq, dv), own)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, dv), values.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(need * 1.25) + (8 << 20)),
        interpret=not _use_pallas(),
        name=name)
    out = call(*scalars, heads_first((q * sm_scale).astype(q.dtype)),
               heads_first(keys), heads_first(values), pos)
    return jnp.moveaxis(out.reshape(B, H, S, dv), 1, 2)


def ring_positions(lengths, ring: int, block_size: int):
    """The absolute position each row of a sequence's gathered ring holds
    once the sequence is ``lengths`` [B] tokens long: ring page ``s``
    holds the newest logical page ``lp <= (length - 1) // block_size``
    with ``lp % ring == s`` -> [B, ring * block_size] int32 (negative
    where the ring page has not been written by this sequence yet)."""
    newest = (lengths[:, None] - 1) // block_size              # [B, 1]
    s = jnp.arange(ring)[None, :]
    page = newest - (newest - s) % ring                        # [B, ring]
    return (page[:, :, None] * block_size
            + jnp.arange(block_size)[None, None, :]).reshape(
                lengths.shape[0], ring * block_size).astype(jnp.int32)


def prefill_attention_path(q, keys) -> str:
    """Which attention ``prefill_attention`` runs, from what it can
    observe: ``"blocked_kernel"`` (``latent_prefill_attention``'s kernel:
    key blocks walked with a running softmax, no logits outside the core)
    on a TPU where heads are whole lane tiles and the tokens whole blocks
    and no mesh of several devices is being traced for; ``"plain"`` (one
    masked softmax in ``jax.numpy``) for everything else."""
    S, d = q.shape[1], q.shape[3]
    T = keys.shape[1]
    mesh = getattr(_TRACE_MESH, "mesh", None)
    fits = (_use_pallas() and d % 128 == 0 and S % 8 == 0 and T % 8 == 0
            and S % min(512, S) == 0 and T % min(512, T) == 0
            and (mesh is None or mesh.size == 1))
    return "blocked_kernel" if fits else "plain"


def prefill_attention(q, keys, values, q_positions, *,
                      window: Optional[int] = None,
                      sm_scale: Optional[float] = None,
                      key_positions=None):
    """Causal softmax attention of a prompt's queries over keys and
    values with grouped heads, q [B, S, H, d], keys / values
    [B, T, Hkv, d], q_positions [B, S] (-1: padding) -> [B, S, H, d]. Key
    ``t`` stands at position ``t`` (or ``key_positions`` [B, T]: a
    gathered ring's, ``ring_positions``); a query at ``p`` sees the keys
    at positions ``<= p`` and, with ``window``, ``> p - window``. On the
    chip one Pallas kernel (``prefill_attention_path``); elsewhere the
    plain form of the same mathematics, float32 softmax, the groups read
    where they lie."""
    B, S, H, d = q.shape
    T, Hkv = keys.shape[1:3]
    if sm_scale is None:
        sm_scale = d ** -0.5
    if key_positions is None and \
            prefill_attention_path(q, keys) == "blocked_kernel":
        return latent_prefill_attention(
            q, keys, values, q_positions, sm_scale, window=window,
            name="prefill_attention")
    G = H // Hkv
    logits = jnp.einsum("bsngd,btnd->bngst", q.reshape(B, S, Hkv, G, d),
                        keys, preferred_element_type=jnp.float32) * sm_scale
    k_pos = jnp.arange(T)[None, :] if key_positions is None \
        else key_positions
    k_pos = k_pos[:, None, :]                                  # [B|1, 1, T]
    q_pos = q_positions[:, :, None]                            # [B, S, 1]
    seen = (k_pos <= q_pos) & (k_pos >= 0)
    if window is not None:
        seen = seen & (k_pos > q_pos - window)
    seen = seen[:, None, None]                                 # [B,1,1,S,T]
    probs = jax.nn.softmax(jnp.where(seen, logits, _NEG_INF), axis=-1)
    # (a row that sees nothing, padding: zeros, not a mean of the values)
    probs = jnp.where(seen, probs, 0.0).astype(values.dtype)
    out = jnp.einsum("bngst,btnd->bsngd", probs, values)
    return out.reshape(B, S, H, values.shape[-1])


def latent_attention(q_nope, q_rope, latent, w_kvb, q_positions, *,
                     v_dim: int, absorbed: bool = False,
                     sm_scale: Optional[float] = None, q_block: int = 512,
                     logits_bytes: Optional[int] = None):
    """Multi-head latent attention (MLA) over cached latents.

    q_nope [B, S, H, dn], q_rope [B, S, H, dr]: the new tokens' queries;
    latent [B, T, R + dr]: each context token's cached row, the
    normalised compressed latent ``c`` (R values) and the key part that
    all heads share; w_kvb [R, H, dn + v_dim]: the up-projection to each
    head's key and value; q_positions [B, S]: a query's absolute
    position (-1: padding, attends to nothing real). Key t is visible to
    a query at position p when t <= p. Returns [B, S, H, v_dim].

    ``absorbed=False`` materialises every head's keys and values from
    the latents (right when S is large: their cost is shared by all
    queries). ``absorbed=True`` folds the up-projection into the query
    and the output instead (``q_nope W_uk`` against ``c``, the
    probabilities' sum of ``c`` through ``W_uv``), so a decode step
    reads the context's latents once and builds nothing per head. On the
    chip a decode step over pages is ``latent_attention_decode``, the
    same mathematics over the pool as stored, of which this form is the
    reference and, off the chip, what runs; prompts and verify windows
    come here on every platform.

    The queries go ``q_block`` at a time. Where a block's float32 logits
    against the whole context would pass ``LATENT_LOGITS_BYTES`` (a
    prompt of 8,192 tokens over 9,216 cached positions and 64 heads: 1.2
    GB a block, several times over while the softmax runs, 19 GB a layer
    written and read again and again: 1.7 s a prompt on the chip, PR 35)
    the materialised form is one Pallas kernel that walks the keys in
    blocks with a running softmax (``latent_prefill_attention``).
    ``logits_bytes`` puts a model's own budget in that one's place
    (LongCat-Flash's: a 2,048-token prompt over 3,072 positions stays
    under the general one and cost 12 ms a sublayer so, 1.9 by the
    kernel: PR 41)."""
    B, S, H, dn = q_nope.shape
    R = w_kvb.shape[0]
    T = latent.shape[1]
    if sm_scale is None:
        sm_scale = (dn + q_rope.shape[-1]) ** -0.5
    c, k_rope = latent[..., :R], latent[..., R:]
    w_uk, w_uv = w_kvb[..., :dn], w_kvb[..., dn:]
    k_pos = jnp.arange(T)[None, None, None, :]
    k_block = next((kb for kb in _KEY_BLOCKS if T % kb == 0 and kb < T), 0)
    by_keys = not absorbed and k_block and \
        B * H * min(S, q_block) * T * 4 > (
            LATENT_LOGITS_BYTES if logits_bytes is None else logits_bytes)
    if absorbed:
        keys = c
        values = c
    else:
        kv = jnp.einsum("btr,rhd->bthd", c, w_kvb)
        keys, values = kv[..., :dn], kv[..., dn:]
        if by_keys:     # one product a key block: [k_n | k_r] a head
            keys = jnp.concatenate([keys, jnp.broadcast_to(
                k_rope[:, :, None, :], (B, T, H, k_rope.shape[-1]))], -1)

    def attend(qn, qr, pos):                    # a block of the queries
        if absorbed:
            qn = jnp.einsum("bshd,rhd->bshr", qn, w_uk)
            logits = jnp.einsum("bshr,btr->bhst", qn, keys,
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("bshd,bthd->bhst", qn, keys,
                                preferred_element_type=jnp.float32)
        logits = (logits + jnp.einsum(
            "bshd,btd->bhst", qr, k_rope,
            preferred_element_type=jnp.float32)) * sm_scale
        mask = k_pos <= pos[:, None, :, None]
        probs = jax.nn.softmax(jnp.where(mask, logits, _NEG_INF), axis=-1)
        probs = probs.astype(values.dtype)
        if absorbed:
            out = jnp.einsum("bhst,btr->bshr", probs, values)
            return jnp.einsum("bshr,rhd->bshd", out, w_uv)
        return jnp.einsum("bhst,bthd->bshd", probs, values)

    if by_keys:
        return latent_prefill_attention(
            jnp.concatenate([q_nope, q_rope], -1), keys, values,
            q_positions, sm_scale, q_block, k_block)
    if S <= q_block or S % q_block:
        return attend(q_nope, q_rope, q_positions)

    def blocks(t):                  # [B, S, ...] -> [S/qb, B, qb, ...]
        return jnp.moveaxis(
            t.reshape(B, S // q_block, q_block, *t.shape[2:]), 1, 0)
    out = jax.lax.map(lambda a: attend(*a), (
        blocks(q_nope), blocks(q_rope), blocks(q_positions)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, v_dim)


# table entries a chunk's copies go by, in both decode kernels over pages
# (``paged_attention_decode``, ``latent_attention_decode``): a page's copy
# costs the core's scalar unit as much to start and wait for as the memory
# needs to bring 16 KB, so where a group of this many entries names
# consecutive pages of the pool (``serve/llm/kv_cache.py`` hands a sequence
# its pages as ascending runs), one copy a pool brings them all
PAGED_RUN_PAGES = 8


def _row_after(len_ref, B, b):
    """The next row after ``b`` that holds a token (B: none does)."""
    return jax.lax.while_loop(
        lambda r: (r < B) & (len_ref[jnp.minimum(r, B - 1)] == 0),
        lambda r: r + 1, b + 1)


def _live_groups(base, live, pages, E, body, ring=None):
    """``body(g, x)`` for every group ``g`` of ``E`` table entries, of the
    chunk of ``pages`` that starts at logical page ``base``, that holds
    one of the row's ``live`` pages; ``x``: the place in the table of the
    group's first entry (in a ring of ``ring`` pages, one remainder a
    chunk)."""
    from jax.experimental import pallas as pl
    at = base if ring is None else jax.lax.rem(base, ring)

    def group(g, carry):
        @pl.when(base + g * E < live)
        def _():
            x = at + g * E
            if ring is not None:
                x = jnp.where(x >= ring, x - ring, x)
            body(g, x)
        return carry
    jax.lax.fori_loop(0, pages // E, group, 0)


def _start_group(pools, sem, layer, bt_ref, b, slot, g, x, E, end,
                 ring=False):
    """Group ``g`` of a chunk of row ``b``, the ``E`` table entries from
    place ``x``, on its way into ``slot`` of every pool's buffer
    (``pools``: one ``(pool in HBM, buffer [2, pages, bs, C])`` or two;
    ``sem`` [2, pools]). Where the table holds the ``E`` entries before
    its end (or the ring's wrap) and they are ``p, p + 1, ..., p + E - 1``
    (every difference is checked: a table with a shared prefix or a
    copied page is not sorted) ONE copy a pool brings ``[p, p + E)`` of
    the layer; any other group takes a copy a page, from the entry at
    ``min(x + i, end)`` (in a ring: at ``x + i`` wrapped). Either way a
    pool's semaphore has the group's ``E`` pages to count."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    NB = bt_ref.shape[1]
    whole = x <= NB - E
    at = jnp.minimum(x, NB - E)
    p = bt_ref[b, at]
    is_run = whole
    for i in range(1, E):
        is_run &= bt_ref[b, at + i] == p + i

    @pl.when(is_run)
    def _():
        for s, (hbm, buf) in enumerate(pools):
            pltpu.make_async_copy(
                hbm.at[layer, pl.ds(p, E)],
                buf.at[slot, pl.ds(g * E, E)],
                sem.at[slot, s]).start()

    @pl.when(jnp.logical_not(is_run))
    def _():
        def page(i, carry):
            if not ring:
                y = jnp.minimum(x + i, end)
            else:
                y = jnp.where(x + i >= NB, x + i - NB, x + i)
            for s, (hbm, buf) in enumerate(pools):
                pltpu.make_async_copy(
                    hbm.at[layer, bt_ref[b, y]],
                    buf.at[slot, g * E + i],
                    sem.at[slot, s]).start()
            return carry
        jax.lax.fori_loop(0, E, page, 0)


def _wait_group(pools, sem, slot, g, E):
    """Group ``g``'s pages are in ``slot`` of every pool's buffer: a
    semaphore counts what its copies brought, a group's fill its pages of
    the buffer however they were copied, so one wait a pool for that many
    bytes sees them all in."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    for s, (_, buf) in enumerate(pools):
        part = buf.at[slot, pl.ds(g * E, E)]
        pltpu.make_async_copy(part, part, sem.at[slot, s]).wait()


def _run_pages(pages: int) -> int:
    """Entries a group of a chunk of ``pages`` holds: ``PAGED_RUN_PAGES``,
    or (a chunk of a table narrower than it is that table) the largest
    count under it that divides the chunk."""
    return max(e for e in range(1, PAGED_RUN_PAGES + 1) if pages % e == 0)


# latent rows the decode kernel attends to at a time, in bytes. A page of
# this pool is small (16 rows of 640 bfloat16 values: 20 KB, 25 ns of the
# chip's memory bandwidth), so a chunk is sized by what it holds, not by
# a count of tokens: a chunk costs ~0.2 us beside its rows and a row ~1 us
# (the loop, the rescaling of the sums, the products of a last chunk's
# empty places), which 64 such pages carry and 16 do not. Read on the
# chip (PR 56, the kernel alone on the device's clock, tables as the
# allocator leaves them, 99% of their groups runs; a layer's call in ms
# at chunks of 256 / 512 / 1,024 / 2,048 tokens, the bytes at 819 GB/s
# in brackets, the copy-a-page kernel before it in parentheses): 32 rows
# of ~6,100 tokens under 64 heads 0.641 / 0.457 / 0.386 / 0.384 [0.307]
# (0.721 / 0.555 / 0.503); 64 rows of ~1,950 under 64 heads 0.441 /
# 0.330 / 0.293 / 0.295 [0.196] (0.491 / 0.397 / 0.382); 64 rows of
# ~1,560 under 32 heads 0.344 / 0.248 / 0.223 / 0.222 [0.157] (0.386 /
# 0.301 / 0.296). One size serves the three; past it two buffers double
# for nothing. Without its copies the kernel takes 0.250 / 0.194 / 0.139
# ms at this size and without its two products 0.353 / 0.251 / 0.195:
# the copies' side is the longer, and the whole within 9-17% of it. One
# wait a buffer for a chunk whose groups are all live, in place of one a
# group, read 0.381 / 0.290 / 0.222: under 1.5%, not worth a second
# path. Tables with NO run (shuffled pages) read 0.789 / 0.550 / 0.426
# where a straight line of 64 copies read 0.503 / 0.382 / 0.296: the
# allocator is held to runs
# (tests/test_llm_kernels_decode.py:test_tables_stay_runs_under_a_cells_churn)
_LATENT_CHUNK_BYTES = 1280 << 10


def _latent_decode_kernel(layer_ref, bt_ref, len_ref, live_ref, chunks_ref,
                          q_ref, pool, o_ref, buf, sem, slot_ref, m_ref,
                          l_ref, acc_ref, *, block_size, pages, run, rank,
                          sm_scale):
    """Grid step ``b``: row b's absorbed query [H, W] against its live
    latent pages, a chunk of ``pages`` pages at a time.

    The pool stays in HBM. The block table names a chunk's pages, copied
    into one of two [pages, bs, W] buffers while the other is attended
    to; the first chunk of the next row that holds a token goes under the
    last chunk of this one (buffers, semaphores and the slot in use
    outlive a grid step). A chunk is ``pages / run`` groups of ``run``
    table entries, copied as ``paged_attention_decode`` copies its own
    (``_start_group``): where a group's entries name ``run`` consecutive
    pages ONE copy brings the group, any other group takes a copy a page,
    and either way one wait stands for the group's bytes. A group wholly
    past a row's last live page (``live_ref``: the pages that hold one of
    its tokens; like ``chunks_ref``, the row's chunks, reckoned outside:
    a division costs the core's scalar unit a third of a microsecond) is
    neither copied nor waited for: the buffers are zeroed once, before
    the first row, so what such a group leaves in them is finite (zeros,
    or latent rows an earlier chunk brought), and its probabilities,
    exactly 0 under the mask by ``length``, keep it out of the sums. A
    chunk wholly past a row's length is not looked at. In a live group
    that is no run, the places past the row's last live page take that
    page again, masked (a table's unused entries are the null page, which
    is never read); a live group that is a run brings the pages the row
    holds and has yet to write, masked the same. The values are the first
    ``rank`` columns of the same buffer. A row of length 0 visits no
    chunk: zeros.
    """
    from jax.experimental import pallas as pl

    b, B = pl.program_id(0), pl.num_programs(0)
    T = pages * block_size
    W = buf.shape[-1]
    layer = layer_ref[0]
    pools = ((pool, buf),)

    def start(r, c, slot):
        """Chunk c of row r on its way into buffer ``slot``."""
        live = live_ref[r]
        _live_groups(c * pages, live, pages, run,
                     lambda g, x: _start_group(pools, sem, layer, bt_ref, r,
                                               slot, g, x, run, live - 1))

    def wait(r, c, slot):
        _live_groups(c * pages, live_ref[r], pages, run,
                     lambda g, x: _wait_group(pools, sem, slot, g, run))

    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        first = _row_after(len_ref, B, -1)

        @pl.when(first < B)
        def _():
            start(first, 0, 0)

    length, n = len_ref[b], chunks_ref[b]
    after = _row_after(len_ref, B, b)
    q = q_ref[...]
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def chunk(c, slot):
        more = c + 1 < n
        nr = jnp.where(more, b, after)

        @pl.when(nr < B)
        def _():
            start(nr, jnp.where(more, c + 1, 0), 1 - slot)
        wait(b, c, slot)
        s = jax.lax.dot_general(
            q, buf[slot].reshape(T, W), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale       # [H, T]
        pos = c * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(buf.dtype), buf[slot, :, :, :rank].reshape(T, rank),
            preferred_element_type=jnp.float32)
        return 1 - slot

    slot_ref[0] = jax.lax.fori_loop(0, n, chunk, slot_ref[0])
    # (a row without a token: zeros, over a floor, not 0 / 0)
    o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def latent_decode_path(pages, rank: int, S: int, layer=0) -> str:
    """Which attention ``models.mla.MLAMixer`` runs over latent pages,
    from what it can observe: ``"latent_kernel"``
    (``latent_attention_decode``) for one new token a row (``S == 1``)
    over the latent pool [L, P, bs, W] on a TPU, where a row and its
    first ``rank`` values (the latent, which is key and value) are whole
    lane tiles, a page whole sublane tiles of the pool's dtype, and no
    mesh of several devices is being traced for (a bare Mosaic call is
    refused there); ``"gather"`` (``paged_gather`` +
    ``latent_attention``) for everything else: a prompt, a window of
    tokens, no pages, the CPU."""
    if pages is None or S != 1 or layer is None or not _use_pallas():
        return "gather"
    bs, W = pages.shape[2:]
    mesh = getattr(_TRACE_MESH, "mesh", None)
    fits = (W % 128 == 0 and rank % 128 == 0 and rank <= W
            and bs % (32 // jnp.dtype(pages.dtype).itemsize) == 0
            and (mesh is None or mesh.size == 1))
    return "latent_kernel" if fits else "gather"


# (a function of its own under ``jit``: a model's latent layers are the
# same call with another ``layer``, and a step program then traces the
# kernel and lowers it to Mosaic once, not once a layer: seven layers'
# took a decode program's first call from 2 s to 16; my chip runs, PR 36)
@functools.partial(jax.jit,
                   static_argnames=("rank", "sm_scale", "interpret"))
def _latent_decode_call(layer, block_tables, lengths, q, pages, *, rank,
                        sm_scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, W = q.shape
    bs = pages.shape[2]
    NB = block_tables.shape[1]
    n_pages = max(1, min(
        _LATENT_CHUNK_BYTES // (bs * W * pages.dtype.itemsize), NB))
    kernel = functools.partial(_latent_decode_kernel, block_size=bs,
                               pages=n_pages, run=_run_pages(n_pages),
                               rank=rank, sm_scale=sm_scale)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(B,),
            in_specs=[pl.BlockSpec((None, H, W), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, rank),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, n_pages, bs, W), pages.dtype),
                pltpu.SemaphoreType.DMA((2, 1)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
        # (the buffers and the slot in use go from one row to the next)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_attention_decode",
    )
    return call(layer, block_tables, lengths, -(-lengths // bs),
                -(-lengths // (n_pages * bs)), q, pages)


def latent_attention_decode(q, pages, block_tables, lengths, *, rank: int,
                            sm_scale: float, layer=0,
                            interpret: bool = False):
    """Pallas latent-attention decode, the absorbed form of
    ``latent_attention`` for one new token a row: q [B, H, W] in the
    pool's dtype (``q_nope W_uk`` in the first ``rank`` columns, the
    query's shared-key part in the next, zeros to W) against layer
    ``layer`` (an int or a traced scalar) of the latent pool
    [L, P, bs, W], read where it lies: nothing is gathered or sliced
    outside the kernel, and only the pages that hold one of a row's
    ``lengths`` tokens (counted after the write of the new one) are
    read, in chunks of ``_LATENT_CHUNK_BYTES``. A cached row is key
    (all W columns) and value (the first ``rank``) of every head, so a
    row's scores are one product [H, W] x [T, W]^T and its sums one
    product [H, T] x [T, rank] on the same buffer. Operands in the
    pool's dtype, products summed in float32, the softmax float32 and
    online, the probabilities cast to the pool's dtype before they meet
    the latents: the mathematics of ``latent_attention(absorbed=True)``.
    Returns the probabilities' sum of the latents [B, H, rank] float32
    (the caller takes it through ``W_uv``); zeros for a row of length 0.
    Off the chip: ``interpret=True`` (tests)."""
    assert pages.shape[3] == q.shape[2] and q.dtype == pages.dtype, \
        (q, pages)
    return _latent_decode_call(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q, pages,
        rank=rank, sm_scale=float(sm_scale), interpret=interpret)


def paged_attention_reference(q, k_pages, v_pages, block_tables,
                              lengths, *, layer=0,
                              sm_scale: Optional[float] = None,
                              window: Optional[int] = None):
    """Single-token decode against one layer of the serving pool, via
    gather (the correctness baseline for the Pallas kernel, and what it
    falls back to off the chip).

    q: [B, H, D] (one query token per sequence); k_pages/v_pages:
    [L, P, bs, Hkv*D]; returns [B, H, D]. With ``window`` the block
    tables are the rows' rings (``ring_positions``) and a row's query, at
    position ``length - 1``, sees the last ``window`` positions only.
    """
    B, _, D = q.shape

    def gathered(pages):                               # [B,NB*bs,Hkv,D]
        return paged_gather(pages, block_tables, layer).reshape(
            B, -1, pages.shape[-1] // D, D)
    if window is not None:
        out = prefill_attention(
            q[:, None], gathered(k_pages), gathered(v_pages),
            lengths[:, None] - 1, window=window, sm_scale=sm_scale,
            key_positions=ring_positions(
                lengths, block_tables.shape[1], k_pages.shape[2]))
        return out[:, 0].astype(q.dtype)
    out = decode_attention(q[:, :, None, :], gathered(k_pages),
                           gathered(v_pages), lengths, sm_scale=sm_scale)
    return out[:, :, 0, :]


# tokens the kernel attends to at a time: the pages that hold them are
# copied into VMEM together while the chunk before is worked on. The
# kernel's one instruction stream starts the copies and runs the products
# in turn: a chunk costs what its K and V tiles take to pass through the
# matrix units under a few query rows (one 128 x 128 tile a unit in ~128
# cycles: 1.1 us for 512 tokens of 1 KiB rows, 0.55 us for 128 of 2 KiB)
# plus what its copies take to start, while the bytes arrive underneath.
# Read on the chip (PR 44, the kernel alone; bytes at 819 GB/s in
# brackets): 96 rows of a mixed queue's contexts at 1 KiB a row (4 kv
# heads of 128, bfloat16), tables of runs, chunks of 128 / 256 / 512 /
# 1,024 tokens 1.47 / 1.05 / 0.87 / 0.85 ms a layer [0.67] (a copy a
# page, PR 43: 1.93 / 1.53 / 1.32), a window of 4,096 1.23 / 0.90 / 0.73
# / 0.73 [0.54]; 64 rows of ~6,500 tokens at 2 KiB a row (8 kv heads)
# 2.91 / 2.35 / 2.34 ms in chunks of 128 / 256 / 512 [2.07]. So rows
# under 2 KiB take 512 tokens and wider rows 256 (what two buffers a
# pool hold in VMEM doubles with each step and gains nothing past these).
_PAGED_CHUNK_TOKENS = 256
_PAGED_NARROW_ROW_BYTES = 2048
_PAGED_NARROW_CHUNK_TOKENS = 512


def paged_chunk_tokens(row_bytes: int) -> int:
    """Tokens a chunk of ``paged_attention_decode`` for a pool whose rows
    hold ``row_bytes``."""
    return (_PAGED_CHUNK_TOKENS if row_bytes >= _PAGED_NARROW_ROW_BYTES
            else _PAGED_NARROW_CHUNK_TOKENS)


def _paged_decode_kernel(layer_ref, bt_ref, len_ref, q_ref, k_hbm, v_hbm,
                         o_ref, k_buf, v_buf, sem, qbd_ref, m_ref, l_ref,
                         acc_ref, *, block_size, pages, run, head_dim,
                         sm_scale, window=None):
    """Every row's one query against its live pages, a chunk of
    ``pages`` pages at a time, all heads at once.

    With ``window`` the block table is the row's ring of ``NB`` pages
    (logical page ``lp`` lies in ring page ``lp % NB``): the chunks start
    at the logical page that holds position ``length - window``, the
    positions before it and from ``length`` on are masked, and no page
    behind the window is copied.

    The pools stay in HBM; the block table names the pages of a chunk,
    copied into one of two [pages, bs, Hkv*D] buffers while the other is
    attended to, the first chunk of the next row under the last of this
    one. A chunk is ``pages / run`` groups of ``run`` table entries.
    Where a group's entries are ``p, p + 1, ..., p + run - 1`` (every
    difference is checked: a table with a shared prefix or a copied page
    is not sorted) ONE copy a pool brings the group from ``[p, p + run)``
    of the layer; any other group (a run broken inside it, a ring that
    wraps inside it, a table's end, the null page's padding) takes a copy
    a page, and either way one wait a pool stands for the group's bytes.
    A group wholly past a row's last live page is neither copied nor
    waited for: the buffers are zeroed once, so what such a group leaves
    in them is finite and its probabilities, exactly 0, keep it out of
    the sums. A chunk wholly past a row's length is not looked at. Heads
    stay side by side on the lanes, as the pool holds them: the G query
    heads of every kv head form a block-diagonal [H, Hkv*D] matrix (row h
    holds head h's query under its kv head's columns, zeros elsewhere),
    so the scores of all heads are one product against the chunk's keys,
    probabilities x values one product [H, T] x [T, Hkv*D], and a head's
    output the columns of its own kv head. No head is sliced out of a
    lane tile.
    """
    from jax.experimental import pallas as pl

    B, G, C = q_ref.shape
    NB = bt_ref.shape[1]
    T = pages * block_size
    E = run
    layer = layer_ref[0]
    pools = ((k_hbm, k_buf), (v_hbm, v_buf))

    def first_page(b):
        """The logical page a row's walk starts at."""
        if window is None:
            return 0
        return jnp.maximum(len_ref[b] - window, 0) // block_size

    def live_groups(b, c, body):
        """``body(g, x)`` for every group ``g`` of chunk ``c`` of row
        ``b`` that holds a live page; ``x``: the group's place in the
        table."""
        base = first_page(b) + c * pages
        live = (len_ref[b] + block_size - 1) // block_size
        _live_groups(base, live, pages, E, body,
                     None if window is None else NB)

    def start(b, c, slot):
        """Chunk ``c`` of row ``b`` on its way into buffer ``slot``."""
        live_groups(b, c, lambda g, x: _start_group(
            pools, sem, layer, bt_ref, b, slot, g, x, E, NB - 1,
            ring=window is not None))

    def wait(b, c, slot):
        live_groups(b, c, lambda g, x: _wait_group(pools, sem, slot, g, E))

    k_buf[...] = jnp.zeros_like(k_buf)
    v_buf[...] = jnp.zeros_like(v_buf)
    first = _row_after(len_ref, B, -1)

    @pl.when(first < B)
    def _():
        start(first, 0, 0)

    rows = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
    kv_head = jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1), head_dim)
    # own[j]: row h = g * G + j against the columns of kv head g
    own = [rows == kv_head * G + j for j in range(G)]

    def row(b, slot):
        length = len_ref[b]
        if window is None:
            n = (length + T - 1) // T
        else:
            n = ((length + block_size - 1) // block_size - first_page(b)
                 + pages - 1) // pages
        after = _row_after(len_ref, B, b)
        qbd = jnp.zeros(acc_ref.shape, jnp.float32)
        for j in range(G):
            qbd = jnp.where(own[j], q_ref[b, pl.ds(j, 1), :], qbd)
        qbd_ref[...] = qbd.astype(qbd_ref.dtype)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def chunk(c, slot):
            more = c + 1 < n
            nb = jnp.where(more, b, after)

            @pl.when(nb < B)
            def _():
                start(nb, jnp.where(more, c + 1, 0), 1 - slot)
            wait(b, c, slot)
            s = jax.lax.dot_general(
                qbd_ref[...], k_buf[slot].reshape(T, C),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale   # [H, T]
            pos = c * T + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if window is None:
                seen = pos < length
            else:
                pos = pos + first_page(b) * block_size
                seen = (pos < length) & (pos >= length - window)
            s = jnp.where(seen, s, _NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_ref[...] = m_new
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p.astype(v_buf.dtype), v_buf[slot].reshape(T, C),
                preferred_element_type=jnp.float32)
            return 1 - slot

        slot = jax.lax.fori_loop(0, n, chunk, slot)
        # (a row without a token: zeros, over a floor, not 0 / 0)
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        for j in range(G):
            o_ref[b, pl.ds(j, 1), :] = jnp.sum(
                jnp.where(own[j], out, 0.0), axis=0, keepdims=True)
        return slot

    jax.lax.fori_loop(0, B, row, 0)


def paged_decode_path(q_heads: int, head_dim: int, pages, S: int,
                      layer=0) -> str:
    """Which attention ``cached_attention`` runs over a paged cache, from
    what it can observe: ``"paged_kernel"`` (``paged_attention_decode``)
    for one new token a row (``S == 1``) over the serving pool
    [L, P, bs, Hkv*D] on a TPU, where the pool's rows are whole lane
    tiles and its pages whole sublane tiles and no mesh of several
    devices is being traced for (a bare Mosaic call is refused there);
    ``"gather"`` (``paged_gather`` + ``decode_attention``) for
    everything else: prefill, a window of tokens, the CPU."""
    if S != 1 or layer is None or not _use_pallas():
        return "gather"
    bs, C = pages.shape[2:]
    mesh = getattr(_TRACE_MESH, "mesh", None)
    fits = (C % 128 == 0 and C % head_dim == 0
            and q_heads % (C // head_dim) == 0
            and bs % (32 // jnp.dtype(pages.dtype).itemsize) == 0
            and (mesh is None or mesh.size == 1))
    return "paged_kernel" if fits else "gather"


def paged_attention_decode(q, k_pages, v_pages, block_tables, lengths,
                           *, layer=0, sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           window: Optional[int] = None):
    """Pallas paged-attention decode: q [B, H, D], one query a row,
    against layer ``layer`` (an int or a traced scalar) of the serving
    pools [L, P, bs, Hkv*D], read where they lie: nothing is gathered,
    sliced or laid out anew outside the kernel, and only the pages that
    hold one of a row's ``lengths`` tokens are read (in chunks of
    ``paged_chunk_tokens``). MHA and GQA (H a multiple of Hkv); the
    products are exact and summed in float32, the softmax is float32
    and online, the probabilities meet V in the pool's dtype: the
    mathematics of ``decode_attention``. A row of length 0 gives zeros.

    ``window`` (static): the layer only ever reads a position's last
    ``window`` predecessors, and the block tables are the rows' rings of
    ``window // bs + 1`` pages (position ``p`` in ring page ``(p // bs) %
    ring``, ``serve/llm/kv_cache.py``): a row's chunks start at the page
    that holds position ``length - window``, positions behind the window
    are masked, nothing behind it is copied. Without it the kernel is the
    code it was.

    Off-TPU (and not ``interpret``) this falls back to the gather
    reference — numerics are identical (gated in tests), so callers
    never branch.
    """
    if interpret is None:
        interpret = False
        if not _use_pallas():
            return paged_attention_reference(
                q, k_pages, v_pages, block_tables, lengths, layer=layer,
                sm_scale=sm_scale, window=window)
    NB, bs = block_tables.shape[1], k_pages.shape[2]
    assert window is None or NB == window // bs + 1, (NB, window, bs)
    return _paged_decode_call(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q,
        k_pages, v_pages, window=window, interpret=interpret,
        sm_scale=float(q.shape[2] ** -0.5 if sm_scale is None else sm_scale))


# (a function of its own under ``jit``, as ``_latent_decode_call``: a
# model's layers of one kind are the same call with another ``layer``,
# and a step program traces the kernel and lowers it to Mosaic once a
# kind, not once a layer)
@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "window", "interpret"))
def _paged_decode_call(layer, block_tables, lengths, q, k_pages, v_pages,
                       *, sm_scale, window, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    bs, C = k_pages.shape[2:]
    NB = block_tables.shape[1]
    Hkv = C // D
    G = H // Hkv
    pages = max(1, min(paged_chunk_tokens(
        C * jnp.dtype(k_pages.dtype).itemsize) // bs, NB))
    run = _run_pages(pages)
    # row h of the kernel's matrices is head h; whole sublane tiles of
    # the pool's dtype
    Hp = -(-H // 16) * 16
    kernel = functools.partial(_paged_decode_kernel, block_size=bs,
                               pages=pages, run=run, head_dim=D,
                               sm_scale=sm_scale, window=window)

    def whole(*_):
        return 0, 0, 0
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[pl.BlockSpec((B, G, C), whole),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((B, G, C), whole),
            scratch_shapes=[
                pltpu.VMEM((2, pages, bs, C), k_pages.dtype),
                pltpu.VMEM((2, pages, bs, C), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((Hp, C), k_pages.dtype),
                pltpu.VMEM((Hp, 1), jnp.float32),
                pltpu.VMEM((Hp, 1), jnp.float32),
                pltpu.VMEM((Hp, C), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, G, C), jnp.float32),
        interpret=interpret,
        name="paged_attention_decode",
    )
    with jax.named_scope("paged_attention_decode"):
        # [B, H, D] -> [B, G, Hkv*D]: query j of every group, side by
        # side as the pool holds the kv heads (float32: single rows of
        # it are whole sublanes; every value is one of q's)
        qf = q.astype(jnp.float32).reshape(B, Hkv, G, D).transpose(
            0, 2, 1, 3).reshape(B, G, C)
        out = call(layer, block_tables, lengths, qf, k_pages, v_pages)
        return out.reshape(B, G, Hkv, D).transpose(0, 2, 1, 3).reshape(
            B, H, D).astype(q.dtype)


def cached_attention(q, k_new, v_new, cache, seq_lengths, *,
                     sm_scale: Optional[float] = None, valid=None,
                     layer=None):
    """Shared incremental-attention step for the model decode paths
    (models/gpt2.py, models/llama.py).

    q/k_new/v_new: [B, S, H|Hkv, D] projections of the S newest tokens
    (q head-major is the CALLER's concern — here everything is token-
    major, matching the cache layouts). ``cache`` holds every layer's
    cache, of which this call reads and writes layer ``layer``: a list
    of contiguous caches, one a layer (``init_kv_cache``), or the
    serving pool, one dict for all layers:

      [{"k": [B,S_max,Hkv,D], "v": ...}, ...]             contiguous
      {"k_pages": [L,P,bs,Hkv*D], "v_pages": ...,
       "block_tables": [B,NB]}                            paged

    The pool's minor axis is heads x head dimension, whole lanes, and
    each block hands the updated cache to the next: in a jit that
    donates the pools a step scatters B*S rows a layer into them and
    copies nothing pool-sized (tests/test_chip_compile.py holds it to
    that). A single contiguous dict, with no ``layer``, is taken too.
    ``seq_lengths`` [B] counts valid cache entries BEFORE this call
    (i.e. the prefix length); ``valid`` ([B, S] bool, optional) marks
    real tokens when the caller padded S to a bucket — padding kv is
    routed to the paged cache's null page and masked out of attention
    by the lengths. Appends the new kv, attends causally, and returns
    (out [B, S, H, D], updated cache in the form given). Over the
    serving pool, which attention runs follows from the shapes
    (``paged_decode_path``): one token a row on the chip reads its live
    pages in place (``paged_attention_decode``); everything else gathers
    every row to the padded context.
    """
    B, S = q.shape[:2]
    layers = cache if isinstance(cache, list) else None
    if layers is not None:
        cache = layers[layer]
    q_positions = None
    if valid is not None:
        new_len = seq_lengths + jnp.sum(valid.astype(jnp.int32), axis=1)
        # right-padding: real token i sits at absolute seq_lengths + i;
        # padding rows attend to nothing real (position -1)
        q_positions = jnp.where(
            valid, seq_lengths[:, None] + jnp.arange(S)[None, :], -1)
    else:
        new_len = seq_lengths + S
    if "k_pages" in cache:
        tables = cache["block_tables"]
        k_pages, v_pages = append_kv_pages(
            k_new, v_new, cache["k_pages"], cache["v_pages"], tables,
            seq_lengths, valid=valid, layer=layer)

        new_cache = dict(cache, k_pages=k_pages, v_pages=v_pages)
        if paged_decode_path(q.shape[2], q.shape[3], k_pages, S, layer) \
                == "paged_kernel":
            # one query a row (``valid`` can only mark whole rows, whose
            # length is then 0): the live pages, read where they lie
            out = paged_attention_decode(
                q[:, 0], k_pages, v_pages, tables, new_len, layer=layer,
                sm_scale=sm_scale)
            return out[:, None], new_cache

        def gathered(pages):                               # [B,NB*bs,Hkv,D]
            return paged_gather(pages, tables, layer).reshape(
                B, -1, *k_new.shape[2:])
        out = decode_attention(
            q.transpose(0, 2, 1, 3), gathered(k_pages), gathered(v_pages),
            new_len, sm_scale=sm_scale, q_positions=q_positions)
    else:
        pos = seq_lengths[:, None] + jnp.arange(S)[None, :]
        bidx = jnp.arange(B)[:, None]
        if valid is not None:
            # padded tokens must not clobber cache slots a later real
            # token will own: clamp their write position in place
            vm = valid[..., None, None]
            k_new = jnp.where(vm, k_new, cache["k"][bidx, pos])
            v_new = jnp.where(vm, v_new, cache["v"][bidx, pos])
        k_cache = cache["k"].at[bidx, pos].set(k_new)
        v_cache = cache["v"].at[bidx, pos].set(v_new)
        out = decode_attention(q.transpose(0, 2, 1, 3), k_cache,
                               v_cache, new_len, sm_scale=sm_scale,
                               q_positions=q_positions)
        new_cache = dict(cache, k=k_cache, v=v_cache)
    if layers is not None:
        new_cache = layers[:layer] + [new_cache] + layers[layer + 1:]
    return out.transpose(0, 2, 1, 3), new_cache
