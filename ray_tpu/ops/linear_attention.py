"""Linear attention with a carried state: the causal short convolution
and Kimi Delta Attention (KDA), the gated delta rule with a decay per
channel.

Per head, with a state ``S`` in R^{dk x dv} (float32), log-decay ``g_t``
in R^{dk} (<= 0), write strength ``beta_t`` in (0, 1):

    S'  = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Three forms of the same recurrence:

* ``kda_recurrent_step`` - one token a row (decode), in XLA: the
  reference, and what runs off the chip. It takes the rows' states as an
  array of their own and reads them twice and writes them once.
* ``kda_recurrent_step_in_place`` - the same step as one Pallas kernel
  over the state POOL [layers, slots, H, dk, dv] where it lies: a
  (row, head block) tile is loaded into VMEM once, both products and the
  update are taken from it there, and it is written back to the slot it
  came from. One read and one write of the state a token, no gather, no
  scatter, no copy of a layer's rows. ``kda_decode_step`` is a served
  decode step's one entry: it takes the pool and runs whichever of the
  two ``kda_decode_path`` names.
* ``kda_chunked`` - a whole sequence in chunks of ``chunk`` tokens
  (prefill, training): inside a chunk the tokens' writes are solved
  together (a unit lower-triangular system of size ``chunk``), between
  chunks the state is carried by a ``lax.scan``. Every exponent that is
  taken is <= 0 (a decay between two positions is computed from the
  difference of the cumulative log-decays, never from exp(-G)), so the
  form holds for any decay, slow or fast.

All take ``g = 0, beta = 0`` at a position as "no token here": the
state passes through unchanged, which is how right-padded rows and
padding rows of a bucket are kept out of it. Every product that touches
the state runs in float32 at ``highest`` precision (in the kernel: on
the vector unit, multiplied and summed in float32, with no pass through
bfloat16): the state is what a sequence remembers, and its rounding is
carried for ever.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention as A

_HI = jax.lax.Precision.HIGHEST
# The state of this many bytes is one grid step's tile of the kernel (as
# many heads of one row as fit, in whole sublane tiles of the vectors):
# the pipeline holds four of them (in and out, two buffers each).
_KDA_TILE_BYTES = 1 << 20


def short_conv(x, tail, weight, n_new=None, bias=None):
    """Causal depthwise convolution over time with a carried tail.

    x [B, S, C]: the new rows; tail [B, K-1, C]: the K-1 rows before
    them (zeros at a sequence's start); weight [K, C], ``weight[K-1]``
    multiplying the current row. ``n_new`` ([B] int, optional) is how
    many of the S rows are real (right-padded buckets; default all).
    ``bias`` ([C], optional) is added to every output row.
    Returns (y [B, S, C], new_tail [B, K-1, C]): the tail that the next
    call of these sequences takes, i.e. the last K-1 real rows.
    """
    B, S, C = x.shape
    K = weight.shape[0]
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B,S+K-1,C]
    y = sum(xx[:, j:j + S] * weight[j].astype(x.dtype) for j in range(K))
    if bias is not None:
        y = y + bias.astype(x.dtype)
    if n_new is None:
        return y, xx[:, S:]
    idx = n_new[:, None] + jnp.arange(K - 1)[None, :]          # [B, K-1]
    return y, jnp.take_along_axis(xx, idx[..., None], axis=1)


def kda_recurrent_step(q, k, v, g, beta, state):
    """One token a row. q, k, g [B, H, dk]; v [B, H, dv]; beta [B, H];
    state [B, H, dk, dv] float32. Returns (o [B, H, dv], new state).

    ``S'^T k`` and ``S'^T q`` are taken from the stored state in one
    pass (``S'^T k = S^T (exp(g) k)``), and the new state in a second:
    two reads and one write of the state a token. The reference of
    ``kda_recurrent_step_in_place``, and the path off the chip."""
    f32 = jnp.float32
    q, k, v, g, beta = (t.astype(f32) for t in (q, k, v, g, beta))
    a = jnp.exp(g)                                          # [B,H,dk]
    both = jnp.stack([a * k, a * q], axis=-1)               # [B,H,dk,2]
    sk, sq = jnp.moveaxis(jnp.einsum(
        "bhij,bhic->bhcj", state, both, precision=_HI), 2, 0)
    r = (v - sk) * beta[..., None]                          # [B,H,dv]
    o = sq + jnp.sum(k * q, axis=-1, keepdims=True) * r
    new = a[..., None] * state + k[..., None] * r[..., None, :]
    return o, new


def kda_decode_path(state_pool, S: int) -> str:
    """Which recurrence ``models.kimi_linear`` runs over the state pool
    [layers, slots, H, dk, dv], from what it can observe:
    ``"kda_kernel"`` (``kda_recurrent_step_in_place``) for one new token
    a row (``S == 1``) on a TPU, where the pool is float32, ``dk`` and
    ``dv`` are whole lane tiles and the heads whole sublane tiles, and no
    mesh of several devices is being traced for (a bare Mosaic call is
    refused there); ``"xla"`` (the rows' states gathered,
    ``kda_recurrent_step`` or ``kda_chunked``, scattered back) for
    everything else: a prompt, no pool, the CPU."""
    if state_pool is None or S != 1 or not A._use_pallas():
        return "xla"
    mesh = getattr(A._TRACE_MESH, "mesh", None)
    fits = (state_pool.ndim == 5 and state_pool.dtype == jnp.float32
            and state_pool.shape[2] % 8 == 0
            and state_pool.shape[3] % 128 == 0
            and state_pool.shape[4] % 128 == 0
            and (mesh is None or mesh.size == 1))
    return "kda_kernel" if fits else "xla"


def _kda_step_kernel(layer_ref, slot_ref, q_ref, k_ref, a_ref, v_ref,
                     beta_ref, s_ref, o_ref, new_ref):
    """One row's block of heads: ``kda_recurrent_step``'s mathematics on
    tiles that are loaded once. The vectors come lane-dense, [heads, dk]
    and [heads, dv]; what multiplies along ``dk`` (the state's sublanes)
    is turned to [dk, heads] here, and a head's column broadcast over
    the lanes."""
    q, k, a, v = q_ref[...], k_ref[...], a_ref[...], v_ref[...]  # [hb,dk]
    beta = beta_ref[...]                                    # [hb,1]
    kq = jnp.sum(k * q, axis=-1, keepdims=True)             # [hb,1]
    a_t, k_t, ak_t, aq_t = a.T, k.T, (a * k).T, (a * q).T   # [dk,hb]
    rows = []
    for h in range(q.shape[0]):
        at = slice(h, h + 1)
        S = s_ref[h]                                        # [dk,dv]
        sk = jnp.sum(S * ak_t[:, at], axis=0, keepdims=True)    # [1,dv]
        sq = jnp.sum(S * aq_t[:, at], axis=0, keepdims=True)
        r = (v[at] - sk) * beta[at]
        rows.append(sq + kq[at] * r)
        new_ref[h] = a_t[:, at] * S + k_t[:, at] * r
    o_ref[...] = jnp.concatenate(rows, axis=0)


# (a function of its own under ``jit``, the layer an argument: a model's
# KDA layers are the same call, and a decode program traces the kernel
# and lowers it to Mosaic once, as ``ops.attention._latent_decode_call``)
@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_step_call(layer, slots, q, k, g, v, beta, pool, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, dk = q.shape
    dv = v.shape[-1]
    hb = max((n for n in range(8, H + 1, 8) if H % n == 0
              and n * dk * dv * 4 <= _KDA_TILE_BYTES), default=min(H, 8))

    def vectors(width):
        return pl.BlockSpec((None, hb, width), lambda b, h, *_: (b, h, 0))
    tile = pl.BlockSpec(
        (None, None, hb, dk, dv),
        lambda b, h, layer, slots: (layer[0], slots[b], h, 0, 0))
    return pl.pallas_call(
        _kda_step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H // hb),
            in_specs=[vectors(dk), vectors(dk), vectors(dk), vectors(dv),
                      vectors(1), tile],
            out_specs=[vectors(dv), tile]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is its own output: tiles no row names are not touched
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * hb * dk * dv * 4 + (16 << 20)),
        interpret=interpret,
        name="kda_recurrence",
    # (the decays are taken here: Mosaic's ``exp`` is some 25 float32
    # roundings off where XLA's is one, and a decay is applied at every
    # token for as long as the state remembers: my chip run, PR 42)
    )(layer, slots, q, k, jnp.exp(g), v, beta[..., None], pool)


def kda_recurrent_step_in_place(q, k, v, g, beta, state_pool, layer, slots,
                                interpret: bool = False):
    """``kda_recurrent_step`` over the pool: row b's state is
    ``state_pool[layer, slots[b]]``. q, k, g [B, H, dk]; v [B, H, dv];
    beta [B, H]; state_pool [layers, slots, H, dk, dv] float32; ``layer``
    an int or a traced scalar; ``slots`` [B] int. Returns (o [B, H, dv]
    float32, the pool with the rows' slots of that layer updated): the
    pool is aliased in and out, so under a jit that donates it nothing is
    copied, and a slot no row names is bit for bit what it was. Rows must
    name distinct slots, but for rows with ``g = 0, beta = 0``, which
    leave their slot as it is and may share one (the null slot of the
    padding rows). Off the chip: ``interpret=True`` (tests)."""
    return _kda_step_call(
        jnp.asarray(layer, jnp.int32).reshape(1), slots.astype(jnp.int32),
        *(t.astype(jnp.float32) for t in (q, k, g, v, beta)), state_pool,
        interpret=interpret)


def kda_decode_step(q, k, v, g, beta, state_pool, layer, slots=None):
    """A served decode step's recurrence, one token a row, over the pool
    [layers, slots, H, dk, dv]: row b's state is
    ``state_pool[layer, slots[b]]``, and without ``slots`` row b is slot
    b + 1 (a full decode batch: a contiguous slice). Returns (o, the
    pool with those slots updated). Runs what ``kda_decode_path`` names:
    the kernel over the pool where it lies, or the rows' states read,
    ``kda_recurrent_step``, and written back."""
    B = q.shape[0]
    if kda_decode_path(state_pool, 1) == "kda_kernel":
        return kda_recurrent_step_in_place(
            q, k, v, g, beta, state_pool, layer,
            1 + jnp.arange(B) if slots is None else slots)
    at = slice(1, 1 + B) if slots is None else slots
    o, new = kda_recurrent_step(q, k, v, g, beta, state_pool[layer, at])
    return o, state_pool.at[layer, at].set(new)


def _chunk_step(state, xs):
    """One chunk of every (row, head). state [B,H,dk,dv]; xs: q, k, g
    [B,H,C,dk], v [B,H,C,dv], beta [B,H,C]."""
    q, k, v, g, beta = xs
    C = q.shape[2]
    G = jnp.cumsum(g, axis=2)                               # [B,H,C,dk]
    # decay from position i to position r >= i, per channel
    diff = G[:, :, :, None, :] - G[:, :, None, :, :]        # [B,H,r,i,dk]
    lower = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
    a_kk = jnp.einsum("bhrc,bhic,bhric->bhri", k, k, decay, precision=_HI)
    a_qk = jnp.einsum("bhrc,bhic,bhric->bhri", q, k, decay, precision=_HI)
    eG = jnp.exp(G)
    # the tokens' writes u_r = beta_r (v_r - (decayed state before r)^T
    # k_r), all at once: (I + diag(beta) strict_tril(a_kk)) U = rhs
    rhs = beta[..., None] * (v - jnp.einsum(
        "bhrc,bhcv->bhrv", k * eG, state, precision=_HI))
    system = jnp.eye(C, dtype=q.dtype) + beta[..., None] * jnp.tril(a_kk, -1)
    u = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True)
    o = jnp.einsum("bhrc,bhcv->bhrv", q * eG, state, precision=_HI) \
        + jnp.einsum("bhri,bhiv->bhrv", a_qk, u, precision=_HI)
    to_end = jnp.exp(G[:, :, -1:, :] - G)                   # [B,H,C,dk]
    new = eG[:, :, -1, :, None] * state + jnp.einsum(
        "bhic,bhiv->bhcv", k * to_end, u, precision=_HI)
    return new, o


def kda_chunked(q, k, v, g, beta, state, chunk: int = 64):
    """A sequence a row, in chunks. q, k, g [B, S, H, dk]; v [B, S, H,
    dv]; beta [B, S, H]; state [B, H, dk, dv] float32 (the state before
    the first token). Returns (o [B, S, H, dv] float32, final state).
    S is padded up to a multiple of ``chunk`` with empty positions."""
    f32 = jnp.float32
    B, S, H, _ = q.shape
    pad = (-S) % chunk
    n = (S + pad) // chunk

    def chunks(t):                   # [B,S,H,...] -> [n,B,H,chunk,...]
        t = jnp.pad(t.astype(f32),
                    [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        t = t.reshape(B, n, chunk, *t.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(t, 3, 2), 1, 0)

    final, o = jax.lax.scan(
        _chunk_step, state.astype(f32),
        (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)           # [B,n,C,H,dv]
    return o.reshape(B, n * chunk, H, -1)[:, :S], final
