"""Linear attention with a carried state: the causal short convolution
and Kimi Delta Attention (KDA), the gated delta rule with a decay per
channel.

Per head, with a state ``S`` in R^{dk x dv} (float32), log-decay ``g_t``
in R^{dk} (<= 0), write strength ``beta_t`` in (0, 1):

    S'  = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Two forms of the same recurrence:

* ``kda_recurrent_step`` - one token a row (decode): the state is read
  twice and written once.
* ``kda_chunked`` - a whole sequence in chunks of ``chunk`` tokens
  (prefill, training): inside a chunk the tokens' writes are solved
  together (a unit lower-triangular system of size ``chunk``), between
  chunks the state is carried by a ``lax.scan``. Every exponent that is
  taken is <= 0 (a decay between two positions is computed from the
  difference of the cumulative log-decays, never from exp(-G)), so the
  form holds for any decay, slow or fast.

Both take ``g = 0, beta = 0`` at a position as "no token here": the
state passes through unchanged, which is how right-padded rows and
padding rows of a bucket are kept out of it. Every product that touches
the state runs in float32 at ``highest`` precision: the state is what a
sequence remembers, and its rounding is carried for ever.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def short_conv(x, tail, weight, n_new=None):
    """Causal depthwise convolution over time with a carried tail.

    x [B, S, C]: the new rows; tail [B, K-1, C]: the K-1 rows before
    them (zeros at a sequence's start); weight [K, C], ``weight[K-1]``
    multiplying the current row. ``n_new`` ([B] int, optional) is how
    many of the S rows are real (right-padded buckets; default all).
    Returns (y [B, S, C], new_tail [B, K-1, C]): the tail that the next
    call of these sequences takes, i.e. the last K-1 real rows.
    """
    B, S, C = x.shape
    K = weight.shape[0]
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [B,S+K-1,C]
    y = sum(xx[:, j:j + S] * weight[j].astype(x.dtype) for j in range(K))
    if n_new is None:
        return y, xx[:, S:]
    idx = n_new[:, None] + jnp.arange(K - 1)[None, :]          # [B, K-1]
    return y, jnp.take_along_axis(xx, idx[..., None], axis=1)


def kda_recurrent_step(q, k, v, g, beta, state):
    """One token a row. q, k, g [B, H, dk]; v [B, H, dv]; beta [B, H];
    state [B, H, dk, dv] float32. Returns (o [B, H, dv], new state).

    ``S'^T k`` and ``S'^T q`` are taken from the stored state in one
    pass (``S'^T k = S^T (exp(g) k)``), and the new state in a second:
    two reads and one write of the state a token."""
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
