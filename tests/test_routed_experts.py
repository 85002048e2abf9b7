"""``RoutedExperts`` for few tokens (``ops/routed_experts.py``): every
row through the experts that got a token, and through no other.

Held against a plain dense product written here: every held expert over
every row, the unrouted pairs weighed zero, float32 at ``highest``
(conftest.py). The kernel runs interpreted on the CPU; that it compiles
for the chip at the served widths is tests/test_chip_compile.py's.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import routed_experts as RE
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import RoutedExperts, SwiGLU

TOL = 2e-5
E_ALL, D, D_FF, TOP_K, SCALING = 16, 24, 32, 4, 2.446


def _layer(held, num_experts=E_ALL, dtype=jnp.float32):
    return RoutedExperts(num_experts, D_FF, TOP_K, held=held,
                         scaling=SCALING, shared_d_ff=D_FF, dtype=dtype)


def _dense_reference(p, x, held, valid):
    """(y without the shared expert, counts): all held experts over all
    rows, a pair the router did not choose weighed zero."""
    first, count = held
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["router_bias"], TOP_K)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * SCALING
    picked = jax.nn.one_hot(chosen, scores.shape[1]) * valid[:, None, None]
    combine = jnp.sum(picked * w[..., None], axis=1)[:, first:first + count]
    counts = jnp.sum(picked, axis=(0, 1))[first:first + count]
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, p["w_gate"])) \
        * jnp.einsum("td,edf->etf", x, p["w_up"])
    y = jnp.einsum("etf,efd->td", h * combine.T[..., None], p["w_down"])
    return y, counts.astype(jnp.int32)


def _params(layer, key, x, bias=None):
    """The layer's seeded parameters, the experts' weights eight times
    their 0.02 so that the routed part is of order 0.1 and not 1e-4."""
    p = dict(layer.init(jax.random.PRNGKey(key), x)["params"])
    for k in ("w_gate", "w_up", "w_down"):
        p[k] = p[k] * 8
    if bias is not None:
        p["router_bias"] = bias
    return p


def _bias(*experts):
    """A router bias that puts these experts before all others."""
    return jnp.zeros((E_ALL,)).at[jnp.array(experts, int)].set(10.0)


# name: (held, router bias, all rows real?, what counts must show)
ROUTINGS = {
    # the bias sends every row to experts 4..7, all that are held
    "every_held_expert_touched": (
        (4, 4), _bias(4, 5, 6, 7), True,
        lambda counts, T: bool(jnp.all(counts == T))),
    # every row chooses 5 and three experts that are not held
    "exactly_one_touched": (
        (4, 4), _bias(5, 0, 1, 2), True,
        lambda counts, T: counts.tolist() == [0, T, 0, 0]),
    # every row's four experts lie outside what is held
    "none_touched_all_chosen_elsewhere": (
        (8, 4), _bias(0, 1, 2, 3), True,
        lambda counts, T: not counts.any()),
    # no row is real: a bucket of padding
    "none_touched_no_row_valid": (
        (0, E_ALL), _bias(), False,
        lambda counts, T: not counts.any()),
    # expert 1 gets every row, the other three choices fall where they do
    "one_expert_takes_all_rows": (
        (0, 8), _bias(1), True,
        lambda counts, T: int(counts[1]) == T == int(counts.max())),
    # seven experts from the fifth on, routed as the scores fall
    "held_is_a_sub_range_not_from_zero": (
        (5, 7), _bias(), True,
        lambda counts, T: T < 8 or 0 < int(counts.sum()) < T * TOP_K),
}


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("T", [1, 8, 64, 256])
def test_few_tokens_equal_the_dense_product(T, routing):
    held, bias, real, shows = ROUTINGS[routing]
    assert T <= moe.WHOLE_ROWS_BELOW
    x = jnp.asarray(np.random.default_rng(T).normal(size=(T, D)),
                    jnp.float32)
    layer = _layer(held)
    p = _params(layer, T, x, bias)
    valid = jnp.full((T,), real)
    y, counts = jax.jit(layer.apply)({"params": p}, x, valid=valid)
    want, want_counts = _dense_reference(p, x, held, valid)
    shared = SwiGLU(D_FF, jnp.float32).apply({"params": p["shared"]}, x)
    np.testing.assert_array_equal(counts, want_counts)
    assert shows(counts, T), counts
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_allclose(y, want + shared, atol=TOL)
    if not counts.any():        # the shared expert alone
        np.testing.assert_allclose(y, shared, atol=1e-7)
    else:
        assert float(jnp.max(jnp.abs(want))) > 1000 * TOL


def test_bfloat16_weights_accumulate_in_float32():
    """As served: bfloat16 operands, float32 sums over width and over
    experts; the result is float32 and close to the float32 product of
    the same (rounded) weights."""
    T, held = 64, (0, 8)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(T, D)),
                    jnp.float32)
    layer = _layer(held, dtype=jnp.bfloat16)
    p = _params(layer, 0, x)
    y, counts = layer.apply({"params": p}, x)
    assert y.dtype == jnp.float32 and p["w_gate"].dtype == jnp.bfloat16
    as32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    xb = x.astype(jnp.bfloat16).astype(jnp.float32)
    want, want_counts = _dense_reference(as32, xb, held, jnp.ones((T,), bool))
    want = want + SwiGLU(D_FF, jnp.float32).apply(
        {"params": as32["shared"]}, xb)
    np.testing.assert_array_equal(counts, want_counts)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(y - want))) < 0.02 * scale


def _skewed(T=64, held=(0, 64)):
    """64 rows over 64 held experts of 64, eight of them favoured: some
    experts get a token and many do not."""
    x = jnp.asarray(np.random.default_rng(7).normal(size=(T, D)),
                    jnp.float32)
    layer = _layer(held, num_experts=64)
    bias = jnp.zeros((64,)).at[jnp.arange(8) * 7].set(0.2)
    return layer, _params(layer, 7, x, bias), x


def test_an_untouched_expert_is_never_read():
    """The skip is a skip and not a mask: NaN in the weights of every
    expert without a token changes nothing (zero times NaN is NaN: a
    dense product weighing them zero would not survive it)."""
    layer, p, x = _skewed()
    y, counts = layer.apply({"params": p}, x)
    idle = np.asarray(counts) == 0
    assert 8 <= idle.sum() <= 56, counts
    poisoned = dict(p, **{
        k: jnp.where(idle.reshape(-1, 1, 1), jnp.nan, p[k])
        for k in ("w_gate", "w_up", "w_down")})
    assert not bool(jnp.all(jnp.isfinite(poisoned["w_down"])))
    again, _ = layer.apply({"params": poisoned}, x)
    assert bool(jnp.all(jnp.isfinite(again)))
    np.testing.assert_array_equal(again, y)
    want, _ = _dense_reference(p, x, (0, 64), jnp.ones((64,), bool))
    shared = SwiGLU(D_FF, jnp.float32).apply({"params": p["shared"]}, x)
    np.testing.assert_allclose(y, want + shared, atol=TOL)


def _products_over(text, elements):
    """The ``dot`` / ``convolution`` instructions of an optimized module
    with an operand of at least so many elements (the compiler may lay
    the stack out anew, [E * d_ff, d] say, before it multiplies)."""
    size_of = {name: int(np.prod([int(n) for n in dims.split(",") if n]))
               for name, dims in re.findall(
                   r"%?([\w.\-]+) = \w+\[([\d,]*)\]", text)}
    return [line.strip()[:160] for line in text.splitlines()
            for m in [re.search(r" (?:dot|convolution)\(([^)]*)\)", line)]
            if m and any(size_of.get(o, 0) >= elements for o in
                         re.findall(r"%([\w.\-]+)", m.group(1)))]


def test_no_product_takes_the_whole_stack_of_experts():
    """At 64 tokens and 64 held experts the compiled layer has no product
    with an operand as large as the stacked weights [64, d, d_ff]: a
    fallback to the dense product fails here, on the CPU, and not only
    on the chip."""
    layer, p, x = _skewed()
    stack = 64 * D * D_FF
    text = jax.jit(layer.apply).lower({"params": p}, x).compile().as_text()
    assert len(_products_over(text, 1)) >= 3
    assert not _products_over(text, stack)
    # and the search does find them where they are
    dense = jax.jit(lambda p, x: _dense_reference(
        p, x, (0, 64), jnp.ones((64,), bool))[0])
    assert len(_products_over(
        dense.lower(p, x).compile().as_text(), stack)) == 3


def test_the_index_map_walks_the_touched_list_and_then_stays():
    """Over a list of three touched experts the weights' index map names
    three experts' blocks, tile after tile, and from then on the block
    that is resident (no new DMA); over an empty list one block."""
    counts = jnp.zeros((16,), jnp.int32).at[jnp.array([9, 2, 5])].set(3)
    order, n = RE.touched_first(counts)
    assert order[:3].tolist() == [2, 5, 9] and n.tolist() == [3]
    assert sorted(order.tolist()) == list(range(16))
    tiles = 2
    walk = [tuple(map(int, RE.live_block(i, j, order, n, tiles - 1)))
            for i in range(16) for j in range(tiles)]
    assert walk[:6] == [(2, 0), (2, 1), (5, 0), (5, 1), (9, 0), (9, 1)]
    assert set(walk[6:]) == {(9, 1)}
    order, n = RE.touched_first(jnp.zeros((16,), jnp.int32))
    assert n.tolist() == [0]
    assert {tuple(map(int, RE.live_block(i, j, order, n, tiles - 1)))
            for i in range(16) for j in range(tiles)} == {(0, 1)}
