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
    return _dense_many(p, x, held, valid, TOP_K, SCALING)


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


# ---- many tokens: the grouped product over the sorted row blocks ----

def _dense_many(p, x, held, valid, top_k, scaling):
    """``_dense_reference`` at any ``top_k`` and scaling: all held
    experts over all rows, the pairs the router did not choose (and the
    rows that are not real) weighed zero."""
    first, count = held
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["router_bias"], top_k)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * scaling
    picked = jax.nn.one_hot(chosen, scores.shape[1]) * valid[:, None, None]
    combine = jnp.sum(picked * w[..., None], axis=1)[:, first:first + count]
    counts = jnp.sum(picked, axis=(0, 1))[first:first + count]
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, p["w_gate"])) \
        * jnp.einsum("td,edf->etf", x, p["w_up"])
    y = jnp.einsum("etf,efd->td", h * combine.T[..., None], p["w_down"])
    return y, counts.astype(jnp.int32)


# the three cells' proportions at toy widths:
# name: (experts, held, top_k, d_ff, tokens, real tokens)
CELLS = {
    # Laguna: many small experts, all of them held
    "many_small_experts_all_held": (64, (0, 64), 8, 16, 600, 441),
    # Kimi-K2: a few wide experts of which a 32nd is held
    "a_32nd_of_wide_experts_held": (128, (8, 4), 8, 96, 600, 431),
    # Kimi-Linear: 64 of 256
    "a_quarter_held": (256, (64, 64), 8, 48, 512, 512),
    # every assignment lands on the experts held here: the worst case
    "every_assignment_lands_here": (4, (0, 4), 4, 32, 700, 700),
    # one row more than goes as whole rows through the touched experts
    "just_above_whole_rows": (16, (4, 8), 4, 32, 257, 250),
}


@pytest.mark.parametrize("cell", CELLS)
def test_many_tokens_equal_the_dense_product(cell):
    """The grouped kernel over the sorted row blocks gives what the
    dense product gives, with padding rows, an expert that gets no token
    (a bias of -10 on the second held expert), and an expert whose rows
    span several blocks (a bias of +10 on the first: every real row)."""
    experts, held, top_k, d_ff, T, n_real = CELLS[cell]
    first, count = held
    assert T > moe.WHOLE_ROWS_BELOW
    x = jnp.asarray(np.random.default_rng(T).normal(size=(T, D)),
                    jnp.float32)
    layer = RoutedExperts(experts, d_ff, top_k, held=held, scaling=SCALING,
                          dtype=jnp.float32)
    bias = jnp.zeros((experts,)).at[first].set(10.0)
    if experts > top_k:         # else every expert is chosen
        bias = bias.at[first + 1].set(-10.0)
    p = _params(layer, T, x, bias)
    valid = jnp.arange(T) < n_real
    y, counts = jax.jit(layer.apply)({"params": p}, x, valid=valid)
    want, want_counts = _dense_many(p, x, held, valid, top_k, SCALING)
    np.testing.assert_array_equal(counts, want_counts)
    plan = moe.expert_product(T, top_k, experts, count, D, 4)
    assert plan.name == "grouped_kernel"
    assert int(counts[0]) == n_real > plan.block_rows   # several blocks
    if experts > top_k:
        assert int(counts[1]) == 0
    else:
        assert int(counts.sum()) == n_real * top_k      # the worst case
    assert plan.rows_multiplied(counts) >= int(counts.sum())
    np.testing.assert_allclose(y, want, atol=TOL)
    assert float(jnp.max(jnp.abs(want))) > 1000 * TOL
    # a padding row gets nothing, not even another row's product
    assert not bool(jnp.any(y[n_real:]))


def test_the_grouped_product_never_reads_an_expert_without_a_token():
    """NaN in the weights of every expert that got no token changes
    nothing: no block names such an expert."""
    experts, held, top_k, d_ff, T, _ = CELLS["a_quarter_held"]
    x = jnp.asarray(np.random.default_rng(3).normal(size=(T, D)),
                    jnp.float32)
    layer = RoutedExperts(experts, d_ff, top_k, held=held, scaling=SCALING,
                          dtype=jnp.float32)
    bias = jnp.zeros((experts,)).at[jnp.arange(64, 128, 2)].set(-10.0)
    p = _params(layer, 3, x, bias)
    y, counts = layer.apply({"params": p}, x)
    idle = np.asarray(counts) == 0
    assert idle.sum() >= 32, counts
    poisoned = dict(p, **{
        k: jnp.where(idle.reshape(-1, 1, 1), jnp.nan, p[k])
        for k in ("w_gate", "w_up", "w_down")})
    again, _ = layer.apply({"params": poisoned}, x)
    assert bool(jnp.all(jnp.isfinite(again)))
    np.testing.assert_array_equal(again, y)


def test_the_row_blocks_index_map_walks_the_live_blocks_and_then_stays():
    """Over five live blocks of three experts the index maps name each
    block with its expert, tile after tile (two blocks of one expert
    name the same weights), and from then on the block that is resident
    (no new DMA); with no live block, one block."""
    block_expert = jnp.array([2, 2, 5, 9, 9, 9, 9, 9], jnp.int32)
    n, tiles = jnp.array([5], jnp.int32), 2
    walk = [tuple(map(int, RE.live_rows(i, j, block_expert, n, tiles - 1)))
            for i in range(8) for j in range(tiles)]
    assert walk[:10] == [(0, 2, 0), (0, 2, 1), (1, 2, 0), (1, 2, 1),
                         (2, 5, 0), (2, 5, 1), (3, 9, 0), (3, 9, 1),
                         (4, 9, 0), (4, 9, 1)]
    assert set(walk[10:]) == {(4, 9, 1)}
    none = jnp.array([0], jnp.int32)
    assert {tuple(map(int, RE.live_rows(i, j, block_expert, none,
                                        tiles - 1)))
            for i in range(8) for j in range(tiles)} == {(0, 2, 1)}


def test_the_blocks_past_the_live_ones_are_not_computed():
    """``grouped_experts`` writes the rows of its first ``n`` blocks and
    multiplies no other: NaN rows past them leave the live rows as they
    are, and each live row is its block's expert's SwiGLU times its
    weight."""
    bm, d, d_ff = 128, D, 16
    rng = np.random.default_rng(5)
    xs = jnp.asarray(rng.normal(size=(4 * bm, d)), jnp.float32)
    weight = jnp.asarray(rng.random(4 * bm), jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(3, d, d_ff)), jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(3, d_ff, d)), jnp.float32)
    block_expert = jnp.array([0, 2, 2, 1], jnp.int32)
    two = jnp.array([2], jnp.int32)
    ys = RE.grouped_experts(xs.at[2 * bm:].set(jnp.nan), weight,
                            block_expert, two, gate, up, down, bm)
    for b, e in ((0, 0), (1, 2)):
        rows = xs[b * bm:(b + 1) * bm]
        want = (jax.nn.silu(rows @ gate[e]) * (rows @ up[e])) @ down[e] \
            * weight[b * bm:(b + 1) * bm, None]
        np.testing.assert_allclose(ys[b * bm:(b + 1) * bm], want,
                                   atol=1e-3, rtol=1e-5)
    assert bool(jnp.all(jnp.isfinite(ys[:2 * bm])))


@pytest.mark.parametrize("T,top_k,experts,held,d,block,held_rows", [
    (64, 8, 256, 64, 2304, 64, 64),             # a decode step
    (1, 8, 384, 12, 7168, 16, 16),
    (8192, 8, 256, 256, 2048, 256, 131072),     # a Laguna prompt: whole
    (8192, 8, 384, 12, 7168, 256, 768),         # Kimi-K2's: 3 blocks
    (2048, 8, 256, 64, 2304, 128, 24576),       # Kimi-Linear's: whole
    (512, 8, 256, 64, 2304, 128, 12288),
    (2048, 8, 256, 8, 2304, 128, 2304),         # a 32nd held: 18 blocks
    (2048, 8, 256, 16, 2304, 128, 18432),       # a 16th: whole (6 times)
    (2048, 12, 768, 16, 6144, 128, 896),        # LongCat-Flash's: 7 blocks
])
def test_the_product_and_its_block_follow_the_shapes(T, top_k, experts, held,
                                                     d, block, held_rows):
    """``expert_product`` reads shapes only: the block is 128 rows where
    an expert expects no more, and the rows held at a time are the worst
    case's (every assignment landing here) where they fit ``ROWS_BYTES``
    as bfloat16 rows and their float32 results and are at most
    ``ROWS_OVER_EXPECTED`` times the rows of even routing, else the whole
    blocks that fit ``CHUNK_BYTES``."""
    plan = moe.expert_product(T, top_k, experts, held, d)
    grouped = T > moe.WHOLE_ROWS_BELOW
    assert plan == ("grouped_kernel" if grouped else "touched_kernel",
                    block, held_rows)
    if grouped:
        worst = T * top_k + held * block
        even = -(-T * top_k * held // experts // block) * block \
            + held * block
        whole = worst * d * 6 <= moe.ROWS_BYTES \
            and worst <= moe.ROWS_OVER_EXPECTED * even
        assert (held_rows == worst and whole) or (
            not whole and moe.CHUNK_BYTES - block * d * 6
            < held_rows * d * 6 <= moe.CHUNK_BYTES)
    counts = np.array([[0, 1, block, block + 1], [0, 0, 0, 5]])
    assert plan.rows_multiplied(counts) == (5 if grouped else 4) * block


# ---- the gate's activation and the router's own input (SmallThinker) ----

def _dense_glu(p, x, router_x, valid, top_k, act):
    """All experts over all rows in plain jax.numpy: softmax scores of
    ``router_x``, top-k, renormalised over the chosen; the gate through
    ``act``; the pairs not chosen (and the rows not real) weighed zero."""
    scores = jax.nn.softmax(router_x @ p["router"], axis=-1)
    _, chosen = jax.lax.top_k(scores + p["router_bias"], top_k)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    picked = jax.nn.one_hot(chosen, scores.shape[1]) * valid[:, None, None]
    combine = jnp.sum(picked * w[..., None], axis=1)
    gate = jnp.einsum("td,edf->etf", x, p["w_gate"])
    gate = jnp.maximum(gate, 0.0) if act == "relu" else jax.nn.silu(gate)
    h = gate * jnp.einsum("td,edf->etf", x, p["w_up"])
    y = jnp.einsum("etf,efd->td", h * combine.T[..., None], p["w_down"])
    return y, jnp.sum(picked, axis=(0, 1)).astype(jnp.int32)


@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("own_router_input", [False, True])
@pytest.mark.parametrize("T", [40, 300])
def test_the_gates_activation_and_the_routers_input_in_both_kernels(
        T, own_router_input, act):
    """40 rows go whole through the touched experts, 300 sorted through
    the grouped kernel (both interpreted): ``act="relu"`` is ReGLU in
    both, ``router_x`` is what the router scores while the experts
    multiply ``x``; against the dense sum. The two activations and the
    two router inputs give different results (so each case tests its
    own), and ``router_x=x`` is the call without it, bit for bit."""
    experts, top_k, d_ff, n_real = 16, 4, 24, T - 7
    rng = np.random.default_rng(T)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    other = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    layer = RoutedExperts(experts, d_ff, top_k, renormalize=True,
                          dtype=jnp.float32, score="softmax", act=act)
    p = _params(layer, T, x)
    valid = jnp.arange(T) < n_real
    router_x = other if own_router_input else None
    y, counts = jax.jit(layer.apply)({"params": p}, x, valid=valid,
                                     router_x=router_x)
    want, want_counts = _dense_glu(
        p, x, other if own_router_input else x, valid, top_k, act)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts.sum()) == n_real * top_k
    np.testing.assert_allclose(y, want, atol=TOL)
    assert float(jnp.max(jnp.abs(want))) > 1000 * TOL
    assert not bool(jnp.any(y[n_real:]))
    plan = moe.expert_product(T, top_k, experts, experts, D, 4)
    assert plan.name == ("touched_kernel" if T <= moe.WHOLE_ROWS_BELOW
                         else "grouped_kernel")
    # the other activation and the other router input are other numbers
    for a, rx in ((("silu" if act == "relu" else "relu"), router_x),
                  (act, x if own_router_input else other)):
        far, _ = RoutedExperts(
            experts, d_ff, top_k, renormalize=True, dtype=jnp.float32,
            score="softmax", act=a).apply({"params": p}, x, valid=valid,
                                          router_x=rx)
        assert float(jnp.max(jnp.abs(far - want))) > 100 * TOL
    if not own_router_input:
        same, _ = jax.jit(layer.apply)({"params": p}, x, valid=valid,
                                       router_x=x)
        np.testing.assert_array_equal(same, y)


def test_an_expert_width_of_three_tiles():
    """768 is the first served expert width that only the 256-wide entry
    of ``_FF_TILES`` divides: three tiles an expert in both kernels (at
    2560 x 768 bfloat16 as published; a toy 24-wide expert is one)."""
    assert RE.ff_tile(2560, 768, 2) == 256
    assert RE.ff_tile(2048, 512, 2) == 512 and RE.ff_tile(D, 24, 4) == 24
    # three tiles walked at a toy width: d_ff 384 with the widest tile
    # made 128
    tiles, RE._FF_TILES = RE._FF_TILES, (128,)
    try:
        T, experts, top_k, d_ff = 20, 4, 2, 384
        x = jnp.asarray(np.random.default_rng(1).normal(size=(T, D)),
                        jnp.float32)
        layer = RoutedExperts(experts, d_ff, top_k, dtype=jnp.float32,
                              score="softmax", act="relu")
        p = _params(layer, 3, x)
        y, counts = layer.apply({"params": p}, x)
        want, _ = _dense_glu(p, x, x, jnp.ones((T,), bool), top_k, "relu")
        np.testing.assert_allclose(y, want, atol=TOL)
    finally:
        RE._FF_TILES = tiles


# ---- the rows between 128 and 512 (PR 53: LFM2's 256-row decode step) ----

# what ``cache_spec`` gives ``expert_product`` beside a step's tokens for
# each routed model the benchmark serves (top_k, router's experts, held,
# hidden, itemsize, zero-compute outputs)
SERVED = {
    "kimi_linear": (8, 256, 64, 2304, 2, 0),
    "kimi_k2": (8, 384, 12, 7168, 2, 0),
    "laguna": (8, 256, 256, 2048, 2, 0),
    "longcat_flash": (12, 512, 16, 6144, 2, 256),
    "smallthinker": (6, 64, 64, 2560, 2, 0),
    "lfm2": (4, 32, 32, 2048, 2, 0),
}
# T -> what the tree before PR 53 chose (name, block rows, rows held) for
# each of them, in SERVED's order
AT_THE_PARENT = {
    1: [("touched_kernel", 16, 16)] * 6,
    64: [("touched_kernel", 64, 64)] * 6,
    128: [("touched_kernel", 128, 128)] * 6,
    512: [("grouped_kernel", 128, 12288), ("grouped_kernel", 128, 5632),
          ("grouped_kernel", 128, 36864), ("grouped_kernel", 128, 8192),
          ("grouped_kernel", 128, 11264), ("grouped_kernel", 128, 6144)],
    2048: [("grouped_kernel", 128, 24576), ("grouped_kernel", 128, 768),
           ("grouped_kernel", 128, 49152), ("grouped_kernel", 128, 896),
           ("grouped_kernel", 256, 28672), ("grouped_kernel", 256, 16384)],
    8192: [("grouped_kernel", 256, 81920), ("grouped_kernel", 256, 768),
           ("grouped_kernel", 256, 131072), ("grouped_kernel", 128, 896),
           ("grouped_kernel", 256, 65536), ("grouped_kernel", 256, 40960)],
}


@pytest.mark.parametrize("T", list(AT_THE_PARENT))
def test_at_most_128_and_at_least_512_rows_choose_as_they_did(T):
    """The choice between 128 and 512 rows was measured at PR 53; at
    most 128 and at least 512 rows choose what they chose before it, for
    every routed model served."""
    for shapes, want in zip(SERVED.values(), AT_THE_PARENT[T]):
        assert tuple(moe.expert_product(T, *shapes)) == want


@pytest.mark.parametrize("model", list(SERVED))
def test_a_bucket_of_256_rows_goes_whole_whatever_an_expert_expects(model):
    """256 rows are the one bucket between 128 and 512 (a power of two).
    The touched form won there at LFM2's shape, where an expert expects
    the most tokens of any model served (32): moe.py has the readings."""
    shapes = SERVED[model]
    top_k, experts = shapes[0], shapes[1] + shapes[5]
    assert 256 * top_k / experts <= 32
    assert tuple(moe.expert_product(256, *shapes)) == (
        "touched_kernel", 256, 256)
    # 129-255 real rows run the 256-row program: its plan reads the
    # bucket, never the rows that are real
    assert tuple(moe.expert_product(160, *shapes)) == (
        "touched_kernel", 160, 160)


@pytest.mark.parametrize("real", [256, 129])
def test_both_forms_give_one_result_at_lfm2s_shape(real, monkeypatch):
    """32 sigmoid-scored experts, 4 a token under a selection bias, the
    1e-6 in the renormalisation, 256 rows of which ``real`` are real: the
    touched form (what ``expert_product`` chooses) and the sorted form at
    blocks of 128 and of 64 rows (forced) give the same rows and the same
    counts, and both are the dense sum's."""
    T, experts, top_k, d_ff = 256, 32, 4, 24
    rng = np.random.default_rng(real)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    layer = RoutedExperts(experts, d_ff, top_k, renormalize=True,
                          renormalize_eps=1e-6, dtype=jnp.float32)
    p = _params(layer, T, x, jnp.asarray(0.1 * rng.normal(size=experts),
                                         jnp.float32))
    valid = jnp.arange(T) < real
    assert moe.expert_product(T, top_k, experts, experts, D, 4).name \
        == "touched_kernel"
    y, counts = jax.jit(layer.apply)({"params": p}, x, valid=valid)
    assert int(counts.sum()) == real * top_k
    # the dense sum, with the bias in the choice alone and the epsilon
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["router_bias"], top_k)
    w = jnp.take_along_axis(scores, chosen, axis=1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    combine = jnp.sum(jax.nn.one_hot(chosen, experts) * w[..., None]
                      * valid[:, None, None], axis=1)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, p["w_gate"])) \
        * jnp.einsum("td,edf->etf", x, p["w_up"])
    want = jnp.einsum("etf,efd->td", h * combine.T[..., None], p["w_down"])
    np.testing.assert_allclose(y, want, atol=TOL)
    assert float(jnp.max(jnp.abs(want))) > 1000 * TOL
    real_product = moe.expert_product
    for bm in (128, 64):
        monkeypatch.setattr(
            moe, "expert_product", lambda T, k, E, held, d, *a, bm=bm:
            moe.ExpertProduct("grouped_kernel", bm,
                              moe._worst_rows(T * k, held, bm)))
        sorted_y, sorted_counts = jax.jit(layer.apply)(
            {"params": p}, x, valid=valid)
        np.testing.assert_array_equal(sorted_counts, counts)
        np.testing.assert_allclose(sorted_y, y, atol=TOL)
        monkeypatch.setattr(moe, "expert_product", real_product)
