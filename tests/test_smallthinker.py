"""SmallThinker at a tiny size on the CPU, against the plain reference
(benchmark/reference/smallthinker_ref.py: float32 at 'highest', the
router's logits from the attention's input, top-k then softmax, attention
a head at a time under a mask over the whole sequence, a loop over ReGLU
experts). Logits and cached rows are compared, never sampled tokens.

Tolerances: everything here runs in float32 with 'highest' products
(tests/conftest.py), so two sides differ by the order of their sums only:
2e-5 absolute on logits of spread ~0.1 and on attention outputs of size
~1 (5e-5 where rows went through a cache, as tests/test_llm_laguna_
serving.py). A router that read the experts' input in place of the
attention's, a SiLU gate, a softmax before the choice that is not
renormalised, rotary on a full layer or a row read from a wrong ring page
each move a logit by 1e-3 or more."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from llm_test_helpers import PAGE, flax_seq, token_prompts

from benchmark.reference import smallthinker_glue as glue
from benchmark.reference import smallthinker_ref as ref
from ray_tpu.models.laguna import FULL, SLIDING, LagunaAttention
from ray_tpu.models.smallthinker import (SmallThinkerConfig,
                                         SmallThinkerModel, cache_spec)
from ray_tpu.ops import attention as A
from ray_tpu.serve.llm import PagedKVCache

TOL = 2e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = SmallThinkerConfig.tiny()
    return cfg, glue.init_for(cfg, 11)


@pytest.mark.parametrize("S", [100, 300])
def test_full_forward_equals_the_reference(tiny, S):
    """Contexts of 3 and 9 windows (32 positions). 2 x 100 tokens take
    the routed layer's whole-row product, 2 x 300 its sorted one (both
    with the ReLU gate, interpreted)."""
    cfg, params = tiny
    ids = np.random.default_rng(1).integers(0, 512, (2, S))
    out = SmallThinkerModel(cfg).apply(params, jnp.asarray(ids, jnp.int32))
    sizes = ref.sizes_of(cfg)
    for b in range(2):
        want = ref.forward(params["params"], ids[b], sizes)
        assert float(jnp.std(want)) > 0.05
        np.testing.assert_allclose(out[b], want, atol=TOL)
    # the control of the benchmark's check is another function: window
    # layers that see the whole context move the logits
    far = ref.forward(params["params"], ids[0], sizes, whole_context=True)
    assert float(jnp.max(jnp.abs(far - out[0]))) > 1e-3


@pytest.mark.parametrize("what", ["router_reads_the_experts_input",
                                  "silu_gate", "softmax_not_renormalised"])
def test_what_the_block_must_not_be(tiny, what):
    """The three ways this family's layer differs from the layer
    ``RoutedExperts`` served before, each taken away in turn: the logits
    move off the reference by far more than the tolerance."""
    from ray_tpu.models import smallthinker as st
    from ray_tpu.parallel.moe import RoutedExperts
    cfg, params = tiny
    ids = np.random.default_rng(2).integers(0, 512, (1, 64))
    want = ref.forward(params["params"], ids[0], ref.sizes_of(cfg))

    class Wrong(RoutedExperts):
        def __call__(self, x, valid=None, router_x=None):
            return RoutedExperts(
                self.num_experts, self.d_ff, self.top_k,
                renormalize=what != "softmax_not_renormalised",
                dtype=self.dtype, score="softmax",
                act="silu" if what == "silu_gate" else "relu",
                parent=None).apply(
                    {"params": self.variables["params"]}, x, valid=valid,
                    router_x=None if what.startswith("router") else router_x)
    good = SmallThinkerModel(cfg).apply(params, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(good[0], want, atol=TOL)
    real = st.RoutedExperts
    st.RoutedExperts = Wrong
    try:
        bad = SmallThinkerModel(cfg).apply(params,
                                           jnp.asarray(ids, jnp.int32))
    finally:
        st.RoutedExperts = real
    assert float(jnp.max(jnp.abs(bad[0] - want))) > 1e-3


def test_published_config_counts_the_published_parameters():
    """The layouts as published (0 1 1 1, thirteen times), the layers
    kept their first eight, and the count of ISSUE 43's cut: 3,966.9 M."""
    cfg = SmallThinkerConfig()
    assert cfg.sliding_window_layout == cfg.rope_layout == (0, 1, 1, 1) * 13
    assert cfg.layer_types[:5] == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    cut = SmallThinkerConfig(num_hidden_layers=8,
                             sliding_window_layout=[0, 1, 1, 1] * 13,
                             rope_layout=[0, 1, 1, 1] * 13)
    assert cut.sliding_window_layout == (0, 1, 1, 1, 0, 1, 1, 1)
    shapes = jax.eval_shape(SmallThinkerModel(cut).init,
                            jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]

    def count(tree):
        return sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes["embed"]) + count(shapes["lm_head"]) \
        == 2 * 151936 * 2560
    layer = shapes["layers_3"]
    assert count(layer["attn"]) == 20_971_520
    assert count(layer["moe"]["router"]) == 163_840
    assert sum(count(layer["moe"][k]) for k in ("w_gate", "w_up", "w_down")) \
        == 64 * 5_898_240
    assert abs(count(shapes) / 1e6 - 3966.9) < 0.05
    spec = cache_spec(cut)
    assert spec["expert_counts"] == (8, 64) and not spec["state"]
    assert {k: (p["layers"], p["row"], p.get("window"), p["q_heads"])
            for k, p in spec["pages"].items()} == {
        "k_full": (2, 512, None, 28), "v_full": (2, 512, None, 28),
        "k_window": (6, 512, 4096, 28), "v_window": (6, 512, 4096, 28)}
    with pytest.raises(ValueError, match="rotary follows its window"):
        SmallThinkerConfig.tiny(rope_layout=(1, 1, 1, 1, 0))


def test_a_full_layer_has_no_rotary_shown_by_shifting_every_position():
    """One token written at position 0 and the same token at position 16
    (every position of the call shifted): a layout-0 layer caches the
    same K row bit for bit (``n W_k``, no position in it), a layout-1
    layer a row turned by the position; V never differs. And the rotary
    of a layout-1 layer is the whole head at theta, plain."""
    cfg = SmallThinkerConfig.tiny()
    assert cfg.rope_of(FULL) is None
    rope = cfg.rope_of(SLIDING)
    assert rope.attention_factor == 1.0
    np.testing.assert_allclose(
        np.asarray(rope.blend.inv_freq()),
        cfg.rope_theta ** (-2.0 * np.arange(8) / 16), rtol=1e-6)
    real = SmallThinkerConfig().rope_of(SLIDING).blend
    assert (real.dim, real.theta) == (128, 1.5e6)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, 1, 64)),
                    jnp.float32)
    pools = jnp.zeros((1, 8, PAGE, 32), jnp.float32)
    for window, same in ((None, True), (cfg.sliding_window_size, False)):
        attn = LagunaAttention(cfg, cfg.num_attention_heads, window)
        tables = jnp.asarray([[1, 2, 3, 4, 5]], jnp.int32)
        params = attn.init(jax.random.PRNGKey(0), x)
        rows = []
        for at in (0, 16):
            _, k_pages, v_pages = attn.apply(
                params, x, k_pages=pools, v_pages=pools,
                block_tables=tables, seq_lengths=jnp.asarray([at]),
                layer=0)
            page = int(tables[0, at // PAGE])
            rows.append((np.asarray(k_pages[0, page, at % PAGE]),
                         np.asarray(v_pages[0, page, at % PAGE])))
        np.testing.assert_array_equal(rows[0][1], rows[1][1])
        assert np.abs(rows[0][0]).max() > 0.1
        if same:
            np.testing.assert_array_equal(rows[0][0], rows[1][0])
        else:
            assert np.abs(rows[0][0] - rows[1][0]).max() > 1e-2


def test_paged_decode_kernel_groups_of_seven_over_whole_and_short_rings():
    """``paged_attention_decode(window=...)`` interpreted with 7 query
    heads a key/value head (28 over 4 as published: rows 28..31 of the
    kernel's 32-row matrices are padding), over rings of 5 pages: rows
    several rings long, exactly a window, and two SHORT rings (a sequence
    that holds 2 and 1 pages of its ring, the rest of its table the null
    page: it never wraps), and an empty row. Against a softmax over the
    window from the flat rows."""
    rng = np.random.default_rng(7)
    Hkv, G, D, bs, window = 4, 7, 128, 16, 64
    ring, B, C = window // bs + 1, 5, Hkv * D
    lengths = np.array([200, 64, 30, 0, 9], np.int32)
    held = [5, 5, 2, 0, 1]
    k_flat = rng.normal(size=(B, 200, C)).astype(np.float32)
    v_flat = rng.normal(size=(B, 200, C)).astype(np.float32)
    k_pages = rng.normal(size=(2, 40, bs, C)).astype(np.float32)
    v_pages = rng.normal(size=(2, 40, bs, C)).astype(np.float32)
    tables = np.zeros((B, ring), np.int32)
    free = list(1 + rng.permutation(39))
    for b, n in enumerate(lengths):
        tables[b, :held[b]] = [free.pop() for _ in range(held[b])]
        for p in range(max(n - ring * bs + bs, 0), n):
            page = tables[b, (p // bs) % ring]
            assert page != 0
            k_pages[1, page, p % bs] = k_flat[b, p]
            v_pages[1, page, p % bs] = v_flat[b, p]
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, D)), jnp.float32)
    args = (q, jnp.asarray(k_pages), jnp.asarray(v_pages),
            jnp.asarray(tables), jnp.asarray(lengths))
    got = A.paged_attention_decode(*args, layer=1, window=window,
                                   interpret=True)
    gather = A.paged_attention_reference(*args, layer=1, window=window)
    want = np.zeros((B, Hkv * G, D), np.float32)
    for b, n in enumerate(lengths):
        lo = max(n - window, 0)
        for h in range(Hkv * G if n else 0):
            g = h // G
            k = k_flat[b, lo:n, g * D:(g + 1) * D]
            v = v_flat[b, lo:n, g * D:(g + 1) * D]
            s = (k @ np.asarray(q[b, h])) * D ** -0.5
            pr = np.exp(s - s.max())
            want[b, h] = (pr / pr.sum()) @ v
    np.testing.assert_allclose(gather, want, atol=TOL)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_a_chunk_holds_more_tokens_only_where_the_pools_rows_are_narrow():
    """The paged kernel's chunk by the width of a pool's rows: rows of
    2 KiB and over take 256 tokens (Laguna's 8 key/value heads of 128 in
    bfloat16, GPT-2 large's 20 of 64 in either type: 128 until a group of
    8 pages came with one copy, PR 44), this model's 4 of 128 in bfloat16
    take 512; either is whole groups of 8 pages of 16 tokens."""
    assert A.paged_chunk_tokens(8 * 128 * 2) == 256
    assert A.paged_chunk_tokens(20 * 64 * 2) == 256
    assert A.paged_chunk_tokens(20 * 64 * 4) == 256
    assert A.paged_chunk_tokens(4 * 128 * 2) == 512
    assert all(A.paged_chunk_tokens(b) % (16 * A.PAGED_RUN_PAGES) == 0
               for b in (1024, 2048, 2560))


@pytest.mark.parametrize("tables", ["shuffled", "one-run", "broken-runs",
                                    "descending"])
@pytest.mark.parametrize("window", [None, 1024], ids=["full", "window"])
def test_paged_decode_kernel_in_chunks_of_512_tokens(window, tables):
    """``paged_attention_decode`` interpreted over a pool whose rows are
    1 KiB (2 key/value heads of 128 in float32), which takes chunks of 32
    pages, four groups of 8: contexts of several chunks and of less than
    one, a row whose last chunk is mostly dead pages (three of its four
    groups are not copied), a whole ring of 65 pages walked from its
    middle round its end (the group that holds the wrap takes a copy a
    page), a ring wrapped several times, a short ring that never wraps
    (its table's rest the null page), and an empty row; tables of
    shuffled pages, of one ascending run a row, of runs of 3 to 12 pages
    (breaks inside the groups), and descending. Against the gather."""
    rng = np.random.default_rng(11)
    Hkv, G, D, bs = 2, 7, 128, 16
    C = Hkv * D
    assert A.paged_chunk_tokens(C * 4) == 512
    lengths = np.array([3000, 1024, 1551, 700, 30, 0, 513], np.int32)
    B = len(lengths)
    ring = None if window is None else window // bs + 1
    NB = ring or 192
    P = 1 + B * NB
    k_pages = jnp.asarray(rng.normal(size=(2, P, bs, C)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(2, P, bs, C)), jnp.float32)
    kind, tables = tables, np.zeros((B, NB), np.int32)
    free = list(1 + rng.permutation(P - 1))
    for b, n in enumerate(lengths):
        held = min(NB, -(-int(n) // bs))
        own = list(range(1 + b * NB, 1 + b * NB + held))
        if kind == "shuffled":
            own = [free.pop() for _ in range(held)]
        elif kind == "descending":
            own = own[::-1]
        elif kind == "broken-runs":
            cuts = sorted(set(rng.integers(0, held + 1, held // 7)))
            runs = [own[i:j] for i, j in zip([0] + cuts, cuts + [held])]
            own = [p for i in rng.permutation(len(runs)) for p in runs[i]]
        tables[b, :held] = own
    q = jnp.asarray(rng.normal(size=(B, Hkv * G, D)), jnp.float32)
    args = (q, k_pages, v_pages, jnp.asarray(tables), jnp.asarray(lengths))
    got = A.paged_attention_decode(*args, layer=1, window=window,
                                   interpret=True)
    want = A.paged_attention_reference(*args, layer=1, window=window)
    live = lengths > 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live], atol=TOL)
    np.testing.assert_array_equal(np.asarray(got)[~live], 0)


def _adapter(blocks=128, **kw):
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    cfg = SmallThinkerConfig.tiny()
    params = glue.init_for(cfg, 7)
    adapter = FlaxModelAdapter("smallthinker", cfg, params)
    cache = PagedKVCache(num_blocks=blocks, block_size=PAGE,
                         windows=adapter.page_windows, **kw)
    adapter.bind_cache(cache)
    return adapter, cache, params, ref.sizes_of(cfg)


def test_prefill_then_decode_through_both_page_groups_wrapping_and_not():
    """One batch: a prompt of 100 tokens (three windows: its 5-page ring
    wraps while it decodes), and prompts of 5 and 11 with budgets that
    end inside 4 and 3 pages: they hold 2 and 3 pages of the window group
    (rings by need), the rest of their tables the null page, and never
    wrap. 14 decode steps together, then the short ones end, a new
    sequence takes their pages and decodes beside the long one. Every
    logits row against the reference's full forward."""
    adapter, cache, params, sizes = _adapter(max_sequences=2,
                                             window_blocks=14)
    assert adapter.page_windows == (32,) and cache.ring_blocks(32) == 5
    assert adapter._arrays["k_window"].shape == (3, 14, PAGE, 32)
    assert adapter._arrays["k_full"].shape == (2, 128, PAGE, 32)
    prompts = token_prompts(43, adapter.vocab_size, (100, 5, 11, 19))
    a = flax_seq(cache, "s0", prompts[0], budget=60)
    b = flax_seq(cache, "s1", prompts[1], budget=15 - 5)      # 15 tokens
    c = flax_seq(cache, "s2", prompts[2], budget=24 - 11)     # 24 tokens
    assert [len(cache.ring_table(s, 32)) for s in ("s0", "s1", "s2")] \
        == [5, 2, 3]

    def serve(seqs, n, rows):
        for _ in range(n):
            for s, got in zip(seqs, rows):
                s.tokens.append(int(got[-1].argmax()))
            for got, r in zip(rows, adapter.decode(seqs)):
                got.append(r)
        return rows
    rows = serve([a, b, c], 9, [[r] for r in adapter.prefill([a, b, c])])
    for s in ("s1", "s2"):
        adapter.release(s)
        cache.free(s)
    d = flax_seq(cache, "s3", prompts[3], budget=21)           # 40: a ring
    assert len(cache.ring_table("s3", 32)) == 5
    rows_ad = serve([a, d], 20, [rows[0], [adapter.prefill([d])[0]]])
    for seq, got in ((a, rows_ad[0]), (b, rows[1]), (c, rows[2]),
                     (d, rows_ad[1])):
        ids = np.asarray(seq.prompt + seq.tokens, np.int32)
        want = np.asarray(ref.forward(params["params"], ids, sizes))[
            len(seq.prompt) - 1:]
        np.testing.assert_allclose(np.stack(got), want[:len(got)],
                                   atol=5e-5)
    assert len(a.prompt) + len(a.tokens) > 4 * 32
    counters = adapter.counters()
    assert 0 < counters["kv_window_pages_held_total"] \
        < counters["kv_window_pages_whole_rings_total"]
    assert np.shape(counters["expert_tokens_total"]) == (5, 16)
