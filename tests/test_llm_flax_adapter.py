"""LLM serving, the flax adapter over the paged pool
(docs/LLM_SERVING.md): GPT-2 and Llama served from pages give the full
forward's tokens through the engine, a KV handoff, copy-on-extend and
the decode window with rollback; and the two keep the step program they
had before a model could state its cache. Tier-1, CPU-only."""

import numpy as np
import pytest
from llm_test_helpers import PAGE, drain_stream, flax_seq, token_prompts

from ray_tpu.serve.llm import (EngineConfig, LLMEngine, PagedKVCache,
                               SamplingParams)


# ------------------------------ the flax adapter over the paged pool
#
# Ground truth throughout: the full forward, no cache, of the model as it
# is trained (one entry a block in its parameters, a Python loop over
# them); the adapter is given those parameters and serves them stacked,
# one block's program looped. Pages hold 8 tokens, so 12-token prompts
# end mid-page and every sequence crosses a page boundary while it
# decodes. ``llama``'s tiny config has fewer kv heads than heads (GQA).

FLAX_KINDS = ["gpt2", "llama"]
_PLAIN = {}


def _plain(kind):
    """The model in its training form, and seeded parameters for it."""
    if kind not in _PLAIN:
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import gpt2, llama
        model = gpt2.GPT2(gpt2.GPT2Config.tiny()) if kind == "gpt2" \
            else llama.LlamaModel(llama.LlamaConfig.tiny())
        _PLAIN[kind] = model, model.init(jax.random.PRNGKey(5),
                                         jnp.zeros((1, 8), jnp.int32))
    return _PLAIN[kind]


def _flax_adapter(kind, taken=0):
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    adapter = FlaxModelAdapter(kind, params=_plain(kind)[1])
    cache = PagedKVCache(num_blocks=32, block_size=PAGE)
    adapter.bind_cache(cache)
    if taken:       # so that two replicas' block tables differ
        cache.allocate("elsewhere", taken * PAGE)
    return adapter, cache


def _full_forward(kind, ids):
    import jax.numpy as jnp
    model, params = _plain(kind)
    return np.asarray(model.apply(params, jnp.asarray([ids]))[0])


def _full_forward_greedy(kind, prompt, n):
    ids = list(prompt)
    for _ in range(n):
        ids.append(int(_full_forward(kind, ids)[-1].argmax()))
    return ids[len(prompt):]


def _greedy(adapter, seqs, n, logits=None):
    """n greedy tokens a sequence through the adapter's contract."""
    if logits is None:
        logits = adapter.prefill(seqs)
    for step in range(n):
        for s, row in zip(seqs, logits):
            s.tokens.append(int(row.argmax()))
        if step + 1 < n:
            logits = adapter.decode(seqs)
    return [s.tokens for s in seqs]


@pytest.mark.parametrize("kind", FLAX_KINDS)
def test_flax_engine_serves_the_full_forwards_tokens(kind):
    """Three prompts (one ends mid-page, the batch pads to a bucket of
    four) through an engine: prefill and every decode step write and
    read the pool layer by layer."""
    adapter, _ = _flax_adapter(kind)
    prompts = token_prompts(31, adapter.vocab_size, (5, 12, 9))
    eng = LLMEngine(adapter, EngineConfig(
        max_running=4, num_blocks=32, block_size=PAGE, max_seq_len=128))
    try:
        sids = [eng.add_request(p, SamplingParams(max_new_tokens=6))
                for p in prompts]
        served = [drain_stream(eng, sid, timeout=120.0)[0]
                  for sid in sids]
    finally:
        eng.stop()
    assert served == [_full_forward_greedy(kind, p, 6) for p in prompts]


@pytest.mark.parametrize("kind", FLAX_KINDS)
def test_flax_kv_handoff_keeps_heads_apart_and_the_tokens(kind):
    """``export_kv`` → ``import_kv``: the blob is [L, nb, bs, Hkv, D]
    whatever the pool's own shape, and a second replica, with other
    pages, goes on with the same tokens."""
    src, src_cache = _flax_adapter(kind)
    dst, dst_cache = _flax_adapter(kind, taken=3)
    prompt, = token_prompts(37, src.vocab_size, (12,))
    want = _full_forward_greedy(kind, prompt, 5)
    a = flax_seq(src_cache, "a", prompt)
    first = src.prefill([a])
    blob = src.export_kv("a", len(prompt))
    heads = (src.n_layers, 2, PAGE, src.n_kv_heads, src.head_dim)
    assert blob["k"].shape == blob["v"].shape == heads
    if kind == "llama":
        assert src.n_kv_heads < src.cfg.n_heads
    b = flax_seq(dst_cache, "b", prompt)
    assert dst_cache.block_table("b") != src_cache.block_table("a")
    dst.import_kv("b", len(prompt), blob)
    assert _greedy(dst, [b], 5, logits=first) == [want]


@pytest.mark.parametrize("kind", FLAX_KINDS)
def test_flax_copy_on_extend_leaves_the_shared_page_alone(kind):
    """A sequence that shares a prefix ending mid-page gets a copy of
    that page (``copy_page``) and writes into the copy: the source
    page's bytes stay, and both sequences serve the right tokens."""
    adapter, cache = _flax_adapter(kind)
    base, tail = token_prompts(41, adapter.vocab_size, (12, 3))
    a = flax_seq(cache, "a", base)
    first = adapter.prefill([a])
    shared = cache.block_table("a")[:2]

    def pages():
        return [np.asarray(p[:, shared]) for p in
                (adapter.k_pages, adapter.v_pages)]
    before = pages()
    b = flax_seq(cache, "b", base + tail, shared_pages=shared)
    b.cached_tokens = len(base)
    got_b = _greedy(adapter, [b], 4)
    assert cache.block_table("b")[0] == shared[0]
    assert cache.block_table("b")[1] != shared[1]
    for was, now in zip(before, pages()):
        np.testing.assert_array_equal(now, was)
    assert got_b == [_full_forward_greedy(kind, base + tail, 4)]
    assert _greedy(adapter, [a], 4, logits=first) \
        == [_full_forward_greedy(kind, base, 4)]


@pytest.mark.parametrize("kind", FLAX_KINDS)
def test_flax_decode_window_and_rollback_match_the_plain_loop(kind):
    """One batched ``decode_window`` gives, at position j, the logits
    of the tokens up to j; after ``rollback`` of the rejected positions
    the plain decode loop goes on as if they had never been written."""
    adapter, cache = _flax_adapter(kind)
    prompts = token_prompts(43, adapter.vocab_size, (12, 5))
    want = [_full_forward_greedy(kind, p, 5) for p in prompts]
    seqs = [flax_seq(cache, f"s{i}", p) for i, p in enumerate(prompts)]
    _greedy(adapter, seqs, 1)
    # window: the last token, two right proposals, one wrong, one more
    wrong = [(w[2] + 1) % adapter.vocab_size for w in want]
    windows = [[w[0], w[1], x, w[3]] for w, x in zip(want, wrong)]
    rows = adapter.decode_window(seqs, windows)
    for p, w, win, got in zip(prompts, want, windows, rows):
        assert [int(r.argmax()) for r in got[:2]] == w[1:3]
        np.testing.assert_allclose(
            got, _full_forward(kind, p + win)[len(p):],
            rtol=1e-4, atol=1e-4)
    for s, w in zip(seqs, want):
        adapter.rollback(s.seq_id, 2)
        s.tokens = w[:2]
    assert _greedy(adapter, seqs, 3, logits=[r[1] for r in rows]) == want


@pytest.mark.parametrize("kind", FLAX_KINDS)
def test_stateless_kinds_keep_their_step_program(kind):
    """gpt2 and llama compile what they compiled before a model could
    state its cache: two donated pools [L, P, bs, Hkv*D] after params and
    tokens, seven arguments, the same program names; a decode step is
    the one-token program, and the counters say what its attention had
    to read."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm.model_runner import bucket_name
    adapter, _ = _flax_adapter(kind)
    assert not adapter.has_state and adapter.counters() == {
        "kv_pages_live_total": 0, "kv_pages_padded_total": 0,
        "kv_run_pages_total": 0, "kv_table_pages_total": 0}
    assert adapter.k_pages.shape == (
        adapter.n_layers, 32, PAGE, adapter.n_kv_heads * adapter.head_dim)
    fn = adapter._step_fn(2, 1)
    lowered = fn.lower(
        adapter.params, jnp.zeros((2, 1), jnp.int32), adapter.k_pages,
        adapter.v_pages, jnp.zeros((2, adapter.nb_max), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 1), bool))
    text = lowered.as_text()
    assert "module @jit_llm_decode_b2 " in text
    assert bucket_name(2, 1) == "llm_decode_b2"
    assert bucket_name(2, 8) == "llm_prefill_b2_s8"
    assert bucket_name(4, 64) == "llm_prefill_b4_s64"
    assert bucket_name(2, 8, True) == "llm_verify_b2_s8"
    n_params = len(jax.tree_util.tree_leaves(adapter.params))
    assert len(jax.tree_util.tree_leaves(lowered.in_avals)) == n_params + 6
    logits, k, v = jax.eval_shape(
        fn, adapter.params, jnp.zeros((2, 1), jnp.int32), adapter.k_pages,
        adapter.v_pages, jnp.zeros((2, adapter.nb_max), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 1), bool))
    assert logits.shape == (2, adapter.vocab_size)
    assert k.shape == v.shape == adapter.k_pages.shape


@pytest.mark.parametrize("kind", FLAX_KINDS)
def test_decode_is_the_one_token_program(kind):
    """``decode`` runs ``llm_decode_b{B}`` with one token a row (three
    rows pad to four), says so on its dispatch span with the pages its
    attention had to read, and its logits are those of the same tokens
    through the 8-token bucket (``decode_window`` of one token) and of
    the full forward."""
    from ray_tpu._private import tracing
    logits = {}
    for how in ("decode", "window"):
        adapter, cache = _flax_adapter(kind)
        prompts = token_prompts(43, adapter.vocab_size, (12, 7, 17))
        seqs = [flax_seq(cache, f"{how}-{i}", p, 4)
                for i, p in enumerate(prompts)]
        for s, row in zip(seqs, adapter.prefill(seqs)):
            s.tokens.append(int(row.argmax()))
        if how == "window":
            rows = adapter.decode_window(seqs, [[s.tokens[-1]]
                                                for s in seqs])
            logits[how] = np.stack([r[0] for r in rows])
            assert (4, 8, True) in adapter._fns
            continue
        with tracing.step_span("test.root") as root:
            logits[how] = adapter.decode(seqs)
        assert (4, 1, False) in adapter._fns \
            and (4, 8, False) not in adapter._fns
        assert adapter._fns[4, 1, False].__wrapped__.__name__ \
            == "llm_decode_b4"
        dispatch = next(c for c in root.rec["children"]
                        if c["name"] == "runner.dispatch")["attrs"]
        live = sum(-(-(len(p) + 1) // PAGE) for p in prompts)
        assert dispatch["S"] == 1 and dispatch["attention"] == "gather"
        assert dispatch["kv_pages_live"] == live
        assert dispatch["kv_pages_padded"] == 4 * adapter.nb_max
        assert adapter.counters()["kv_pages_live_total"] == live
        # the pages the rows' tables hold, and those among them that a
        # kernel's one copy would bring: a fresh pool gives whole runs
        held = [adapter.cache.blocks_for(s.budget_tokens()) for s in seqs]
        assert dispatch["kv_table_pages"] == sum(held)
        assert dispatch["kv_run_pages"] == sum(n // 8 * 8 for n in held)
        assert adapter.counters()["kv_run_pages_total"] \
            == dispatch["kv_run_pages"]
    np.testing.assert_allclose(logits["decode"], logits["window"],
                               rtol=1e-5, atol=1e-5)
    for row, s in zip(logits["decode"], seqs):
        full = _full_forward(kind, list(s.prompt) + s.tokens)[-1]
        np.testing.assert_allclose(row, full, rtol=2e-4, atol=2e-4)
