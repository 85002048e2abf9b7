"""LLM inference engine on serve (docs/LLM_SERVING.md; ROADMAP item 1):
continuous batching vs static batching equivalence, paged-attention
kernel numerics vs the whole-kv reference, incremental model decode vs
full forward, cost-aware admission, KV-aware graceful drain through a
rolling update, token streaming end to end (handle iterator + HTTP
SSE, first token BEFORE generation completes), chaos mid-stream
replica kill (clean failure or retry, never silent truncation), LLM
autoscaler signals, trace phase spans, and the llm-chat game day with
per-token reconciliation. Tier-1, CPU-only.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.exceptions import (ReplicaOverloadedError,
                                      StreamBrokenError)
from ray_tpu.serve.llm import (EngineConfig, LLMEngine, LLMServer,
                               PagedKVCache, SamplingParams, ToyAdapter)
from ray_tpu.serve.llm.kv_cache import OutOfKVBlocksError

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------ kernel numerics


def test_paged_attention_matches_whole_kv_reference():
    """The Pallas paged-decode kernel (interpret mode on CPU), the
    paged gather reference, and the contiguous whole-kv decode path
    must agree bit-for-bit-ish on the same cache contents."""
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    rng = np.random.RandomState(0)
    B, H, Hkv, D, bs, NB = 3, 8, 2, 16, 8, 4
    P = 1 + B * NB
    lengths = jnp.asarray([5, 17, 30], jnp.int32)
    k_pages = jnp.asarray(rng.randn(P, bs, Hkv, D), jnp.float32)
    v_pages = jnp.asarray(rng.randn(P, bs, Hkv, D), jnp.float32)
    bt = jnp.asarray(np.arange(1, 1 + B * NB).reshape(B, NB), jnp.int32)
    q = jnp.asarray(rng.randn(B, H, D), jnp.float32)

    ref = A.paged_attention_reference(q, k_pages, v_pages, bt, lengths)
    kernel = A.paged_attention_decode(q, k_pages, v_pages, bt, lengths,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    # contiguous whole-kv path over the SAME logical cache
    k_cont = A.paged_gather(k_pages, bt)
    v_cont = A.paged_gather(v_pages, bt)
    whole = A.decode_attention(q[:, :, None, :], k_cont, v_cont,
                               lengths)[:, :, 0, :]
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_paged_kv_allocator_exact_admission():
    c = PagedKVCache(num_blocks=8, block_size=4)   # 7 usable pages
    assert c.blocks_for(9) == 3
    t1 = c.allocate("a", 9)             # 3 pages
    assert 0 not in t1                  # page 0 reserved (null page)
    assert c.can_allocate(16)           # 4 pages left
    assert not c.can_allocate(17)       # 5 needed, 4 free
    with pytest.raises(OutOfKVBlocksError):
        c.allocate("b", 17)
    assert abs(c.occupancy() - 3 / 7) < 1e-9
    assert c.free("a") == 3
    assert c.occupancy() == 0.0
    assert c.free("a") == 0             # double free is a no-op


# --------------------------------------------------- incremental decode


def test_gpt2_incremental_decode_matches_full_forward():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2
    cfg = gpt2.GPT2Config.tiny()
    m = gpt2.GPT2(cfg)
    ids = jnp.asarray(
        np.random.RandomState(1).randint(0, cfg.vocab_size, (2, 10)))
    params = m.init(jax.random.PRNGKey(0), ids)
    full = m.apply(params, ids)

    cache = gpt2.init_kv_cache(cfg, 2, 32)
    L = jnp.zeros((2,), jnp.int32)
    lg, cache = m.apply(params, ids[:, :6], kv_cache=cache,
                        seq_lengths=L)
    outs, L = [lg], L + 6
    for t in range(6, 10):
        lg, cache = m.apply(params, ids[:, t:t + 1], kv_cache=cache,
                            seq_lengths=L)
        outs.append(lg)
        L = L + 1
    inc = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                               rtol=1e-4, atol=1e-4)


def test_llama_incremental_decode_matches_full_forward():
    """GQA + rotary offsets: the decode path must rotate each new
    token by its TRUE absolute position."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    cfg = llama.LlamaConfig.tiny()     # n_kv_heads < n_heads
    m = llama.LlamaModel(cfg)
    ids = jnp.asarray(
        np.random.RandomState(2).randint(0, cfg.vocab_size, (2, 9)))
    params = m.init(jax.random.PRNGKey(0), ids)
    full = m.apply(params, ids)

    cache = llama.init_kv_cache(cfg, 2, 32)
    L = jnp.zeros((2,), jnp.int32)
    lg, cache = m.apply(params, ids[:, :5], kv_cache=cache,
                        seq_lengths=L)
    outs, L = [lg], L + 5
    for t in range(5, 9):
        lg, cache = m.apply(params, ids[:, t:t + 1], kv_cache=cache,
                            seq_lengths=L)
        outs.append(lg)
        L = L + 1
    inc = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(inc), np.asarray(full),
                               rtol=1e-4, atol=1e-4)


# ------------------------------ the flax adapter over the paged pool
#
# Ground truth throughout: the full forward, no cache, of the model as it
# is trained (one entry a block in its parameters, a Python loop over
# them); the adapter is given those parameters and serves them stacked,
# one block's program looped. Pages hold 8 tokens, so 12-token prompts
# end mid-page and every sequence crosses a page boundary while it
# decodes. ``llama``'s tiny config has fewer kv heads than heads (GQA).

FLAX_KINDS = ["gpt2", "llama"]
PAGE = 8
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


def _flax_seq(cache, sid, prompt, budget=8, shared_pages=()):
    from ray_tpu.serve.llm.engine import Sequence
    cache.allocate_with_prefix(sid, len(prompt) + budget,
                               list(shared_pages))
    return Sequence(sid, None, list(prompt),
                    SamplingParams(max_new_tokens=budget))


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


def _prompts(seed, vocab, lengths):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, vocab, n)] for n in lengths]


@pytest.mark.parametrize("kind", FLAX_KINDS)
def test_flax_engine_serves_the_full_forwards_tokens(kind):
    """Three prompts (one ends mid-page, the batch pads to a bucket of
    four) through an engine: prefill and every decode step write and
    read the pool layer by layer."""
    adapter, _ = _flax_adapter(kind)
    prompts = _prompts(31, adapter.vocab_size, (5, 12, 9))
    eng = LLMEngine(adapter, EngineConfig(
        max_running=4, num_blocks=32, block_size=PAGE, max_seq_len=128))
    try:
        sids = [eng.add_request(p, SamplingParams(max_new_tokens=6))
                for p in prompts]
        served = [_drain_stream(eng, sid, timeout=120.0)[0]
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
    prompt, = _prompts(37, src.vocab_size, (12,))
    want = _full_forward_greedy(kind, prompt, 5)
    a = _flax_seq(src_cache, "a", prompt)
    first = src.prefill([a])
    blob = src.export_kv("a", len(prompt))
    heads = (src.n_layers, 2, PAGE, src.n_kv_heads, src.head_dim)
    assert blob["k"].shape == blob["v"].shape == heads
    if kind == "llama":
        assert src.n_kv_heads < src.cfg.n_heads
    b = _flax_seq(dst_cache, "b", prompt)
    assert dst_cache.block_table("b") != src_cache.block_table("a")
    dst.import_kv("b", len(prompt), blob)
    assert _greedy(dst, [b], 5, logits=first) == [want]


@pytest.mark.parametrize("kind", FLAX_KINDS)
def test_flax_copy_on_extend_leaves_the_shared_page_alone(kind):
    """A sequence that shares a prefix ending mid-page gets a copy of
    that page (``copy_page``) and writes into the copy: the source
    page's bytes stay, and both sequences serve the right tokens."""
    adapter, cache = _flax_adapter(kind)
    base, tail = _prompts(41, adapter.vocab_size, (12, 3))
    a = _flax_seq(cache, "a", base)
    first = adapter.prefill([a])
    shared = cache.block_table("a")[:2]

    def pages():
        return [np.asarray(p[:, shared]) for p in
                (adapter.k_pages, adapter.v_pages)]
    before = pages()
    b = _flax_seq(cache, "b", base + tail, shared_pages=shared)
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
    prompts = _prompts(43, adapter.vocab_size, (12, 5))
    want = [_full_forward_greedy(kind, p, 5) for p in prompts]
    seqs = [_flax_seq(cache, f"s{i}", p) for i, p in enumerate(prompts)]
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


# ------------------------------------------ a model with state (Kimi-Linear)
# Logits are compared, not tokens. Everything here is float32 at 'highest'
# on both sides (tests/conftest.py), so the served rows differ from the
# reference's full forward by the order of sums only: 5e-5 absolute on
# logits of spread ~0.16 (chunked prefill + up to 30 one-token updates of
# the state). A stale state, a wrong slot or a wrong page moves a row by
# 1e-2 or more (test_kimi_a_stale_state_shows).

KIMI_TOL = 5e-5
_KIMI = {}


def _kimi():
    if not _KIMI:
        from benchmark.reference import kimi_linear_glue, kimi_linear_ref
        from ray_tpu.models.kimi_linear import KimiLinearConfig
        cfg = KimiLinearConfig.tiny()
        _KIMI.update(cfg=cfg, params=kimi_linear_glue.init_for(cfg, 7),
                     sizes=kimi_linear_ref.sizes_of(cfg),
                     ref=kimi_linear_ref)
    return _KIMI


def _kimi_adapter(max_running=4):
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    k = _kimi()
    adapter = FlaxModelAdapter("kimi_linear", k["cfg"], k["params"])
    cache = PagedKVCache(num_blocks=64, block_size=PAGE)
    adapter.bind_cache(cache)
    adapter.bind_state(max_running)
    return adapter, cache


def _kimi_reference_rows(prompt, tokens):
    """The reference's logits after the prompt and after each of
    ``tokens`` but the last: what prefill and each decode returned."""
    k = _kimi()
    ids = np.asarray(list(prompt) + list(tokens[:-1]), np.int32)
    rows = k["ref"].forward(k["params"]["params"], ids, k["sizes"])
    return np.asarray(rows[len(prompt) - 1:])


def _kimi_serve(adapter, seqs, n, rows=None):
    """Prefill (unless ``rows`` has each sequence's logits so far) and n
    greedy decode steps; every logits row that came back, a sequence."""
    if rows is None:
        rows = [[r] for r in adapter.prefill(seqs)]
    for _ in range(n):
        for s, got in zip(seqs, rows):
            s.tokens.append(int(got[-1].argmax()))
        for got, r in zip(rows, adapter.decode(seqs)):
            got.append(r)
    return rows


def _kimi_check(seq, rows):
    want = _kimi_reference_rows(
        seq.prompt, seq.tokens + [0])          # one row a logits row
    np.testing.assert_allclose(np.stack(rows), want[:len(rows)],
                               atol=KIMI_TOL)


def test_kimi_pages_and_slots_serve_the_references_logits():
    """Rows of unequal length in one batch (70, 5 and 33 tokens: a
    bucket of 4 x 128, one prompt longer than a KDA chunk, one shorter
    than a page), decode in a bucket of 4; one sequence ends and the rest go on in a bucket of 2; a new
    sequence takes the freed slot and joins them."""
    adapter, cache = _kimi_adapter()
    prompts = _prompts(41, adapter.vocab_size, (70, 5, 33, 19))
    a, b, c = (_flax_seq(cache, f"s{i}", p, budget=24)
               for i, p in enumerate(prompts[:3]))
    rows = _kimi_serve(adapter, [a, b, c], 4)
    slot_b = adapter._state["s1"]["slot"]
    adapter.release("s1")
    cache.free("s1")
    assert adapter.counters()["state_slots_in_use"] == 2
    rows_ac = _kimi_serve(adapter, [a, c], 3, rows=[rows[0], rows[2]])
    d = _flax_seq(cache, "s3", prompts[3], budget=24)
    rows_d = _kimi_serve(adapter, [d], 0)
    assert adapter._state["s3"]["slot"] == slot_b      # the slot is reused
    rows_acd = _kimi_serve(adapter, [a, c, d], 3,
                           rows=rows_ac + rows_d)
    for seq, got in zip((a, b, c, d), (rows_acd[0], rows[1], rows_acd[1],
                                       rows_acd[2])):
        _kimi_check(seq, got)
    assert {k[:2] for k in adapter._fns if isinstance(k, tuple)} == {
        (4, 128), (4, 1), (2, 1), (1, 32)}
    totals = np.asarray(adapter.counters()["expert_tokens_total"])
    assert totals.shape == (3, 4) and totals.sum() > 0


def test_kimi_a_stale_state_shows(monkeypatch):
    """Without the zeroing at admission the second user of a slot starts
    from the first one's state and conv tail, and its logits are off by
    far more than the tolerance: the comparison above would see it."""
    adapter, cache = _kimi_adapter(max_running=1)
    first, second = _prompts(43, adapter.vocab_size, (40, 21))
    a = _flax_seq(cache, "a", first)
    _kimi_serve(adapter, [a], 2)
    adapter.release("a")
    cache.free("a")
    monkeypatch.setattr(adapter, "_zero_fn",
                        lambda: lambda idx, *arrays: arrays)
    b = _flax_seq(cache, "b", second)
    stale = _kimi_serve(adapter, [b], 1)[0]
    want = _kimi_reference_rows(second, b.tokens + [0])
    assert float(np.abs(np.stack(stale) - want[:2]).max()) > 100 * KIMI_TOL
    monkeypatch.undo()
    adapter.release("b")
    cache.free("b")
    c = _flax_seq(cache, "c", second)
    _kimi_check(c, _kimi_serve(adapter, [c], 1)[0])


def test_kimi_greedy_rows_fetch_tokens_not_logits():
    """``tokens_only``: the step's greedy tokens, found on the device,
    are the argmax of the logits the same step gives a twin adapter, in
    prefill, in a decode bucket in row order and in the full bucket in
    slot order (slots taken out of order). The engine asks for them only
    of an adapter that offers them and only when no row samples."""
    from ray_tpu.serve.llm.engine import Sequence
    pair = [_kimi_adapter(max_running=2) for _ in range(2)]
    prompts = _prompts(53, pair[0][0].vocab_size, (20, 7, 11))
    got = []
    for (adapter, cache), only in zip(pair, (True, False)):
        assert adapter.greedy_on_device
        x = _flax_seq(cache, "x", prompts[2])
        adapter.prefill([x])                        # slot 1
        seqs = [_flax_seq(cache, f"g{i}", p) for i, p in
                enumerate(prompts[:2])]
        first = adapter.prefill(seqs[:1], tokens_only=only)     # slot 2
        adapter.release("x")
        cache.free("x")
        outs = [np.concatenate([first, adapter.prefill(
            seqs[1:], tokens_only=only)])]      # slot 1: not row order
        assert [adapter._state[s.seq_id]["slot"] for s in seqs] == [2, 1]
        for _ in range(2):
            toks = outs[-1] if only else outs[-1].argmax(-1)
            for s, t in zip(seqs, toks):
                s.tokens.append(int(t))
            outs.append(adapter.decode(seqs, tokens_only=only))
        outs.append(adapter.decode(seqs[:1], tokens_only=only))  # b1
        got.append(outs)
    for toks, logits in zip(*got):
        assert toks.dtype == np.int32 and toks.ndim == 1
        assert toks.tolist() == logits.argmax(-1).tolist()
    eng = LLMEngine(pair[0][0], EngineConfig(
        max_running=2, num_blocks=64, block_size=PAGE, max_seq_len=128))
    toy = LLMEngine(ToyAdapter(), EngineConfig())
    try:
        greedy = Sequence("a", None, [1], SamplingParams())
        sampled = Sequence("b", None, [1], SamplingParams(temperature=0.7),
                           rng=__import__("random").Random(0))
        assert eng._tokens_only([greedy]) == {"tokens_only": True}
        assert eng._tokens_only([greedy, sampled]) == {}
        assert toy._tokens_only([greedy]) == {}
        assert eng._sample(greedy, np.int32(7)) == 7
    finally:
        eng.stop()
        toy.stop()


def test_kimi_engine_counts_slots_experts_and_the_admit_span():
    """Through ``LLMEngine``: 6 requests on 3 slots, so slots are
    released and taken again; tokens are the reference's greedy ones;
    the step log has ``runner.state.admit`` under ``llm.step.prefill``
    and the decode fetch carries the experts the step touched."""
    adapter, _ = _kimi_adapter()
    prompts = _prompts(47, adapter.vocab_size, (30, 9, 66, 12, 40, 5))
    eng = LLMEngine(adapter, EngineConfig(
        max_running=3, num_blocks=64, block_size=PAGE, max_seq_len=128,
        max_prefill_tokens=64))
    try:
        assert eng.metrics()["state_slots_total"] == 3
        sids = [eng.add_request(p, SamplingParams(max_new_tokens=5))
                for p in prompts]
        served = [_drain_stream(eng, sid, timeout=180.0)[0]
                  for sid in sids]
        deadline = time.time() + 10
        while eng.metrics()["state_slots_in_use"] and time.time() < deadline:
            time.sleep(0.05)
        m = eng.metrics()
        log = eng.step_log()
    finally:
        eng.stop()
    for p, toks in zip(prompts, served):
        want = _kimi_reference_rows(p, toks)
        gap = want.max(-1) - want[np.arange(5), toks]
        assert float(gap.max()) <= KIMI_TOL
    assert m["state_slots_in_use"] == 0
    assert np.sum(m["expert_tokens_total"]) > 0
    assert np.shape(m["expert_tokens_last_step"]) == (3, 4)

    def walk(span):
        yield span
        for child in span.get("children", ()):
            yield from walk(child)
    admits = [c["name"] for step in log for s in walk(step)
              if s["name"] == "llm.step.prefill" for c in walk(s)]
    assert admits.count("runner.state.admit") >= 3
    fetches = [s for step in log for d in walk(step)
               if d["name"] == "llm.step.decode" for s in walk(d)
               if s["name"] == "runner.fetch"]
    assert fetches and all(
        0 <= f["attrs"]["experts_touched"] <= 12
        and f["attrs"]["moe_max_over_mean"] >= 1.0
        for f in fetches if f["attrs"].get("expert_tokens"))


@pytest.mark.parametrize("what", [
    "enable_prefix_cache", "spec_k", "decode_window", "rollback",
    "export_kv", "import_kv", "prefill_export", "adopt_request"])
def test_kimi_refuses_what_needs_a_snapshot_of_the_state(what):
    """Dropping cached tokens, sharing them by page and shipping them
    as pages each need the state as it was at that token."""
    from ray_tpu.serve.llm.model_runner import RecurrentStateError
    adapter, cache = _kimi_adapter()
    base = dict(max_running=2, num_blocks=64, block_size=PAGE,
                max_seq_len=128)
    with pytest.raises(RecurrentStateError, match="state") as err:
        if what == "enable_prefix_cache":
            LLMEngine(adapter, EngineConfig(enable_prefix_cache=True,
                                            **base))
        elif what == "spec_k":
            LLMEngine(adapter, EngineConfig(spec_k=2, **base))
        elif what in ("prefill_export", "adopt_request"):
            eng = LLMEngine(adapter, EngineConfig(**base))
            try:
                if what == "prefill_export":
                    eng.prefill_export([1, 2, 3])
                else:
                    eng.adopt_request([1, 2, 3], 4, {"kind": "x"})
            finally:
                eng.stop()
        else:
            seq = _flax_seq(cache, "a", [1, 2, 3])
            adapter.prefill([seq])
            {"decode_window": lambda: adapter.decode_window([seq], [[1, 2]]),
             "rollback": lambda: adapter.rollback("a", 1),
             "export_kv": lambda: adapter.export_kv("a", 3),
             "import_kv": lambda: adapter.import_kv("a", 3, {}),
             }[what]()
    assert "snapshot" in str(err.value)


@pytest.mark.parametrize("kind", FLAX_KINDS)
def test_stateless_kinds_keep_their_step_program(kind):
    """gpt2 and llama compile what they compiled before a model could
    state its cache: two donated pools [L, P, bs, Hkv*D] after params and
    tokens, seven arguments, the same program names, and a decode step
    that is the shortest prefill bucket."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm.model_runner import bucket_name
    adapter, _ = _flax_adapter(kind)
    assert not adapter.has_state and adapter.counters() == {}
    assert adapter.k_pages.shape == (
        adapter.n_layers, 32, PAGE, adapter.n_kv_heads * adapter.head_dim)
    fn = adapter._step_fn(2, 8)
    lowered = fn.lower(
        adapter.params, jnp.zeros((2, 8), jnp.int32), adapter.k_pages,
        adapter.v_pages, jnp.zeros((2, adapter.nb_max), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 8), bool))
    text = lowered.as_text()
    assert "module @jit_llm_decode_b2 " in text
    assert bucket_name(2, 8) == "llm_decode_b2"
    assert bucket_name(4, 64) == "llm_prefill_b4_s64"
    assert bucket_name(2, 8, True) == "llm_verify_b2_s8"
    n_params = len(jax.tree_util.tree_leaves(adapter.params))
    assert len(jax.tree_util.tree_leaves(lowered.in_avals)) == n_params + 6
    logits, k, v = jax.eval_shape(
        fn, adapter.params, jnp.zeros((2, 8), jnp.int32), adapter.k_pages,
        adapter.v_pages, jnp.zeros((2, adapter.nb_max), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2, 8), bool))
    assert logits.shape == (2, adapter.vocab_size)
    assert k.shape == v.shape == adapter.k_pages.shape


# --------------------------------------------------------------- engine


def _drain_stream(eng, sid, timeout=30.0):
    toks, cur = [], 0
    deadline = time.time() + timeout
    while time.time() < deadline:
        ch = eng.poll(sid, cur, max_wait_s=5.0)
        toks += ch["tokens"]
        cur = ch["cursor"]
        if ch["done"]:
            return toks, ch
    raise TimeoutError("stream did not finish")


def test_continuous_vs_static_batching_same_tokens():
    """The headline correctness property: continuous batching changes
    WHEN sequences run, never WHAT they produce. The toy model reads
    its prefix back through the block tables, so a paging bug breaks
    this too."""
    rng = np.random.RandomState(0)
    reqs = [(list(rng.randint(0, 256, rng.randint(3, 12))),
             int(rng.randint(2, 10))) for _ in range(9)]

    def run(policy):
        eng = LLMEngine(ToyAdapter(seed=3), EngineConfig(
            max_running=4, num_blocks=64, block_size=8,
            max_seq_len=128, policy=policy))
        sids = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                for p, n in reqs]
        outs = [_drain_stream(eng, sid)[0] for sid in sids]
        eng.stop()
        return outs

    assert run("continuous") == run("static")


def test_cost_aware_admission_long_prefill_goes_alone():
    """A prompt over the per-step prefill budget is admitted ALONE
    (and others never behind it in the same step) — and everything
    still completes."""
    eng = LLMEngine(ToyAdapter(), EngineConfig(
        max_running=8, max_prefill_tokens=8, num_blocks=64,
        block_size=8, max_seq_len=256))
    short = eng.add_request([1] * 6, SamplingParams(max_new_tokens=3))
    long = eng.add_request([2] * 40, SamplingParams(max_new_tokens=3))
    t_short, _ = _drain_stream(eng, short)
    t_long, _ = _drain_stream(eng, long)
    assert len(t_short) == 3 and len(t_long) == 3
    m = eng.metrics()
    assert m["finished_total"] == 2
    assert m["kv_occupancy"] == 0.0    # all pages returned
    eng.stop()


def test_kv_exhaustion_queues_instead_of_oom():
    """A sequence that doesn't fit the pool WAITS for pages (freed by
    finishing sequences) instead of failing mid-decode."""
    # 15 usable pages * 4 tokens = 60 tokens capacity; each request
    # needs 8+24=32 tokens -> 8 pages; two can't run at once
    eng = LLMEngine(ToyAdapter(), EngineConfig(
        max_running=8, num_blocks=16, block_size=4, max_seq_len=64))
    a = eng.add_request([1] * 8, SamplingParams(max_new_tokens=24))
    b = eng.add_request([2] * 8, SamplingParams(max_new_tokens=24))
    ta, ca = _drain_stream(eng, a)
    tb, cb = _drain_stream(eng, b)
    assert len(ta) == 24 and len(tb) == 24
    assert ca["finish_reason"] == "length"
    assert cb["finish_reason"] == "length"
    eng.stop()


def test_engine_sheds_when_waiting_room_full():
    eng = LLMEngine(ToyAdapter(per_seq_delay_s=0.01),
                    EngineConfig(max_running=1, max_waiting=1,
                                 num_blocks=64, block_size=8,
                                 max_seq_len=128))
    sids = []
    with pytest.raises(ReplicaOverloadedError):
        for _ in range(12):  # 1 running + 1 waiting, the rest shed
            sids.append(eng.add_request(
                [1, 2, 3], SamplingParams(max_new_tokens=20)))
    assert eng.metrics()["shed_total"] >= 1
    for sid in sids:
        _drain_stream(eng, sid)
    eng.stop()


def test_engine_drain_finishes_in_flight_sheds_new():
    eng = LLMEngine(ToyAdapter(per_seq_delay_s=0.005),
                    EngineConfig(max_running=4, num_blocks=64,
                                 block_size=8, max_seq_len=128))
    sid = eng.add_request([1] * 4, SamplingParams(max_new_tokens=30))
    eng.prepare_drain()
    with pytest.raises(ReplicaOverloadedError):
        eng.add_request([2] * 4, SamplingParams(max_new_tokens=2))
    toks, ch = _drain_stream(eng, sid)
    assert len(toks) == 30 and ch["finish_reason"] == "length"
    assert eng.in_flight() == 0
    eng.stop()


def test_temperature_sampling_is_seeded_deterministic():
    def gen(seed):
        eng = LLMEngine(ToyAdapter(), EngineConfig(
            num_blocks=32, block_size=8, max_seq_len=128))
        # temperature high enough to actually spread the toy model's
        # peaked logits — 1.0 still collapses to the argmax token
        sid = eng.add_request(
            [5, 6, 7], SamplingParams(max_new_tokens=12,
                                      temperature=3.0, seed=seed),
            request_id="r1")
        toks, _ = _drain_stream(eng, sid)
        eng.stop()
        return toks

    assert gen(7) == gen(7)
    assert gen(7) != gen(8)


# ---------------------------------------------------- autoscaler signals


def test_autoscaler_scales_on_llm_signals():
    from ray_tpu.serve._private.autoscaling import (AutoscalingConfig,
                                                    AutoscalingPolicy)
    cfg = AutoscalingConfig(min_replicas=1, max_replicas=8,
                            target_num_ongoing_requests_per_replica=100,
                            target_tokens_per_s_per_replica=50.0,
                            target_kv_occupancy=0.8,
                            upscale_delay_s=1.0, downscale_delay_s=1.0)
    p = AutoscalingPolicy(cfg)
    # queue is quiet but throughput demands 4 replicas
    assert p.get_decision(2, 0.0, now=0.0,
                          signals={"tokens_per_s": 200.0,
                                   "kv_occupancy": 0.1}) == 2  # delay
    assert p.get_decision(2, 0.0, now=2.0,
                          signals={"tokens_per_s": 200.0,
                                   "kv_occupancy": 0.1}) == 4
    # KV pressure alone scales out: 2 replicas at 100% occupancy
    # against a 0.8 target want ceil(2 * 1.0/0.8) = 3
    p2 = AutoscalingPolicy(cfg)
    p2.get_decision(2, 0.0, now=0.0, signals={"kv_occupancy": 1.0})
    assert p2.get_decision(2, 0.0, now=2.0,
                           signals={"kv_occupancy": 1.0}) == 3
    # no signals -> pure queue behavior unchanged
    p3 = AutoscalingPolicy(cfg)
    assert p3.get_decision(2, 0.0, now=0.0) == 2


# ------------------------------------------------------- cluster tests


@pytest.fixture(scope="module")
def llm_cluster():
    ctx = ray_tpu.init(num_cpus=8, ignore_reinit_error=True,
                       object_store_memory=128 * 1024 * 1024)
    deps = []

    def deploy(name, http_port=None, route=None, **kw):
        llm_kw = {"model": kw.pop("model", "toy"),
                  "model_config": kw.pop("model_config", {}),
                  "engine_config": kw.pop("engine_config",
                                          {"num_blocks": 128,
                                           "block_size": 8,
                                           "max_seq_len": 256})}
        dep = serve.deployment(name=name, **kw)(LLMServer)
        h = serve.run(dep.bind(llm_kw["model"],
                               llm_kw["model_config"],
                               llm_kw["engine_config"]),
                      name=name, route_prefix=route or f"/{name}",
                      http_port=http_port)
        deps.append(name)
        return h

    yield deploy
    serve.shutdown()
    ray_tpu.shutdown()


def test_streaming_handle_end_to_end(llm_cluster):
    """Handle streaming delivers tokens incrementally: multiple
    chunks, the first long before the stream is done, and the final
    token list equals the unary result (acceptance criterion)."""
    h = llm_cluster("llmh", num_replicas=1, max_concurrent_queries=16,
                    model_config={"per_seq_delay_s": 0.02})
    payload = {"prompt": "the quick brown fox", "max_new_tokens": 10}
    unary = ray_tpu.get(h.remote(payload), timeout=60.0)
    assert unary["n_tokens"] == 10

    chunks, stamps = [], []
    for ch in h.stream(payload):
        chunks.append(ch)
        stamps.append(time.time())
    toks = [t for c in chunks for t in c["tokens"]]
    assert toks == unary["tokens"]
    assert chunks[-1]["done"] and chunks[-1]["finish_reason"] == "length"
    assert len(chunks) >= 3, "tokens must stream, not arrive in bulk"
    # first chunk lands well before the stream completes
    assert stamps[0] < stamps[-1] - 0.05


def test_streaming_http_sse_first_token_early(llm_cluster):
    """SSE through the proxy: events arrive incrementally on the
    socket (first data event before [DONE] by a real margin),
    X-Request-Id echoes, token payloads match the unary path."""
    import http.client
    llm_cluster("llmsse", http_port=8917, num_replicas=1,
                max_concurrent_queries=16,
                model_config={"per_seq_delay_s": 0.02})
    proxy = ray_tpu.get_actor("SERVE_PROXY")
    port = ray_tpu.get(proxy.get_port.remote())

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    body = json.dumps({"prompt": "stream me", "max_new_tokens": 10,
                       "stream": True})
    conn.request("POST", "/llmsse", body,
                 {"Content-Type": "application/json",
                  "X-Request-Id": "sse-e2e-1"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.getheader("Content-Type") == "text/event-stream"
    assert resp.getheader("X-Request-Id") == "sse-e2e-1"
    events, stamps = [], []
    while True:
        line = resp.fp.readline()
        if not line:
            break
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        if line[6:] == b"[DONE]":
            stamps.append(("done", time.time()))
            break
        events.append(json.loads(line[6:]))
        stamps.append(("data", time.time()))
    conn.close()
    toks = [t for e in events for t in e.get("tokens", [])]
    assert len(toks) == 10
    assert events[-1].get("done") and not events[-1].get("error")
    data_times = [t for kind, t in stamps if kind == "data"]
    done_time = dict(stamps[-1:])  # ("done", t)
    assert len(events) >= 3, "SSE must deliver multiple events"
    # the FIRST token event beat the end of generation by a margin
    assert data_times[0] < done_time["done"] - 0.05

    # unary through the same route still works (no stream flag)
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/llmsse",
        json.dumps({"prompt": "stream me",
                    "max_new_tokens": 10}).encode(),
        {"Content-Type": "application/json"})
    u = json.loads(urllib.request.urlopen(req, timeout=60).read())
    assert u["tokens"] == toks


def test_rolling_update_drains_kv_zero_dropped_streams(llm_cluster):
    """KV-aware graceful drain (satellite): streams in flight when a
    rolling update lands must finish on the draining replicas — full
    token counts, zero broken streams — while the new version takes
    over fresh traffic."""
    name = "llmroll"
    h = llm_cluster(name, num_replicas=2, max_concurrent_queries=32,
                    model_config={"per_seq_delay_s": 0.03},
                    user_config={"v": 1},
                    graceful_shutdown_timeout_s=60.0)
    n_tok = 60   # ~2s+ of decoding: the update lands mid-stream
    streams = [h.stream({"tokens": [i + 1, i + 2, i + 3],
                         "max_new_tokens": n_tok},
                        request_id=f"roll-{i}") for i in range(4)]
    results: dict = {}
    errors: list = []

    def consume(i, st):
        toks = []
        try:
            for ch in st:
                toks += ch["tokens"]
            results[i] = (toks, st.finish_reason)
        except Exception as e:  # noqa: BLE001 — the assertion target
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=consume, args=(i, st))
               for i, st in enumerate(streams)]
    for t in threads:
        t.start()
    time.sleep(0.3)  # streams decoding; now redeploy a new version
    dep = serve.deployment(name=name, num_replicas=2,
                           max_concurrent_queries=32,
                           user_config={"v": 2},
                           graceful_shutdown_timeout_s=60.0)(LLMServer)
    serve.run(dep.bind("toy", {"per_seq_delay_s": 0.03},
                       {"num_blocks": 128, "block_size": 8,
                        "max_seq_len": 256}),
              name=name, route_prefix=f"/{name}", http_port=None,
              _blocking_timeout=120.0)
    for t in threads:
        t.join(timeout=120.0)
    assert not errors, errors
    assert len(results) == 4
    for i, (toks, reason) in results.items():
        assert len(toks) == n_tok, \
            f"stream {i} truncated: {len(toks)}/{n_tok}"
        assert reason == "length"
    # and the new version serves fresh requests
    out = ray_tpu.get(h.remote({"tokens": [9, 9], "max_new_tokens": 2}),
                      timeout=60.0)
    assert out["n_tokens"] == 2


def test_serve_metrics_and_prometheus_llm_gauges(llm_cluster):
    """Autoscaler-signal satellite: the controller aggregates engine
    telemetry per deployment and /metrics exports the
    ``ray_tpu_serve_llm_*`` gauges."""
    import urllib.request

    from ray_tpu.dashboard.dashboard import start_dashboard
    h = llm_cluster("llmmet", num_replicas=1, max_concurrent_queries=8)
    for i in range(3):
        ray_tpu.get(h.remote({"tokens": [1, 2, 3, 4],
                              "max_new_tokens": 6}), timeout=60.0)

    def llm_agg():
        m = serve.metrics().get("llmmet") or {}
        return m.get("llm")

    deadline = time.time() + 15.0
    agg = None
    while time.time() < deadline:
        agg = llm_agg()
        if agg and agg.get("generated_tokens_total", 0) >= 18:
            break
        time.sleep(0.5)
    assert agg, "controller never aggregated llm telemetry"
    assert agg["generated_tokens_total"] >= 18
    assert agg["kv_blocks_total"] > 0
    assert "tokens_per_s" in agg and "kv_occupancy" in agg

    port = start_dashboard(port=18475)
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=15).read().decode()
    for gauge in ("ray_tpu_serve_llm_tokens_per_s",
                  "ray_tpu_serve_llm_kv_occupancy",
                  "ray_tpu_serve_llm_running_sequences",
                  "ray_tpu_serve_llm_waiting_sequences",
                  "ray_tpu_serve_llm_generated_tokens_total"):
        assert f'{gauge}{{deployment="llmmet"}}' in text, gauge


def test_trace_spans_cover_prefill_decode_kv(llm_cluster):
    """Tracing satellite: a sampled request's trace decomposes into
    the engine's phase spans (prefill + decode at minimum; kv_alloc
    and queue appear when they take measurable time), all parented
    into the request's span tree."""
    from ray_tpu._private import tracing
    from ray_tpu.experimental.state import api as state_api
    h = llm_cluster("llmtr", num_replicas=1, max_concurrent_queries=8,
                    model_config={"per_seq_delay_s": 0.005})
    rid = "trace-llm-1"
    st = h.stream({"tokens": [3, 1, 4, 1, 5], "max_new_tokens": 8},
                  request_id=rid)
    toks = [t for ch in st for t in ch["tokens"]]
    assert len(toks) == 8

    spans = None
    deadline = time.time() + 10.0
    while time.time() < deadline:
        doc = state_api.get_trace(rid)
        spans = doc.get("spans") or []
        names = {s["name"].split(":")[0] for s in spans}
        if {"llm.prefill", "llm.decode"} <= names:
            break
        time.sleep(0.5)
    names = {s["name"].split(":")[0] for s in spans}
    assert {"llm.prefill", "llm.decode"} <= names, sorted(names)
    ok, detail = tracing.tree_complete(spans)
    assert ok, detail
    decode = next(s for s in spans
                  if s["name"].startswith("llm.decode"))
    assert decode["attrs"]["tokens"] == 8
    assert decode["phase"] == "execute"


# -------------------------------------------- subprocess isolation tests


def _run_script(script, extra_env=None, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RTPU_PRESTART_WORKERS="0")
    env.update(extra_env or {})
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True,
                          timeout=timeout, cwd=REPO_ROOT)


def test_mid_stream_replica_kill_is_clean_never_truncated():
    """Chaos satellite: a replica SIGKILLed mid-stream (seeded chaos,
    serve.replica.request op=kill) must surface as StreamBrokenError
    (or a retried-whole, full-length stream) — never a silently short
    token list presented as success."""
    script = r"""
import json, sys, time
import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.exceptions import StreamBrokenError
from ray_tpu.serve.llm import LLMServer

ray_tpu.init(num_cpus=4, object_store_memory=128*1024*1024,
             _system_config={"prestart_workers": False})
dep = serve.deployment(name="llmkill", num_replicas=1,
                       max_concurrent_queries=16)(LLMServer)
h = serve.run(dep.bind("toy", {"per_seq_delay_s": 0.03},
                       {"num_blocks": 128, "block_size": 8,
                        "max_seq_len": 256}),
              http_port=None, _blocking_timeout=120.0)
n_tok = 50
verdict = None
try:
    st = h.stream({"tokens": [1, 2, 3], "max_new_tokens": n_tok},
                  request_id="kill-1")
    toks = []
    for ch in st:   # the poll that trips the chaos counter kills the
        toks += ch["tokens"]  # replica under us
    # stream completed: only acceptable at FULL length
    verdict = {"outcome": "complete", "n": len(toks), "want": n_tok}
except StreamBrokenError as e:
    verdict = {"outcome": "broken", "tokens_so_far": e.tokens_so_far}
except Exception as e:
    verdict = {"outcome": "other", "error": repr(e)}
print("VERDICT=" + json.dumps(verdict))
serve.shutdown(); ray_tpu.shutdown()
"""
    # the replica dies at its 8th accepted request: the open + a few
    # polls land first, then a poll hits the counter mid-generation
    chaos = {"seed": 11, "schedule": [
        {"site": "serve.replica.request", "op": "kill", "at": 8,
         "method": "llmkill", "proc": "worker"}]}
    r = _run_script(script, {"RTPU_CHAOS": json.dumps(chaos)})
    assert r.returncode == 0, r.stdout + r.stderr
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("VERDICT=")]
    assert line, r.stdout + r.stderr
    v = json.loads(line[0][len("VERDICT="):])
    if v["outcome"] == "complete":
        assert v["n"] == v["want"], f"silent truncation: {v}"
    else:
        assert v["outcome"] == "broken", v


def test_llm_chat_gameday_reconciles_per_token():
    """The llm-chat game day (satellite): heavy-tail streaming load +
    a rolling update, graded outside-in — zero failed requests and an
    exact per-token client/engine reconciliation."""
    script = r"""
import json
from ray_tpu.gameday.runner import run_scenario
from ray_tpu.gameday.scenario import load_scenario
res = run_scenario(load_scenario("llm-chat"), scale=0.4,
                   dashboard_port=18476)
out = {
    "passed": res.passed,
    "failed": res.report["overall"]["failed"],
    "admitted": res.report["overall"]["admitted"],
    "llm": res.report.get("llm"),
    "checks": {c["name"]: c["ok"]
               for c in res.reconciliation.get("checks", [])},
    "details": [c for c in res.reconciliation.get("checks", [])
                if not c["ok"]],
}
print("GAMEDAY=" + json.dumps(out))
"""
    r = _run_script(script, timeout=300)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("GAMEDAY=")]
    assert line, r.stdout + r.stderr
    out = json.loads(line[0][len("GAMEDAY="):])
    assert out["failed"] == 0, out
    assert out["admitted"] > 30, out
    assert out["checks"].get("llm-tokens") is True, out["details"]
    assert out["passed"], out["details"]
    assert out["llm"]["tokens_total"] > 100, out["llm"]


def test_bench_llm_smoke():
    """The `_BENCH_LLM=1` harness runs end to end in smoke mode and
    emits the gate numbers PERF.md records."""
    env = dict(os.environ, _BENCH_LLM="1", LLM_BENCH_SMOKE="1",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench.py"], env=env,
                       capture_output=True, text=True, timeout=240,
                       cwd=REPO_ROOT)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
    assert "continuous_tokens_per_s" in r.stdout, r.stdout[-2000:]
    assert "paged_kernel_max_err" in r.stdout, r.stdout[-2000:]
