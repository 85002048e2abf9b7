"""LLM serving, a model whose layers cache over TWO page groups (Laguna:
full-attention layers that keep every position, sliding-window layers
that keep a ring of ``window / block_size + 1`` pages a sequence), held
to the plain reference's logits (docs/LLM_SERVING.md, "Page groups").
Tier-1, CPU-only.

Logits are compared, not tokens. Everything here is float32 at 'highest'
on both sides (tests/conftest.py; the replica of the cluster test runs
float32 on the CPU), so the served rows differ from the reference's full
forward by the order of sums only: 5e-5 absolute on logits of spread
~0.16. A row read from a wrong ring page or rotated at a wrong position
moves a logit by 1e-2 or more. The tiny preset's window is 32 positions
(a ring of 5 pages of 8), and every sequence here runs several windows
long, so its ring wraps more than once.

What a windowed page group cannot do is refused with its reason
(``WindowedPagesError``): a ring page is overwritten as its sequence
grows, so it cannot be shared (``enable_prefix_cache``), prefilled behind
a cached prefix, rolled back (``decode_window`` / ``rollback``,
``spec_k``) or shipped as a prompt's pages (``export_kv`` /
``import_kv``)."""

import os
import subprocess
import sys

import numpy as np
import pytest
from llm_test_helpers import PAGE, drain_stream, flax_seq, token_prompts

from ray_tpu.serve.llm import (EngineConfig, LLMEngine, PagedKVCache,
                               SamplingParams)
from ray_tpu.serve.llm.kv_cache import OutOfKVBlocksError
from ray_tpu.serve.llm.model_runner import WindowedPagesError

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-5
_L = {}


def _laguna():
    if not _L:
        from benchmark.reference import laguna_glue, laguna_ref
        from ray_tpu.models.laguna import LagunaConfig
        cfg = LagunaConfig.tiny()
        _L.update(cfg=cfg, params=laguna_glue.init_for(cfg, 7),
                  sizes=laguna_ref.sizes_of(cfg), ref=laguna_ref)
    return _L


def _adapter(blocks=128, max_sequences=4):
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    k = _laguna()
    adapter = FlaxModelAdapter("laguna", k["cfg"], k["params"])
    cache = PagedKVCache(num_blocks=blocks, block_size=PAGE,
                         windows=adapter.page_windows,
                         max_sequences=max_sequences)
    adapter.bind_cache(cache)
    return adapter, cache


def _reference_rows(prompt, tokens, params=None):
    """The reference's logits after the prompt and after each of
    ``tokens`` but the last: what prefill and each decode returned."""
    k = _laguna()
    ids = np.asarray(list(prompt) + list(tokens[:-1]), np.int32)
    rows = k["ref"].forward((params or k["params"])["params"], ids,
                            k["sizes"])
    return np.asarray(rows[len(prompt) - 1:])


def _serve(adapter, seqs, n, rows=None):
    if rows is None:
        rows = [[r] for r in adapter.prefill(seqs)]
    for _ in range(n):
        for s, got in zip(seqs, rows):
            s.tokens.append(int(got[-1].argmax()))
        for got, r in zip(rows, adapter.decode(seqs)):
            got.append(r)
    return rows


def _greedy_gap(prompt, served, params=None):
    want = _reference_rows(prompt, served, params)
    return want.max(-1) - want[np.arange(len(served)), served]


def test_laguna_prefill_then_decode_through_both_page_groups():
    """Prompts of 100, 5 and 47 tokens in one batch (a bucket of 4 x
    128: longer than the window, shorter than a page, between), then 70
    decode steps: contexts of 3-5 windows, so the 5-page ring wraps
    twice and more; one sequence ends and a new one takes its ring.
    Every logits row against the reference's full forward."""
    adapter, cache = _adapter()
    assert not adapter.has_state and adapter.greedy_on_device
    assert adapter.page_windows == (32,) and cache.ring_blocks(32) == 5
    shapes = {k: a.shape for k, a in adapter._arrays.items()}
    assert shapes == {"k_full": (2, 128, PAGE, 32), "v_full": (2, 128, PAGE, 32),
                      "k_window": (3, 21, PAGE, 32),
                      "v_window": (3, 21, PAGE, 32)}
    prompts = token_prompts(41, adapter.vocab_size, (100, 5, 47, 19))
    a, b, c = (flax_seq(cache, f"s{i}", p, budget=80)
               for i, p in enumerate(prompts[:3]))
    rows = _serve(adapter, [a, b, c], 40)
    ring_b = cache.ring_table("s1", 32)
    adapter.release("s1")
    cache.free("s1")
    d = flax_seq(cache, "s3", prompts[3], budget=80)
    assert sorted(cache.ring_table("s3", 32)) == sorted(ring_b)
    rows_acd = _serve(adapter, [a, c, d], 30,
                      rows=[rows[0], rows[2]] + _serve(adapter, [d], 0))
    for seq, got in zip((a, b, c, d), (rows_acd[0], rows[1], rows_acd[1],
                                       rows_acd[2])):
        want = _reference_rows(seq.prompt, seq.tokens + [0])
        np.testing.assert_allclose(np.stack(got), want[:len(got)], atol=TOL)
    assert len(a.prompt) + len(a.tokens) > 5 * 32
    counters = adapter.counters()
    assert counters["kv_window_pages_live_total"] \
        <= counters["kv_window_pages_padded_total"]
    assert np.shape(counters["expert_tokens_total"]) == (4, 16)
    # the greedy tokens found on the device are the logits' argmax
    cache.free("s0")
    adapter.release("s0")
    e = flax_seq(cache, "s4", prompts[1], budget=4)
    assert adapter.prefill([e], tokens_only=True).tolist() \
        == [int(rows[1][0].argmax())]


def test_allocator_accounts_by_page_group():
    """Admission is refused when EITHER group lacks pages and takes
    nothing then; a ring's pages come back at release; ``stats()``
    reports each group; with one group nothing of the allocator's
    behaviour or its report changes."""
    cache = PagedKVCache(num_blocks=64, block_size=PAGE, windows=(32,),
                         max_sequences=2)
    assert cache.group_blocks(32) == 2 * 5 + 1 and cache.windows == (32,)
    a = cache.allocate("a", 100)
    assert len(a) == 13 and len(cache.ring_table("a", 32)) == 5
    assert 0 not in cache.ring_table("a", 32)       # the null page
    cache.allocate("b", 40)
    assert not set(cache.ring_table("a", 32)) & set(cache.ring_table("b", 32))
    free = cache.free_blocks()
    assert not cache.can_allocate(8)
    with pytest.raises(OutOfKVBlocksError, match="window-32 group") as e:
        cache.allocate("c", 8)      # pages enough, none left of the group
    assert e.value.group == 32
    assert cache.free_blocks() == free and cache.block_table("c") is None
    groups = cache.stats()["kv_window_groups"]
    assert groups == {32: {"ring_blocks": 5, "blocks_total": 10,
                           "blocks_used": 10, "occupancy": 1.0,
                           "sequences": 2, "blocks_whole_rings": 10,
                           "run_pages_share": 0.0}}
    cache.free("a")
    assert cache.ring_table("a", 32) is None
    assert cache.stats()["kv_window_groups"][32]["blocks_used"] == 5
    assert cache.can_allocate(8)
    with pytest.raises(OutOfKVBlocksError, match="KV blocks") as e:
        cache.allocate("c", 64 * PAGE)      # a ring left, pages not enough
    assert e.value.group == "full"
    assert cache.stats()["kv_window_groups"][32]["blocks_used"] == 5
    with pytest.raises(ValueError, match="cannot be shared"):
        cache.allocate_with_prefix("d", 16, [cache.block_table("b")[0]])
    with pytest.raises(ValueError, match="max_sequences"):
        PagedKVCache(64, PAGE, windows=(32,))
    # one group: as it always was
    plain = PagedKVCache(num_blocks=64, block_size=PAGE)
    assert plain.windows == () and plain.allocate("a", 100) == a
    assert set(plain.stats()) == {"kv_blocks_total", "kv_blocks_used",
                                  "kv_block_size", "kv_occupancy",
                                  "kv_sequences", "kv_run_pages_share"}
    # 13 pages of a fresh pool are one run: one whole group of 8
    assert plain.stats()["kv_run_pages_share"] == 8 / 13
    # and a model with a window refuses a cache without its group
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    k = _laguna()
    with pytest.raises(ValueError, match="page windows"):
        FlaxModelAdapter("laguna", k["cfg"], k["params"]).bind_cache(plain)


@pytest.mark.parametrize("tokens, pages", [
    (1, 1), (PAGE, 1), (PAGE + 1, 2), (4 * PAGE, 4), (4 * PAGE + 1, 5),
    (5 * PAGE, 5), (100, 5), (1000, 5)])
def test_a_sequence_takes_what_it_needs_of_a_ring(tokens, pages):
    """Rings by need: ``min(ring, blocks_for(num_tokens))`` pages of the
    window group, all real pages, given back whole at release; the full
    group's table is what it was."""
    cache = PagedKVCache(num_blocks=256, block_size=PAGE, windows=(32,),
                         max_sequences=2)
    assert cache.ring_blocks(32) == 5
    assert cache.ring_need(32, tokens) == pages
    table = cache.allocate("a", tokens)
    ring = cache.ring_table("a", 32)
    assert len(table) == cache.blocks_for(tokens)
    assert len(ring) == pages and 0 not in ring and len(set(ring)) == pages
    group = cache.stats()["kv_window_groups"][32]
    assert group["blocks_used"] == pages and group["blocks_whole_rings"] == 5
    cache.free("a")
    assert cache.stats()["kv_window_groups"][32]["blocks_used"] == 0
    assert cache.free_blocks() == 255


def test_admission_is_exact_on_both_groups_with_rings_by_need():
    """A window group with a stated size (``window_blocks``: 8 pages and
    the null page, less than two whole rings of 5): short sequences are
    admitted until the GROUP is short, by their need and not by a whole
    ring; a refusal takes nothing of either group and names the group;
    release admits. The default size is ``max_sequences`` whole rings, as
    it was."""
    cache = PagedKVCache(num_blocks=64, block_size=PAGE, windows=(32,),
                         max_sequences=4, window_blocks=9)
    assert cache.group_blocks(32) == 9
    assert PagedKVCache(64, PAGE, windows=(32,), max_sequences=4
                        ).group_blocks(32) == 4 * 5 + 1
    assert PagedKVCache(64, PAGE, windows=(32,), window_blocks=9
                        ).group_blocks(32) == 9     # (no max_sequences)
    cache.allocate("a", 100)                        # a whole ring: 5
    cache.allocate("b", 2 * PAGE)                   # 2
    assert cache.can_allocate(PAGE) and not cache.can_allocate(2 * PAGE)
    free = cache.free_blocks()
    with pytest.raises(OutOfKVBlocksError, match="need 2 pages of the "
                       "window-32 group, 1 free") as e:
        cache.allocate("c", 2 * PAGE)
    assert e.value.group == 32 and cache.free_blocks() == free
    assert cache.block_table("c") is None and cache.ring_table("c", 32) is None
    cache.allocate("c", PAGE)                       # 1: the group is full
    assert cache.stats()["kv_window_groups"][32] == {
        "ring_blocks": 5, "blocks_total": 8, "blocks_used": 8,
        "occupancy": 1.0, "sequences": 3, "blocks_whole_rings": 15,
        "run_pages_share": 0.0}
    # the full group short, the window group not: nothing is taken
    cache.free("a")
    with pytest.raises(OutOfKVBlocksError, match="KV blocks") as e:
        cache.allocate("d", 64 * PAGE)
    assert e.value.group == "full"
    assert cache.stats()["kv_window_groups"][32]["blocks_used"] == 3
    assert len(cache.ring_table(cache.allocate("d", 100) and "d", 32)) == 5


def test_engine_admits_by_both_groups_and_says_what_its_steps_read():
    """Through ``LLMEngine``: the window group's pool is sized from
    ``max_running`` alone (no option names it), three requests on two
    slots are all served with the reference's greedy tokens, and the
    decode steps' dispatch spans and the metrics report both groups."""
    adapter = _adapter()[0]
    eng = LLMEngine(adapter, EngineConfig(
        max_running=2, num_blocks=64, block_size=PAGE, max_seq_len=128,
        max_prefill_tokens=64))
    assert eng.cache.group_blocks(32) == 2 * 5 + 1
    assert adapter._arrays["k_window"].shape[1] == 11
    prompts = token_prompts(47, adapter.vocab_size, (60, 9, 41))
    try:
        sids = [eng.add_request(p, SamplingParams(max_new_tokens=50))
                for p in prompts]
        toks = [drain_stream(eng, sid, timeout=240.0)[0] for sid in sids]
        metrics, steps = eng.metrics(), eng.step_log()
    finally:
        eng.stop()
    for p, t in zip(prompts, toks):
        assert len(t) == 50
        assert float(_greedy_gap(p, t).max()) <= TOL
    groups = metrics["kv_window_groups"][32]
    assert groups["blocks_total"] == 10 and groups["blocks_used"] == 0
    assert metrics["kv_window_pages_padded_total"] \
        >= metrics["kv_window_pages_live_total"] > 0

    def walk(span):
        yield span
        for child in span.get("children", ()):
            yield from walk(child)
    decodes = [s["attrs"] for step in steps for d in walk(step)
               if d["name"] == "llm.step.decode" for s in walk(d)
               if s["name"] == "runner.dispatch"]
    assert decodes and all(
        {"attention", "live_tokens", "window_tokens", "kv_pages_live",
         "kv_pages_padded", "kv_window_pages_live", "kv_window_pages_held",
         "kv_window_pages_whole_rings", "kv_window_pages_padded",
         "kv_run_pages", "kv_table_pages"} <= set(a)
        for a in decodes)
    # both groups' tables: the full group's pages and the rings'
    assert all(a["kv_run_pages"] <= a["kv_table_pages"]
               and a["kv_table_pages"] > a["kv_window_pages_held"]
               for a in decodes)
    assert metrics["kv_table_pages_total"] \
        == sum(a["kv_table_pages"] for a in decodes)
    assert all(a["attention"] == "gather" for a in decodes)     # the CPU
    assert all(a["window_tokens"] <= a["live_tokens"]
               and a["kv_window_pages_live"] <= a["kv_window_pages_held"]
               <= a["kv_window_pages_whole_rings"] for a in decodes)
    assert any(a["window_tokens"] < a["live_tokens"] for a in decodes)
    prefills = [s["attrs"] for step in steps for d in walk(step)
                if d["name"] == "llm.step.prefill" for s in walk(d)
                if s["name"] == "runner.dispatch"]
    assert sorted(a["prompt_tokens"] for a in prefills) == [9, 41, 60]


@pytest.mark.parametrize("what", ["enable_prefix_cache", "spec_k",
                                  "prefill_export", "adopt_request"])
def test_engine_refuses_what_a_ring_cannot_do(what):
    adapter = _adapter()[0]
    config = dict(max_running=2, num_blocks=64, block_size=PAGE,
                  max_seq_len=128)
    if what == "enable_prefix_cache":
        with pytest.raises(WindowedPagesError, match="cannot be shared"):
            LLMEngine(adapter, EngineConfig(enable_prefix_cache=True,
                                            **config))
        return
    if what == "spec_k":
        with pytest.raises(WindowedPagesError, match="overwritten"):
            LLMEngine(adapter, EngineConfig(
                spec_k=2, draft_model="toy", **config))
        return
    eng = LLMEngine(adapter, EngineConfig(**config))
    try:
        with pytest.raises(WindowedPagesError, match="a ring is not"):
            if what == "prefill_export":
                eng.prefill_export([1, 2, 3])
            else:
                eng.adopt_request([1, 2, 3], 4, {"kind": "flax:laguna"})
    finally:
        eng.stop()


@pytest.mark.parametrize("what", ["decode_window", "rollback", "export_kv",
                                  "import_kv", "prefill_from_a_prefix"])
def test_adapter_refuses_what_a_ring_cannot_do(what):
    """Each entry point that shares, rolls back or ships cached tokens
    says why it cannot over a ring, and leaves the sequence as it was:
    the next decode step still serves the reference's logits."""
    adapter, cache = _adapter()
    prompt, = token_prompts(53, adapter.vocab_size, (40,))
    seq = flax_seq(cache, "s0", prompt, budget=16)
    rows = _serve(adapter, [seq], 2)
    with pytest.raises(WindowedPagesError, match="windowed page group"):
        if what == "decode_window":
            adapter.decode_window([seq], [[1, 2]])
        elif what == "rollback":
            adapter.rollback("s0", 1)
        elif what == "export_kv":
            adapter.export_kv("s0", len(prompt))
        elif what == "import_kv":
            adapter.import_kv("s0", len(prompt), {"kind": "flax:laguna"})
        else:
            other = flax_seq(cache, "s1", prompt, budget=8)
            other.cached_tokens = 16
            adapter.prefill([other])
    rows = _serve(adapter, [seq], 2, rows=rows)
    want = _reference_rows(seq.prompt, seq.tokens + [0])
    np.testing.assert_allclose(np.stack(rows[0]), want[:5], atol=TOL)


def test_laguna_streams_the_references_greedy_tokens_through_serve_run():
    """``serve.run`` of an ``LLMServer("laguna", ...)`` replica (tiny
    preset, weights from a seed), clients on ``handle.stream``: tokens
    arrive in chunks and are, teacher-forced through the reference on
    the same weights, each its row's largest logit. Tokens are compared,
    not how they are chunked beyond "more than one chunk" (ROADMAP D15);
    48 tokens a request past prompts of 40 and 13: contexts to 88, the
    ring wraps."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    params = FlaxModelAdapter("laguna", seed=5).params
    prompts = token_prompts(59, 512, (40, 13))
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                 object_store_memory=128 * 1024 * 1024)
    try:
        dep = serve.deployment(name="laguna", num_replicas=1,
                               max_concurrent_queries=8)(LLMServer)
        h = serve.run(dep.bind("laguna", {"seed": 5}, {
            "num_blocks": 64, "block_size": PAGE, "max_seq_len": 128,
            "max_running": 2}), name="laguna", route_prefix="/laguna",
            http_port=None)
        for p in prompts:
            chunks = list(h.stream({"tokens": p, "max_new_tokens": 48,
                                    "temperature": 0.0}))
            toks = [t for c in chunks for t in c["tokens"]]
            assert chunks[-1]["done"] and len(toks) == 48
            assert len(chunks) >= 2, "tokens must stream"
            assert float(_greedy_gap(p, toks, params).max()) <= 1e-4
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


def test_the_new_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --rehearse`` of laguna_xs_2.
    serve_closed64_ctx8k at tiny widths (a window of 32 under prompts of
    33-64: the ring wraps): the replica is deployed, every reachable
    shape warmed (one prefill program), the window served with no failed
    request, four requests held to the reference in both page groups,
    the traced run's readers run; exit code 3."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "laguna_xs_2.serve_closed64_ctx8k", "--seed", "3700000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=280)
    text = out.stdout + out.stderr
    assert out.returncode == 3, text[-3000:]
    assert "rehearsal passed" in text and " 0 failed {}" in text
    assert "warmed 1 prefill and 4 decode row counts" in text
    assert text.count("pools fed the right tokens: True") == 4
    assert "kv_window_pages_share.serve = " in text
