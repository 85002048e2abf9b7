"""LLM serving, SmallThinker: NoPE full layers and rotary window layers
over TWO page groups, a router that reads the attention's input, ReGLU
experts, and window rings taken BY NEED (a sequence holds ``min(ring,
blocks_for(budget))`` pages of the window group, whose pool has a stated
size), held to the plain reference (docs/LLM_SERVING.md, "Page groups").
Tier-1, CPU-only.

Everything here is float32 at 'highest' on both sides (tests/conftest.py;
the replica of the cluster test runs float32 on the CPU), so a served
token's reference logit lies under its row's maximum by the order of sums
only: 5e-5 on logits of spread ~0.1. The tiny preset's window is 32
positions (a ring of 5 pages of 8): requests whose prompt and budget end
inside 32 tokens hold fewer pages and never wrap, longer ones wrap, and
both kinds share every batch here."""

import os
import subprocess
import sys

import numpy as np
import pytest
from llm_test_helpers import PAGE, drain_stream, flax_seq, token_prompts

from ray_tpu.serve.llm import (EngineConfig, LLMEngine, PagedKVCache,
                               SamplingParams)
from ray_tpu.serve.llm.model_runner import WindowedPagesError

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-5
_S = {}


def _smallthinker():
    if not _S:
        from benchmark.reference import smallthinker_glue, smallthinker_ref
        from ray_tpu.models.smallthinker import SmallThinkerConfig
        cfg = SmallThinkerConfig.tiny()
        _S.update(cfg=cfg, params=smallthinker_glue.init_for(cfg, 7),
                  sizes=smallthinker_ref.sizes_of(cfg), ref=smallthinker_ref)
    return _S


def _adapter():
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    k = _smallthinker()
    return FlaxModelAdapter("smallthinker", k["cfg"], k["params"])


def _greedy_gap(prompt, served, params=None):
    k = _smallthinker()
    ids = np.asarray(list(prompt) + list(served[:-1]), np.int32)
    want = np.asarray(k["ref"].forward(
        (params or k["params"])["params"], ids, k["sizes"]))[len(prompt) - 1:]
    return want.max(-1) - want[np.arange(len(served)), served]


def _walk(span):
    yield span
    for child in span.get("children", ()):
        yield from _walk(child)


def test_engine_serves_mixed_lengths_and_a_short_group_makes_a_request_wait():
    """Four slots, a full group with room for all, and a window group of
    10 pages where four whole rings would be 20: requests of (prompt,
    budget) (60, 30), (9, 12), (41, 40), (5, 10), (13, 14) need 5, 3, 5,
    2 and 4 window pages. The first two are admitted (8 pages), the third
    WAITS on the window group though a slot and full pages are free, and
    is admitted when a release returns pages; every request is served
    the reference's greedy tokens; the counters say who waited on what
    and what the running sequences held of their whole rings."""
    adapter = _adapter()
    eng = LLMEngine(adapter, EngineConfig(
        max_running=4, num_blocks=64, block_size=PAGE, max_seq_len=128,
        max_prefill_tokens=64, window_blocks=11))
    assert eng.cache.group_blocks(32) == 11
    assert adapter._arrays["k_window"].shape[1] == 11
    lengths, budgets = (60, 9, 41, 5, 13), (30, 12, 40, 10, 14)
    prompts = token_prompts(47, adapter.vocab_size, lengths)
    try:
        sids = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                for p, n in zip(prompts, budgets)]
        toks = [drain_stream(eng, sid, timeout=240.0)[0] for sid in sids]
        metrics, steps = eng.metrics(), eng.step_log()
        requests = eng.request_log()
    finally:
        eng.stop()
    for p, n, t in zip(prompts, budgets, toks):
        assert len(t) == n
        assert float(_greedy_gap(p, t).max()) <= TOL
    assert metrics["finished_total"] == 5
    waited = metrics["admissions_waited_total"]
    assert waited.get("window_32", 0) >= 1 and "full" not in waited
    group = metrics["kv_window_groups"][32]
    assert group["blocks_total"] == 10 and group["blocks_used"] == 0
    assert 0 < metrics["kv_window_pages_held_total"] \
        < metrics["kv_window_pages_whole_rings_total"]
    decodes = [s["attrs"] for step in steps for d in _walk(step)
               if d["name"] == "llm.step.decode" for s in _walk(d)
               if s["name"] == "runner.dispatch"]
    assert decodes and all(
        a["kv_window_pages_live"] <= a["kv_window_pages_held"]
        <= min(a["kv_window_pages_whole_rings"], 10) for a in decodes)
    assert any(a["kv_window_pages_held"] < a["kv_window_pages_whole_rings"]
               for a in decodes)
    # the third request was admitted after an earlier one had finished
    by_arrival = sorted(requests, key=lambda r: r["t_arrival"])
    assert len(by_arrival) == 5
    assert by_arrival[2]["t_admit"] >= min(
        r["t_finish"] for r in by_arrival[:2])


def test_a_request_no_group_could_ever_hold_is_refused_or_waits_alone():
    """Exact admission on both groups at the allocator the engine built:
    with the window group full, a request whose need is free again after
    ``free`` is admitted, and one the full group cannot hold is refused
    by THAT group."""
    from ray_tpu.serve.llm.kv_cache import OutOfKVBlocksError
    adapter = _adapter()
    cache = PagedKVCache(num_blocks=32, block_size=PAGE,
                         windows=adapter.page_windows, window_blocks=9)
    adapter.bind_cache(cache)
    prompts = token_prompts(3, adapter.vocab_size, (40, 10, 6))
    flax_seq(cache, "a", prompts[0], budget=8)        # 48 tokens: a ring
    flax_seq(cache, "b", prompts[1], budget=8)        # 18 tokens: 3 pages
    with pytest.raises(OutOfKVBlocksError, match="window-32 group") as e:
        flax_seq(cache, "c", prompts[2], budget=2)    # 1 page: none free
    assert e.value.group == 32
    cache.free("b")
    c = flax_seq(cache, "c", prompts[2], budget=2)
    assert len(cache.ring_table("c", 32)) == 1
    got = adapter.prefill([c])
    k = _smallthinker()
    want = np.asarray(k["ref"].forward(
        k["params"]["params"], np.asarray(prompts[2], np.int32),
        k["sizes"]))[-1]
    np.testing.assert_allclose(got[0], want, atol=TOL)
    with pytest.raises(OutOfKVBlocksError, match="KV blocks") as e:
        cache.allocate("d", 40 * PAGE)
    assert e.value.group == "full"


@pytest.mark.parametrize("what", ["enable_prefix_cache", "spec_k",
                                  "prefill_export", "adopt_request",
                                  "decode_window", "rollback", "export_kv",
                                  "import_kv", "prefill_from_a_prefix"])
def test_what_a_ring_cannot_do_is_still_refused(what):
    """Rings by need change none of it: sharing, rolling back and
    shipping are refused with their reasons by the engine and by the
    adapter, for a sequence with a whole ring and for one with a short
    ring alike."""
    adapter = _adapter()
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
    if what in ("prefill_export", "adopt_request"):
        eng = LLMEngine(adapter, EngineConfig(**config))
        try:
            with pytest.raises(WindowedPagesError, match="a ring is not"):
                if what == "prefill_export":
                    eng.prefill_export([1, 2, 3])
                else:
                    eng.adopt_request([1, 2, 3], 4,
                                      {"kind": "flax:smallthinker"})
        finally:
            eng.stop()
        return
    cache = PagedKVCache(num_blocks=64, block_size=PAGE,
                         windows=adapter.page_windows, max_sequences=3)
    adapter.bind_cache(cache)
    prompt, = token_prompts(53, adapter.vocab_size, (12,))
    seq = flax_seq(cache, "s0", prompt, budget=8)     # a short ring
    assert len(cache.ring_table("s0", 32)) == 3
    adapter.prefill([seq])
    with pytest.raises(WindowedPagesError, match="windowed page group"):
        if what == "decode_window":
            adapter.decode_window([seq], [[1, 2]])
        elif what == "rollback":
            adapter.rollback("s0", 1)
        elif what == "export_kv":
            adapter.export_kv("s0", len(prompt))
        elif what == "import_kv":
            adapter.import_kv("s0", len(prompt),
                              {"kind": "flax:smallthinker"})
        else:
            other = flax_seq(cache, "s1", prompt, budget=8)
            other.cached_tokens = 8
            adapter.prefill([other])


def test_smallthinker_streams_the_references_greedy_tokens_through_serve_run():
    """``serve.run`` of an ``LLMServer("smallthinker", ...)`` replica
    (tiny preset, weights from a seed, a stated window pool), clients on
    ``handle.stream``: tokens arrive in chunks and are, teacher-forced
    through the reference on the same weights, each its row's largest
    logit. Prompts of 40 (48 tokens more: the ring wraps) and 6 (12 more:
    a short ring)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    params = FlaxModelAdapter("smallthinker", seed=5).params
    prompts = token_prompts(59, 512, (40, 6))
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                 object_store_memory=128 * 1024 * 1024)
    try:
        dep = serve.deployment(name="smallthinker", num_replicas=1,
                               max_concurrent_queries=8)(LLMServer)
        h = serve.run(dep.bind("smallthinker", {"seed": 5}, {
            "num_blocks": 64, "block_size": PAGE, "max_seq_len": 128,
            "max_running": 2, "window_blocks": 9}), name="smallthinker",
            route_prefix="/smallthinker", http_port=None)
        for p, n in zip(prompts, (48, 12)):
            chunks = list(h.stream({"tokens": p, "max_new_tokens": n,
                                    "temperature": 0.0}))
            toks = [t for c in chunks for t in c["tokens"]]
            assert chunks[-1]["done"] and len(toks) == n
            assert len(chunks) >= 2, "tokens must stream"
            assert float(_greedy_gap(p, toks, params).max()) <= 1e-4
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


def test_the_new_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --rehearse`` of smallthinker_21b_a3b.
    serve_closed96_mix8k at tiny widths (a window of 32 under prompts of
    9-64 and outputs of 8-16: some requests wrap their ring, some hold a
    short one): the replica is deployed, every reachable shape warmed,
    the window served with no failed request, the checked requests (one
    that wrapped and one that did not among them) held to the reference
    in both page groups, the traced run's readers run; exit code 3."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "smallthinker_21b_a3b.serve_closed96_mix8k", "--seed",
         "4300000019", "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=280)
    text = out.stdout + out.stderr
    assert out.returncode == 3, text[-3000:]
    assert "rehearsal passed" in text and " 0 failed {}" in text
    assert text.count("pools fed the right tokens: True") == 4
    assert "[correct] verdict: True" in text
    assert "wrapped its ring: True" in text
    assert "wrapped its ring: False" in text
    assert "kv_ring_held_share.serve = " in text
