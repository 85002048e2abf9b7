"""LongCat-Flash through the serving path at a tiny size on the CPU: the
adapter over a paged pool of TWO latent sublayers a layer, the engine
with a shared prefix and with speculative windows, ``serve.run``, and the
benchmark cell's rehearsal; all against the plain reference
(benchmark/reference/longcat_flash_ref.py), logits and never sampled
tokens.

Tolerance: float32 with 'highest' products on both sides
(tests/conftest.py); 5e-5 absolute on logits of spread ~0.2, as
tests/test_llm_kimi_k2_serving.py."""

import os
import subprocess
import sys

import numpy as np
import pytest
from llm_test_helpers import PAGE, drain_stream, flax_seq, token_prompts

from ray_tpu.serve.llm import (EngineConfig, LLMEngine, PagedKVCache,
                               SamplingParams)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-5
_LC = {}


def _lc():
    if not _LC:
        from benchmark.reference import longcat_flash_glue, longcat_flash_ref
        from ray_tpu.models.longcat_flash import LongcatFlashConfig
        cfg = LongcatFlashConfig.tiny()
        _LC.update(cfg=cfg, params=longcat_flash_glue.init_for(cfg, 7),
                   sizes=longcat_flash_ref.sizes_of(cfg),
                   ref=longcat_flash_ref)
    return _LC


def _adapter(blocks=64):
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    k = _lc()
    adapter = FlaxModelAdapter("longcat_flash", k["cfg"], k["params"])
    cache = PagedKVCache(num_blocks=blocks, block_size=PAGE)
    adapter.bind_cache(cache)
    return adapter, cache


def _reference_rows(prompt, tokens, params=None):
    """The reference's logits after the prompt and after each of
    ``tokens`` but the last: what prefill and each decode returned."""
    k = _lc()
    ids = np.asarray(list(prompt) + list(tokens[:-1]), np.int32)
    rows = k["ref"].forward((params or k["params"])["params"], ids,
                            k["sizes"])
    return np.asarray(rows[len(prompt) - 1:])


def _serve(adapter, seqs, n, rows=None):
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


def _check(seq, rows):
    want = _reference_rows(seq.prompt, seq.tokens + [0])
    np.testing.assert_allclose(np.stack(rows), want[:len(rows)], atol=TOL)


def _greedy_gap(prompt, served, params=None):
    want = _reference_rows(prompt, served, params)
    return want.max(-1) - want[np.arange(len(served)), served]


def _chosen(ids):
    """[layers, len(ids), top_k]: what the reference's routers chose."""
    k = _lc()
    return np.asarray(k["ref"].routing(k["params"]["params"],
                                       np.asarray(ids, np.int32),
                                       k["sizes"]))


def test_longcat_prefill_then_decode_serve_the_references_logits():
    """Rows of unequal length in one batch (70, 5 and 33 tokens: a bucket
    of 4 x 128), four decode steps in a bucket of 4, one sequence ends
    and the rest go on in a bucket of 2. The pool has 2 x 2 layers; no
    state slot is taken. The counters equal a count done by hand from the
    reference's routing of every token fed: the held experts' real tokens
    and the zero-compute assignments, which are in no expert's count."""
    adapter, cache = _adapter()
    cfg = adapter.cfg
    assert not adapter.has_state and adapter.greedy_on_device \
        and adapter.decode_ahead
    assert adapter._arrays["kv_pages"].shape == (4, 64, PAGE, 128)
    prompts = token_prompts(41, adapter.vocab_size, (70, 5, 33))
    a, b, c = (flax_seq(cache, f"s{i}", p, budget=24)
               for i, p in enumerate(prompts))
    rows = _serve(adapter, [a, b, c], 4)
    adapter.release("s1")
    cache.free("s1")
    rows_ac = _serve(adapter, [a, c], 3, rows=[rows[0], rows[2]])
    for seq, got in zip((a, b, c), (rows_ac[0], rows[1], rows_ac[1])):
        _check(seq, got)
    assert {k[:2] for k in adapter._fns if isinstance(k, tuple)} == {
        (4, 128), (4, 1), (2, 1)}
    counters = adapter.counters()
    # by hand: every token that went through a step (``_serve`` feeds
    # each token it appends)
    fed = [list(s.prompt) + s.tokens for s in (a, b, c)]
    chosen = np.concatenate([_chosen(ids) for ids in fed], axis=1)
    first, count = cfg.experts_held
    tokens = sum(len(ids) for ids in fed)
    assert counters["routed_tokens_total"] == tokens
    assert counters["zero_expert_tokens_total"] == (
        chosen >= cfg.n_routed_experts).sum(axis=(1, 2)).tolist()
    assert counters["expert_tokens_total"] == [
        [int((layer == first + e).sum()) for e in range(count)]
        for layer in chosen]
    zero = sum(counters["zero_expert_tokens_total"])
    real_here = int(np.sum(counters["expert_tokens_total"]))
    assert 0 < zero < cfg.moe_topk * tokens * cfg.num_layers
    assert zero + real_here < cfg.moe_topk * tokens * cfg.num_layers
    assert counters["state_slots_total"] == 0
    # the greedy tokens found on the device are the logits' argmax
    e = flax_seq(cache, "s4", prompts[1], budget=4)
    assert adapter.prefill([e], tokens_only=True).tolist() \
        == [int(rows[1][0].argmax())]


def test_longcat_engine_shares_a_prefix_and_its_steps_say_what_cost_nothing():
    """Through ``LLMEngine`` with ``enable_prefix_cache``: the second and
    third prompt share 24 tokens (three pages) of BOTH sublayers' rows
    with the first; every served token is the reference's greedy one.
    Each step's fetch span carries its zero-compute assignments beside
    the real ones, and they add up to ``counters()``' totals."""
    adapter, _ = _adapter()
    base, t1, t2 = token_prompts(47, adapter.vocab_size, (24, 9, 14))
    prompts = [base + t1, base + t2, base + t1 + t2]
    eng = LLMEngine(adapter, EngineConfig(
        max_running=2, num_blocks=64, block_size=PAGE, max_seq_len=128,
        max_prefill_tokens=64, enable_prefix_cache=True))
    try:
        served = []
        for p in prompts:       # one after another: the tree is filled
            sid = eng.add_request(p, SamplingParams(max_new_tokens=5))
            served.append(drain_stream(eng, sid, timeout=180.0)[0])
        m = eng.metrics()
        log = eng.step_log()
    finally:
        eng.stop()
    for p, toks in zip(prompts, served):
        assert float(_greedy_gap(p, toks).max()) <= TOL
    assert m["cache_hit_tokens_total"] == 24 + 32

    def walk(span):
        yield span
        for child in span.get("children", ()):
            yield from walk(child)
    fetched = [s["attrs"] for step in log for s in walk(step)
               if s["name"] == "runner.fetch"
               and "zero_expert_tokens" in s.get("attrs", {})]
    assert fetched and all(
        0 <= f["zero_expert_tokens"]
        <= adapter.cfg.moe_topk * f["routed_tokens"] * 2
        and f["expert_rows_multiplied"] >= f["expert_tokens"]
        for f in fetched)
    assert sum(f["routed_tokens"] for f in fetched) \
        == m["routed_tokens_total"] == 33 + 14 + 15 + 3 * 4
    assert sum(f["zero_expert_tokens"] for f in fetched) \
        == sum(m["zero_expert_tokens_total"]) > 0
    assert sum(f["expert_tokens"] for f in fetched) \
        == int(np.sum(m["expert_tokens_total"]))


def test_longcat_decode_window_rollback_and_shipped_pages():
    """One batched ``decode_window`` gives, at position j, the logits of
    the tokens up to j; after ``rollback`` the plain loop goes on; and
    ``export_kv`` ships the prompt's rows of all four sublayers, with
    which another adapter's decode goes on."""
    adapter, cache = _adapter()
    prompts = token_prompts(43, adapter.vocab_size, (12, 5))
    seqs = [flax_seq(cache, f"s{i}", p) for i, p in enumerate(prompts)]
    first = _serve(adapter, seqs, 0)
    want = []
    for p, r in zip(prompts, first):    # the reference's greedy five
        toks = [int(r[0].argmax())]
        for _ in range(4):
            toks.append(int(_reference_rows(p, toks + [0])[-1].argmax()))
        want.append(toks)
    for s, w in zip(seqs, want):
        s.tokens = [w[0]]
    wrong = [(w[2] + 1) % adapter.vocab_size for w in want]
    windows = [[w[0], w[1], x, w[3]] for w, x in zip(want, wrong)]
    rows = adapter.decode_window(seqs, windows)
    for p, win, got in zip(prompts, windows, rows):
        np.testing.assert_allclose(
            got, _reference_rows(p, win + [0])[1:], atol=TOL)
    for s, w in zip(seqs, want):
        adapter.rollback(s.seq_id, 2)
        s.tokens = w[:2]
    rest = _serve(adapter, seqs, 3, rows=[[r[1]] for r in rows])
    for s, w in zip(seqs, want):
        assert s.tokens == w
    _check(seqs[0], [first[0][0], rows[0][0]] + rest[0])
    # shipped pages
    dst, dst_cache = _adapter()
    dst_cache.allocate("taken", 3 * PAGE)
    prompt, = token_prompts(37, adapter.vocab_size, (12,))
    a = flax_seq(cache, "a", prompt)
    got = adapter.prefill([a])
    blob = adapter.export_kv("a", len(prompt))
    assert blob["kind"] == "flax:longcat_flash" and blob["n"] == 12
    assert blob["pages"]["kv_pages"].shape == (4, 2, PAGE, 128)
    b = flax_seq(dst_cache, "b", prompt)
    dst.import_kv("b", len(prompt), blob)
    _check(b, _serve(dst, [b], 4, rows=[[got[0]]])[0])


def test_longcat_streams_the_references_greedy_tokens_through_serve_run():
    """``serve.run`` of an ``LLMServer("longcat_flash", ...)`` replica
    (tiny preset, weights from a seed), clients on ``handle.stream``:
    tokens arrive in chunks and are, teacher-forced through the reference
    on the same weights, each its row's largest logit; the replica's
    ``__llm_metrics__`` carries the zero-compute totals."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    params = FlaxModelAdapter("longcat_flash", seed=5).params
    prompts = token_prompts(59, 512, (40, 13))
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                 object_store_memory=128 * 1024 * 1024)
    try:
        dep = serve.deployment(name="lc", num_replicas=1,
                               max_concurrent_queries=8)(LLMServer)
        h = serve.run(dep.bind("longcat_flash", {"seed": 5}, {
            "num_blocks": 64, "block_size": PAGE, "max_seq_len": 128,
            "max_running": 2}), name="lc", route_prefix="/lc",
            http_port=None)
        for p in prompts:
            chunks = list(h.stream({"tokens": p, "max_new_tokens": 48,
                                    "temperature": 0.0}))
            toks = [t for c in chunks for t in c["tokens"]]
            assert chunks[-1]["done"] and len(toks) == 48
            assert not chunks[0]["done"] and len(chunks) >= 3, \
                "tokens must stream"
            assert float(_greedy_gap(p, toks, params).max()) <= 1e-4
        m = ray_tpu.get(h.options("__llm_metrics__").remote(), timeout=60.0)
        assert m["routed_tokens_total"] == 40 + 13 + 2 * 47
        assert len(m["zero_expert_tokens_total"]) == 2 \
            and sum(m["zero_expert_tokens_total"]) > 0
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


def test_the_new_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --rehearse`` of longcat_flash_omni.
    serve_closed64_ctx2k at tiny widths: the replica is deployed, every
    reachable shape warmed (one prefill program), the window served with
    no failed request, four requests held to the reference, the traced
    run's readers run (the zero-compute share among them); exit code
    3."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "longcat_flash_omni.serve_closed64_ctx2k", "--seed", "4100000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=280)
    text = out.stdout + out.stderr
    assert out.returncode == 3, text[-3000:]
    assert "rehearsal passed" in text and " 0 failed {}" in text
    assert "warmed 1 prefill and 4 decode row counts" in text
    correct = [ln for ln in text.splitlines() if "[correct] request" in ln]
    assert len(correct) == 4 and all(
        "pool fed the right tokens: True" in ln for ln in correct)
    assert "engine_step_wall_p50_ms.serve = " in text
    assert "longcat_zero_expert_share.serve = " in text
