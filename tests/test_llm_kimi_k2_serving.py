"""LLM serving, a model that states ONE latent page pool and no
per-sequence state (Kimi-K2: rotary MLA in every layer, routed experts),
held to the plain reference's logits (docs/LLM_SERVING.md). Tier-1,
CPU-only.

Logits are compared, not tokens. Everything here is float32 at 'highest'
on both sides (tests/conftest.py; the replica of the cluster test runs
float32 on the CPU), so the served rows differ from the reference's full
forward by the order of sums only: 5e-5 absolute on logits of spread
~0.16. A row rotated at a wrong position or read from a wrong page moves
a logit by 1e-2 or more.

What ``RecurrentStateError`` refuses a model with state, this one may
use, because every cached token of it is a page row written at its
absolute position: a shared prefix (``enable_prefix_cache``), a verified
window and its rollback (``decode_window`` / ``rollback``, ``spec_k``),
shipped pages (``export_kv`` / ``import_kv``)."""

import os
import subprocess
import sys

import numpy as np
import pytest
from llm_test_helpers import PAGE, drain_stream, flax_seq, token_prompts

from ray_tpu.serve.llm import (EngineConfig, LLMEngine, PagedKVCache,
                               SamplingParams)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K2_TOL = 5e-5
_K2 = {}


def _k2():
    if not _K2:
        from benchmark.reference import kimi_k2_glue, kimi_k2_ref
        from ray_tpu.models.kimi_k2 import KimiK2Config
        cfg = KimiK2Config.tiny()
        _K2.update(cfg=cfg, params=kimi_k2_glue.init_for(cfg, 7),
                   sizes=kimi_k2_ref.sizes_of(cfg), ref=kimi_k2_ref)
    return _K2


def _adapter(blocks=64):
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    k = _k2()
    adapter = FlaxModelAdapter("kimi_k2", k["cfg"], k["params"])
    cache = PagedKVCache(num_blocks=blocks, block_size=PAGE)
    adapter.bind_cache(cache)
    return adapter, cache


def _reference_rows(prompt, tokens, params=None):
    """The reference's logits after the prompt and after each of
    ``tokens`` but the last: what prefill and each decode returned."""
    k = _k2()
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
    np.testing.assert_allclose(np.stack(rows), want[:len(rows)],
                               atol=K2_TOL)


def _greedy_gap(prompt, served, params=None):
    """How far each served token's reference logit lies under its row's
    maximum, teacher-forced."""
    want = _reference_rows(prompt, served, params)
    return want.max(-1) - want[np.arange(len(served)), served]


def test_k2_prefill_then_decode_through_the_pool_serve_the_references_logits():
    """Rows of unequal length in one batch (70, 5 and 33 tokens: a
    bucket of 4 x 128), decode in a bucket of 4; one sequence ends and
    the rest go on in a bucket of 2; a new one joins them. No state
    slot is taken: the model names none."""
    adapter, cache = _adapter()
    assert not adapter.has_state and adapter.greedy_on_device
    assert adapter._arrays["kv_pages"].shape == (3, 64, PAGE, 128)
    prompts = token_prompts(41, adapter.vocab_size, (70, 5, 33, 19))
    a, b, c = (flax_seq(cache, f"s{i}", p, budget=24)
               for i, p in enumerate(prompts[:3]))
    rows = _serve(adapter, [a, b, c], 4)
    adapter.release("s1")
    cache.free("s1")
    rows_ac = _serve(adapter, [a, c], 3, rows=[rows[0], rows[2]])
    d = flax_seq(cache, "s3", prompts[3], budget=24)
    rows_acd = _serve(adapter, [a, c, d], 3,
                      rows=rows_ac + _serve(adapter, [d], 0))
    for seq, got in zip((a, b, c, d), (rows_acd[0], rows[1], rows_acd[1],
                                       rows_acd[2])):
        _check(seq, got)
    assert {k[:2] for k in adapter._fns if isinstance(k, tuple)} == {
        (4, 128), (4, 1), (2, 1), (1, 32)}
    counters = adapter.counters()
    assert counters["state_slots_total"] == 0
    assert np.shape(counters["expert_tokens_total"]) == (2, 4)
    # the greedy tokens found on the device are the logits' argmax
    e = flax_seq(cache, "s4", prompts[1], budget=4)
    assert adapter.prefill([e], tokens_only=True).tolist() \
        == [int(rows[1][0].argmax())]


def test_k2_engine_shares_a_prefix_and_says_what_its_steps_read():
    """Through ``LLMEngine`` with ``enable_prefix_cache``: the second and
    third prompt share 24 tokens (three pages) with the first, so their
    prefill starts at a non-zero length, rotated from there; every served
    token is the reference's greedy one. The dispatch spans carry the
    prompt's and the bucket's tokens, and a decode step's live tokens."""
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
        assert float(_greedy_gap(p, toks).max()) <= K2_TOL
    assert m["cache_hit_tokens_total"] == 24 + 32

    def walk(span):
        yield span
        for child in span.get("children", ()):
            yield from walk(child)
    dispatch = {"llm.step.prefill": [], "llm.step.decode": []}
    fetched = []
    for step in log:
        for s in walk(step):
            if s["name"] in dispatch:
                dispatch[s["name"]] += [
                    d["attrs"] for d in walk(s)
                    if d["name"] == "runner.dispatch"]
            if s["name"] == "llm.step.prefill":
                fetched += [d["attrs"] for d in walk(s)
                            if d["name"] == "runner.fetch"]
    pre = dispatch["llm.step.prefill"]
    # which product a prompt's routed experts run (buckets of 64 and 16
    # rows go whole through the touched experts), and the rows that
    # went through an expert: a touched expert multiplies all of them
    assert [d["expert_product"] for d in pre] == ["touched_kernel"] * 3
    assert [f["expert_rows_multiplied"] for f in fetched] == [
        f["experts_touched"] * rows for f, rows in zip(fetched, (64, 16, 16))]
    assert all(f["expert_rows_multiplied"] >= f["expert_tokens"] > 0
               for f in fetched)
    # (the third shares four whole pages with the first: 24 + 8 tokens)
    assert [d["prompt_tokens"] for d in pre] == [33, 14, 15]
    assert [d["padded_tokens"] for d in pre] == [64, 16, 16]
    dec = dispatch["llm.step.decode"]
    assert dec and all(
        d["live_tokens"] > 24 and d["kv_pages_padded"] == 32
        and d["kv_pages_live"] == -(-d["live_tokens"] // PAGE)
        # (off the chip a latent decode step gathers, and says so)
        and d["attention"] == "gather" for d in dec)


def test_k2_decode_window_and_rollback_match_the_plain_loop():
    """One batched ``decode_window`` (``llm_verify_b2_s8``) gives, at
    position j, the logits of the tokens up to j; after ``rollback`` of
    the rejected positions the plain decode loop goes on as if they had
    never been written (their rows are written again, rotated at the
    same positions)."""
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
    assert (2, 8, True) in adapter._fns
    for p, win, got in zip(prompts, windows, rows):
        np.testing.assert_allclose(
            got, _reference_rows(p, win + [0])[1:], atol=K2_TOL)
    for s, w in zip(seqs, want):
        adapter.rollback(s.seq_id, 2)
        s.tokens = w[:2]
    rest = _serve(adapter, seqs, 3, rows=[[r[1]] for r in rows])
    for s, w in zip(seqs, want):
        assert s.tokens == w
    _check(seqs[0], [first[0][0], rows[0][0]] + rest[0])


def test_k2_exported_pages_go_on_in_another_replica():
    """``export_kv`` ships the prompt's latent pages, every layer's, as
    one array a pool; ``import_kv`` binds them to other pages of another
    adapter, whose decode goes on with the reference's logits."""
    src, src_cache = _adapter()
    dst, dst_cache = _adapter()
    dst_cache.allocate("taken", 3 * PAGE)
    prompt, = token_prompts(37, src.vocab_size, (12,))
    a = flax_seq(src_cache, "a", prompt)
    first = src.prefill([a])
    blob = src.export_kv("a", len(prompt))
    assert blob["kind"] == "flax:kimi_k2" and blob["n"] == 12
    assert blob["pages"]["kv_pages"].shape == (3, 2, PAGE, 128)
    b = flax_seq(dst_cache, "b", prompt)
    assert dst_cache.block_table("b") != src_cache.block_table("a")
    dst.import_kv("b", len(prompt), blob)
    _check(b, _serve(dst, [b], 4, rows=[[first[0]]])[0])
    with pytest.raises(ValueError, match="does not match"):
        dst.import_kv("b", 12, dict(blob, kind="flax:gpt2"))


def test_k2_engine_accepts_what_a_model_with_state_is_refused():
    """``spec_k`` with a draft that proposes the target's own greedy
    tokens half of the time: the engine verifies windows in one step and
    serves the reference's greedy tokens."""
    adapter, _ = _adapter()
    prompt, = token_prompts(53, adapter.vocab_size, (20,))
    eng = LLMEngine(adapter, EngineConfig(
        max_running=2, num_blocks=64, block_size=PAGE, max_seq_len=128,
        spec_k=2, draft_model="toy",
        draft_model_config={"vocab_size": adapter.vocab_size}))
    try:
        sid = eng.add_request(prompt, SamplingParams(max_new_tokens=6))
        toks = drain_stream(eng, sid, timeout=180.0)[0]
    finally:
        eng.stop()
    assert len(toks) == 6
    assert float(_greedy_gap(prompt, toks).max()) <= K2_TOL


def test_k2_streams_the_references_greedy_tokens_through_serve_run():
    """``serve.run`` of an ``LLMServer("kimi_k2", ...)`` replica (tiny
    preset, weights from a seed), clients on ``handle.stream``: tokens
    stream (the first chunk reaches the client before the last token was
    made: it is not ``done`` and holds fewer than all 48) and are,
    teacher-forced through the reference on the same weights, each its
    row's largest logit. How many chunks the 48 tokens come in is not
    asserted: a chunk is one round trip of the client's poll, and a
    client that shares its cores with five other test workers decides
    that count, not the server (the driver's runs of PRs 35 and 45)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    params = FlaxModelAdapter("kimi_k2", seed=5).params
    prompts = token_prompts(59, 512, (40, 13))
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                 object_store_memory=128 * 1024 * 1024)
    try:
        dep = serve.deployment(name="k2", num_replicas=1,
                               max_concurrent_queries=8)(LLMServer)
        h = serve.run(dep.bind("kimi_k2", {"seed": 5}, {
            "num_blocks": 64, "block_size": PAGE, "max_seq_len": 128,
            "max_running": 2}), name="k2", route_prefix="/k2",
            http_port=None)
        for p in prompts:
            chunks = list(h.stream({"tokens": p, "max_new_tokens": 48,
                                    "temperature": 0.0}))
            toks = [t for c in chunks for t in c["tokens"]]
            assert chunks[-1]["done"] and len(toks) == 48
            assert not chunks[0]["done"] \
                and len(chunks[0]["tokens"]) < 48, "tokens must stream"
            assert float(_greedy_gap(p, toks, params).max()) <= 1e-4
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


def test_the_new_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --rehearse`` of kimi_k2_7_code.
    serve_closed32_ctx8k at tiny widths: the replica is deployed, every
    reachable shape warmed (one prefill program), the window served with
    no failed request, four requests held to the reference, the traced
    run's readers run; exit code 3."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "kimi_k2_7_code.serve_closed32_ctx8k", "--seed", "3500000019",
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
