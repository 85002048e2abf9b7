"""LLM serving, a state that is a tail alone (LFM2-MoE: two rows of the
gated convolution's input a sequence for 6 layers of 8, K and V pages for
the other 2 with query/key norms, two dense layers and six routed ones)
held to the plain reference's logits (docs/LLM_SERVING.md). Tier-1,
CPU-only.

Logits are compared, not tokens. Everything here is float32 at 'highest'
on both sides (tests/conftest.py), so the served rows differ from the
reference's full forward by the order of sums only: 5e-5 absolute on
logits of spread ~0.16. A stale tail, a wrong slot or a wrong page moves
a row by 1e-3 or more (test_a_stale_slot_shows)."""

import os
import subprocess
import sys
import time

import numpy as np
import pytest
from llm_test_helpers import PAGE, drain_stream, flax_seq, token_prompts

from ray_tpu.serve.llm import (EngineConfig, LLMEngine, PagedKVCache,
                               SamplingParams)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-5
_L = {}


def _lfm2():
    if not _L:
        from benchmark.reference import lfm2_glue, lfm2_ref
        from ray_tpu.models.lfm2 import Lfm2Config
        cfg = Lfm2Config.tiny()
        _L.update(cfg=cfg, params=lfm2_glue.init_for(cfg, 7),
                  sizes=lfm2_ref.sizes_of(cfg), ref=lfm2_ref)
    return _L


def _adapter(max_running=4):
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    k = _lfm2()
    adapter = FlaxModelAdapter("lfm2", k["cfg"], k["params"])
    cache = PagedKVCache(num_blocks=64, block_size=PAGE)
    adapter.bind_cache(cache)
    adapter.bind_state(max_running)
    return adapter, cache


def _reference_rows(prompt, tokens, params=None):
    """The reference's logits after the prompt and after each of
    ``tokens`` but the last: what prefill and each decode returned."""
    k = _lfm2()
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


def _walk(span):
    yield span
    for child in span.get("children", ()):
        yield from _walk(child)


def test_pages_and_slots_serve_the_references_logits():
    """Rows of unequal length in ONE right-padded prefill step, decode in
    the full bucket (rows in slot order) and in a narrower one (by
    ``slots``); a sequence ends, a new one takes the slot it left and
    joins the others. A state WITHOUT a recurrence: ``counters()`` has
    the slots and the admissions and none of a recurrence's keys."""
    adapter, cache = _adapter()
    prompts = token_prompts(41, adapter.vocab_size, (70, 5, 33, 19))
    a, b, c = (flax_seq(cache, f"s{i}", p, budget=24)
               for i, p in enumerate(prompts[:3]))
    rows = _serve(adapter, [a, b, c], 4)
    slot_b = adapter._state["s1"]["slot"]
    adapter.release("s1")
    cache.free("s1")
    assert adapter.counters()["state_slots_in_use"] == 2
    rows_ac = _serve(adapter, [a, c], 3, rows=[rows[0], rows[2]])
    d = flax_seq(cache, "s3", prompts[3], budget=24)
    rows_d = _serve(adapter, [d], 0)
    assert adapter._state["s3"]["slot"] == slot_b      # the slot is reused
    rows_acd = _serve(adapter, [a, c, d], 3, rows=rows_ac + rows_d)
    for seq, got in zip((a, b, c, d), (rows_acd[0], rows[1], rows_acd[1],
                                       rows_acd[2])):
        _check(seq, got)
    assert {k[:2] for k in adapter._fns if isinstance(k, tuple)} == {
        (4, 128), (4, 1), (2, 1), (1, 32)}
    assert adapter._by_slot(4, 1) and not adapter._by_slot(2, 1)
    m = adapter.counters()
    # two admitted groups (3 + 1 sequences), and their host seconds
    assert m["state_admits_total"] == 4
    assert m["state_admit_seconds_total"] > 0
    assert m["state_slots_total"] == 4 and m["state_slots_in_use"] == 3
    assert adapter.has_state and adapter._decode_recurrence is None
    assert "recurrence_kernel_steps_total" not in m
    assert "kda_kernel_steps_total" not in m
    # six routed layers of eight experts, two a token
    total = np.asarray(m["expert_tokens_total"])
    assert total.shape == (6, 8)
    np.testing.assert_array_equal(total.sum(axis=1),
                                  2 * m["routed_tokens_total"])
    assert "zero_expert_tokens_total" not in m


def test_a_slot_another_has_just_left_gives_a_fresh_slots_logits():
    """A sequence admitted into the slot another has just left gives the
    logits it gives in a fresh slot: the tail is zeroed at admission."""
    adapter, cache = _adapter(max_running=1)
    first, second = token_prompts(43, adapter.vocab_size, (40, 21))
    a = flax_seq(cache, "a", first)
    _serve(adapter, [a], 3)
    adapter.release("a")
    cache.free("a")
    b = flax_seq(cache, "b", second)
    reused = _serve(adapter, [b], 3)[0]
    assert adapter._state["b"]["slot"] == 1
    fresh_adapter, fresh_cache = _adapter(max_running=1)
    c = flax_seq(fresh_cache, "c", second)
    fresh = _serve(fresh_adapter, [c], 3)[0]
    np.testing.assert_allclose(np.stack(reused), np.stack(fresh), atol=TOL)
    _check(b, reused)
    # what the benchmark's probe reads: the slot's rows, a layer each
    tail = adapter.state_of("b")["conv_tail"]
    assert tail.shape == (6, 2, 64)
    k = _lfm2()
    ids = np.asarray(list(second) + b.tokens, np.int32)
    _, want = k["ref"].forward(k["params"]["params"], ids, k["sizes"],
                               state_after=len(ids) - 1)
    np.testing.assert_allclose(tail, want, atol=TOL)


def test_a_stale_slot_shows(monkeypatch):
    """Without the zeroing at admission the second user of a slot starts
    from the first one's tail, and its logits are off by far more than
    the tolerance."""
    adapter, cache = _adapter(max_running=1)
    first, second = token_prompts(43, adapter.vocab_size, (40, 21))
    a = flax_seq(cache, "a", first)
    _serve(adapter, [a], 2)
    adapter.release("a")
    cache.free("a")
    monkeypatch.setattr(adapter, "_zero_fn",
                        lambda: lambda idx, *arrays: arrays)
    b = flax_seq(cache, "b", second)
    stale = _serve(adapter, [b], 1)[0]
    want = _reference_rows(second, b.tokens + [0])
    assert float(np.abs(np.stack(stale) - want[:2]).max()) > 20 * TOL


def test_three_times_max_running_requests_through_four_slots():
    """Through ``LLMEngine``: 12 short requests on 4 slots, so every slot
    changes hands and several prompts share a prefill step; tokens are the
    reference's greedy ones; the step log has ``runner.state.admit`` under
    ``llm.step.prefill``; a decode step's dispatch span says which
    attention and which routed product ran and names no recurrence, and
    its fetch span carries the rows multiplied beside the routed pairs."""
    adapter, _ = _adapter()
    lengths = (30, 9, 66, 12, 40, 5, 17, 23, 50, 8, 35, 14)
    prompts = token_prompts(47, adapter.vocab_size, lengths)
    eng = LLMEngine(adapter, EngineConfig(
        max_running=4, num_blocks=64, block_size=PAGE, max_seq_len=128,
        max_prefill_tokens=64))
    try:
        assert eng.metrics()["state_slots_total"] == 4
        sids = [eng.add_request(p, SamplingParams(max_new_tokens=5))
                for p in prompts]
        served = [drain_stream(eng, sid, timeout=240.0)[0] for sid in sids]
        deadline = time.time() + 10
        while eng.metrics()["state_slots_in_use"] and time.time() < deadline:
            time.sleep(0.05)
        m = eng.metrics()
        log = eng.step_log()
    finally:
        eng.stop()
    for p, toks in zip(prompts, served):
        want = _reference_rows(p, toks)
        gap = want.max(-1) - want[np.arange(5), toks]
        assert float(gap.max()) <= TOL
    assert m["state_slots_in_use"] == 0
    assert m["state_admits_total"] == 12
    assert 0 < m["state_admit_seconds_total"] < 60
    assert "recurrence_kernel_steps_total" not in m
    prefills = [s for step in log for s in _walk(step)
                if s["name"] == "llm.step.prefill"]
    assert sum(s["attrs"]["n"] for s in prefills) == 12
    assert max(s["attrs"]["n"] for s in prefills) >= 2     # batched
    admits = [c["name"] for s in prefills for c in _walk(s)]
    assert admits.count("runner.state.admit") >= 3
    decodes = [d for step in log for d in _walk(step)
               if d["name"] == "llm.step.decode"]
    said = {(s["attrs"].get("recurrence"), s["attrs"].get("attention"),
             s["attrs"].get("expert_product"))
            for d in decodes for s in _walk(d)
            if s["name"] == "runner.dispatch"}
    assert said == {(None, "gather", "touched_kernel")}
    fetched = [s["attrs"] for d in decodes for s in _walk(d)
               if s["name"] == "runner.fetch"
               and "expert_rows_multiplied" in s["attrs"]]
    assert fetched and all(
        a["expert_rows_multiplied"] >= a["expert_tokens"] > 0
        for a in fetched)


@pytest.mark.parametrize("what", [
    "enable_prefix_cache", "spec_k", "decode_window", "rollback",
    "export_kv", "import_kv"])
def test_refuses_what_needs_a_snapshot_of_the_state(what):
    """Dropping cached tokens, sharing them by page and shipping them as
    pages each need the tail as it was at that token: a state without a
    recurrence is refused as one with."""
    from ray_tpu.serve.llm.model_runner import RecurrentStateError
    adapter, cache = _adapter()
    base = dict(max_running=2, num_blocks=64, block_size=PAGE,
                max_seq_len=128)
    with pytest.raises(RecurrentStateError, match="state") as err:
        if what == "enable_prefix_cache":
            LLMEngine(adapter, EngineConfig(enable_prefix_cache=True,
                                            **base))
        elif what == "spec_k":
            LLMEngine(adapter, EngineConfig(spec_k=2, **base))
        else:
            seq = flax_seq(cache, "a", [1, 2, 3])
            adapter.prefill([seq])
            {"decode_window": lambda: adapter.decode_window([seq], [[1, 2]]),
             "rollback": lambda: adapter.rollback("a", 1),
             "export_kv": lambda: adapter.export_kv("a", 3),
             "import_kv": lambda: adapter.import_kv("a", 3, {}),
             }[what]()
    assert "snapshot" in str(err.value)


def test_make_adapter_knows_the_kinds_it_names():
    """The factory's list of kinds is the one tuple: its docstring and
    its error are made from it, and the adapter builds each of them."""
    from ray_tpu.serve.llm import model_runner as mr
    assert mr.FLAX_KINDS == ("gpt2", "llama", "kimi_linear", "kimi_k2",
                             "laguna", "longcat_flash", "smallthinker",
                             "jamba", "lfm2")
    for kind in mr.FLAX_KINDS:
        assert mr.make_adapter(kind).kind == kind
    adapter = mr.make_adapter("lfm2")
    assert adapter.has_state and adapter.page_windows == ()
    assert adapter.greedy_on_device and adapter.decode_ahead
    with pytest.raises(ValueError) as err:
        mr.make_adapter("lfm3")
    assert all(kind in str(err.value) for kind in ("toy",) + mr.FLAX_KINDS)
    with pytest.raises(ValueError, match="unknown model kind"):
        mr.FlaxModelAdapter("lfm3")


@pytest.mark.parametrize("together", (False, True))
def test_lfm2_streams_the_references_greedy_tokens_through_serve_run(
        together):
    """``serve.run`` of an ``LLMServer("lfm2", ...)`` replica (tiny
    preset, weights from a seed), clients on ``handle.stream``: tokens
    arrive in chunks and are, teacher-forced through the reference on the
    same weights, each its row's largest logit. ``together``: the second
    client asks while the first is answered, so its prompt's program is
    left in flight behind a decode step and the step after feeds its
    first token on the device; one after the other, a prompt is in flight
    alone. Either way every prompt was."""
    import threading
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    params = FlaxModelAdapter("lfm2", seed=5).params
    prompts = token_prompts(59, 512, (40, 6))
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True,
                 object_store_memory=128 * 1024 * 1024)
    try:
        dep = serve.deployment(name="lfm2", num_replicas=1,
                               max_concurrent_queries=8)(LLMServer)
        h = serve.run(dep.bind("lfm2", {"seed": 5}, {
            "num_blocks": 64, "block_size": PAGE, "max_seq_len": 256,
            "max_running": 2}), name="lfm2", route_prefix="/lfm2",
            http_port=None)
        # (together: an answer long enough to be still going when the
        # second question has been admitted)
        budgets = (200 if together else 24, 12)
        got = {0: [], 1: []}

        def ask(i, p, n):
            for chunk in h.stream({"tokens": p, "max_new_tokens": n,
                                   "temperature": 0.0}):
                got[i].append(chunk)
        clients = [threading.Thread(target=ask, args=(i, p, n))
                   for i, (p, n) in enumerate(zip(prompts, budgets))]
        for c in clients:
            c.start()
            while together and not got[0] and c.is_alive():
                time.sleep(0.005)   # the first is being answered
            if not together:
                c.join(timeout=240)
        for c in clients:
            c.join(timeout=240)
        for i, (p, n) in enumerate(zip(prompts, budgets)):
            chunks = got[i]
            toks = [t for c in chunks for t in c["tokens"]]
            assert chunks[-1]["done"] and len(toks) == n
            assert len(chunks) >= 2, "tokens must stream"
            want = _reference_rows(p, toks, params)
            gap = want.max(-1) - want[np.arange(n), toks]
            assert float(gap.max()) <= 1e-4
        m = ray_tpu.get(h.options("__llm_metrics__").remote(), timeout=60.0)
        assert m["prefill_steps_ahead_total"] == m["prefill_steps_total"] == 2
        assert m["decode_steps_ahead_total"] >= 20
        prefills = [s for step in m["step_log"] for s in step["children"]
                    if s["name"] == "llm.step.prefill"]
        assert [s["attrs"]["ahead"] for s in prefills] == [True, True]
        if together:    # the second prompt flew behind a decode step
            step = next(st for st in m["step_log"]
                        if prefills[1] in st["children"])
            assert step["children"][0]["name"] == "llm.step.decode"
            assert m["decode_tokens_discarded_total"] == 0
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


def test_the_new_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --rehearse`` of lfm2_8b_a1b.serve_closed256_1k
    at tiny widths: the replica is deployed, every reachable shape warmed,
    the window served with no failed request, the checked requests' logits
    and slot tails held to the reference, the traced run's readers run;
    exit code 3."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "lfm2_8b_a1b.serve_closed256_1k", "--seed", "5300000019",
         "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=280)
    text = out.stdout + out.stderr
    assert out.returncode == 3, text[-3000:]
    assert "rehearsal passed" in text and " 0 failed {}" in text
    assert text.count("slot and pages fed the right tokens: True") == 4
    assert "[correct] verdict: True" in text
    assert "state_admit_ms_per_request.serve = " in text
    assert "lfm2_expert_rows_over_pairs.serve = " in text
