"""What a prefill step left in flight records of itself (docs/TRACING.md,
"Step spans"): the benchmark's readers of a prompt's program take, under
ONE ``llm.step.prefill`` span, its ``runner.dispatch`` (the prompts' real
and padded tokens) and its ``runner.fetch`` (what the program counted of
its routed experts). The fetch comes a step later than the dispatch, so
its record is hung under the span that dispatched the program, once, and
the counts are counted once. Tier-1, CPU-only: the tiny Kimi-K2 (routed
experts, one latent pool) through an engine that leaves its prompts in
flight and one held synchronous (``Synchronous`` withholds the adapter's
``decode_ahead``)."""

import time

import pytest
from llm_test_helpers import (PAGE, Synchronous, drain_stream,
                              token_prompts)

from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams

# (prompt tokens, max_new_tokens): a prompt a step, slots taken again
SHAPES = ((30, 2), (9, 3), (20, 1), (12, 4), (41, 2), (17, 1), (25, 3),
          (50, 2))
_RUNS = {}


def _walk(span):
    yield span
    for child in span["children"]:
        yield from _walk(child)


def _named(span, name):
    return [s for s in _walk(span) if s["name"] == name]


def _runs():
    """{synchronous?: (step log, what ``counters()`` counted of the run,
    ``metrics()``)} of the same requests through one adapter."""
    if _RUNS:
        return _RUNS
    from benchmark.reference import kimi_k2_glue as glue
    from ray_tpu.models.kimi_k2 import KimiK2Config
    from ray_tpu.serve.llm.model_runner import FlaxModelAdapter
    cfg = KimiK2Config.tiny()
    adapter = FlaxModelAdapter("kimi_k2", cfg, glue.init_for(cfg, 7))
    prompts = token_prompts(53, adapter.vocab_size, [n for n, _ in SHAPES])
    for synchronous in (True, False):
        eng = LLMEngine(Synchronous(adapter) if synchronous else adapter,
                        EngineConfig(max_running=4, num_blocks=96,
                                     block_size=PAGE, max_seq_len=128,
                                     max_prefill_tokens=8))
        try:
            before = adapter.counters()
            sids = [eng.add_request(p, SamplingParams(max_new_tokens=m))
                    for p, (_, m) in zip(prompts, SHAPES)]
            tokens = [drain_stream(eng, sid, timeout=240.0)[0]
                      for sid in sids]
            deadline = time.time() + 30
            while (eng.in_flight() or adapter._state) \
                    and time.time() < deadline:
                time.sleep(0.02)
        finally:
            eng.stop()
        after = adapter.counters()
        counted = {
            "expert_tokens": sum(map(sum, after["expert_tokens_total"]))
            - sum(map(sum, before.get("expert_tokens_total", [[0]]))),
            "routed_tokens": after["routed_tokens_total"]
            - before.get("routed_tokens_total", 0)}
        _RUNS[synchronous] = (eng.step_log(), counted, eng.metrics(), tokens)
    return _RUNS


@pytest.mark.parametrize("synchronous", (False, True))
def test_every_prefill_span_holds_its_dispatch_and_its_fetch(synchronous):
    """One ``runner.dispatch`` with ``prompt_tokens`` / ``padded_tokens``
    and one ``runner.fetch`` with ``expert_tokens`` / ``experts_touched``
    under every ``llm.step.prefill``, which says ``ahead``; left in flight
    the fetch lies after its span's end, inside the next step's decode."""
    log, _, metrics, _ = _runs()[synchronous]
    spans = [p for step in log for p in _named(step, "llm.step.prefill")]
    assert len(spans) == len(SHAPES) == metrics["prefill_steps_total"]
    assert [p["attrs"]["ahead"] for p in spans] \
        == [not synchronous] * len(SHAPES)
    assert metrics["prefill_steps_ahead_total"] \
        == (0 if synchronous else len(SHAPES))
    for p, (n, _) in zip(spans, SHAPES):
        (d,), (f,) = _named(p, "runner.dispatch"), _named(p, "runner.fetch")
        assert d["attrs"]["prompt_tokens"] == n
        assert d["attrs"]["padded_tokens"] >= n
        assert f["attrs"]["expert_tokens"] > 0
        assert 0 < f["attrs"]["experts_touched"]
        assert (f["t0"] >= p["t1"]) == (not synchronous)


def test_a_prompts_fetch_is_recorded_and_counted_once():
    """Every ``runner.fetch`` of the log is a decode step's (under its
    ``llm.step.decode``) or a prompt's (under its ``llm.step.prefill``),
    never both; summed, their ``expert_tokens`` are what ``counters()``
    counted, and that is what the synchronous engine counted of the same
    requests."""
    runs = _runs()
    assert runs[False][3] == runs[True][3]      # the same tokens served
    assert runs[False][1] == runs[True][1]
    for synchronous in (False, True):
        log, counted, metrics, _ = runs[synchronous]
        fetches = [f for step in log for f in _named(step, "runner.fetch")]
        assert len({id(f) for f in fetches}) == len(fetches)
        under = {"llm.step.decode": 0, "llm.step.prefill": 0}
        for step in log:
            for name in under:
                for span in _named(step, name):
                    under[name] += len(_named(span, "runner.fetch"))
        assert under["llm.step.prefill"] == len(SHAPES)
        assert sum(under.values()) == len(fetches)
        # a decode span holds at most its own step's fetch
        assert all(len(_named(d, "runner.fetch")) <= 1 for step in log
                   for d in _named(step, "llm.step.decode"))
        assert sum(f["attrs"]["expert_tokens"] for f in fetches) \
            == counted["expert_tokens"]


@pytest.mark.parametrize("synchronous", (False, True))
def test_runner_ms_counts_a_late_fetch_in_the_step_that_waited(synchronous):
    """``llm.step`` says ``runner_ms`` by the clock: the ``runner.*``
    spans that ran between its ends, whichever tree holds their records.
    A prompt left in flight is dispatched in one step and fetched in the
    next: by the tree the first step holds both records, by the clock
    each step holds what it ran, and over the log both sums are every
    runner span's time, once."""
    log = _runs()[synchronous][0]

    def ms(span):
        return (span["t1"] - span["t0"]) * 1e3

    def runner(step):
        return [s for s in _walk(step) if s["name"].startswith("runner.")]

    spans = [s for step in log for s in runner(step)]
    assert sum(step["attrs"]["runner_ms"] for step in log) \
        == pytest.approx(sum(map(ms, spans)))
    for step in log:
        ran = [s for s in spans
               if step["t0"] <= s["t0"] and s["t1"] <= step["t1"]]
        assert step["attrs"]["runner_ms"] == pytest.approx(
            sum(map(ms, ran)), abs=1e-6)
        assert step["attrs"]["runner_ms"] <= ms(step)
    # every prompt's fetch is late: in the tree of the step that
    # dispatched it, in the ``runner_ms`` of a later one
    late = [s for step in log for s in runner(step)
            if s["t0"] >= step["t1"]]
    assert len(late) == (0 if synchronous else len(SHAPES))
    assert all(s["name"] == "runner.fetch" for s in late)
