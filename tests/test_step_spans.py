"""Step spans and counters inside the program (docs/TRACING.md, "Step
spans"): ``tracing.step_span`` itself, the ``llm.step`` trees and the
request log of the serve engine, the model runner's bucket names, the
feed's spans, and the names the kernels and the train step put on a
device trace."""

import dataclasses
import gc
import logging
import os
import sys
import threading
import time
from collections import deque

import numpy as np
import pytest

from ray_tpu._private import tracing
from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.serve.llm.model_runner import (FlaxModelAdapter, ToyAdapter,
                                            bucket_name)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:          # benchmark/harness/xplane reads captures
    sys.path.insert(0, ROOT)

ENGINE = dict(max_running=4, num_blocks=64, block_size=16, max_seq_len=128,
              max_prefill_tokens=64)


# spans that are over when they are known (tracing.step_event)
EVENTS = tracing.PROCESS_EVENTS


def make_adapter(kind):
    if kind == "ahead":     # states its cache: the engine looks ahead
        return FlaxModelAdapter("kimi_k2")
    return ToyAdapter() if kind == "toy" else FlaxModelAdapter("gpt2")


def serve(engine, prompts, new_tokens=5):
    """Every request to its end; the served tokens by request."""
    sids = [engine.add_request(p, SamplingParams(max_new_tokens=new_tokens),
                               request_id=f"r{i}")
            for i, p in enumerate(prompts)]
    served = []
    for sid in sids:
        cursor, tokens = 0, []
        while True:
            chunk = engine.poll(sid, cursor, max_wait_s=60.0)
            tokens += chunk["tokens"]
            cursor = chunk["cursor"]
            if chunk["done"]:
                assert not chunk.get("error"), chunk
                break
        served.append(tokens)
    deadline = time.time() + 10.0       # the last step closes its span
    while (engine.metrics()["finished_total"] < len(prompts)
           or engine.in_flight()) and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.05)
    return served


PROMPTS = [list(range(1, 10 + 3 * i)) for i in range(7)]


@pytest.fixture(scope="module", params=["toy", "flax", "toy-prefix", "ahead"])
def run(request):
    kind = request.param.split("-")[0]
    config = EngineConfig(enable_prefix_cache="prefix" in request.param,
                          **ENGINE)
    engine = LLMEngine(make_adapter(kind), config)
    # with the prefix cache on, the second round finds the first's pages
    served = serve(engine, PROMPTS) + serve(engine, PROMPTS)
    out = {"served": served, "metrics": engine.metrics(),
           "steps": engine.step_log(), "requests": engine.request_log(),
           "slow": engine.slow_steps(), "kind": request.param}
    yield out
    engine.stop()


def walk(span):
    yield span
    for child in span["children"]:
        yield from walk(child)


def ms(span):
    return (span["t1"] - span["t0"]) * 1e3


def spans_of(span):
    """A span's children that were spans; its events apart."""
    return ([c for c in span["children"] if c["name"] not in EVENTS],
            [c for c in span["children"] if c["name"] in EVENTS])


def test_step_trees_nest(run):
    assert run["steps"] and all(s["name"] == "llm.step" for s in run["steps"])
    assert [s["attrs"]["i"] for s in run["steps"]] == list(
        range(1, run["metrics"]["steps_total"] + 1))
    for step in run["steps"]:
        for span in walk(step):
            assert span["t0"] <= span["t1"]
            children, events = spans_of(span)
            end = span["t0"]
            if span["name"] == "llm.step.prefill" and span["attrs"]["ahead"]:
                # its program was left in flight: the fetch, made by the
                # step after, is hung under the span that dispatched it
                assert children.pop()["t0"] >= step["t1"]
                assert run["kind"] == "ahead"
            for child in children:              # in order, inside, disjoint
                assert end <= child["t0"] <= child["t1"] <= span["t1"]
                end = child["t1"]
            # an event is hung where it ends, a clock read or two from
            # the span's own: inside its step all the same
            for event in events:
                assert event["children"] == []
                assert step["t0"] <= event["t0"] <= event["t1"] \
                    <= step["t1"] + 1e-3
        names = [c["name"] for c in spans_of(step)[0]]
        assert names.count("llm.step.admit") == 1
        admit = spans_of(step)[0][names.index("llm.step.admit")]
        assert ("llm.step.prefill" in names) == (
            admit["attrs"]["admitted"] > 0)
    if run["kind"] == "flax":
        calls = [s for st in run["steps"] for s in walk(st)
                 if s["name"] in ("llm.step.decode", "llm.step.prefill")]
        assert all([c["name"] for c in spans_of(call)[0]] == [
            "runner.build_inputs", "runner.dispatch", "runner.fetch"]
            for call in calls)


def test_every_step_accounts_for_its_waits(run):
    """``cpu_ms`` and ``lock_wait_ms`` on every step, one
    ``llm.step.retire`` a commit that finished a request, with the wait
    for the step in flight and each sequence's release under it."""
    retired = waits = 0
    for step in run["steps"]:
        attrs = step["attrs"]
        assert attrs["lock_wait_ms"] >= 0.0
        assert 0.0 <= attrs["cpu_ms"] <= ms(step) + 5.0
        for commit in (s for s in walk(step)
                       if s["name"] == "llm.step.commit"):
            kids, _ = spans_of(commit)
            assert [k["name"] for k in kids] == (
                ["llm.step.retire"] if commit["attrs"]["finished"] else [])
            for retire in kids:
                n = retire["attrs"]["n"]
                assert n == commit["attrs"]["finished"]
                names = [k["name"] for k in spans_of(retire)[0]]
                waits += names.count("runner.wait")
                assert names[names.count("runner.wait"):] == [
                    "runner.release", "llm.step.finalize"] * n
                assert names.count("runner.wait") <= 1
                retired += n
        for fetch in (s for s in walk(step) if s["name"] == "runner.fetch"):
            # the wait apart from the copy, where the model states its
            # cache; the path that returns logits waits and copies in one
            assert ("wait_ms" in fetch["attrs"]) == (run["kind"] == "ahead")
            assert 0.0 <= fetch["attrs"].get("wait_ms", 0.0) \
                <= ms(fetch) + 0.1
    assert retired == run["metrics"]["finished_total"]
    # a request that finishes while a step is in flight waits for it
    assert (waits > 0) == (run["kind"] == "ahead")
    assert run["metrics"]["lock_wait_seconds_total"] >= 0.0
    assert run["metrics"]["slow_steps_total"] == len(run["slow"])


def test_span_counts_are_the_engines_counters(run):
    spans = [s for st in run["steps"] for s in walk(st)]
    m = run["metrics"]

    def total(name, attr):
        return sum(s["attrs"][attr] for s in spans if s["name"] == name)

    first_tokens = total("llm.step.prefill", "n")
    assert first_tokens == len(run["requests"]) == m["prefill_seqs_total"]
    assert total("llm.step.decode", "n") + first_tokens \
        == m["generated_tokens_total"] == sum(map(len, run["served"]))
    assert total("llm.step.decode", "n") == m["decode_rows_total"]
    cached = sum(r["n_cached"] for r in run["requests"])
    assert (cached > 0) == ("prefix" in run["kind"])
    assert total("llm.step.admit", "prefill_tokens") \
        == total("llm.step.prefill", "tokens") \
        == m["prefill_tokens_total"] \
        == sum(r["n_prompt"] for r in run["requests"]) - cached
    assert m["prefill_steps_total"] == sum(
        1 for s in spans if s["name"] == "llm.step.prefill")
    assert total("llm.step.commit", "finished") == m["finished_total"]
    assert 0 < m["runner_seconds_total"] <= m["step_seconds_total"]


def test_request_log_is_ordered_and_complete(run):
    log = run["requests"]
    assert len(log) == run["metrics"]["finished_total"] == 2 * len(PROMPTS)
    for r, served in zip(log, run["served"]):
        assert r["t_arrival"] <= r["t_admit"] <= r["t_prefill_start"] \
            <= r["t_first_token"] <= r["t_finish"]
        assert r["n_tokens"] == len(served) and r["finish_reason"] == "length"
    assert [r["request_id"] for r in log[:len(PROMPTS)]] == [
        f"r{i}" for i in range(len(PROMPTS))]
    assert [r["n_prompt"] for r in log[:len(PROMPTS)]] == list(
        map(len, PROMPTS))


def test_first_call_is_true_once_a_bucket(run):
    if run["kind"].startswith("toy"):
        assert run["metrics"]["bucket_first_calls_total"] == 0
        return
    dispatches = [s["attrs"] for st in run["steps"] for s in walk(st)
                  if s["name"] == "runner.dispatch"]
    buckets = {(d["B"], d["S"]) for d in dispatches}
    firsts = [(d["B"], d["S"]) for d in dispatches if d["first_call"]]
    assert sorted(firsts) == sorted(buckets)
    assert run["metrics"]["bucket_first_calls_total"] == len(buckets)


@pytest.mark.parametrize("B,S,full,name", [
    (2, 1, False, "llm_decode_b2"), (1, 32, False, "llm_prefill_b1_s32"),
    (2, 8, True, "llm_verify_b2_s8")])
def test_each_buckets_module_carries_its_name(B, S, full, name):
    import jax.numpy as jnp
    from ray_tpu.serve.llm.kv_cache import PagedKVCache
    assert bucket_name(B, S, full) == name
    adapter = FlaxModelAdapter("gpt2")
    adapter.bind_cache(PagedKVCache(16, 16))
    lowered = adapter._step_fn(B, S, full).lower(
        adapter.params, jnp.zeros((B, S), jnp.int32), adapter.k_pages,
        adapter.v_pages, jnp.zeros((B, adapter.nb_max), jnp.int32),
        jnp.zeros((B,), jnp.int32), jnp.zeros((B, S), bool))
    assert f"module @jit_{name} " in lowered.as_text()


class Stalling(ToyAdapter):
    """A toy adapter whose ``at``-th decode first runs ``stall``."""

    def __init__(self, stall, at=2):
        super().__init__()
        self.stall, self.at, self.calls = stall, at, 0
        self.entered = threading.Event()

    def decode(self, seqs):
        self.calls += 1
        if self.calls == self.at:
            self.entered.set()
            self.stall()
        return super().decode(seqs)


@pytest.mark.parametrize("kind", ["toy", "flax"])
def test_tracing_off_leaves_the_logs_empty_and_the_tokens_equal(
        kind, monkeypatch):
    def once():
        # the toy's second decode step takes 1.1 s: a slow step
        adapter = Stalling(lambda: time.sleep(1.1)) if kind == "toy" \
            else make_adapter(kind)
        engine = LLMEngine(adapter, EngineConfig(**ENGINE))
        ring = len(tracing.step_roots())
        try:
            served = serve(engine, PROMPTS)
            gc.collect()                # on a thread with no span open
            return (served, engine.metrics(), engine.step_log(),
                    engine.request_log(), engine.slow_steps(),
                    len(tracing.step_roots()) - ring)
        finally:
            engine.stop()

    served_on, _, steps_on, requests_on, slow_on, ring_on = once()
    monkeypatch.setenv("RTPU_TRACING", "0")
    tracing.refresh()
    try:
        before = tracing.process_counters()
        served_off, metrics, steps_off, requests_off, slow_off, ring_off \
            = once()
    finally:
        monkeypatch.undo()
        tracing.refresh()
    assert steps_on and len(requests_on) == len(PROMPTS) and ring_on > 0
    # (a flax step that compiles may be a slow one too, whatever else the
    # machine does meanwhile)
    from ray_tpu.serve.llm.step_watch import VERDICTS
    assert len(slow_on) == 1 if kind == "toy" else all(
        r["verdict"] in VERDICTS for r in slow_on)
    assert steps_off == [] and requests_off == [] and slow_off == []
    assert ring_off == 0
    assert served_off == served_on
    # the counters are counted whatever the switch says
    assert metrics["steps_total"] > 0
    assert metrics["prefill_seqs_total"] == len(PROMPTS)
    assert metrics["slow_steps_total"] >= (kind == "toy")
    assert metrics["gc_collections_total"] > before["gc_collections_total"]
    assert metrics["gc_seconds_total"] > before["gc_seconds_total"]
    assert metrics["lock_wait_seconds_total"] >= 0.0


def test_a_profiler_capture_holds_the_step_spans(tmp_path):
    """``LLMServer.__llm_profile__`` on a live engine: the host plane of
    the capture holds the program's spans under their own names."""
    from benchmark.harness import xplane
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.util import timeline
    server = LLMServer("gpt2", engine_config=ENGINE)
    # the capture is also merged into this process's timeline ring, which
    # other files' tests count: put the ring back as it was
    ring = list(timeline._events)
    try:
        serve(server.engine, PROMPTS[:4], new_tokens=8)        # compile
        stop, rounds = threading.Event(), []

        def traffic():       # until the capture is over, however long
            while not stop.is_set():    # the profiler takes to start
                rounds.append(serve(server.engine, PROMPTS[:4], new_tokens=8))

        worker = threading.Thread(target=traffic)
        worker.start()
        try:
            info = server.__llm_profile__(str(tmp_path), 1.0)
        finally:
            stop.set()
            worker.join(timeout=60.0)
        assert rounds and not worker.is_alive()
        metrics = server.__llm_metrics__()
    finally:
        server.engine.stop()
        timeline._events[:] = ring
    assert info["log_dir"] == str(tmp_path) and info["t1"] - info["t0"] >= 1.0
    names = {"llm.step", "llm.step.decode", "runner.dispatch", "runner.fetch"}
    trace = xplane.load(xplane.find_xplane(str(tmp_path)), host_names=names)
    assert {e.name for e in trace.host_spans} == names
    steps = [e for e in trace.host_spans if e.name == "llm.step"]
    # (a step that was open when the capture began or ended has lost
    # its own annotation, not its children's)
    inner = [e for e in trace.host_spans if e.name == "runner.dispatch"
             and e.start >= min(s.start for s in steps)
             and e.end <= max(s.end for s in steps)]
    assert inner and all(any(s.start <= e.start and e.end <= s.end
                             for s in steps) for e in inner)
    assert len(metrics["step_log"]) == metrics["steps_total"]
    assert len(metrics["request_log"]) == metrics["finished_total"] \
        == 4 * (1 + len(rounds))


# ------------------------------------------- before a replica is ready

SETUP = ("llm.setup.adapter", "llm.setup.params", "llm.setup.cache",
         "llm.setup.engine")


@pytest.fixture(scope="module", params=["toy", "gpt2"])
def served_setup(request):
    """A replica's ``__llm_metrics__()["setup"]`` after two requests
    through the engine and one bucket met OUTSIDE any step (what a
    warm-up does), and what the adapter counted."""
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.serve.llm.engine import Sequence
    t_before = time.time()
    server = LLMServer(request.param, engine_config=ENGINE)
    t_after = time.time()
    try:
        serve(server.engine, PROMPTS[:2])
        outside = None
        if request.param != "toy":
            seqs = []
            for i in range(3):      # a prefill bucket no request reached
                server.engine.cache.allocate(f"warm-{i}", 12)
                seqs.append(Sequence(f"warm-{i}", None, [1] * 11,
                                     SamplingParams(max_new_tokens=1)))
            server.adapter.prefill(seqs)
            for seq in seqs:
                server.adapter.release(seq.seq_id)
                server.engine.cache.free(seq.seq_id)
            outside = (4, 16)
        metrics = server.__llm_metrics__()
        return {"kind": request.param, "setup": metrics["setup"],
                "metrics": metrics, "between": (t_before, t_after),
                "outside": outside, "buckets": [
                    k for k in getattr(server.adapter, "_fns", ())
                    if isinstance(k, tuple)]}
    finally:
        server.engine.stop()


def test_the_setup_tree_lies_in_order_inside_its_parent(served_setup):
    import json
    setup = served_setup["setup"]
    assert set(setup) == {"process_t0", "spans", "first_calls", "programs",
                          "counters"}
    json.dumps(setup)                           # plain data, as it is
    (root,) = setup["spans"]
    t_before, t_after = served_setup["between"]
    assert root["name"] == "llm.setup"
    assert root["attrs"]["model"] == served_setup["kind"]
    assert root["attrs"]["kind"] == (
        "ToyAdapter" if served_setup["kind"] == "toy"
        else "FlaxModelAdapter")
    assert setup["process_t0"] < t_before <= root["t0"] <= root["t1"] \
        <= t_after
    children = spans_of(root)[0]
    assert [c["name"] for c in children] == list(
        SETUP[2:] if served_setup["kind"] == "toy" else SETUP)
    end = root["t0"]
    for child in children:                      # in order, inside, disjoint
        assert end <= child["t0"] <= child["t1"] <= root["t1"]
        end = child["t1"]
    by_name = {c["name"]: c for c in children}
    assert by_name["llm.setup.cache"]["attrs"]["bytes"] > 0
    if served_setup["kind"] != "toy":
        assert by_name["llm.setup.adapter"]["attrs"] == {"kind": "gpt2"}
        assert by_name["llm.setup.params"]["attrs"]["bytes"] > 0
    assert setup["counters"] == {
        k: served_setup["metrics"][k] for k in setup["counters"]}


def test_every_bucket_leaves_one_first_call_record(served_setup):
    setup = served_setup["setup"]
    if served_setup["kind"] == "toy":
        assert setup["first_calls"] == []   # (programs: the process's)
        return
    calls = setup["first_calls"]
    assert all(c["name"] == "runner.dispatch" and c["attrs"]["first_call"]
               for c in calls)
    met = [(c["attrs"]["B"], c["attrs"]["S"]) for c in calls]
    assert sorted(met) == sorted((B, S) for B, S, _ in
                                 served_setup["buckets"])
    assert len(met) == len(set(met)) \
        == served_setup["metrics"]["bucket_first_calls_total"]
    # the one met outside a step is among them, and in no step's tree
    assert served_setup["outside"] in met
    in_steps = {(s["attrs"]["B"], s["attrs"]["S"])
                for st in served_setup["metrics"]["step_log"]
                for s in walk(st) if s["name"] == "runner.dispatch"}
    assert served_setup["outside"] not in in_steps
    funs = {r["fun"] for r in setup["programs"]}
    for call in calls:
        # the bucket's own three stages are among its children, and
        # nothing but jax's events
        assert {c["name"] for c in call["children"]} == {
            "jax.trace", "jax.lower", "jax.compile"}
        name = "jit_" + bucket_name(call["attrs"]["B"], call["attrs"]["S"])
        assert [c["name"] for c in call["children"]
                if c["attrs"]["fun"] == name] == [
                    "jax.trace", "jax.lower", "jax.compile"]
        assert name in funs
        assert all(call["t0"] <= c["t0"] <= c["t1"] <= call["t1"]
                   for c in call["children"])


def test_runner_ms_is_the_runner_spans_of_the_step(run):
    """``llm.step`` says ``runner_ms``: the ``runner.*`` spans that ran
    between its ends. Over a run their sum is every runner span's, a
    prompt's late fetch counted once, in the step that waited for it."""
    steps = run["steps"]
    assert all(s["attrs"]["runner_ms"] >= 0.0 for s in steps)
    assert all(s["attrs"]["runner_ms"] <= ms(s) + 1e-6 for s in steps)
    spans = [s for st in steps for s in walk(st)
             if s["name"].startswith("runner.")]
    assert sum(s["attrs"]["runner_ms"] for s in steps) == pytest.approx(
        sum(map(ms, spans)))
    if not run["kind"].startswith("toy"):
        assert spans and sum(s["attrs"]["runner_ms"] for s in steps) > 0


# ------------------------------------------------------------ step_span

def test_step_span_nests_by_thread_and_fills_the_ring():
    ring = deque(maxlen=2)
    other = []

    def elsewhere():
        with tracing.step_span("other.root", ring=other, where="thread"):
            pass

    for i in range(3):
        with tracing.step_span("root", ring, i=i) as root:
            with tracing.step_span("child") as child:
                t = threading.Thread(target=elsewhere)
                t.start()
                t.join(timeout=10.0)
                child.set(rows=4)
            root.set(done=True)
    assert [r["attrs"] for r in ring] == [{"i": 1, "done": True},
                                          {"i": 2, "done": True}]
    assert [c["name"] for c in ring[0]["children"]] == ["child"]
    assert ring[0]["children"][0]["attrs"] == {"rows": 4}
    # another thread's span is a root of its own, not a child
    assert [o["name"] for o in other] == ["other.root"] * 3
    assert all(o["children"] == [] for o in other)


def test_step_span_without_a_ring_lands_in_the_modules(monkeypatch):
    with tracing.step_span("test.only.root", k=1):
        pass
    assert tracing.step_roots("test.only.root")[-1]["attrs"] == {"k": 1}
    monkeypatch.setenv("RTPU_TRACING", "0")
    tracing.refresh()
    try:
        n = len(tracing.step_roots())
        with tracing.step_span("test.only.root", k=2) as span:
            span.set(more=1)                   # a no-op, not an error
        assert len(tracing.step_roots()) == n
    finally:
        monkeypatch.undo()
        tracing.refresh()


def test_step_event_hangs_under_the_open_span_or_in_the_modules_ring():
    ring = deque()
    with tracing.step_span("root", ring):
        time.sleep(0.05)
        tracing.step_event("test.event", 0.02, k=1)
    (event,) = ring[0]["children"]
    assert (event["name"], event["attrs"], event["children"]) == (
        "test.event", {"k": 1}, [])
    assert event["t1"] - event["t0"] == pytest.approx(0.02)
    assert ring[0]["t0"] <= event["t0"] <= event["t1"] <= ring[0]["t1"]
    # one that claims to be older than the open span starts with it
    with tracing.step_span("root", ring):
        tracing.step_event("test.event", 5.0)
    assert ring[1]["children"][0]["t0"] == ring[1]["t0"]

    def elsewhere():        # no span open on this thread
        tracing.step_event("test.event", 0.5, k=2)

    t = threading.Thread(target=elsewhere, name="test-elsewhere")
    t.start()
    t.join(timeout=10.0)
    last = tracing.step_roots("test.event")[-1]
    assert last["attrs"] == {"k": 2, "thread": "test-elsewhere"}
    assert last["t1"] - last["t0"] == pytest.approx(0.5)
    assert tracing.step_roots("test.event", "no.such")[-1] is last


def test_a_collection_inside_a_span_is_its_child_and_is_counted():
    tracing.watch_process()
    tracing.watch_process()                     # idempotent
    assert gc.callbacks.count(tracing._on_gc) == 1
    before = tracing.process_counters()
    ring = deque()
    with tracing.step_span("root", ring):
        with tracing.step_span("child"):
            gc.collect()
    (child,) = spans_of(ring[0])[0]
    pauses = [e for e in child["children"] if e["name"] == "py.gc"]
    assert pauses and pauses[-1]["attrs"]["generation"] == 2
    assert pauses[-1]["attrs"]["collected"] >= 0
    assert child["t0"] <= pauses[-1]["t0"] < pauses[-1]["t1"] <= child["t1"]
    after = tracing.process_counters()
    assert after["gc_collections_total"] > before["gc_collections_total"]
    assert after["gc_seconds_total"] > before["gc_seconds_total"]


def test_a_first_call_compiles_under_the_span_that_made_it():
    import jax
    import jax.numpy as jnp
    tracing.watch_process()
    fn = jax.jit(lambda x: jnp.tanh(x * 3.0 + 39.0))
    x = jnp.ones((3, 5))
    x.block_until_ready()
    before = tracing.process_counters()
    ring = deque()
    for _ in range(2):
        with tracing.step_span("llm.step", ring):
            with tracing.step_span("runner.dispatch", B=3, S=5):
                fn(x).block_until_ready()
    first, second = (
        [e for e in step["children"][0]["children"] if e["name"] != "py.gc"]
        for step in ring)
    assert [e["name"] for e in first] == [
        "jax.trace", "jax.lower", "jax.compile"] and second == []
    assert len({e["attrs"]["fun"] for e in first}) == 1
    dispatch = ring[0]["children"][0]
    assert all(dispatch["t0"] <= e["t0"] < e["t1"] <= dispatch["t1"]
               for e in first)
    after = tracing.process_counters()
    assert after["compiles_total"] == before["compiles_total"] + 1
    assert after["compile_seconds_total"] > before["compile_seconds_total"]


# ----------------------------------------------------------- a slow step

def slow_run(stall, beside=None):
    """Two requests through an engine whose second decode step runs
    ``stall``; ``beside(engine, adapter)`` meanwhile on a thread of its
    own. -> the engine's slow-step records and its metrics."""
    adapter = Stalling(stall)
    engine = LLMEngine(adapter, EngineConfig(**ENGINE))
    try:
        worker = None
        if beside is not None:
            worker = threading.Thread(target=beside, args=(engine, adapter),
                                      name="test-beside")
            worker.start()
        served = serve(engine, PROMPTS[:2], new_tokens=6)
        if worker is not None:
            worker.join(timeout=60.0)
            assert not worker.is_alive()
        assert list(map(len, served)) == [6, 6]
        return engine.slow_steps(), engine.metrics()
    finally:
        engine.stop()


def engine_frames(record):
    return record["stacks"][record["engine_thread"]]


def test_a_step_the_adapter_held_says_device_or_runtime(caplog):
    with caplog.at_level(logging.WARNING,
                         logger="ray_tpu.serve.llm.step_watch"):
        records, metrics = slow_run(lambda: time.sleep(1.2))
    (rec,) = records
    assert metrics["slow_steps_total"] == 1
    assert rec["verdict"] == "device or runtime", rec["why"]
    assert set(rec) >= {"i", "t0", "t1", "cpu_ms", "tree", "stacks",
                        "stacks_at", "watch_late_ms", "events", "verdict"}
    assert rec["t1"] - rec["t0"] >= 1.2 and rec["cpu_ms"] < 300.0
    assert rec["tree"]["name"] == "llm.step" \
        and rec["tree"]["attrs"]["i"] == rec["i"]
    assert rec["t0"] + 1.0 <= rec["stacks_at"] <= rec["t1"]
    # the engine thread's stack names the adapter, innermost first
    frames = engine_frames(rec)
    assert len(frames) <= 12
    assert any(f.split(":")[0] == __file__ and f.endswith(" decode")
               for f in frames)
    assert rec["watch_late_ms"] < 600.0
    (line,) = [r.getMessage() for r in caplog.records
               if "llm.step" in r.getMessage()]
    assert f"llm.step {rec['i']} took" in line \
        and "device or runtime" in line


def test_a_step_that_waited_for_the_interpreter_says_who_held_it():
    # one C call that keeps the interpreter for ~1.8 s: sized by the
    # fastest of three samples (a slow sample would size it too short)
    def sample():
        t0 = time.perf_counter()
        sum(range(3_000_000))
        return time.perf_counter() - t0

    n = int(3_000_000 * 1.8 / min(sample() for _ in range(3)))

    def hog(engine, adapter):
        assert adapter.entered.wait(timeout=60.0)
        sum(range(n))

    records, _ = slow_run(lambda: time.sleep(0.05), beside=hog)
    (rec,) = records
    assert rec["verdict"] == "interpreter held", rec["why"]
    assert rec["watch_late_ms"] > 500.0 * (rec["t1"] - rec["t0"])
    assert rec["cpu_ms"] < 300.0
    assert "test-beside" in rec["why"] or "moved on" in rec["why"]


@pytest.mark.parametrize("line,waits", [
    ("data = self._read(numbytes - len(buf))", True),
    ("item = self._queue.get(timeout=1.0)", True),
    ("ready.wait_for(lambda: done)", True),
    ("total = sum(range(n))", False),
    ("value = self._result(key)", False),
    ("spread(values)", False)])
def test_what_counts_as_a_wait(tmp_path, line, waits):
    """A thread whose innermost frame stands at a wait (``self._read(``
    too: execnet's reader thread of an xdist worker) is not named as the
    one that held the interpreter; one busy in another private helper
    is."""
    from ray_tpu.serve.llm import step_watch
    src = tmp_path / "held.py"
    src.write_text(line + "\n")
    assert step_watch._waits([(str(src), 1, "run")]) is waits


def test_a_step_that_waited_for_the_engine_lock_says_lock():
    def hold(engine, adapter):
        assert adapter.entered.wait(timeout=60.0)
        with engine._lock:              # as a caller's thread would
            time.sleep(1.2)

    records, metrics = slow_run(lambda: time.sleep(0.05), beside=hold)
    (rec,) = records
    assert rec["verdict"] == "lock", rec["why"]
    waited = sum(s["attrs"].get("lock_wait_ms", 0.0)
                 for s in walk(rec["tree"]))
    assert waited > 1000.0
    assert metrics["lock_wait_seconds_total"] > 1.0


def test_a_step_the_collector_held_says_gc():
    graph = [[i] for i in range(1_500_000)]     # what a collection walks

    def collect():
        end = time.monotonic() + 1.2
        while time.monotonic() < end:
            gc.collect()

    try:
        records, _ = slow_run(collect)
    finally:
        del graph
    (rec,) = records
    assert rec["verdict"] == "gc", rec["why"]
    pauses = [s for s in walk(rec["tree"]) if s["name"] == "py.gc"]
    assert sum(ms(p) for p in pauses) > 600.0
    assert all(p["attrs"]["generation"] == 2 for p in pauses)


def test_the_feed_records_its_batches():
    import jax
    from ray_tpu.data.dataset import Dataset  # noqa: F401 - the module
    from ray_tpu import data as rd
    import ray_tpu
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        before = len(tracing.step_roots("data.feed.host_batch"))
        ds = rd.from_numpy({"x": np.arange(32, dtype=np.float32),
                            "y": np.arange(32, dtype=np.int32)})
        batches = list(ds.iter_device_batches(batch_size=8))
    finally:
        ray_tpu.shutdown()
    assert len(batches) == 4 and isinstance(batches[0]["x"], jax.Array)
    host = tracing.step_roots("data.feed.host_batch")[before:]
    put = tracing.step_roots("data.feed.device_put")[-4:]
    # four batches and the call that found the feed empty
    assert [h["attrs"] for h in host] == [
        {"rows": 8, "bytes": 64}] * 4 + [{}]
    assert [p["attrs"] for p in put] == [{"bytes": 64}] * 4


# ------------------------------------- names on the device trace itself

def test_a_train_worker_records_its_setup_and_logs_it_once(caplog):
    """``make_causal_lm_trainer`` runs under ``train.setup.build`` and
    ``trainer.init`` under ``train.setup.init`` (``trainer.step`` stays
    the jitted function: its first call is its row of ``programs()``);
    the worker's first ``session.report`` logs the set-up in one line."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.air import session
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.spmd import make_causal_lm_trainer
    spec = MeshSpec()
    t0 = time.time()
    trainer = make_causal_lm_trainer(
        GPT2Config.tiny(), mesh=spec.build(jax.devices()[:1]), spec=spec)
    state = trainer.init(jax.random.PRNGKey(0))
    assert hasattr(trainer.step, "lower")       # jitted, not wrapped
    batch = {k: jnp.zeros((2, 16), jnp.int32)
             for k in ("input_ids", "labels")}
    state, metrics = trainer.step(state, batch)
    jax.block_until_ready(metrics["loss"])
    report = tracing.setup_report()
    build, init = [s for s in report["spans"] if s["t0"] >= t0
                   and s["name"].startswith("train.setup.")]
    assert (build["name"], init["name"]) == (
        "train.setup.build", "train.setup.init")
    assert report["process_t0"] < build["t0"] <= build["t1"] <= init["t0"]
    stages = [(e["name"], e["attrs"]["fun"]) for e in init["children"]
              if e["name"].startswith("jax.")]
    assert stages[-3:] == [("jax.trace", "jit_init_fn"),
                           ("jax.lower", "jit_init_fn"),
                           ("jax.compile", "jit_init_fn")]
    rows = {r["fun"]: r for r in report["programs"]}
    assert rows["jit_train_step"]["compiles"] >= 1
    assert rows["jit_train_step"]["t_last"] >= init["t1"]
    held = session._Session()
    session._set_session(held)
    try:
        with caplog.at_level(logging.INFO, logger="ray_tpu.air.session"):
            session.report({"loss": 1.0})
            session.report({"loss": 0.5})
    finally:
        session._set_session(None)
    lines = [r.getMessage() for r in caplog.records
             if r.name == "ray_tpu.air.session"]
    assert len(lines) == 1 and "\n" not in lines[0]
    assert lines[0].startswith("set-up: process start to ")
    assert "train.setup.build" in lines[0] and "train.setup.init" in lines[0]
    assert "programs: trace " in lines[0]
    assert held.result_queue.qsize() == 2


def pallas_call_names(jaxpr, outer=""):
    """``name`` of every pallas_call in a jaxpr with the scopes it was
    traced under, through every nested jaxpr (the scopes inside a
    ``jit`` of its own start anew: they follow the call's, as they do
    once the program is lowered)."""
    out = []
    for eqn in jaxpr.eqns:
        stack = "/".join(s for s in (outer, str(eqn.source_info.name_stack))
                         if s)
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"], stack))
        for value in eqn.params.values():
            for v in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(v, "jaxpr", v)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    out.extend(pallas_call_names(
                        inner, stack if eqn.primitive.name in ("jit", "pjit")
                        else outer))
    return out


@pytest.fixture(scope="module")
def tiny_train_step():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.ops import attention
    from ray_tpu.parallel.mesh import MeshSpec
    from ray_tpu.train.spmd import make_causal_lm_trainer
    cfg = dataclasses.replace(GPT2Config.tiny(), attention_backend="flash")
    spec = MeshSpec()
    mesh = spec.build(jax.devices()[:1])
    trainer = make_causal_lm_trainer(cfg, mesh=mesh, spec=spec)
    state = jax.eval_shape(trainer.init, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((2, 128), jnp.int32)
             for k in ("input_ids", "labels")}
    lowered = trainer.step.lower(state, batch).as_text(debug_info=True)
    # the kernels as the chip would get them: traced, never lowered here
    # (a second trainer: the first's step is traced already)
    use = attention._use_pallas
    attention._use_pallas = lambda: True
    ring = deque()
    try:
        with tracing.step_span("test.trace_step", ring):
            jaxpr = jax.make_jaxpr(make_causal_lm_trainer(
                cfg, mesh=mesh, spec=spec).step)(state, batch)
    finally:
        attention._use_pallas = use
    return {"lowered": lowered, "kernels": pallas_call_names(jaxpr.jaxpr),
            "cfg": cfg, "events": ring[0]["children"]}


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd"])
def test_train_step_jaxpr_names_its_kernels(tiny_train_step, kernel):
    found = [stack for name, stack in tiny_train_step["kernels"]
             if name == kernel]
    assert len(found) == 2                              # one a layer
    assert all(f"/{kernel}" in stack and "attn" in stack for stack in found)


def test_tracing_a_train_step_leaves_its_flash_plans(tiny_train_step):
    """One ``attention.flash_plan`` event a ``flash_attention_packed``
    call (one a layer), with the numbers of the plan the kernels were laid
    out by and the layout: the tiny model's four 32-wide heads are one
    128-lane block of ``c_attn``'s output, so the packed kernels run."""
    from ray_tpu.ops.attention import flash_plan, packed_heads
    cfg = tiny_train_step["cfg"]
    plans = [e["attrs"] for e in tiny_train_step["events"]
             if e["name"] == "attention.flash_plan"]
    assert len(plans) == cfg.n_layer == 2
    head_dim = cfg.n_embd // cfg.n_head
    want = flash_plan(128, 128, head_dim, True)
    assert want["path"] == "whole_kv_causal"
    assert want["blocks_visited"] == want["blocks_total"] == 1
    assert packed_heads(128, cfg.n_head, cfg.n_head, head_dim, True) == 4
    want = {**want, "packed": True, "heads_per_program": 4}
    assert plans == [want, want]


@pytest.mark.parametrize("scope,path", [
    ("lm_head", "jvp(GPT2)/lm_head/"), ("lm_head", "/lm_head/"),
    ("loss", "jvp(loss)/"), ("loss", "transpose(jvp(loss))/"),
    ("optimizer", "jit(train_step)/optimizer/")])
def test_train_step_lowering_carries_the_scopes(tiny_train_step, scope, path):
    assert path in tiny_train_step["lowered"]


@pytest.mark.parametrize("exact,kernels", [
    (True, {"flash_fwd_blocked", "flash_bwd_dkv", "flash_bwd_dq"}),
    (False, {"flash_fwd", "flash_bwd"})])
def test_flash_attention_names_every_kernel(exact, kernels):
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import flash_attention
    q = jax.ShapeDtypeStruct((1, 2, 128, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, force_pallas=True,
                               exact=exact).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    found = pallas_call_names(jaxpr.jaxpr)
    assert {name for name, _ in found} == kernels
    assert all(name in stack for name, stack in found)


def test_paged_decode_kernel_is_named():
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import paged_attention_decode
    jaxpr = jax.make_jaxpr(
        lambda *a: paged_attention_decode(*a, interpret=True))(
        jax.ShapeDtypeStruct((2, 4, 64), jnp.float32),
        jax.ShapeDtypeStruct((1, 8, 16, 4 * 64), jnp.float32),
        jax.ShapeDtypeStruct((1, 8, 16, 4 * 64), jnp.float32),
        jax.ShapeDtypeStruct((2, 4), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32))
    assert [name for name, _ in pallas_call_names(jaxpr.jaxpr)] == [
        "paged_attention_decode"]
