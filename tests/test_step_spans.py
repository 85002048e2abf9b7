"""Step spans and counters inside the program (docs/TRACING.md, "Step
spans"): ``tracing.step_span`` itself, the ``llm.step`` trees and the
request log of the serve engine, the model runner's bucket names, the
feed's spans, and the names the kernels and the train step put on a
device trace."""

import dataclasses
import os
import sys
import threading
import time

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


def make_adapter(kind):
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


@pytest.fixture(scope="module", params=["toy", "flax", "toy-prefix"])
def run(request):
    kind = request.param.split("-")[0]
    config = EngineConfig(enable_prefix_cache="prefix" in request.param,
                          **ENGINE)
    engine = LLMEngine(make_adapter(kind), config)
    # with the prefix cache on, the second round finds the first's pages
    served = serve(engine, PROMPTS) + serve(engine, PROMPTS)
    out = {"served": served, "metrics": engine.metrics(),
           "steps": engine.step_log(), "requests": engine.request_log(),
           "kind": request.param}
    yield out
    engine.stop()


def walk(span):
    yield span
    for child in span["children"]:
        yield from walk(child)


def test_step_trees_nest(run):
    assert run["steps"] and all(s["name"] == "llm.step" for s in run["steps"])
    assert [s["attrs"]["i"] for s in run["steps"]] == list(
        range(1, run["metrics"]["steps_total"] + 1))
    for step in run["steps"]:
        for span in walk(step):
            assert span["t0"] <= span["t1"]
            end = span["t0"]
            for child in span["children"]:      # in order, inside, disjoint
                assert end <= child["t0"] <= child["t1"] <= span["t1"]
                end = child["t1"]
        names = [c["name"] for c in step["children"]]
        assert names.count("llm.step.admit") == 1
        assert ("llm.step.prefill" in names) == (
            step["children"][names.index("llm.step.admit")]
            ["attrs"]["admitted"] > 0)
    if run["kind"] == "flax":
        calls = [s for st in run["steps"] for s in walk(st)
                 if s["name"] in ("llm.step.decode", "llm.step.prefill")]
        assert all([c["name"] for c in call["children"]] == [
            "runner.build_inputs", "runner.dispatch", "runner.fetch"]
            for call in calls)


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
        == m["prefill_tokens_total"] == m["prompt_tokens_total"] - cached
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
    if run["kind"] != "flax":
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


@pytest.mark.parametrize("kind", ["toy", "flax"])
def test_tracing_off_leaves_the_logs_empty_and_the_tokens_equal(
        kind, monkeypatch):
    def once():
        engine = LLMEngine(make_adapter(kind), EngineConfig(**ENGINE))
        try:
            return (serve(engine, PROMPTS), engine.metrics(),
                    engine.step_log(), engine.request_log())
        finally:
            engine.stop()

    served_on, _, steps_on, requests_on = once()
    monkeypatch.setenv("RTPU_TRACING", "0")
    tracing.refresh()
    try:
        served_off, metrics, steps_off, requests_off = once()
    finally:
        monkeypatch.undo()
        tracing.refresh()
    assert steps_on and len(requests_on) == len(PROMPTS)
    assert steps_off == [] and requests_off == []
    assert served_off == served_on
    # the counters are counted whatever the switch says
    assert metrics["steps_total"] > 0
    assert metrics["prefill_seqs_total"] == len(PROMPTS)


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


# ------------------------------------------------------------ step_span

def test_step_span_nests_by_thread_and_fills_the_ring():
    from collections import deque
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

def pallas_call_names(jaxpr):
    """``name`` of every pallas_call in a jaxpr with the scopes it was
    traced under, through every nested jaxpr."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append((eqn.params["name"], str(eqn.source_info.name_stack)))
        for value in eqn.params.values():
            for v in (value if isinstance(value, (list, tuple)) else [value]):
                inner = getattr(v, "jaxpr", v)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    out.extend(pallas_call_names(inner))
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
    try:
        jaxpr = jax.make_jaxpr(make_causal_lm_trainer(
            cfg, mesh=mesh, spec=spec).step)(state, batch)
    finally:
        attention._use_pallas = use
    return {"lowered": lowered, "kernels": pallas_call_names(jaxpr.jaxpr)}


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd"])
def test_train_step_jaxpr_names_its_kernels(tiny_train_step, kernel):
    found = [stack for name, stack in tiny_train_step["kernels"]
             if name == kernel]
    assert len(found) == 2                              # one a layer
    assert all(f"/{kernel}" in stack and "attn" in stack for stack in found)


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
