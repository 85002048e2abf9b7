"""The ``setup_*`` readers and ``engine_self_by_clock_p50_ms.serve`` on a
made-up record of a replica (``__llm_metrics__()["setup"]`` as
``tracing.setup_report`` shapes it, docs/TRACING.md, "Before a process is
ready"): what ended before the window's ``t0`` counts, what came after
it does not, and a program without the record reads ``None``."""

import importlib.util
import os
import types

import pytest

from benchmark.harness import cells

T0 = 1000.0         # the window's start; the replica's process began at 900
SETUP = ("setup_replica_boot_s.serve", "setup_constructor_s.serve",
         "setup_trace_lower_s.serve", "setup_compile_s.serve",
         "setup_cache_misses.serve")
NEW = SETUP + ("engine_self_by_clock_p50_ms.serve",)


def reader(name):
    path = os.path.join(cells.BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, t0, t1, children=(), **attrs):
    return {"name": name, "t0": t0, "t1": t1, "attrs": attrs,
            "children": list(children)}


def row(fun, t_last, trace=0.0, nested=0.0, lower=0.0, compile_s=0.0,
        hits=0, misses=0, compiles=None, n=1):
    return {"fun": fun, "n": n, "trace_s": trace, "nested_trace_s": nested,
            "lower_s": lower, "compiles": hits + misses
            if compiles is None else compiles, "compile_s": compile_s,
            "cache_hits": hits, "cache_misses": misses,
            "t_first": 900.0, "t_last": t_last}


def made_up():
    setup = {
        "process_t0": 900.0,
        "spans": [span(
            "llm.setup", 912.5, 920.0, model="gpt2", kind="FlaxModelAdapter",
            children=[
                span("llm.setup.adapter", 912.5, 913.0, kind="gpt2"),
                span("llm.setup.params", 913.0, 917.0, bytes=4096, children=[
                    span("jax.trace", 913.0, 913.5, fun="jit_stack"),
                    span("jax.lower", 913.5, 913.75, fun="jit_stack"),
                    span("jax.compile", 913.75, 914.75, fun="jit_stack",
                         cache="hit", retrieval_ms=900.0)]),
                span("llm.setup.cache", 917.0, 919.5, bytes=8192,
                     memory_in_use_bytes=12288),
                span("llm.setup.engine", 919.5, 920.0)])],
        "first_calls": [
            span("runner.dispatch", 930.0, 936.0, B=4, S=1, first_call=True,
                 children=[span("jax.trace", 930.0, 932.0, fun="jit_d4"),
                           span("jax.lower", 932.0, 933.0, fun="jit_d4"),
                           span("jax.compile", 933.0, 935.0, fun="jit_d4",
                                cache="miss")]),
            # a bucket first met inside the window: not set-up
            span("runner.dispatch", 1010.0, 1013.0, B=8, S=1,
                 first_call=True)],
        "programs": [
            row("jit_stack", 914.75, trace=0.5, lower=0.25, compile_s=1.0,
                hits=1),
            row("jit_d4", 935.0, trace=2.0, lower=1.0, compile_s=2.0,
                misses=1),
            # traced inside jit_d4's trace: in that row's seconds already
            row("jit_multiply", 931.0, trace=0.75, nested=0.75, compiles=0,
                n=40),
            row("jit_upload", 940.0, trace=0.125, lower=0.125,
                compile_s=0.25, compiles=1),       # did not ask the cache
            row("jit_d8", 1013.0, trace=1.0, lower=1.0, compile_s=1.0,
                misses=1)],                         # the window's
        "counters": {"compile_cache_retrieval_seconds_total": 0.9}}
    steps = [span("llm.step", T0 + i, T0 + i + 0.010 * (i + 1),
                  runner_ms=6.0 * (i + 1), children=[
                      span("llm.step.decode", T0 + i, T0 + i + 0.001,
                           children=[span("runner.dispatch", T0 + i,
                                          T0 + i + 0.001)])])
             for i in range(3)]
    return types.SimpleNamespace(
        t0=T0, t1=T0 + 10.0,
        engine_metrics={"setup": setup, "step_log": steps})


@pytest.mark.parametrize("name,value", [
    ("setup_replica_boot_s.serve", 12.5),
    ("setup_constructor_s.serve", 7.5),
    # (0.5 + 0.25) + (2 + 1) + (0.75 - 0.75) + (0.125 + 0.125)
    ("setup_trace_lower_s.serve", 4.0),
    ("setup_compile_s.serve", 3.25),
    ("setup_cache_misses.serve", 1.0),
    # steps of 10, 20, 30 ms less 6, 12, 18 of the runner's
    ("engine_self_by_clock_p50_ms.serve", 8.0)])
def test_a_reader_counts_what_ended_before_the_window(name, value, capsys):
    assert reader(name).read(made_up()) == pytest.approx(value)
    said = capsys.readouterr().out
    assert said.startswith(("[setup] ", "[program_spans] "))
    if name == "setup_cache_misses.serve":
        assert "['jit_d4']" in said and "1 requests did not ask" in said
    if name == "setup_trace_lower_s.serve":
        assert "1 buckets (1 decode row counts)" in said
        assert "left out: ['jit_d8']" in said


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_record_reads_none(name):
    obs = made_up()
    del obs.engine_metrics["setup"]
    for step in obs.engine_metrics["step_log"]:
        del step["attrs"]["runner_ms"]
    assert reader(name).read(obs) is None
    assert reader(name).read(types.SimpleNamespace(t0=T0, t1=T0 + 10)) \
        is None


@pytest.mark.parametrize("name", SETUP[:2])
def test_a_constructor_still_open_at_the_window_is_no_setup(name):
    obs = made_up()
    obs.engine_metrics["setup"]["spans"][0]["t1"] = T0 + 1.0
    assert reader(name).read(obs) is None


def test_every_new_metric_has_its_entry_and_its_cells():
    bench = cells.benchmark_json()
    entries = {e["name"]: e for e in bench["per_layer"]}
    serving = [w["name"] for w in bench["workloads"]
               if w["traffic"].startswith("serve")]
    for name in NEW:
        mod, entry = reader(name), entries[name]
        assert entry["workloads"] == serving and len(serving) == 8
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            name, entry["unit"], entry["layer"], entry["moves"],
            entry["source"])
        assert entry["moves"] == (
            "setup_s" if name in SETUP else "serve_tokens_per_s")
    assert [e["name"] for e in bench["per_layer"]][-6:] == list(NEW)
