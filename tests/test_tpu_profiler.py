"""jax.profiler → framework-timeline integration (SURVEY §5.1: keep
the chrome-trace timeline; integrate jax.profiler/xplane traces per
worker and merge by host)."""

import numpy as np
import pytest

import ray_tpu


def test_trace_merges_xla_events_into_local_timeline():
    import jax
    import jax.numpy as jnp

    from ray_tpu.util import timeline, tpu_profiler

    @jax.jit
    def f(x):
        return x @ x

    with tpu_profiler.trace(label="xla-test") as d:
        x = jnp.ones((128, 128))
        for _ in range(3):
            x = f(x)
        x.block_until_ready()
    # raw artifacts exist for TensorBoard
    assert tpu_profiler.load_chrome_events(d)
    evs = timeline.collect()
    xla = [e for e in evs if e.get("cat") == "xla-test"]
    assert xla, "no XLA events merged"
    names = [e for e in evs if e.get("name") == "process_name"
             and "xla-test" in str(e.get("args"))]
    assert names, "XLA process rows not labeled"
    # rebased to wall-clock: within an hour of now, not a raw steady-
    # clock offset
    import time
    now_us = time.time() * 1e6
    assert all(abs(e["ts"] - now_us) < 3600e6 for e in xla)


def test_trace_events_reach_driver_timeline_dump():
    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        @ray_tpu.remote
        def traced_work():
            import jax
            import jax.numpy as jnp

            from ray_tpu.util import tpu_profiler

            @jax.jit
            def g(x):
                return (x * x).sum()

            with tpu_profiler.trace(label="xla-task"):
                v = g(jnp.arange(64, dtype=jnp.float32))
                float(v)
            from ray_tpu.util import timeline
            timeline.flush()
            return True

        assert ray_tpu.get(traced_work.remote(), timeout=120)
        import time
        deadline = time.time() + 15
        merged = []
        while time.time() < deadline:
            merged = [e for e in ray_tpu.timeline()
                      if e.get("cat") == "xla-task"]
            if merged:
                break
            time.sleep(1.0)
        assert merged, "worker XLA capture did not reach the merged dump"
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------------- unit layer
# load/rebase/merge units (ISSUE 13 satellite): previously only the
# jax-integration paths above exercised these; synthetic captures pin
# the contract each piece owns.


def _write_capture(log_dir, rel_path, events):
    import gzip
    import json
    import os
    path = os.path.join(log_dir, rel_path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": events}, f)


def test_load_chrome_events_walks_nested_captures(tmp_path):
    from ray_tpu.util import tpu_profiler
    _write_capture(str(tmp_path), "plugins/profile/run1/h1.trace.json.gz",
                   [{"name": "a", "ph": "X", "ts": 10.0, "dur": 5.0,
                     "pid": 1, "tid": 0}])
    _write_capture(str(tmp_path), "plugins/profile/run1/h2.trace.json.gz",
                   [{"name": "b", "ph": "X", "ts": 20.0, "dur": 7.0,
                     "pid": 2, "tid": 0}])
    # non-matching files are ignored
    (tmp_path / "notes.json").write_text("{}")
    evs = tpu_profiler.load_chrome_events(str(tmp_path))
    assert {e["name"] for e in evs} == {"a", "b"}
    assert tpu_profiler.load_chrome_events(str(tmp_path / "empty")) == []


def test_merge_rebases_to_wall_clock_and_filters(tmp_path):
    """Rebase: the capture's steady-clock ts land at wall_start_us +
    (ts - min ts); sub-floor spans and the per-capture cap apply."""
    from ray_tpu.util import timeline, tpu_profiler
    events = [
        {"name": "big", "ph": "X", "ts": 1000.0, "dur": 100.0,
         "pid": 7, "tid": 3},
        {"name": "later", "ph": "X", "ts": 1500.0, "dur": 50.0,
         "pid": 7, "tid": 3},
        {"name": "tiny", "ph": "X", "ts": 1200.0, "dur": 0.5,
         "pid": 7, "tid": 3},  # below min_dur_us
        {"name": "meta", "ph": "M", "ts": 0.0, "pid": 7},  # not 'X'
    ]
    wall = 1_700_000_000 * 1e6
    # the merged events are found by their label, not by their position
    # after what was there before: the timeline is a ring of 10,000
    # events, and in a process whose earlier tests filled it (under
    # `--dist loadfile`, whichever file this worker ran before) its
    # length no longer grows
    n = tpu_profiler.merge_into_timeline(
        events, wall_start_us=wall, label="unit-xla", min_dur_us=5.0)
    assert n == 2
    merged = [e for e in timeline.collect() if e.get("cat") == "unit-xla"]
    by_name = {e["name"]: e for e in merged}
    assert set(by_name) == {"big", "later"}
    assert by_name["big"]["ts"] == wall          # min ts -> wall start
    assert by_name["later"]["ts"] == wall + 500.0
    # cap keeps the LONGEST spans, not the first ones
    many = [{"name": f"s{i}", "ph": "X", "ts": float(i),
             "dur": float(i + 1), "pid": 1, "tid": 0}
            for i in range(50)]
    n = tpu_profiler.merge_into_timeline(
        many, wall_start_us=wall, label="unit-cap", max_events=10,
        min_dur_us=0.0)
    assert n == 10
    kept = [e for e in timeline.collect() if e.get("cat") == "unit-cap"]
    assert {e["name"] for e in kept} == {f"s{i}" for i in range(40, 50)}


def test_merge_xla_pid_rows_are_stable_and_separated():
    """_XLA_PID_BASE row mapping: XLA process rows never collide with
    framework task pids, distinct source pids get distinct rows, and
    the digest is restart-stable (same node+pid -> same row)."""
    from ray_tpu.util import timeline, tpu_profiler
    events = [{"name": "x", "ph": "X", "ts": 1.0, "dur": 10.0,
               "pid": 11, "tid": 0},
              {"name": "y", "ph": "X", "ts": 2.0, "dur": 10.0,
               "pid": 22, "tid": 0}]
    def spans():        # by label: the ring may be full (see above)
        return [e for e in timeline.collect()
                if e.get("cat") == "unit-rows" and e.get("ph") == "X"]

    tpu_profiler.merge_into_timeline(
        events, wall_start_us=0.0, label="unit-rows", min_dur_us=0.0)
    first = spans()
    assert len(first) == 2
    pids1 = {e["name"]: e["pid"] for e in first}
    assert pids1["x"] != pids1["y"]
    assert all(p >= tpu_profiler._XLA_PID_BASE for p in pids1.values())
    # process_name metadata labels each synthetic row
    metas = [e for e in timeline.collect()
             if e.get("name") == "process_name"
             and "unit-rows" in str(e.get("args"))]
    assert len(metas) == 2
    # stability: a second merge (fresh seen_pids map) lands on the
    # same rows — crc32 digest, not Python's randomized hash()
    tpu_profiler.merge_into_timeline(
        events, wall_start_us=0.0, label="unit-rows", min_dur_us=0.0)
    second = spans()[2:]
    assert len(second) == 2
    pids2 = {e["name"]: e["pid"] for e in second}
    assert pids2 == pids1
    timeline.stop_flusher()
