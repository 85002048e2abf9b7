"""The reduction from a trace to busy time, per-operation time and
labelled idle gaps: on a hand-made trace whose answers are known, and on
a small recorded ``.xplane.pb`` (three steps on the CPU, so it holds the
benchmark's host spans and no device plane)."""

import os

import pytest

from benchmark.harness import trace_views, xplane
from benchmark.harness.xplane import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def hand_made():
    ops = [Event("%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p)",
                 10 * MS, 20 * MS),
           Event("%fusion.2 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} "
                 "%custom-call.7)", 25 * MS, 10 * MS),      # overlaps
           Event("%tpu_custom_call.3 = (bf16[4]{0}, f32[4]{0}) "
                 "custom-call(bf16[4]{0} %q)", 50 * MS, 10 * MS),
           Event("%copy.9 = bf16[2,4]{1,0} copy(bf16[2,4]{0,1} %r)",
                 70 * MS, 5 * MS)]
    modules = [Event("jit_step(1)", 10 * MS, 25 * MS),
               Event("jit_step(1)", 50 * MS, 25 * MS),
               Event("jit_other(2)", 90 * MS, 1 * MS)]
    host = [Event("bench.window", 0, 100 * MS),
            Event("step", 5 * MS, 35 * MS),
            Event("next(feed)", 40 * MS, 8 * MS),
            Event("step", 48 * MS, 30 * MS)]
    return Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules}, host,
                 {})


def test_busy_is_the_union_of_intervals():
    t = hand_made()
    w = xplane.window_of(t)
    assert w == (0, 100 * MS)
    # [10,35) + [50,60) + [70,75) = 40 ms
    assert xplane.busy_seconds(t, w) == pytest.approx(0.040)
    assert xplane.busy_seconds(t, (30 * MS, 55 * MS)) == pytest.approx(0.010)


def test_idle_gaps_go_to_the_span_that_covers_them():
    t = hand_made()
    gaps = dict(xplane.idle_gaps(t, (0, 100 * MS),
                                 ["next(feed)", "step"]))
    # [0,10): middle 5 is in step 1; [35,50): middle 42.5 in next(feed);
    # [60,70): step 2; [75,100): middle 87.5, nothing -> between-steps
    assert gaps["step"] == pytest.approx(0.020)
    assert gaps["next(feed)"] == pytest.approx(0.015)
    assert gaps["between-steps"] == pytest.approx(0.025)


def test_top_ops_group_one_kind_of_work():
    t = hand_made()
    top = dict(xplane.top_ops(t, (0, 100 * MS)))
    assert top["%fusion bf16[8,8] fusion"] == pytest.approx(0.030)
    assert top["%tpu_custom_call custom-call"] == pytest.approx(0.010)
    assert top["%copy bf16[2,4] copy"] == pytest.approx(0.005)


def test_mosaic_is_the_custom_call_itself_not_its_consumer():
    ops = hand_made().device_ops["/device:TPU:0"]
    assert [trace_views.is_mosaic(e) for e in ops] == \
        [False, False, True, False]


def test_step_views():
    class Obs:
        trace = hand_made()
        trace_window = (0, 100 * MS)
    assert trace_views.step_device_ms(Obs) == pytest.approx(25.0)
    # one kernel of 10 ms inside the two steps' span -> 5 ms a step
    assert trace_views.mosaic_ms_per_step(Obs) == pytest.approx(5.0)


def test_recorded_trace_is_read_with_the_benchmarks_spans():
    path = os.path.join(HERE, "data", "cpu_three_steps.xplane.pb")
    t = xplane.load(path, host_names={"bench.window", "step",
                                      "next(feed)"})
    lo, hi = xplane.window_of(t)
    assert 0.005 < (hi - lo) / 1e9 < 5.0
    assert len(xplane.spans_named(t, "step", (lo, hi))) == 3
    assert len(xplane.spans_named(t, "next(feed)", (lo, hi))) == 3
    assert t.device_ops == {} and xplane.busy_seconds(t, (lo, hi)) == 0.0
