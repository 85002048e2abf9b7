"""The readers of what an engine step says of its own waits
(``benchmark/harness/step_cover.py`` and the seven per-layer metrics on
it, PR 39): on a hand-made ``step_log`` with the new attributes and
spans, hand-made events and slow-step records, and a hand-made device
trace whose answers are known (no capture in ``data/`` holds an
``llm.step.retire`` annotation); ``None`` on an ``obs`` of the parent's
shape."""

import pytest

from benchmark.checks.test_program_spans import (MS, Obs, annotation_events,
                                                 device_obs, log_of_five,
                                                 reader, span, step)
from benchmark.harness import program_spans as ps, step_cover as sc
from benchmark.harness.xplane import Event

NEW = ["engine_lock_wait_p99_ms.serve", "retire_ms_per_request.serve",
       "idle_in_retire_share.serve", "idle_in_fetch_share.serve",
       "slow_steps_in_window.serve", "gc_pause_share.serve",
       "compile_seconds_in_window.serve"]
m = 1e-3


def waiting_step(t0, lock_ms=(0.0, 0.0, 0.0), finished=0, wait_ms=12.0,
                 release_ms=6.0, events=()):
    """``step`` of test_program_spans with what PR 39 adds: ``cpu_ms``
    and ``lock_wait_ms`` (on the step, its commit and its admission),
    ``wait_ms`` on the fetch, and, with ``finished``, an
    ``llm.step.retire`` of one ``runner.wait`` and a ``runner.release``
    and an ``llm.step.finalize`` (0.5 ms) a request inside the commit,
    which grows by them. ``events``: hung under the decode span."""
    st = step(t0)
    st["attrs"].update(cpu_ms=3.0, lock_wait_ms=lock_ms[0])
    decode, commit, admit = st["children"]
    decode["children"][2]["attrs"]["wait_ms"] = 90.0
    decode["children"] += list(events)
    commit["attrs"].update(lock_wait_ms=lock_ms[1], finished=finished)
    admit["attrs"]["lock_wait_ms"] = lock_ms[2]
    if finished:
        a = commit["t0"] + 0.2 * m
        kids = [span(sc.WAIT, a, a + wait_ms * m)]
        a += wait_ms * m
        for _ in range(finished):
            kids += [span(sc.RELEASE, a, a + release_ms * m),
                     span("llm.step.finalize", a + release_ms * m,
                          a + (release_ms + 0.5) * m)]
            a += (release_ms + 0.5) * m
        retire = span(sc.RETIRE, commit["t0"] + 0.2 * m, a + 0.3 * m, kids,
                      n=finished, lock_wait_ms=0.25)
        grow = retire["t1"] - retire["t0"]
        commit["children"] = [retire]
        commit["t1"] += grow
        admit["t0"] += grow
        admit["t1"] += grow
        st["t1"] += grow
    return st


def gc_event(t0, ms_, generation=0, thread=None):
    attrs = {"generation": generation, "collected": 3}
    if thread:
        attrs["thread"] = thread
    return span(sc.GC, t0, t0 + ms_ * m, **attrs)


def waits_obs(**more):
    """Window [100, 101]: four steps inside it. Lock waits by step 0,
    0.9 (0.2 + 0.3 + 0.4), 0, 0.25 (its retire's); the last step retires
    two requests (wait 12 ms, twice release 6 + finalize 0.5, 0.3 of its
    own: 25.3 ms). A collection of 2 ms under a decode span, one of 8 ms
    on another thread, one before the window; a compile of 30 ms inside
    it and one of 2 s before it."""
    log = [step(99.95), waiting_step(100.1),
           waiting_step(100.3, lock_ms=(0.2, 0.3, 0.4)),
           waiting_step(100.5, events=[gc_event(100.52, 2.0)]),
           waiting_step(100.7, finished=2)]
    ring = [gc_event(99.0, 50.0, 2, "w"), gc_event(100.05, 8.0, 1, "w"),
            span(sc.COMPILE, 97.0, 99.0, thread="w"),
            span(sc.COMPILE, 100.9, 100.93, thread="w")]
    obs = Obs(log, **more)
    obs.engine_metrics.update(process_events=ring, slow_steps=[],
                              slow_steps_total=0, steps_total=5)
    return obs


def test_lock_wait_is_summed_over_a_steps_tree(capsys):
    obs = waits_obs()
    assert [sc.lock_wait_ms(s) for s in ps.window_steps(obs)] \
        == pytest.approx([0.0, 0.9, 0.0, 0.25])
    # numpy's percentile of [0, 0, 0.25, 0.9] at 99
    assert reader(NEW[0]).read(obs) == pytest.approx(0.25 + 0.65 * 0.97)
    assert "steps that waited at all: 2" in capsys.readouterr().out
    assert sc.lock_wait_ms(step(100.0)) is None


def test_retire_time_over_the_requests_retired(capsys):
    assert reader(NEW[1]).read(waits_obs()) == pytest.approx(25.3 / 2)
    said = capsys.readouterr().out
    assert "1 llm.step.retire spans (1 commits that finished a request) " \
        "retired 2 requests" in said
    assert "runner.wait 6.000 ms a request, runner.release 6.000" in said
    assert "disagree: 0" in said and "wait_ms over the span: 0 of 4" in said


def test_events_come_from_the_trees_and_the_ring(capsys):
    obs = waits_obs()
    assert [e["t0"] for e in sc.events(obs, sc.GC)] == [100.05, 100.52]
    assert reader(NEW[5]).read(obs) == pytest.approx(100 * 0.010 / 1.0)
    assert "by generation {0: 1, 1: 1}" in capsys.readouterr().out
    assert reader(NEW[6]).read(obs) == pytest.approx(0.030)
    # an event that straddles the window's edge counts its part inside
    obs.engine_metrics["process_events"].append(gc_event(100.996, 10.0))
    assert reader(NEW[5]).read(obs) == pytest.approx(100 * 0.014 / 1.0)


def test_slow_steps_of_the_window_are_counted_and_printed(capsys):
    obs = waits_obs()
    rec = {"i": 7, "t0": 100.2, "t1": 101.6, "cpu_ms": 4.0,
           "tree": step(100.2), "engine_thread": "rtpu-llm-engine",
           "stacks": {"rtpu-llm-engine": ["model_runner.py:820 fetch",
                                          "engine.py:900 _decode"]},
           "stacks_at": 101.2, "watch_late_ms": 2.0, "events": [],
           "verdict": "device or runtime", "why": "waited"}
    obs.engine_metrics["slow_steps"] = [dict(rec, i=1, t0=90.0, t1=91.5),
                                        rec]
    assert reader(NEW[4]).read(obs) == 1.0
    said = capsys.readouterr().out
    assert "llm.step 7: 1.400 s, verdict 'device or runtime'" in said
    assert "model_runner.py:820 fetch <- engine.py:900 _decode" in said


# ------------------------------------------- device trace, hand-made

def retire_obs():
    """``device_obs`` of test_program_spans (busy [4,98), [114,208),
    [219,322) of a window [0,340)) with a retire [100,102) inside the
    first commit (its wait [100,100.5), a release to 101.5) and one
    [205,212) that the second decode program runs into until 208."""
    obs = device_obs()
    obs._step_cover_annotations = sorted(annotation_events() + [
        Event(sc.RETIRE, 100 * MS, 2 * MS),
        Event(sc.WAIT, 100 * MS, MS // 2),
        Event(sc.RELEASE, 100 * MS + MS // 2, 1 * MS),
        Event(sc.RETIRE, 205 * MS, 7 * MS),
        Event(sc.WAIT, 205 * MS, 3 * MS)], key=lambda e: e.start)
    return obs


def test_idle_inside_the_retire_annotations(capsys):
    obs = retire_obs()
    # [100,102) all idle; of [205,212) the chip is busy until 208
    assert reader(NEW[2]).read(obs) == pytest.approx(100 * (2 + 4) / 340)
    assert sc.idle_share_in(obs, sc.WAIT) == pytest.approx(100 * 0.5 / 340)
    assert "a part of idle_in_engine_share.serve" in capsys.readouterr().out
    assert reader(NEW[2]).read(obs) <= reader(
        "idle_in_engine_share.serve").read(obs)


def test_the_three_idle_shares_add_up_to_the_windows_idle_share():
    obs = retire_obs()
    three = [reader(n).read(obs) for n in (
        "idle_in_runner_share.serve", NEW[3], "idle_in_engine_share.serve")]
    assert three[1] == pytest.approx(100 * 7 / 340)
    assert sum(three) == pytest.approx(100 * (340 - 94 - 94 - 103) / 340)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("obs", [
    Obs(), Obs([], []), Obs(log_of_five(), []),
    Obs([step(99.95), step(100.95)], [])],
    ids=["parent-program", "empty-logs", "parent-steps",
         "only-straddling-steps"])
def test_a_program_without_the_records_reads_none(name, obs):
    assert reader(name).read(obs) is None


def test_every_new_metric_has_its_entry_and_its_cells():
    from benchmark.harness import cells
    entries = {e["name"]: e for e in cells.benchmark_json()["per_layer"]}
    for name in NEW:
        assert entries[name]["workloads"] == list(sc.SERVING_CELLS)
        assert entries[name]["moves"] == "serve_tokens_per_s"
    assert [e["name"] for e in cells.benchmark_json()["per_layer"]][-7:] \
        == NEW
