"""What the program itself records of its steps, as the per-layer metrics
read it: ``tracing.step_span`` trees (``__llm_metrics__`` ``step_log``,
host clock), the same spans as profiler annotations on the device trace's
clock (read here from the run's ``.xplane.pb``: ``run.py`` keeps only its
own spans from the host plane), one record a finished request
(``request_log``), and the ``jax.named_scope`` paths of device operations
(from the trace's own copy of each program, ``hlo_names``).

A program without these (the parent of the PR that added them) gives
``None`` everywhere, and a metric's line then leaves the metric out.
"""

from __future__ import annotations

import bisect
import os
import re
from typing import Any, Dict, Iterator, List, Optional, Tuple

from benchmark.harness import cells, hlo_names, stats, trace_views, xplane

BENCH_OUT = os.path.join(cells.ROOT, ".bench_out")

STEP = "llm.step"
RUNNER_HOST = ("runner.build_inputs", "runner.dispatch")
RUNNER_FETCH = "runner.fetch"
PROGRAM_SPANS = (STEP, "llm.step.decode", "llm.step.admit",
                 "llm.step.prefill", "llm.step.commit") + RUNNER_HOST + (
                 RUNNER_FETCH, "data.feed.host_batch",
                 "data.feed.device_put")


def note(msg: str) -> None:
    """A reading that belongs beside a metric's value in the run's log
    (the result line is the last line of standard output, not this)."""
    print(f"[program_spans] {msg}", flush=True)


# ------------------------------------------------- step_log (host clock)

def walk(span: Dict[str, Any]) -> Iterator[Dict[str, Any]]:
    yield span
    for child in span["children"]:
        yield from walk(child)


def ms(span: Dict[str, Any]) -> float:
    return (span["t1"] - span["t0"]) * 1e3


def steps_between(log, t0: float, t1: float) -> List[Dict[str, Any]]:
    """Steps that lie whole inside [t0, t1]; one that straddles an edge
    is left out."""
    return [s for s in log or () if s["t0"] >= t0 and s["t1"] <= t1]


def window_steps(obs) -> Optional[List[Dict[str, Any]]]:
    """The ``llm.step`` trees of the measured window; None where the
    program hands out none."""
    log = (getattr(obs, "engine_metrics", None) or {}).get("step_log")
    return steps_between(log, obs.t0, obs.t1) or None


def named(step: Dict[str, Any], *names: str) -> List[Dict[str, Any]]:
    return [s for s in walk(step) if s["name"] in names]


def self_ms(step: Dict[str, Any]) -> float:
    """The engine's own part of a step: the step less the model runner's
    spans under it."""
    return ms(step) - sum(ms(s) for s in named(
        step, *RUNNER_HOST, RUNNER_FETCH))


def decode_runner_host_ms(step: Dict[str, Any]) -> Optional[float]:
    """Host time of the step's decode call before the device has all it
    needs: numpy inputs, uploads and the dispatch."""
    parts = [ms(s) for d in step["children"]
             if d["name"] == "llm.step.decode"
             for s in d["children"] if s["name"] in RUNNER_HOST]
    return sum(parts) if parts else None


def describe_steps(obs) -> None:
    """Medians of every span of the window's steps, and the median
    ``llm.step`` inside the traced seconds against outside them (what
    the open profiler costs a step)."""
    steps = window_steps(obs)
    if not steps:
        return
    by_name: Dict[str, List[float]] = {}
    for st in steps:
        for s in walk(st):
            by_name.setdefault(s["name"], []).append(ms(s))
    note("span medians over the window, ms (count): " + ", ".join(
        f"{k} {stats.median(v):.3f} ({len(v)})"
        for k, v in sorted(by_name.items())))
    plain = [st for st in steps if not named(st, "llm.step.prefill")]
    note(f"decode-only steps: {len(plain)} of {len(steps)}, median "
         f"{stats.median([ms(s) for s in plain]) or 0:.3f} ms")
    tw = getattr(obs, "trace_window_host", None) or {}
    if "t0" in tw:
        inside = [ms(s) for s in steps_between(plain, tw["t0"], tw["t1"])]
        outside = [ms(s) for s in plain
                   if s["t1"] <= tw["t0"] or s["t0"] >= tw["t1"]]
        note(f"decode-only llm.step median inside the traced "
             f"{tw['t1'] - tw['t0']:.1f} s: {stats.median(inside) or 0:.3f}"
             f" ms (n={len(inside)}); outside: "
             f"{stats.median(outside) or 0:.3f} ms (n={len(outside)})")


# --------------------------------------------- request_log (host clock)

def queue_waits_ms(obs) -> List[float]:
    log = (getattr(obs, "engine_metrics", None) or {}).get("request_log")
    return [(r["t_admit"] - r["t_arrival"]) * 1e3 for r in log or ()
            if r.get("t_admit") is not None
            and obs.t0 <= r["t_arrival"] <= obs.t1]


# ------------------------------- annotations on the device trace's clock

def annotations(obs) -> List[xplane.Event]:
    """The program's step spans as the profiler saw them, inside the
    traced window, by start time. Read once a run."""
    cached = getattr(obs, "_program_annotations", None)
    if cached is not None:
        return cached
    out: List[xplane.Event] = []
    path = trace_path(obs)
    if path and getattr(obs, "trace_window", None):
        lo, hi = obs.trace_window
        trace = xplane.load(path, host_names=set(PROGRAM_SPANS),
                            keep_stats=False)
        out = sorted((e for e in trace.host_spans
                      if e.start >= lo and e.end <= hi),
                     key=lambda e: e.start)
    obs._program_annotations = out
    return out


def annotated(obs, *names: str) -> List[xplane.Event]:
    return [e for e in annotations(obs) if e.name in names]


def device_ms_in(obs, name: str) -> List[float]:
    """Device-busy time inside each annotation of that name."""
    if obs.trace is None or not obs.trace.device_ops:
        return []
    return [xplane.device_seconds_in(obs.trace, (e.start, e.end)) * 1e3
            for e in annotated(obs, name)]


def idle_shares(obs) -> Optional[Dict[str, float]]:
    """The first chip's idle time in the traced window, as shares of the
    window in %, by the span it lies in: ``runner`` (numpy inputs,
    uploads and dispatch), ``fetch`` (the wait for the logits and their
    copy to the host), ``engine`` (everything else: admission, commit,
    the engine's own statements, between steps). A gap is cut where a
    span ends (between two decode steps one gap runs from the end of a
    fetch through commit and admission to the moment the next step's
    program starts, deep in its dispatch), so the three add up to the
    window's idle share exactly."""
    if obs.trace is None or not obs.trace.device_ops \
            or not annotated(obs, STEP):
        return None
    cached = getattr(obs, "_idle_shares", None)     # two metrics read it
    if cached is not None:
        return cached
    lo, hi = obs.trace_window
    ops = next(iter(obs.trace.device_ops.values()))
    busy = xplane.clip(xplane.busy_intervals(ops), lo, hi)
    spans = annotated(obs, *RUNNER_HOST, RUNNER_FETCH)   # disjoint
    starts = [e.start for e in spans]
    acc = {"runner": 0, "fetch": 0, "engine": 0}
    cursor = lo
    for s, e in busy + [(hi, hi)]:
        if s > cursor:
            i = max(0, bisect.bisect_right(starts, cursor) - 1)
            inside = 0
            while i < len(spans) and spans[i].start < s:
                part = min(s, spans[i].end) - max(cursor, spans[i].start)
                if part > 0:
                    acc["fetch" if spans[i].name == RUNNER_FETCH
                        else "runner"] += part
                    inside += part
                i += 1
            acc["engine"] += s - cursor - inside
        cursor = max(cursor, e)
    obs._idle_shares = {k: 100.0 * v / (hi - lo) for k, v in acc.items()}
    return obs._idle_shares


# ------------------------------------ named scopes of device operations

def trace_path(obs) -> Optional[str]:
    """The ``.xplane.pb`` ``run.py`` wrote for this cell, if it did."""
    try:
        return xplane.find_xplane(os.path.join(
            BENCH_OUT, obs.cell["name"], "trace"))
    except FileNotFoundError:
        return None


def op_paths(obs) -> Dict[str, Dict[str, str]]:
    """{program: {HLO instruction: its name path}} of the traced run
    (``hlo_names``: the trace's own copy of each optimized module). Read
    once a run."""
    cached = getattr(obs, "_op_paths", None)
    if cached is None:
        path = trace_path(obs)
        cached = obs._op_paths = hlo_names.op_names(path) if path else {}
    return cached


def scope_pattern(scopes: Tuple[str, ...]) -> "re.Pattern":
    """A ``jax.named_scope`` is a component of an operation's name path:
    ``jit(train_step)/jvp(GPT2)/lm_head/...`` going forward,
    ``.../transpose(jvp(loss))/...`` transposed."""
    return re.compile(r"(?:^|[/(])(?:" + "|".join(map(re.escape, scopes))
                      + r")(?:[/)]|$)")


def step_ops(obs) -> List[Tuple[xplane.Event, str]]:
    """The device operations inside the traced steps, each with its name
    path ('' where the module gives the instruction none)."""
    steps = trace_views.step_events(obs)
    if not steps or not obs.trace.device_ops:
        return []
    paths = op_paths(obs).get(steps[0].name)
    if not paths:
        return []
    spans = sorted((e.start, e.end) for e in steps)
    starts = [s for s, _ in spans]
    out = []
    for e in next(iter(obs.trace.device_ops.values())):
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= spans[i][1]:
            out.append((e, paths.get(hlo_names.instruction_of(e.name), "")))
    return out


def scope_step_share(obs, scopes: Tuple[str, ...]) -> Optional[float]:
    """Device time of the operations under these scopes inside the traced
    steps, over the steps' device time, in %. None where no operation
    carries such a scope."""
    pat = scope_pattern(scopes)
    scoped = sum(e.dur for e, path in step_ops(obs) if pat.search(path))
    if not scoped:
        return None
    return 100.0 * scoped / sum(
        e.dur for e in trace_views.step_events(obs))


def describe_scopes(obs, scopes: Tuple[str, ...]) -> None:
    """Per scope: device ms a step, and one name path as the trace's
    module has it."""
    ops = step_ops(obs)
    n_steps = len(trace_views.step_events(obs))
    for scope in scopes:
        pat = scope_pattern((scope,))
        hits = [(e, path) for e, path in ops if pat.search(path)]
        if hits:
            total_ms = sum(e.dur for e, _ in hits) / 1e6
            note(f"scope {scope}: {total_ms / n_steps:.3f} ms a step in "
                 f"{len(hits) // n_steps} operations, e.g. "
                 f"{hlo_names.instruction_of(hits[0][0].name)} <- "
                 f"{hits[0][1]}")
    unnamed = sum(e.dur for e, path in ops if not path)
    if ops:
        note(f"operations with no name path: {unnamed / 1e6 / n_steps:.3f} ms "
             f"a step of {sum(e.dur for e, _ in ops) / 1e6 / n_steps:.3f}")
