"""What an engine step says of its own waits, as the per-layer metrics
read it (PR 39): ``lock_wait_ms`` / ``cpu_ms`` / ``wait_ms`` on the
``step_log`` trees, ``llm.step.retire`` with ``runner.wait`` and
``runner.release`` under it, the replica's ``py.gc`` and ``jax.compile``
events (in the trees where the engine thread had a span open, else in
``process_events``), the ``slow_steps`` records, and the same spans as
annotations on the device trace's clock.

The host plane is loaded here with this module's own set of names
(``program_spans.PROGRAM_SPANS`` lacks the five below). A program
without these records (the parent of PR 39) gives ``None`` everywhere.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.harness import program_spans as ps, xplane

RETIRE, WAIT, RELEASE = "llm.step.retire", "runner.wait", "runner.release"
NAMES = ps.PROGRAM_SPANS + (RETIRE, WAIT, RELEASE, "llm.step.finalize",
                            "runner.state.admit")
GC, COMPILE = "py.gc", "jax.compile"

SERVING_CELLS = ("gpt2_large.serve_closed32",
                 "kimi_linear_48b_a3b.serve_closed64",
                 "kimi_k2_7_code.serve_closed32_ctx8k",
                 "laguna_xs_2.serve_closed64_ctx8k")


def note(msg: str) -> None:
    print(f"[step_cover] {msg}", flush=True)


def metrics(obs) -> Dict[str, Any]:
    return getattr(obs, "engine_metrics", None) or {}


# ------------------------------------------------- step_log (host clock)

def lock_wait_ms(step: Dict[str, Any]) -> Optional[float]:
    """The step's waits for the engine lock, summed over its tree; None
    where no span of it carries the attribute."""
    waits = [s["attrs"]["lock_wait_ms"] for s in ps.walk(step)
             if "lock_wait_ms" in s.get("attrs", {})]
    return sum(waits) if waits else None


def describe_long_steps(obs, over_ms: float = 100.0) -> None:
    """What the window's decode-only steps of ``over_ms`` or more say of
    their waits, inside the traced seconds, in the seconds after them
    (the profiler's ``stop_trace`` at work in the replica) and elsewhere:
    medians of the step, its ``cpu_ms``, its fetch's ``wait_ms``, its
    ``lock_wait_ms`` and, where the watcher woke in it, how late."""
    from benchmark.harness import stats
    steps = [s for s in ps.window_steps(obs) or ()
             if not ps.named(s, "llm.step.prefill")]
    tw = getattr(obs, "trace_window_host", None) or {}
    lo, hi = tw.get("t0", float("inf")), tw.get("t1", float("inf"))
    groups = {"in the traced seconds": [], "in the 30 s after them": [],
              "elsewhere": []}
    for s in steps:
        key = "in the traced seconds" if lo <= s["t0"] and s["t1"] <= hi \
            else "in the 30 s after them" if hi <= s["t0"] <= hi + 30.0 \
            else "elsewhere"
        groups[key].append(s)

    def med(vals):
        vals = [v for v in vals if v is not None]
        return f"{stats.median(vals):.3f}" if vals else "-"

    for key, group in groups.items():
        long = [s for s in group if ps.ms(s) >= over_ms]
        if not group:
            continue
        late = [s["attrs"]["watch_late_ms"] for s in long
                if "watch_late_ms" in s["attrs"]]
        note(f"decode-only steps {key}: {len(group)}, median "
             f"{med(map(ps.ms, group))} ms, cpu_ms {med(s['attrs'].get('cpu_ms') for s in group)}; "
             f"of {over_ms:g} ms or more: {len(long)}"
             + (f", median {med(map(ps.ms, long))} ms (largest "
                f"{max(map(ps.ms, long)):.1f}), cpu_ms "
                f"{med(s['attrs'].get('cpu_ms') for s in long)}, "
                f"runner.fetch {med(sum(map(ps.ms, ps.named(s, ps.RUNNER_FETCH))) for s in long)}"
                f" of which wait_ms "
                f"{med(sum(f['attrs'].get('wait_ms', 0.0) for f in ps.named(s, ps.RUNNER_FETCH)) for s in long)}"
                f", runner.dispatch {med(sum(map(ps.ms, ps.named(s, 'runner.dispatch'))) for s in long)}"
                f", lock_wait_ms {med(map(lock_wait_ms, long))}, "
                f"watch_late_ms in {len(late)} of them: median "
                f"{med(late)}, largest {max(late, default=0):.1f}"
                if long else ""))


def check_trees(obs) -> None:
    """What must hold on the window's trees: one ``llm.step.retire`` a
    commit that finished a request, ``wait_ms`` at most its
    ``runner.fetch``, ``cpu_ms`` at most its step plus the thread
    clock's error (where the kernel accounts CPU time by ticks, as the
    chip's host does, ``time.thread_time()`` moves in steps of 10 ms).
    Says how many break each."""
    steps = ps.window_steps(obs) or ()
    unmatched = sum(
        1 for st in steps for c in ps.named(st, "llm.step.commit")
        if (c["attrs"].get("finished", 0) > 0)
        != any(k["name"] == RETIRE for k in c["children"]))
    fetches = [f for st in steps for f in ps.named(st, ps.RUNNER_FETCH)
               if "wait_ms" in f["attrs"]]
    over = sum(1 for f in fetches if f["attrs"]["wait_ms"] > ps.ms(f))
    cpu = [st for st in steps if "cpu_ms" in st["attrs"]]
    cpu_over = sum(1 for st in cpu
                   if st["attrs"]["cpu_ms"] > ps.ms(st) + 10.0)
    quantum = min((st["attrs"]["cpu_ms"] for st in cpu
                   if st["attrs"]["cpu_ms"] > 0), default=0.0)
    note(f"tree checks over {len(steps)} steps: commits whose finished > 0 "
         f"and llm.step.retire disagree: {unmatched}; runner.fetch with "
         f"wait_ms over the span: {over} of {len(fetches)}; llm.step with "
         f"cpu_ms over the step + 10 ms: {cpu_over} of {len(cpu)} (the "
         f"smallest cpu_ms above 0: {quantum:.3f}); median cpu_ms over "
         f"step length "
         + (f"{100 * sorted(st['attrs']['cpu_ms'] / ps.ms(st) for st in cpu)[len(cpu) // 2]:.1f}%"
            if cpu else "-"))


def events(obs, name: str) -> Optional[List[Dict[str, Any]]]:
    """The replica's events of that name that overlap the window, from
    every thread: those hung into the step trees and those of the
    module's ring. None where the program hands out no ring."""
    ring = metrics(obs).get("process_events")
    if ring is None:
        return None
    found = [s for step in metrics(obs).get("step_log") or ()
             for s in ps.walk(step) if s["name"] == name]
    found += [e for e in ring if e["name"] == name]
    return sorted((e for e in found
                   if e["t1"] > obs.t0 and e["t0"] < obs.t1),
                  key=lambda e: e["t0"])


def seconds_inside(obs, found: List[Dict[str, Any]]) -> float:
    return sum(min(e["t1"], obs.t1) - max(e["t0"], obs.t0) for e in found)


def slow_steps(obs) -> Optional[List[Dict[str, Any]]]:
    """The ``slow_steps`` records whose step lies in the window (or
    straddles an edge of it)."""
    records = metrics(obs).get("slow_steps")
    if records is None:
        return None
    return [r for r in records if r["t1"] > obs.t0 and r["t0"] < obs.t1]


# ------------------------------- annotations on the device trace's clock

def annotations(obs) -> List[xplane.Event]:
    """The program's step spans, this module's names, as the profiler
    saw them inside the traced window, by start time. Read once a run."""
    cached = getattr(obs, "_step_cover_annotations", None)
    if cached is not None:
        return cached
    out: List[xplane.Event] = []
    path = ps.trace_path(obs)
    if path and getattr(obs, "trace_window", None):
        lo, hi = obs.trace_window
        trace = xplane.load(path, host_names=set(NAMES), keep_stats=False)
        out = sorted((e for e in trace.host_spans
                      if e.start >= lo and e.end <= hi),
                     key=lambda e: e.start)
    obs._step_cover_annotations = out
    return out


def idle_share_in(obs, name: str) -> Optional[float]:
    """The first chip's idle time inside the annotations of that name,
    over the traced window, in %. None without a device trace or where
    the trace holds no such annotation."""
    if obs.trace is None or not obs.trace.device_ops:
        return None
    spans = [e for e in annotations(obs) if e.name == name]
    if not spans:
        return None
    lo, hi = obs.trace_window
    idle = sum(e.dur - xplane.device_seconds_in(
        obs.trace, (e.start, e.end)) * 1e9 for e in spans)
    return 100.0 * idle / (hi - lo)
