"""What a replica did before it was ready, as the ``setup_*`` per-layer
metrics read it: ``__llm_metrics__()["setup"]`` (``tracing.setup_report``:
``process_t0``, the ``llm.setup`` tree, one ``runner.dispatch`` record a
bucket's first call, one row a program the process traced, lowered or
compiled, and the process's counters; docs/TRACING.md, "Before a process
is ready"). Only what ended before the window's ``t0`` counts as set-up.

A program without the record (the parent of the PR that added it) gives
``None`` everywhere, and a metric's line then leaves the metric out.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from benchmark.harness import program_spans as ps

ROOT = "llm.setup"
JAX = ("jax.trace", "jax.lower", "jax.compile")


def note(msg: str) -> None:
    print(f"[setup] {msg}", flush=True)


def report(obs) -> Optional[Dict[str, Any]]:
    return (getattr(obs, "engine_metrics", None) or {}).get("setup")


def tree(obs) -> Optional[Dict[str, Any]]:
    """The replica's ``llm.setup`` span, if it ended before the window."""
    for span in (report(obs) or {}).get("spans", ()):
        if span["name"] == ROOT and span["t1"] <= obs.t0:
            return span
    return None


def seconds(span: Dict[str, Any]) -> float:
    return span["t1"] - span["t0"]


def jax_seconds(spans: List[Dict[str, Any]]) -> Dict[str, float]:
    """Seconds of the ``jax.*`` events at or below these spans, by name."""
    out = dict.fromkeys(JAX, 0.0)
    for span in spans:
        for s in ps.walk(span):
            if s["name"] in out:
                out[s["name"]] += seconds(s)
    return out


def rows_before(obs) -> Optional[List[Dict[str, Any]]]:
    """The rows of ``programs`` whose last event ended before the window;
    says which rows the window itself touched."""
    rep = report(obs)
    if rep is None:
        return None
    late = [r for r in rep["programs"] if r["t_last"] > obs.t0]
    if late:
        note(f"programs met again after the window began, left out: "
             f"{[r['fun'] for r in late][:8]} ({len(late)})")
    return [r for r in rep["programs"] if r["t_last"] <= obs.t0]


def describe_first_calls(obs) -> None:
    """The buckets' first calls before the window: how many, their
    dispatches' seconds, what of those jax's three stages took (the rest
    is the calls' own host work and, where a call waits, the device), and
    the five longest."""
    calls = [c for c in (report(obs) or {}).get("first_calls", ())
             if c["t1"] <= obs.t0]
    if not calls:
        return
    inside = jax_seconds(calls)
    total = sum(map(seconds, calls))
    decode = [c for c in calls if c["attrs"].get("S") == 1]
    note(f"first calls before the window: {len(calls)} buckets "
         f"({len(decode)} decode row counts), their runner.dispatch spans "
         f"{total:.2f} s, of which trace {inside['jax.trace']:.2f}, lower "
         f"{inside['jax.lower']:.2f}, compile or cache "
         f"{inside['jax.compile']:.2f}; the longest: " + ", ".join(
             f"B{c['attrs']['B']} S{c['attrs']['S']} {seconds(c):.2f} s"
             for c in sorted(calls, key=seconds, reverse=True)[:5]))


def describe_rows(rows: List[Dict[str, Any]], key, what: str) -> None:
    top = sorted(rows, key=key, reverse=True)[:5]
    note(f"{what} over {len(rows)} programs; the largest: " + ", ".join(
        f"{r['fun']} {key(r):.2f} s (traced {r['n']}x, compiled "
        f"{r['compiles']}x: {r['cache_hits']} hit, {r['cache_misses']} "
        f"miss)" for r in top))
