"""Device time of a served decode step by ``jax.named_scope``, and the
step's own counters, for the per-layer metrics of a model whose step is
more than one kind of layer (``kda``, ``mla``, ``moe/*``).

The decode steps are the executions of the programs named
``jit_llm_decode_b<B>`` inside the traced window (line ``XLA Modules``);
an operation's scope is its name path in the trace's own copy of that
program (``hlo_names``). Control-flow parents (``while``, ``conditional``,
``call``) span their children and are left out, so times add up. A
program without the scope, a run without a trace, a step log without the
counters: ``None``, never an error.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from benchmark.harness import hlo_names, program_spans as ps, stats, xplane

DECODE_PROGRAM = "jit_llm_decode_b"
_PARENTS = (" while(", " conditional(", " call(")


def decode_steps(obs) -> List[xplane.Event]:
    if obs.trace is None or not obs.trace.device_modules:
        return []
    lo, hi = obs.trace_window
    return [e for e in next(iter(obs.trace.device_modules.values()))
            if e.name.startswith(DECODE_PROGRAM)
            and e.start >= lo and e.end <= hi]


def decode_ops(obs) -> List[Tuple[xplane.Event, str]]:
    """(operation, name path) of every leaf operation inside a traced
    decode step. Read once a run."""
    cached = getattr(obs, "_decode_ops", None)
    if cached is not None:
        return cached
    out: List[Tuple[xplane.Event, str]] = []
    steps = sorted(decode_steps(obs), key=lambda e: e.start)
    if steps and obs.trace.device_ops:
        paths = ps.op_paths(obs)
        starts = [e.start for e in steps]
        for e in next(iter(obs.trace.device_ops.values())):
            i = bisect.bisect_right(starts, e.start) - 1
            if i < 0 or e.end > steps[i].end \
                    or any(p in e.name for p in _PARENTS):
                continue
            names = paths.get(steps[i].name) or {}
            out.append((e, names.get(hlo_names.instruction_of(e.name), "")))
    obs._decode_ops = out
    return out


def step_ms(obs) -> Optional[float]:
    """Median device time of a decode step's program."""
    steps = decode_steps(obs)
    return stats.median([e.dur / 1e6 for e in steps]) if steps else None


def scope_ms(obs, scopes: Tuple[str, ...]) -> Optional[float]:
    """Device ms a decode step under these scopes (mean over the traced
    steps); None where no operation carries one."""
    steps = decode_steps(obs)
    pat = ps.scope_pattern(scopes)
    total = sum(e.dur for e, path in decode_ops(obs) if pat.search(path))
    if not steps or not total:
        return None
    return total / 1e6 / len(steps)


def scope_share(obs, scopes: Tuple[str, ...]) -> Optional[float]:
    """The same over the decode steps' own device time, in %."""
    steps = decode_steps(obs)
    part = scope_ms(obs, scopes)
    if part is None:
        return None
    return 100.0 * part * len(steps) / (sum(e.dur for e in steps) / 1e6)


def describe(obs) -> None:
    if getattr(obs, "_decode_scopes_said", False) or not decode_ops(obs):
        return
    obs._decode_scopes_said = True
    n = len(decode_steps(obs))
    ops = decode_ops(obs)
    ps.note(f"{n} decode steps traced, median {step_ms(obs):.3f} ms; leaf "
            f"operations {sum(e.dur for e, _ in ops) / 1e6 / n:.3f} ms a "
            "step; by scope, ms a step: " + ", ".join(
                f"{s} {scope_ms(obs, (s,)) or 0:.3f}" for s in (
                    "kda", "kda/conv", "kda/recurrence", "mla",
                    "moe/router", "moe/experts", "moe/shared", "mlp",
                    "lm_head")))
    slow = sorted(ops, key=lambda x: -x[0].dur)[:n * 6:n]
    ps.note("slowest operations of a step: " + "; ".join(
        f"{xplane.short_name(e.name, 60)} {e.dur / 1e3:.0f} us <- "
        f"{path[-60:]}" for e, path in slow))


def step_counters(obs) -> Optional[Dict[str, float]]:
    """Medians over the window's decode calls of what the program counts
    of each: rows, experts touched, (token, expert) pairs held here, the
    load's largest over mean; and of the benchmark's own span round
    ``adapter.decode``: the rows' live context tokens."""
    rows, touched, pairs, skew = [], [], [], []
    for step in ps.window_steps(obs) or ():
        for d in ps.named(step, "llm.step.decode"):
            for f in ps.named(d, ps.RUNNER_FETCH):
                a = f.get("attrs", {})
                if "experts_touched" in a:
                    rows.append(d.get("attrs", {}).get("n", 0))
                    touched.append(a["experts_touched"])
                    pairs.append(a["expert_tokens"])
                    skew.append(a["moe_max_over_mean"])
    if not rows:
        return None
    live = [s["live_tokens"] for s in getattr(obs, "spans", ())
            if s["name"] == "adapter.decode" and obs.t0 <= s["t0"]
            and s["t1"] <= obs.t1]
    return {"n_seqs": stats.median(rows),
            "experts_touched": stats.median(touched),
            "assignments": stats.median(pairs),
            "max_over_mean": stats.median(skew),
            "live_tokens": stats.median(live) if live else 0.0}
