"""What the Jamba cell's per-layer metrics read beside ``decode_scopes``,
``k2_views`` and ``laguna_views`` (whose prefill programs of the traced
window and device time of a prefill program's scopes serve this cell as
they are): the decode steps' own counters for a model without experts,
what the window's prefill steps held, program by program (this cell's
prompts fall into fifteen programs of one to eight rows), and what a
state slot's hand-over cost since the warm-up. A program without the
spans, counters or scopes (the parent of the PR that added them), a run
without a trace: ``None``, never an error.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Optional, Tuple

from benchmark.harness import k2_views, program_spans as ps, stats
from benchmark.harness.laguna_views import prefill_scope_ms  # noqa: F401


def decode_counters(obs) -> Optional[Dict[str, float]]:
    """Medians over the window's decode steps of what the program counts
    of each (``runner.dispatch``): the rows, their live tokens; and how
    many steps named each (recurrence, attention) path."""
    rows, live, said = [], [], {}
    for step in ps.window_steps(obs) or ():
        for d in ps.named(step, "llm.step.decode"):
            for s in ps.named(d, "runner.dispatch"):
                a = s.get("attrs", {})
                if "recurrence" not in a or "live_tokens" not in a:
                    continue
                rows.append(d.get("attrs", {}).get("n", 0))
                live.append(a["live_tokens"])
                key = f"{a['recurrence']}+{a.get('attention')}"
                said[key] = said.get(key, 0) + 1
    if not rows:
        return None
    return {"n_seqs": stats.median(rows), "live_tokens": stats.median(live),
            "steps": len(rows), "paths": said}


def prompts_by_program(obs) -> Dict[Tuple[int, int], list]:
    """The window's prefill steps by their padded program (rows,
    length): for each, the (real tokens, prompts) of every step that ran
    it."""
    out: Dict[Tuple[int, int], list] = {}
    for step in ps.window_steps(obs) or ():
        for p in ps.named(step, "llm.step.prefill"):
            for s in ps.named(p, "runner.dispatch"):
                a = s.get("attrs", {})
                if "prompt_tokens" in a:
                    out.setdefault((int(a["B"]), int(a["S"])), []).append(
                        (a["prompt_tokens"], p.get("attrs", {}).get("n", 1)))
    return out


def traced_prefill_need(obs, need: Callable[[float, float], float]
                        ) -> Optional[Tuple[float, float, Dict]]:
    """(what the traced prefill programs needed, their device seconds,
    how many ran by program): each program whole inside the traced window
    is charged the mean of ``need(tokens, prompts)`` over the WINDOW's
    prefill steps of its own (rows, length) (a program's name says both;
    which prompts it ran is not on the trace)."""
    runs = k2_views.prefill_programs(obs)
    by_program = prompts_by_program(obs)
    if not runs or not by_program:
        return None
    mean = {k: sum(need(n, r) for n, r in v) / len(v)
            for k, v in by_program.items()}
    total, took, seen = 0.0, 0.0, {}
    for e in runs:
        m = re.search(r"_b(\d+)_s(\d+)", e.name)
        key = (int(m.group(1)), int(m.group(2))) if m else None
        if key not in mean:
            continue
        total += mean[key]
        took += e.dur / 1e9
        seen[key] = seen.get(key, 0) + 1
    return (total, took, seen) if took else None


def state_admits(obs) -> Optional[Tuple[int, float]]:
    """(sequences given a zeroed state slot, host seconds inside
    ``runner.state.admit``) between the end of the warm-up and the end of
    the run: the program's ``state_admits_total`` and
    ``state_admit_seconds_total`` now, less what the runner read of them
    before the load started (the warm-up's admissions compile the
    hand-over's program)."""
    now = getattr(obs, "engine_metrics", None) or {}
    before = getattr(obs, "counters_before", None) or {}
    if "state_admits_total" not in now:
        return None
    n = now["state_admits_total"] - before.get("state_admits_total", 0)
    s = now["state_admit_seconds_total"] \
        - before.get("state_admit_seconds_total", 0.0)
    return (int(n), float(s)) if n > 0 else None
