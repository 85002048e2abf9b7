"""What the Laguna cell's per-layer metrics read beside ``decode_scopes``
and ``k2_views``: what the program's dispatch spans say of the window's
decode steps by page group, and the device time of a prefill program's
operations by scope. A program without the spans, counters or scopes
(the parent of the PR that added them), a run without a trace: ``None``,
never an error.
"""

from __future__ import annotations

import bisect
from typing import Dict, Optional, Tuple

from benchmark.harness import decode_scopes as ds, hlo_names, k2_views, \
    program_spans as ps, stats

_DISPATCH = ("live_tokens", "window_tokens", "kv_pages_live",
             "kv_window_pages_live", "kv_window_pages_held")


def decode_counters(obs) -> Optional[Dict[str, float]]:
    """Medians over the window's decode steps of what the program counts
    of each: rows, experts touched, (token, expert) pairs
    (``runner.fetch``); the rows' live tokens, what their windows hold,
    the live pages of the full group, the pages the window group holds
    for them (``runner.dispatch``)."""
    got = {k: [] for k in ("n_seqs", "experts_touched", "assignments",
                           *_DISPATCH)}
    for step in ps.window_steps(obs) or ():
        for d in ps.named(step, "llm.step.decode"):
            a = next((s.get("attrs", {})
                      for s in ps.named(d, "runner.dispatch")), {})
            f = next((s.get("attrs", {})
                      for s in ps.named(d, ps.RUNNER_FETCH)), {})
            if "window_tokens" not in a or "experts_touched" not in f:
                continue
            got["n_seqs"].append(d.get("attrs", {}).get("n", 0))
            got["experts_touched"].append(f["experts_touched"])
            got["assignments"].append(f["expert_tokens"])
            for k in _DISPATCH:
                got[k].append(a[k])
    if not got["n_seqs"]:
        return None
    return {k: stats.median(v) for k, v in got.items()}


def window_pages_share(obs) -> Optional[float]:
    """Over the window's decode steps: the pages the window group holds
    for the running sequences over the pages their whole contexts take
    in a layer (what a sliding layer kept whole would hold), in %."""
    held = live = 0
    for step in ps.window_steps(obs) or ():
        for d in ps.named(step, "llm.step.decode"):
            for s in ps.named(d, "runner.dispatch"):
                a = s.get("attrs", {})
                if "kv_window_pages_held" in a:
                    held += a["kv_window_pages_held"]
                    live += a["kv_pages_live"]
    return 100.0 * held / live if live else None


def prefill_scope_ms(obs, scopes: Tuple[str, ...]) -> Optional[float]:
    """Device ms a traced prefill program under these scopes (mean over
    the programs whole inside the traced window), as
    ``decode_scopes.scope_ms`` reads a decode step's."""
    runs = sorted(k2_views.prefill_programs(obs), key=lambda e: e.start)
    if not runs or not obs.trace.device_ops:
        return None
    paths = ps.op_paths(obs)
    pat = ps.scope_pattern(scopes)
    starts = [e.start for e in runs]
    total = 0
    for e in next(iter(obs.trace.device_ops.values())):
        i = bisect.bisect_right(starts, e.start) - 1
        if i < 0 or e.end > runs[i].end \
                or any(p in e.name for p in ds._PARENTS):
            continue
        names = paths.get(runs[i].name) or {}
        if pat.search(names.get(hlo_names.instruction_of(e.name), "")):
            total += e.dur
    return total / 1e6 / len(runs) if total else None
