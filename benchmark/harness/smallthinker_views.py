"""What the SmallThinker cell's per-layer metrics read beside
``decode_scopes``, ``k2_views`` and ``laguna_views`` (whose medians of
the decode steps' counters and whose device time of a prefill program's
scopes serve this cell as they are): what the window group held of the
running sequences' whole rings, and what the window's prompts need,
bucket by bucket, since this cell's prompts fall into five programs. A
program without the spans, counters or scopes (the parent of the PR that
added them), a run without a trace: ``None``, never an error.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Optional, Tuple

from benchmark.harness import k2_views, program_spans as ps
from benchmark.harness.laguna_views import (  # noqa: F401 (the readers')
    decode_counters, prefill_scope_ms)


def ring_pages(steps) -> Tuple[int, int]:
    """Summed over those steps' decode dispatches: the window group's
    pages the running sequences held (rings taken by need), and the pages
    their whole rings would be."""
    held = whole = 0
    for step in steps or ():
        for d in ps.named(step, "llm.step.decode"):
            for s in ps.named(d, "runner.dispatch"):
                a = s.get("attrs", {})
                if "kv_window_pages_whole_rings" in a:
                    held += a["kv_window_pages_held"]
                    whole += a["kv_window_pages_whole_rings"]
    return held, whole


def ring_held_share(obs) -> Optional[float]:
    """``ring_pages`` over the window's steps: held over whole, in %."""
    held, whole = ring_pages(ps.window_steps(obs))
    return 100.0 * held / whole if whole else None


def prompts_by_bucket(obs) -> Dict[int, list]:
    """The window's prefill steps by their padded bucket: for each, the
    (real tokens, (token, expert) pairs) of every step that ran it."""
    out: Dict[int, list] = {}
    for step in ps.window_steps(obs) or ():
        for p in ps.named(step, "llm.step.prefill"):
            d = [s.get("attrs", {}) for s in ps.named(p, "runner.dispatch")]
            f = [s.get("attrs", {}) for s in ps.named(p, ps.RUNNER_FETCH)]
            if d and "prompt_tokens" in d[0] and f \
                    and "expert_tokens" in f[0]:
                out.setdefault(int(d[0]["padded_tokens"]), []).append(
                    (d[0]["prompt_tokens"], f[0]["expert_tokens"]))
    return out


def traced_prefill_need(obs, need: Callable[[float, float], float]
                        ) -> Optional[Tuple[float, float, Dict[int, int]]]:
    """(what the traced prefill programs needed, their device seconds,
    how many ran by bucket): each program whole inside the traced window
    is charged the mean of ``need(tokens, pairs)`` over the WINDOW's
    prefill steps of its own bucket (a program's name says its bucket;
    which prompt it ran is not on the trace), so a mix of short and long
    prompts is counted by what each program can have run."""
    runs = k2_views.prefill_programs(obs)
    by_bucket = prompts_by_bucket(obs)
    if not runs or not by_bucket:
        return None
    mean = {b: sum(need(n, a) for n, a in v) / len(v)
            for b, v in by_bucket.items()}
    total, took, seen = 0.0, 0.0, {}
    for e in runs:
        m = re.search(r"_b(\d+)_s(\d+)", e.name)
        bucket = int(m.group(1)) * int(m.group(2)) if m else None
        if bucket not in mean:
            continue
        total += mean[bucket]
        took += e.dur / 1e9
        seen[bucket] = seen.get(bucket, 0) + 1
    return (total, took, seen) if took else None
