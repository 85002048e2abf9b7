"""What the LFM2 cell's per-layer metrics read beside ``decode_scopes``,
``k2_views``, ``laguna_views`` and ``jamba_views`` (whose prefill programs
of the traced window, device time of a prefill program's scopes, prefill
steps by program and charge of the traced programs serve this cell as they
are): the decode steps' own counters for a model with routed experts, K
and V pages and NO recurrence, and the rows its routed product multiplied
beside the pairs that were routed. A program without the spans, counters
or scopes (the parent of the PR that added them), a run without a trace:
``None``, never an error.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.harness import program_spans as ps, stats
from benchmark.harness.jamba_views import (  # noqa: F401 (the readers')
    prompts_by_program, traced_prefill_need)
from benchmark.harness.laguna_views import prefill_scope_ms  # noqa: F401


def decode_counters(obs) -> Optional[Dict[str, float]]:
    """Medians over the window's decode steps of what the program counts
    of each: rows, experts touched, (token, expert) pairs
    (``runner.fetch``), the rows' live tokens (``runner.dispatch``); and
    how many steps named each (attention, routed product) path."""
    got = {k: [] for k in ("n_seqs", "experts_touched", "assignments",
                           "live_tokens")}
    said: Dict[str, int] = {}
    for step in ps.window_steps(obs) or ():
        for d in ps.named(step, "llm.step.decode"):
            a = next((s.get("attrs", {})
                      for s in ps.named(d, "runner.dispatch")), {})
            f = next((s.get("attrs", {})
                      for s in ps.named(d, ps.RUNNER_FETCH)), {})
            if "live_tokens" not in a or "experts_touched" not in f:
                continue
            got["n_seqs"].append(d.get("attrs", {}).get("n", 0))
            got["experts_touched"].append(f["experts_touched"])
            got["assignments"].append(f["expert_tokens"])
            got["live_tokens"].append(a["live_tokens"])
            key = f"{a.get('attention')}+{a.get('expert_product')}"
            said[key] = said.get(key, 0) + 1
    if not got["n_seqs"]:
        return None
    return dict({k: stats.median(v) for k, v in got.items()},
                steps=len(got["n_seqs"]), paths=said)


def rows_over_pairs(obs) -> Optional[float]:
    """Over the window's decode steps: the rows that went through an
    expert's three products (``expert_rows_multiplied``) over the routed
    (token, expert) pairs (``expert_tokens``)."""
    rows = pairs = 0
    for step in ps.window_steps(obs) or ():
        for d in ps.named(step, "llm.step.decode"):
            for f in ps.named(d, ps.RUNNER_FETCH):
                a = f.get("attrs", {})
                if "expert_rows_multiplied" in a and a.get("expert_tokens"):
                    rows += a["expert_rows_multiplied"]
                    pairs += a["expert_tokens"]
    return rows / pairs if pairs else None
