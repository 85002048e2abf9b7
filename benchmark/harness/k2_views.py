"""What the Kimi-K2 cell's per-layer metrics read beside
``decode_scopes``: the prefill programs of the traced window, and what
the program's spans say of the window's prefill steps. A program without
the spans or counters (the parent of the PR that added them), a run
without a trace: ``None``, never an error.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmark.harness import program_spans as ps, xplane

PREFILL_PROGRAM = "jit_llm_prefill_b"


def prefill_programs(obs) -> List[xplane.Event]:
    """Executions of a prefill program whole inside the traced window."""
    if obs.trace is None or not obs.trace.device_modules:
        return []
    lo, hi = obs.trace_window
    return [e for e in next(iter(obs.trace.device_modules.values()))
            if e.name.startswith(PREFILL_PROGRAM)
            and e.start >= lo and e.end <= hi]


def prefill_steps(obs) -> Optional[Dict[str, float]]:
    """Means over the measured window's prefill steps of what the
    program counts of each: the prompts' real tokens and their padded
    bucket (``runner.dispatch``), the (token, expert) pairs its held
    experts got (``runner.fetch``)."""
    tokens, padded, pairs = [], [], []
    for step in ps.window_steps(obs) or ():
        for p in ps.named(step, "llm.step.prefill"):
            d = [s.get("attrs", {}) for s in ps.named(p, "runner.dispatch")]
            f = [s.get("attrs", {}) for s in ps.named(p, ps.RUNNER_FETCH)]
            if d and "prompt_tokens" in d[0] and f \
                    and "expert_tokens" in f[0]:
                tokens.append(d[0]["prompt_tokens"])
                padded.append(d[0]["padded_tokens"])
                pairs.append(f[0]["expert_tokens"])
    if not tokens:
        return None
    n = float(len(tokens))
    return {"steps": n, "prompt_tokens": sum(tokens) / n,
            "padded_tokens": sum(padded) / n,
            "assignments": sum(pairs) / n,
            "prompt_tokens_sq": sum(t * t for t in tokens) / n}
