"""What the LongCat-Flash cell's per-layer metrics read beside
``decode_scopes``, ``k2_views`` and ``laguna_views``: what the program's
fetch spans say of the routers over the measured window. A program
without the counters (the parent of the PR that added them): ``None``,
never an error.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmark.harness import program_spans as ps

# the scopes of a step's four dense sublayers (models/longcat_flash.py)
DENSE_SCOPES = ("sub0/mla", "sub0/mlp", "sub1/mla", "sub1/mlp")
SCOPES = DENSE_SCOPES + ("moe/router", "moe/experts", "moe/zero", "lm_head")
# inside both ``sub*/mla`` (models/mla.py)
MLA_SCOPES = ("mla/q_lora", "mla/rope", "mla/write", "mla/attend", "mla/out")


def window_routing(step_log, t0: float, t1: float
                   ) -> Optional[Dict[str, int]]:
    """Sums over the steps whole inside [t0, t1] (prefill and decode) of
    what each ``runner.fetch`` span counts: the real tokens that went
    through the routers, their assignments to a zero-compute expert (all
    layers) and to a real expert held here, of ``routed_assignments``
    (tokens x routed layers x experts a token)."""
    out = {"routed_tokens": 0, "routed_assignments": 0,
           "zero_expert_tokens": 0, "expert_tokens": 0}
    seen = False
    for step in ps.steps_between(step_log, t0, t1):
        for f in ps.named(step, ps.RUNNER_FETCH):
            a = f.get("attrs", {})
            if "zero_expert_tokens" in a:
                seen = True
                for k in out:
                    out[k] += a[k]
    return out if seen else None


def zero_expert_share(obs) -> Optional[float]:
    """Assignments to a zero-compute expert over all assignments (tokens
    x layers x experts a token) of the measured window, in %."""
    r = window_routing(
        (getattr(obs, "engine_metrics", None) or {}).get("step_log"),
        obs.t0, obs.t1)
    if r is None or not r["routed_assignments"]:
        return None
    return 100.0 * r["zero_expert_tokens"] / r["routed_assignments"]
