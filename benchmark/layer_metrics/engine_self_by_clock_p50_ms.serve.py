"""Median of an engine step less its runner_ms: the model runner's spans that ran between the step's ends on the engine thread, a prompt's late runner.fetch counted in the step that waited for it (not in the step whose tree holds its record, as engine_self_p50_ms.serve counts it). Scheduling, admission, sampling and publishing, locks included. None where no step carries the attribute."""

NAME = "engine_self_by_clock_p50_ms.serve"
UNIT = "ms"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(obs):
    from benchmark.harness import program_spans as ps, stats
    steps = [s for s in ps.window_steps(obs) or ()
             if "runner_ms" in s.get("attrs", {})]
    if not steps:
        return None
    ps.note(f"steps with runner_ms: {len(steps)}; median runner_ms "
            f"{stats.median([s['attrs']['runner_ms'] for s in steps]):.3f}"
            f", median step {stats.median([ps.ms(s) for s in steps]):.3f} "
            f"ms; by the tree the median self is "
            f"{stats.median([ps.self_ms(s) for s in steps]):.3f} ms")
    return stats.median([ps.ms(s) - s["attrs"]["runner_ms"] for s in steps])
