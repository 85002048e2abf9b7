"""Median wall time of an engine step in the window: the program's own llm.step spans (__llm_metrics__ step_log, host clock), prefill steps among them."""

NAME = "engine_step_wall_p50_ms.serve"
UNIT = "ms"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(obs):
    from benchmark.harness import program_spans as ps, stats
    steps = ps.window_steps(obs)
    if not steps:
        return None
    ps.describe_steps(obs)
    return stats.median([ps.ms(s) for s in steps])
