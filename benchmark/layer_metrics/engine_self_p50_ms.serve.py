"""Median of an engine step less the model runner's spans under it (runner.build_inputs, runner.dispatch, runner.fetch): scheduling, admission, sampling and publishing, locks included."""

NAME = "engine_self_p50_ms.serve"
UNIT = "ms"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(obs):
    from benchmark.harness import program_spans as ps, stats
    steps = ps.window_steps(obs)
    if not steps:
        return None
    return stats.median([ps.self_ms(s) for s in steps])
