"""Median host time of a decode call before the device has all it needs: runner.build_inputs (numpy) + runner.dispatch (five uploads and the jitted call), from step_log."""

NAME = "runner_host_p50_ms.serve"
UNIT = "ms"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(obs):
    from benchmark.harness import program_spans as ps, stats
    values = [ps.decode_runner_host_ms(s) for s in ps.window_steps(obs) or ()]
    return stats.median([v for v in values if v is not None])
