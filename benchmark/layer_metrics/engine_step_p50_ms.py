"""The engine's own median decode step, host clock round the synced step (__llm_metrics__ itl_p50_s: its last 2048 tokens)."""

NAME = "engine_step_p50_ms"
UNIT = "ms"
LAYER = "engine"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"


def read(obs):
    v = obs.engine_metrics.get("itl_p50_s")
    return None if not v else v * 1e3
