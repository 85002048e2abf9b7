"""The engine's own median time to first token (__llm_metrics__ ttft_p50_s: its last 512 requests)."""

NAME = "engine_ttft_p50_ms"
UNIT = "ms"
LAYER = "engine"
MOVES = "ttft_p90_ms"
SOURCE = "program_counter"


def read(obs):
    v = obs.engine_metrics.get("ttft_p50_s")
    return None if not v else v * 1e3
