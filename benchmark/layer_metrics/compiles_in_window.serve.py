"""Compile requests (cache hit or not) the replica saw inside the window; 0 when every shape was warmed."""

NAME = "compiles_in_window.serve"
UNIT = "count"
LAYER = "model step"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"


def read(obs):
    if obs.kind != "serve":
        return None
    return float(obs.compiles_in_window)
