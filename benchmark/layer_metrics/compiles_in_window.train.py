"""Compile requests (cache hit or not) the train worker saw inside the window; 0 when every shape was warmed."""

NAME = "compiles_in_window.train"
UNIT = "count"
LAYER = "train step"
MOVES = "train_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    if obs.kind != "train":
        return None
    return float(obs.compiles_in_window)
