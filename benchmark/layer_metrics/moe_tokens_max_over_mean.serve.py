"""Per decode step, the largest number of tokens any held expert of a routed layer got over the mean (median of the layers), from the per-expert counts the program returns beside the logits; median over the window's decode steps."""

NAME = "moe_tokens_max_over_mean.serve"
UNIT = "x"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import decode_scopes as ds
    counters = ds.step_counters(obs)
    return None if counters is None else counters["max_over_mean"]
