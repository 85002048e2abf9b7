"""Device time of the operations traced under the optimizer scope (clipping, AdamW, apply_updates, the gradient norm) over the step's device time."""

NAME = "optimizer_step_share.train"
UNIT = "%"
LAYER = "train step"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import program_spans as ps
    return ps.scope_step_share(obs, ("optimizer",))
