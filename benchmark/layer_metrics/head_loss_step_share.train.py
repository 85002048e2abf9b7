"""Device time of the operations traced under the lm_head and loss scopes (forward and transposed) over the step's device time."""

NAME = "head_loss_step_share.train"
UNIT = "%"
LAYER = "train step"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import program_spans as ps
    ps.describe_scopes(obs, ("lm_head", "loss", "optimizer", "flash_fwd",
                             "flash_bwd"))
    return ps.scope_step_share(obs, ("lm_head", "loss"))
