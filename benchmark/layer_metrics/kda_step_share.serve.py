"""Device time of the operations traced under the kda scope (projections, kda/conv, kda/recurrence, gates) over the decode steps' device time."""

NAME = "kda_step_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import decode_scopes as ds
    ds.describe(obs)
    return ds.scope_share(obs, ("kda",))
