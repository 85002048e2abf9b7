"""Device time of the operations traced under the routed layers' scopes (moe/router, moe/experts, moe/shared) over the decode steps' device time."""

NAME = "moe_step_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import decode_scopes as ds
    ds.describe(obs)
    return ds.scope_share(obs, ("moe/router", "moe/experts", "moe/shared"))
