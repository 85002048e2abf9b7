"""Device time of the operations traced under the mla scope (projections, the latent page write and gather, absorbed attention) over the decode steps' device time."""

NAME = "mla_step_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import decode_scopes as ds
    ds.describe(obs)
    return ds.scope_share(obs, ("mla",))
