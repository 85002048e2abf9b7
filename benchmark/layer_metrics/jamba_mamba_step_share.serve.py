"""Device time of the operations traced under the mamba scope (mamba/in_proj, mamba/conv with the tail's read and write, mamba/x_proj, mamba/step, mamba/out_proj: the 26 Mamba mixers) over the decode steps' device time."""

NAME = "jamba_mamba_step_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import decode_scopes as ds
    return ds.scope_share(obs, ("mamba",))
