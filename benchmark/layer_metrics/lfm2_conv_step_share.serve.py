"""Device time of the operations traced under the conv scope (conv/in_proj, conv/mix with the two gates, the three taps and the tail's read and write, conv/out_proj: the 12 gated short convolutions) over the decode steps' device time."""

NAME = "lfm2_conv_step_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import decode_scopes as ds
    return ds.scope_share(obs, ("conv",))
