"""Device time of the operations traced under the attn_window scope (projections, rotary, the page write, the paged kernel, the output) over the decode steps' device time."""

NAME = "smallthinker_attn_window_step_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import decode_scopes as ds
    return ds.scope_share(obs, ("attn_window",))
