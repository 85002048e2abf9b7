"""Device time of the operations traced under the four dense sublayers' scopes (sub0/mla, sub0/mlp, sub1/mla, sub1/mlp: the two attentions and two SwiGLUs that the shortcut sets beside every routed product) over the decode steps' device time."""

NAME = "longcat_dense_step_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import decode_scopes as ds, longcat_views
    if not ds.decode_ops(obs):
        return None
    print(f"[{NAME}] by scope, ms a decode step: " + ", ".join(
        f"{s} {ds.scope_ms(obs, (s,)) or 0:.3f}"
        for s in longcat_views.SCOPES), flush=True)
    return ds.scope_share(obs, longcat_views.DENSE_SCOPES)
