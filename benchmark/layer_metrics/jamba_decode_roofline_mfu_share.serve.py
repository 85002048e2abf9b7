"""Least time the chip could take for a whole Jamba decode step (the larger of its FLOPs over peak and its bytes over bandwidth, from the configuration's fields and the step's counters: every weight read once with the token table as the head, every running sequence's Mamba state and convolution tail in and out, the live K and V of the two attention layers: benchmark/harness/costs_jamba.py) over the step's device time: the share that bounds any later claim in the cell."""

NAME = "jamba_decode_roofline_mfu_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

SCOPES = ("mamba", "mamba/in_proj", "mamba/conv", "mamba/x_proj",
          "mamba/step", "mamba/out_proj", "attn_full", "attn_full/attend",
          "mlp", "lm_head")


def read(obs):
    from benchmark.harness import costs, costs_jamba as cj, \
        decode_scopes as ds, jamba_views as jv
    took = ds.step_ms(obs)
    counters = jv.decode_counters(obs)
    if took is None or counters is None or obs.peaks is None:
        return None
    need = cj.decode_step_cost(obs.config["model"]["kwargs"],
                               counters["n_seqs"], counters["live_tokens"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[{NAME}] bound: {least['bound']}; least "
          f"{least['seconds'] * 1e3:.3f} ms ({need['bytes'] / 1e9:.3f} GB, "
          f"{need['flops'] / 1e9:.1f} GFLOP; counters {counters}), "
          f"measured {took:.3f} ms a step; by scope, ms a step: "
          + ", ".join(f"{s} {ds.scope_ms(obs, (s,)) or 0:.3f}"
                      for s in SCOPES), flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
