"""Least time the chip could take for a whole LFM2 decode step (the larger of its needed FLOPs over peak and its bytes over bandwidth, from the configuration's fields and the step's counters: every weight outside the experts read once with the token table as the head, the touched experts' weights once and the ROUTED pairs' FLOPs whatever rows the product multiplies, every running sequence's convolution tail in and out, the live K and V of the four attention layers: benchmark/harness/costs_lfm2.py) over the step's device time: the share that bounds any later claim in the cell."""

NAME = "lfm2_decode_roofline_mfu_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

SCOPES = ("conv", "conv/in_proj", "conv/mix", "conv/out_proj", "attn_full",
          "attn_full/qkv", "attn_full/norm", "attn_full/rope",
          "attn_full/write", "attn_full/attend", "attn_full/out", "mlp",
          "moe/router", "moe/experts", "lm_head")


def read(obs):
    from benchmark.harness import costs, costs_lfm2 as cl, \
        decode_scopes as ds, lfm2_views as lv
    took = ds.step_ms(obs)
    counters = lv.decode_counters(obs)
    if took is None or counters is None or obs.peaks is None:
        return None
    need = cl.decode_step_cost(
        obs.config["model"]["kwargs"], counters["n_seqs"],
        counters["live_tokens"], counters["experts_touched"],
        counters["assignments"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[{NAME}] bound: {least['bound']}; least "
          f"{least['seconds'] * 1e3:.3f} ms ({need['bytes'] / 1e9:.3f} GB, "
          f"{need['flops'] / 1e9:.1f} GFLOP; counters {counters}), "
          f"measured {took:.3f} ms a step; by scope, ms a step: "
          + ", ".join(f"{s} {ds.scope_ms(obs, (s,)) or 0:.3f}"
                      for s in SCOPES), flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
