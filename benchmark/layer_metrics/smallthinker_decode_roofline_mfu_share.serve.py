"""Least time the chip could take for a whole SmallThinker decode step (the larger of its FLOPs over peak and its bytes over bandwidth, from the configuration's fields and the step's counters: attention, router and head weights read whatever the routing, touched experts, the live K and V of the full layers, what each row's window holds of the window layers: benchmark/harness/costs_smallthinker.py) over the step's device time: the share that bounds any later claim in the cell."""

NAME = "smallthinker_decode_roofline_mfu_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, costs_smallthinker as cs, \
        decode_scopes as ds, smallthinker_views as sv
    took = ds.step_ms(obs)
    counters = sv.decode_counters(obs)
    if took is None or counters is None or obs.peaks is None:
        return None
    need = cs.decode_step_cost(
        obs.config["model"]["kwargs"], counters["n_seqs"],
        counters["live_tokens"], counters["window_tokens"],
        counters["experts_touched"], counters["assignments"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[{NAME}] bound: {least['bound']}; least "
          f"{least['seconds'] * 1e3:.3f} ms ({need['bytes'] / 1e9:.3f} GB, "
          f"{need['flops'] / 1e9:.1f} GFLOP; counters {counters}), "
          f"measured {took:.3f} ms a step; by scope, ms a step: "
          + ", ".join(f"{s} {ds.scope_ms(obs, (s,)) or 0:.3f}" for s in (
              "attn_full", "attn_window", "moe/router", "moe/experts",
              "lm_head")), flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
