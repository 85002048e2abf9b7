"""Least time the chip could take for a SmallThinker decode step's routed experts (the touched experts' weights once + the rows in and out against bandwidth, the routed pairs' FLOPs against peak: benchmark/harness/costs_smallthinker.py) over the device time under moe/experts (the touched-experts kernel over 11.8 MB ReGLU experts, three 256-wide tiles an expert)."""

NAME = "smallthinker_moe_experts_roofline_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, costs_smallthinker as cs, \
        decode_scopes as ds
    took = ds.scope_ms(obs, ("moe/experts",))
    counters = ds.step_counters(obs)
    if took is None or counters is None or obs.peaks is None:
        return None
    need = cs.moe_experts_cost(obs.config["model"]["kwargs"],
                               counters["experts_touched"],
                               counters["assignments"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[{NAME}] bound: {least['bound']}; least "
          f"{least['seconds'] * 1e3:.3f} ms ({need['bytes'] / 1e9:.3f} GB, "
          f"{need['flops'] / 1e9:.1f} GFLOP; counters {counters}), "
          f"measured {took:.3f} ms a step", flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
