"""Least time the chip could take for a whole LongCat-Flash decode step (the larger of its FLOPs over peak and its bytes over bandwidth, from the configuration's fields and the step's counters: two attentions and two dense SwiGLUs a layer read whatever the routing, the touched experts, nothing for a zero-compute expert, live latent rows once a sublayer: benchmark/harness/costs_longcat_flash.py) over the step's device time: the share that bounds any later claim in the cell."""

NAME = "longcat_decode_roofline_mfu_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, costs_longcat_flash as cl, \
        decode_scopes as ds
    took = ds.step_ms(obs)
    counters = ds.step_counters(obs)
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
          f"measured {took:.3f} ms a step", flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
