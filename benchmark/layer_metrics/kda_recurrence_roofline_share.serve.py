"""Least time the chip could take for a decode step's KDA recurrences (every running sequence's state read once and written once, all KDA layers: benchmark/harness/costs_kimi_linear.py) over the device time under kda/recurrence."""

NAME = "kda_recurrence_roofline_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, costs_kimi_linear as ck, \
        decode_scopes as ds
    took = ds.scope_ms(obs, ("kda/recurrence",))
    counters = ds.step_counters(obs)
    if took is None or counters is None or obs.peaks is None:
        return None
    need = ck.kda_recurrence_cost(obs.config["model"]["kwargs"],
                                  counters["n_seqs"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[kda_recurrence_roofline_share.serve] bound: {least['bound']}; least "
          f"{least['seconds'] * 1e3:.3f} ms ({need['bytes'] / 1e9:.3f} GB, "
          f"{need['flops'] / 1e9:.1f} GFLOP; counters {counters}), "
          f"measured {took:.3f} ms a step", flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
