"""Least time the chip could take for a decode step's Mamba-1 recurrences (every running sequence's state read once and written once, the step's operands in and its output out, A once a layer, all 26 Mamba layers, against bandwidth: benchmark/harness/costs_jamba.py) over the device time under mamba/step (the in-place kernel mamba_recurrence and the skip taken beside it)."""

NAME = "jamba_mamba_step_roofline_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, costs_jamba as cj, \
        decode_scopes as ds, jamba_views as jv
    took = ds.scope_ms(obs, ("mamba/step",))
    counters = jv.decode_counters(obs)
    if took is None or counters is None or obs.peaks is None:
        return None
    # the kernel reads and writes every row of the bucket it runs, a
    # free slot's too (``dt = 0``: the state passes through); what is
    # needed is the running sequences'
    need = cj.mamba_step_cost(obs.config["model"]["kwargs"],
                              counters["n_seqs"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[{NAME}] bound: {least['bound']}; least "
          f"{least['seconds'] * 1e3:.3f} ms ({need['bytes'] / 1e9:.3f} GB, "
          f"{need['flops'] / 1e9:.1f} GFLOP; counters {counters}), "
          f"measured {took:.3f} ms a step", flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
