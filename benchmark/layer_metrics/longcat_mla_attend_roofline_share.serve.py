"""Least time the chip could take for a decode step's latent attention proper over its eight sublayers (the running sequences' live latent rows once a sublayer and the absorbed form's FLOPs: benchmark/harness/costs_longcat_flash.py) over the device time under mla/attend (the absorbed query, the kernel over the live pages, the sum through W_uv)."""

NAME = "longcat_mla_attend_roofline_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, costs_longcat_flash as cl, \
        decode_scopes as ds
    took = ds.scope_ms(obs, ("mla/attend",))
    counters = ds.step_counters(obs)
    if took is None or counters is None or obs.peaks is None:
        return None
    need = cl.mla_attend_cost(obs.config["model"]["kwargs"],
                              counters["n_seqs"], counters["live_tokens"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[{NAME}] bound: {least['bound']}; least "
          f"{least['seconds'] * 1e3:.3f} ms ({need['bytes'] / 1e9:.3f} GB, "
          f"{need['flops'] / 1e9:.1f} GFLOP; counters {counters}), "
          f"measured {took:.3f} ms a step under mla/attend; by scope, ms a "
          "step: " + ", ".join(
              f"{s} {ds.scope_ms(obs, (s,)) or 0:.3f}" for s in (
                  "mla/q_lora", "mla/rope", "mla/write", "mla/attend",
                  "mla/out")), flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
