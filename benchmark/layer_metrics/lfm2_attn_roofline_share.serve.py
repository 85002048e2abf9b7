"""Least time the chip could take for a decode step's attention proper (the running rows' live K and V once in each of the four attention layers, q and the output, 32 query heads of 64 in groups of 4 over rows of 512 values: benchmark/harness/costs_lfm2.py) over the device time under attn_full/attend (the paged kernel at this shape)."""

NAME = "lfm2_attn_roofline_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, costs_lfm2 as cl, \
        decode_scopes as ds, lfm2_views as lv
    took = ds.scope_ms(obs, ("attn_full/attend",))
    counters = lv.decode_counters(obs)
    if took is None or counters is None or obs.peaks is None:
        return None
    need = cl.attend_cost(obs.config["model"]["kwargs"], counters["n_seqs"],
                          counters["live_tokens"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[{NAME}] bound: {least['bound']}; least "
          f"{least['seconds'] * 1e3:.3f} ms ({need['bytes'] / 1e9:.3f} GB, "
          f"{need['flops'] / 1e9:.1f} GFLOP; counters {counters}), "
          f"measured {took:.3f} ms a step under attn_full/attend; by scope, "
          "ms a step: " + ", ".join(
              f"{s} {ds.scope_ms(obs, ('attn_full/' + s,)) or 0:.3f}"
              for s in ("qkv", "norm", "rope", "write", "attend", "out")),
          flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
