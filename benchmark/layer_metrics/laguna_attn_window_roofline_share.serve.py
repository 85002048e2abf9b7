"""Least time the chip could take for a decode step's attention proper in the window layers (what the rows' windows hold once a sliding layer, groups of 8 query heads over the ring: benchmark/harness/costs_laguna.py) over the device time under attn_window/attend (the paged kernel)."""

NAME = "laguna_attn_window_roofline_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, costs_laguna as cl, \
        decode_scopes as ds, laguna_views as lv
    took = ds.scope_ms(obs, ("attn_window/attend",))
    counters = lv.decode_counters(obs)
    if took is None or counters is None or obs.peaks is None:
        return None
    need = cl.attend_cost(
        obs.config["model"]["kwargs"], cl.SLIDING, counters["n_seqs"],
        counters["window_tokens"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[{NAME}] bound: {least['bound']}; least "
          f"{least['seconds'] * 1e3:.3f} ms ({need['bytes'] / 1e9:.3f} GB, "
          f"{need['flops'] / 1e9:.1f} GFLOP; counters {counters}), "
          f"measured {took:.3f} ms a step under attn_window/attend; by scope, ms a "
          "step: " + ", ".join(
              f"{s} {ds.scope_ms(obs, ('attn_window/' + s,)) or 0:.3f}" for s in (
                  "qkv", "rope", "write", "attend", "gate", "out")) + "", flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
