"""Least time the chip could take for a decode step's attention proper in the window layers (what each row really reads, min(length, 4096) positions once a window layer, groups of 7 query heads over the ring or the part of it a row holds: benchmark/harness/costs_smallthinker.py) over the device time under attn_window/attend (the paged kernel with window=4096)."""

NAME = "smallthinker_attn_window_roofline_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, costs_smallthinker as cs, \
        decode_scopes as ds, smallthinker_views as sv
    took = ds.scope_ms(obs, ("attn_window/attend",))
    counters = sv.decode_counters(obs)
    if took is None or counters is None or obs.peaks is None:
        return None
    need = cs.attend_cost(
        obs.config["model"]["kwargs"], cs.WINDOW, counters["n_seqs"],
        counters["window_tokens"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[{NAME}] bound: {least['bound']}; least "
          f"{least['seconds'] * 1e3:.3f} ms ({need['bytes'] / 1e9:.3f} GB, "
          f"{need['flops'] / 1e9:.1f} GFLOP; counters {counters}), "
          f"measured {took:.3f} ms a step under attn_window/attend; by "
          "scope, ms a step: " + ", ".join(
              f"{s} {ds.scope_ms(obs, ('attn_window/' + s,)) or 0:.3f}"
              for s in ("qkv", "rope", "write", "attend", "out")),
          flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
