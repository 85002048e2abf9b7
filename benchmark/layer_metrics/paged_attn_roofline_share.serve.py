"""Least time the chip could take for a decode step's attention (every running row's live K and V once in all layers, q and the output, at the HBM bandwidth, or its FLOPs at the peak: benchmark/harness/costs_paged_attention.py) over the device time under paged_attention_decode."""

NAME = "paged_attn_roofline_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, costs_paged_attention as cp, \
        decode_scopes as ds, stats
    took = ds.scope_ms(obs, ("paged_attention_decode",))
    tw = getattr(obs, "trace_window_host", None)
    steps = [s for s in getattr(obs, "spans", ())
             if s["name"] == "adapter.decode" and tw and "t0" in tw
             and s["t0"] >= tw["t0"] and s["t1"] <= tw["t1"]]
    if took is None or not steps or obs.peaks is None:
        return None
    kw = obs.config["model"]["kwargs"]
    live = stats.median([s["live_tokens"] for s in steps])
    rows = stats.median([s["n"] for s in steps])
    need = cp.paged_attention_decode_cost(
        kw["n_layer"], kw["n_embd"], live, rows,
        obs.config["model"]["kv_bytes"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[paged_attn_roofline_share.serve] bound: {least['bound']}; least "
          f"{least['seconds'] * 1e3:.3f} ms ({need['bytes'] / 1e9:.3f} GB, "
          f"{need['flops'] / 1e9:.2f} GFLOP; {rows:g} rows holding {live:g} "
          f"tokens, medians of {len(steps)} traced steps), measured "
          f"{took:.3f} ms a step", flush=True)
    return 100.0 * least["seconds"] * 1e3 / took
