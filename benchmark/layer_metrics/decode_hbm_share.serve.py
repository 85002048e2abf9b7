"""Least time a decode step needs to move its bytes (weights as stored + live KV pages once, at the chip's HBM bandwidth: benchmark/harness/costs.py) over the step's device time; medians over the traced window."""

NAME = "decode_hbm_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, stats, trace_views
    device_ms = stats.median(trace_views.decode_span_device_ms(obs))
    tw = obs.trace_window_host
    steps = [s for s in obs.spans if s["name"] == "adapter.decode"
             and tw and s["t0"] >= tw["t0"] and s["t1"] <= tw["t1"]]
    if not device_ms or not steps or obs.peaks is None:
        return None
    kw = obs.config["model"]["kwargs"]
    least_ms = stats.median([costs.decode_step_bytes(
        kw["n_layer"], kw["n_embd"], kw["vocab_size"], kw["n_positions"],
        obs.config["model"]["param_bytes"], s["live_tokens"], s["n"],
        obs.engine["block_size"], obs.config["model"]["kv_bytes"])
        / obs.peaks["hbm_bytes_per_s"] * 1e3 for s in steps])
    return 100.0 * least_ms / device_ms
