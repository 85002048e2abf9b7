"""Required FLOPs of a step (6 N tokens + causal attention, no recompute: benchmark/harness/costs.py) over the step's device time times the chip's bf16 peak."""

NAME = "step_flops_share.train"
UNIT = "%"
LAYER = "train step"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, trace_views
    ms = trace_views.step_device_ms(obs)
    if ms is None or obs.peaks is None:
        return None
    kw = obs.sizes.get("model_kwargs") or obs.config["model"]["kwargs"]
    flops = costs.gpt2_train_step_flops(
        kw["n_layer"], kw["n_embd"], kw["vocab_size"],
        obs.sizes["batch_size"], obs.sizes["seq_len"])
    return 100.0 * flops / (ms / 1e3 * obs.peaks["bf16_flops_per_s"])
