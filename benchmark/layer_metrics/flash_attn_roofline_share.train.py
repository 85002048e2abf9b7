"""Least time the chip could take for a step's attention kernels (the larger of FLOPs over peak and bytes over bandwidth: benchmark/harness/costs.py) over their device time."""

NAME = "flash_attn_roofline_share.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs, trace_views
    kernel = trace_views.mosaic_ms_per_step(obs)
    if kernel is None or obs.peaks is None:
        return None
    kw = obs.sizes.get("model_kwargs") or obs.config["model"]["kwargs"]
    need = costs.flash_attention_train_cost(
        kw["n_layer"], kw["n_embd"], obs.sizes["batch_size"],
        obs.sizes["seq_len"])
    least = costs.roofline_least_seconds(need["flops"], need["bytes"],
                                         obs.peaks)
    print(f"[flash_attn_roofline_share.train] bound: {least['bound']}; "
          f"least {least['seconds'] * 1e3:.3f} ms, kernels {kernel:.3f} ms "
          "a step", flush=True)
    return 100.0 * least["seconds"] * 1e3 / kernel
