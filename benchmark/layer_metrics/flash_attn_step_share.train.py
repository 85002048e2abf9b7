"""Device time of the Mosaic (Pallas flash attention, forward and backward) custom calls over the step's device time."""

NAME = "flash_attn_step_share.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import trace_views
    kernel = trace_views.mosaic_ms_per_step(obs)
    step = trace_views.step_device_ms(obs)
    if kernel is None or step is None:
        return None
    return 100.0 * kernel / step
