"""Median device time of one optimizer step: the executions of the step's program on the chip (line XLA Modules), inside the traced window."""

NAME = "step_device_ms.train"
UNIT = "ms"
LAYER = "train step"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import trace_views
    return trace_views.step_device_ms(obs)
