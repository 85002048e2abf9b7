"""Median device-busy time inside the benchmark's span round adapter.decode, over the decode steps of the traced window."""

NAME = "decode_device_ms.serve"
UNIT = "ms"
LAYER = "model step"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import stats, trace_views
    return stats.median(trace_views.decode_span_device_ms(obs))
