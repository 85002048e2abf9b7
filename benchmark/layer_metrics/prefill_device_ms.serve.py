"""Median device-busy time inside the program's llm.step.prefill annotations of the traced window: a prefill's device time apart from decode."""

NAME = "prefill_device_ms.serve"
UNIT = "ms"
LAYER = "model step"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import program_spans as ps, stats
    return stats.median(ps.device_ms_in(obs, "llm.step.prefill"))
