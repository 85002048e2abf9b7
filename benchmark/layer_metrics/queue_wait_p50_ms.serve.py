"""Median wait of a request between arrival and admission (request_log t_admit - t_arrival) over the requests that arrived in the window. Not in BENCHMARK.json yet: it moves ttft_p90_ms, which comes with the open-loop cell."""

NAME = "queue_wait_p50_ms.serve"
UNIT = "ms"
LAYER = "engine"
MOVES = "ttft_p90_ms"
SOURCE = "program_counter"


def read(obs):
    from benchmark.harness import program_spans as ps, stats
    return stats.median(ps.queue_waits_ms(obs))
