"""Median over the window's requests of the client's time to first token (from send) minus the engine's own ttft_s of the same request: what handle, router and replica add."""

NAME = "client_overhead_ms.serve"
UNIT = "ms"
LAYER = "serve router + proxy"
MOVES = "ttft_p90_ms"
SOURCE = "host_clock"


def read(obs):
    from benchmark.harness import stats
    sample = stats.client_overhead_sample_ms(obs.records, obs.t0, obs.t1)
    return stats.median(sample)
