"""How late the open-loop generator sent: 95th percentile of (sent - due) over the window's requests."""

NAME = "gen_lag_p95_ms"
UNIT = "ms"
LAYER = "benchmark client"
MOVES = "ttft_p90_ms"
SOURCE = "host_clock"


def read(obs):
    from benchmark.harness import stats
    return stats.percentile(obs.gen_lag_ms, 95.0)
