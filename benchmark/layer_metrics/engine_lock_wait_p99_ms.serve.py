"""99th percentile over the window's steps of the time the engine thread waited to acquire the engine lock in the step: lock_wait_ms summed over the step's tree (an acquisition that found the lock free counts 0). Prints the median, the largest and the share of engine_self_p50_ms.serve. None where no span carries the attribute."""

NAME = "engine_lock_wait_p99_ms.serve"
UNIT = "ms"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(obs):
    from benchmark.harness import program_spans as ps, stats, step_cover
    waits = [w for w in map(step_cover.lock_wait_ms,
                            ps.window_steps(obs) or ()) if w is not None]
    if not waits:
        return None
    self_p50 = stats.median([ps.self_ms(s) for s in ps.window_steps(obs)])
    step_cover.note(
        f"engine lock waits over {len(waits)} steps, ms: median "
        f"{stats.median(waits):.4f}, p99 {stats.percentile(waits, 99.0):.4f}"
        f", largest {max(waits):.4f}, mean {sum(waits) / len(waits):.4f} ("
        f"{100.0 * sum(waits) / len(waits) / self_p50:.2f}% of the median "
        f"engine self time {self_p50:.3f}); steps that waited at all: "
        f"{sum(1 for w in waits if w > 0)}; since the replica started: "
        f"{step_cover.metrics(obs).get('lock_wait_seconds_total')} s")
    step_cover.describe_long_steps(obs)
    return stats.percentile(waits, 99.0)
