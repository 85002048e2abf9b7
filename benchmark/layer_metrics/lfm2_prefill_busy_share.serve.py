"""Device time of the prefill programs (jit_llm_prefill_b*) whole inside the traced window over the window's device-busy time: how much of what the chip does is prompts (some seventeen a second of 128-1,024 tokens, each step holding up 256 decode rows), where the decode steps are what the callers wait for."""

NAME = "lfm2_prefill_busy_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import k2_views, xplane
    runs = k2_views.prefill_programs(obs)
    if not runs:
        return None
    busy_s = xplane.busy_seconds(obs.trace, obs.trace_window)
    return 100.0 * sum(e.dur for e in runs) / 1e9 / busy_s if busy_s else None
