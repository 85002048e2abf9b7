"""Device time of the operations traced under mamba/scan (a prompt's recurrence: the rows' states read, the loop over the positions, the states written back) over the device time of the prefill programs whole inside the traced window: what a kernel that keeps the state on the chip over a prompt's positions would shrink."""

NAME = "jamba_mamba_scan_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import jamba_views as jv, k2_views
    runs = k2_views.prefill_programs(obs)
    part = jv.prefill_scope_ms(obs, ("mamba/scan",))
    if not runs or part is None:
        return None
    return 100.0 * part * len(runs) / (sum(e.dur for e in runs) / 1e6)
