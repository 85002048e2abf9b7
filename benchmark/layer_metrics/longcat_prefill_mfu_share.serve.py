"""Required FLOPs of the traced window's prefill programs (by the prompts' real tokens and the held experts' real assignments of the window's prefill steps, nothing for a zero-compute expert, causal attention counted once a sublayer: benchmark/harness/costs_longcat_flash.py) over their device time at the chip's bf16 peak. Padding to the 2,048 bucket and the sorted product's half-filled blocks are work done and not required, so they lower it."""

NAME = "longcat_prefill_mfu_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs_longcat_flash as cl, k2_views, \
        laguna_views, longcat_views
    runs = k2_views.prefill_programs(obs)
    steps = k2_views.prefill_steps(obs)
    if not runs or steps is None or obs.peaks is None:
        return None
    c = obs.config["model"]["kwargs"]
    n = steps["prompt_tokens"]
    flops = cl.prefill_flops(c, n, steps["assignments"],
                             steps["prompt_tokens_sq"])
    took_s = sum(e.dur for e in runs) / 1e9 / len(runs)
    print(f"[{NAME}] {len(runs)} prefill programs traced, mean "
          f"{took_s * 1e3:.1f} ms ({sorted({e.name for e in runs})}); the "
          f"window's {steps['steps']:.0f} prefill steps: mean prompt "
          f"{n:.0f} tokens in {steps['padded_tokens']:.0f} padded, "
          f"{steps['assignments']:.0f} real (token, expert) pairs held; "
          f"required {flops / 1e12:.2f} TFLOP a prompt; by scope, ms a "
          "program: " + ", ".join(
              f"{s} {laguna_views.prefill_scope_ms(obs, (s,)) or 0:.2f}"
              for s in longcat_views.SCOPES + longcat_views.MLA_SCOPES),
          flush=True)
    return 100.0 * flops / obs.peaks["bf16_flops_per_s"] / took_s
