"""Required FLOPs of a prompt's attention (causal, counted once; the sliding layers' at the window: benchmark/harness/costs_laguna.py) at the chip's bf16 peak over the device time of a traced prefill program under attn_full/attend and attn_window/attend (the blocked kernel prefill_attention: key blocks walked with a running softmax, grouped heads read where they lie). Compute-bound: a head's keys and values are read once a query block from VMEM."""

NAME = "laguna_prefill_attn_roofline_share.serve"
UNIT = "%"
LAYER = "kernels"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs_laguna as cl, k2_views, \
        laguna_views as lv
    steps = k2_views.prefill_steps(obs)
    took = lv.prefill_scope_ms(obs, ("attn_full/attend",
                                     "attn_window/attend"))
    if took is None or steps is None or obs.peaks is None:
        return None
    flops = cl.prefill_attention_flops(
        obs.config["model"]["kwargs"], steps["prompt_tokens"],
        steps["prompt_tokens_sq"])
    print(f"[{NAME}] required {flops / 1e12:.3f} TFLOP a prompt (mean "
          f"{steps['prompt_tokens']:.0f} tokens); measured {took:.2f} ms a "
          "prefill program under */attend: full "
          f"{lv.prefill_scope_ms(obs, ('attn_full/attend',)) or 0:.2f}, "
          f"window {lv.prefill_scope_ms(obs, ('attn_window/attend',)) or 0:.2f}",
          flush=True)
    return 100.0 * flops / obs.peaks["bf16_flops_per_s"] / (took / 1e3)
