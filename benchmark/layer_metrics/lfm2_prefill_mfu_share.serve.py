"""Required FLOPs of the traced window's prefill programs (each program charged the mean over the window's prefill steps of its own rows x length, by the prompts' real tokens: every matrix outside the experts, four experts a token a routed layer, the head for one position a prompt, causal attention counted once at the least its pairs can be: benchmark/harness/costs_lfm2.py, jamba_views.traced_prefill_need) over their device time at the chip's bf16 peak. Right-padding several prompts to one program's rows x length and a group's padding to whole row blocks are work done and not required, so they lower it."""

NAME = "lfm2_prefill_mfu_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

SCOPES = ("conv", "conv/in_proj", "conv/mix", "conv/out_proj", "attn_full",
          "attn_full/attend", "mlp", "moe/router", "moe/experts", "lm_head")


def read(obs):
    from benchmark.harness import costs_lfm2 as cl, lfm2_views as lv
    if obs.peaks is None:
        return None
    c = obs.config["model"]["kwargs"]
    got = lv.traced_prefill_need(
        obs, lambda n, rows: cl.prefill_flops(c, n, rows))
    if got is None:
        return None
    flops, took_s, seen = got
    by_program = lv.prompts_by_program(obs)
    print(f"[{NAME}] {sum(seen.values())} prefill programs traced, by "
          f"(rows, length) {dict(sorted(seen.items()))}, "
          f"{took_s * 1e3:.1f} ms in all; the window's prefill steps by "
          "program (count, mean real tokens): " + ", ".join(
              f"{k}: {len(v)}, {sum(n for n, _ in v) / len(v):.0f}"
              for k, v in sorted(by_program.items()))
          + f"; required {flops / 1e12:.2f} TFLOP for the traced; by scope, "
          "ms a program: " + ", ".join(
              f"{s} {lv.prefill_scope_ms(obs, (s,)) or 0:.2f}"
              for s in SCOPES), flush=True)
    return 100.0 * flops / obs.peaks["bf16_flops_per_s"] / took_s
