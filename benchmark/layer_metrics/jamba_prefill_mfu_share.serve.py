"""Required FLOPs of the traced window's prefill programs (each program charged the mean over the window's prefill steps of its own rows x length, by the prompts' real tokens: every matrix, the head for one position a prompt, the Mamba recurrence as the token-by-token form needs it, causal attention counted once at the least its pairs can be: benchmark/harness/costs_jamba.py, jamba_views.traced_prefill_need) over their device time at the chip's bf16 peak. Right-padding several prompts to one program's rows x length and a scan that carries the state through memory are work done and not required, so they lower it."""

NAME = "jamba_prefill_mfu_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"

SCOPES = ("mamba", "mamba/in_proj", "mamba/conv", "mamba/x_proj",
          "mamba/scan", "mamba/out_proj", "attn_full", "mlp", "lm_head")


def read(obs):
    from benchmark.harness import costs_jamba as cj, jamba_views as jv
    if obs.peaks is None:
        return None
    c = obs.config["model"]["kwargs"]
    got = jv.traced_prefill_need(
        obs, lambda n, rows: cj.prefill_flops(c, n, rows))
    if got is None:
        return None
    flops, took_s, seen = got
    by_program = jv.prompts_by_program(obs)
    print(f"[{NAME}] {sum(seen.values())} prefill programs traced, by "
          f"(rows, length) {dict(sorted(seen.items()))}, "
          f"{took_s * 1e3:.1f} ms in all; the window's prefill steps by "
          "program (count, mean real tokens): " + ", ".join(
              f"{k}: {len(v)}, {sum(n for n, _ in v) / len(v):.0f}"
              for k, v in sorted(by_program.items()))
          + f"; required {flops / 1e12:.2f} TFLOP for the traced; by scope, "
          "ms a program: " + ", ".join(
              f"{s} {jv.prefill_scope_ms(obs, (s,)) or 0:.2f}"
              for s in SCOPES), flush=True)
    return 100.0 * flops / obs.peaks["bf16_flops_per_s"] / took_s
