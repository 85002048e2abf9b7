"""Required FLOPs of the traced window's prefill programs (each program charged the mean over the window's prefill steps of its own bucket, by the prompts' real tokens and the experts' assignments, causal attention counted once and the window layers' at the window: benchmark/harness/costs_smallthinker.py, smallthinker_views.traced_prefill_need) over their device time at the chip's bf16 peak. Padding to a bucket and the sorted product's half-filled blocks are work done and not required, so they lower it."""

NAME = "smallthinker_prefill_mfu_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import costs_smallthinker as cs, \
        smallthinker_views as sv
    if obs.peaks is None:
        return None
    c = obs.config["model"]["kwargs"]
    got = sv.traced_prefill_need(
        obs, lambda n, pairs: cs.prefill_flops(c, n, pairs))
    if got is None:
        return None
    flops, took_s, seen = got
    by_bucket = sv.prompts_by_bucket(obs)
    print(f"[{NAME}] {sum(seen.values())} prefill programs traced, by "
          f"padded bucket {dict(sorted(seen.items()))}, {took_s * 1e3:.1f} "
          f"ms in all; the window's prefill steps by bucket (count, mean "
          "real tokens): " + ", ".join(
              f"{b}: {len(v)}, {sum(n for n, _ in v) / len(v):.0f}"
              for b, v in sorted(by_bucket.items()))
          + f"; required {flops / 1e12:.2f} TFLOP for the traced; by scope, "
          "ms a program: " + ", ".join(
              f"{s} {sv.prefill_scope_ms(obs, (s,)) or 0:.2f}" for s in (
                  "attn_full", "attn_window", "moe/router", "moe/experts",
                  "lm_head")), flush=True)
    return 100.0 * flops / obs.peaks["bf16_flops_per_s"] / took_s
