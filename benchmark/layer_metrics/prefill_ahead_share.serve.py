"""Share of the window's prefill steps whose prompts' program was dispatched and left in flight, the decode step behind it given to the device before the prompts' first tokens were fetched: llm.step.prefill spans with ahead true, over those that carry the attribute (step_log, host clock). None where no span carries it: a program whose prefill step fetches at once."""

NAME = "prefill_ahead_share.serve"
UNIT = "%"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(obs):
    from benchmark.harness import program_spans as ps
    flags = [p["attrs"]["ahead"] for step in ps.window_steps(obs) or ()
             for p in ps.named(step, "llm.step.prefill")
             if "ahead" in p.get("attrs", {})]
    if not flags:
        return None
    metrics = getattr(obs, "engine_metrics", None) or {}
    ps.note(f"prefill steps of the window: {len(flags)}, left in flight "
            f"{sum(flags)}; since the replica started: "
            f"{metrics.get('prefill_steps_ahead_total')} of "
            f"{metrics.get('prefill_steps_total')}")
    return 100.0 * sum(flags) / len(flags)
