"""Share of the window's decode steps whose program was dispatched before the previous step's tokens were fetched: llm.step.decode spans with ahead true, over those that carry the attribute (step_log, host clock). None where no span carries it: a program without the look-ahead."""

NAME = "decode_ahead_share.serve"
UNIT = "%"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(obs):
    from benchmark.harness import program_spans as ps
    flags = [d["attrs"]["ahead"] for step in ps.window_steps(obs) or ()
             for d in ps.named(step, "llm.step.decode")
             if "ahead" in d.get("attrs", {})]
    if not flags:
        return None
    metrics = getattr(obs, "engine_metrics", None) or {}
    ps.note(f"decode steps of the window: {len(flags)}, dispatched ahead "
            f"{sum(flags)}; since the replica started: "
            f"{metrics.get('decode_steps_ahead_total')} steps ahead, "
            f"{metrics.get('decode_tokens_discarded_total')} tokens "
            "discarded")
    return 100.0 * sum(flags) / len(flags)
