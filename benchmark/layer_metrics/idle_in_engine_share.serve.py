"""Share of the traced window in which the chip was idle outside every runner.* span: llm.step.admit, llm.step.commit, the engine's own statements, between steps. With idle_in_runner_share.serve and the idle inside runner.fetch (printed) it adds up to the line's idle share."""

NAME = "idle_in_engine_share.serve"
UNIT = "%"
LAYER = "engine"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import program_spans as ps
    shares = ps.idle_shares(obs)
    if shares is None:
        return None
    ps.note("idle shares of the traced window, %: " + ", ".join(
        f"{k} {v:.3f}" for k, v in shares.items())
        + f"; together {sum(shares.values()):.3f}")
    return shares["engine"]
