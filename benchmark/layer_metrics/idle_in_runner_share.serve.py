"""Share of the traced window in which the chip was idle while the model runner built numpy inputs, uploaded them and dispatched (the part of its idle gaps that lies in runner.build_inputs / runner.dispatch)."""

NAME = "idle_in_runner_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import program_spans as ps
    shares = ps.idle_shares(obs)
    return None if shares is None else shares["runner"]
