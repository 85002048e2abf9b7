"""Share of the traced window in which the chip was idle inside runner.fetch annotations (the wait for a program that has ended already, the copy to the host and the cutting there). With idle_in_runner_share.serve and idle_in_engine_share.serve it adds up to the line's idle share."""

NAME = "idle_in_fetch_share.serve"
UNIT = "%"
LAYER = "model step"
MOVES = "serve_tokens_per_s"
SOURCE = "device_trace"


def read(obs):
    from benchmark.harness import program_spans as ps
    shares = ps.idle_shares(obs)
    return None if shares is None else shares["fetch"]
