"""Share of the window the train loop spent inside next(feed): the benchmark's own span round the call into iter_device_batches."""

NAME = "data_wait_share"
UNIT = "%"
LAYER = "data feed"
MOVES = "train_tokens_per_s"
SOURCE = "program_span"


def read(obs):
    waits = [s["t1"] - s["t0"] for s in obs.spans
             if s["name"] == "next(feed)"]
    if not waits:
        return None
    return 100.0 * sum(waits) / obs.window_s
