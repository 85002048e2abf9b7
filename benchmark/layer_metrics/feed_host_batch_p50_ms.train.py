"""Median of the feed's own data.feed.host_batch annotations in the traced window: the next numpy batch out of iter_batches, inside iter_device_batches."""

NAME = "feed_host_batch_p50_ms.train"
UNIT = "ms"
LAYER = "data feed"
MOVES = "train_tokens_per_s"
SOURCE = "program_span"


def read(obs):
    from benchmark.harness import program_spans as ps, stats
    return stats.median([e.dur / 1e6 for e in ps.annotated(
        obs, "data.feed.host_batch")])
