"""Training traffic: a Dataset of seeded random token rows, read epoch
after epoch through ``iter_device_batches`` inside the timed window."""

from __future__ import annotations

import numpy as np


def make_rows(traffic, seed, vocab, seq_len):
    rng = np.random.default_rng([int(seed), 11])
    return rng.integers(0, vocab, (int(traffic["rows"]), seq_len),
                        dtype=np.int32)


def make_dataset(traffic, seed, vocab, seq_len):
    from ray_tpu import data as rt_data
    rows = make_rows(traffic, seed, vocab, seq_len)
    return rt_data.from_items(
        [{"input_ids": r, "labels": r} for r in rows], parallelism=4)


def epochs(shard, batch_size, sharding):
    """Device batches for ever: a new ``iter_device_batches`` each
    epoch, as a training loop over epochs makes one."""
    while True:
        n = 0
        for batch in shard.iter_device_batches(
                batch_size=batch_size, sharding=sharding, drop_last=True,
                pad_to_batch=False):
            n += 1
            yield batch
        if n == 0:
            raise RuntimeError("the dataset shard yields no full batch")
