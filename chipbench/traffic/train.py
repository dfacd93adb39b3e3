"""Training input: recordio shards of seeded full-length sequences,
read back by the program's own StreamingInputService.

Every sample is one record of three int64 fields (source ids, target
ids, labels), each ``[seq, 1]``, ids uniform in [1, vocab): full-length
sequences stand for packed sentence pairs, so no position is padding.
The seed changes the ids and nothing else — every seed gives the same
number of batches of the same shape.
"""
from __future__ import annotations

import os

import numpy as np

FIELDS = 3
FEED_NAMES = ("src_ids", "trg_ids", "trg_labels", "pos_ids")


def write_shards(workdir: str, seed: int, batch: int, seq: int,
                 vocab: int, shards: int, shard_batches: int) -> list:
    """``shards`` files of ``shard_batches`` batches each; the service
    loops over them for as many epochs as the window needs."""
    from paddle_tpu import recordio
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(shards):
        path = os.path.join(workdir, f"train-{i:02d}.recordio")
        data = rng.integers(1, vocab, (shard_batches * batch, FIELDS,
                                       seq, 1), dtype=np.int64)
        with recordio.Writer(path) as w:
            for sample in data:
                w.write(sample.tobytes())
        paths.append(path)
    return paths


def collate_with_positions(samples):
    """Streaming collate (module level: spawn workers unpickle it by
    reference): stack the three id fields and add the shared,
    un-batched position feed."""
    src, trg, lbl = (np.stack([s[i] for s in samples])
                     for i in range(FIELDS))
    return src, trg, lbl, np.arange(src.shape[1], dtype=np.int64)
