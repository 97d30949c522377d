"""Token data from the seed, written as the system's record shards, and
files pushed out of the page cache so that the window reads from disk as
a fresh job would."""

from __future__ import annotations

import os
from typing import List

import numpy as np


def make_tokens(seed: int, records: int, record_tokens: int,
                vocab: int) -> np.ndarray:
    """(records, record_tokens) int32 tokens drawn uniformly from the
    vocabulary; the same seed gives the same records."""
    rng = np.random.default_rng([seed, 0x70CE5])
    return rng.integers(0, vocab, size=(records, record_tokens), dtype=np.int32)


def write_shards(device, root: str, tokens: np.ndarray,
                 num_shards: int) -> List[str]:
    """Records split into ``num_shards`` consecutive runs, one shard file
    each, through the system's record writer (which fsyncs)."""
    from repro.store.recordio import RecordShardWriter

    os.makedirs(root, exist_ok=True)
    per = len(tokens) // num_shards
    paths = []
    for s in range(num_shards):
        path = f"{root}/shard_{s:05d}.rio"
        w = RecordShardWriter(device, path, tokens.shape[1] * 4)
        for r in tokens[s * per:(s + 1) * per]:
            w.append(r.tobytes())
        w.close()
        paths.append(path)
    return paths


def evict(root: str) -> int:
    """fsync and drop from the page cache every file under ``root``;
    returns the bytes dropped."""
    n = 0
    for d, _, files in os.walk(root):
        for name in files:
            fd = os.open(os.path.join(d, name), os.O_RDONLY)
            try:
                os.fsync(fd)
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
                n += os.fstat(fd).st_size
            finally:
                os.close(fd)
    return n


def expected_batch(tokens: np.ndarray, seed: int, batch: int, epoch: int,
                   step: int) -> np.ndarray:
    """The records batch (epoch, step) holds under the loader's documented
    order: ``permutation(seed, epoch)[step * B:(step + 1) * B]`` over the
    records in shard order."""
    perm = np.random.default_rng((seed, epoch)).permutation(len(tokens))
    return tokens[perm[step * batch:(step + 1) * batch]]
