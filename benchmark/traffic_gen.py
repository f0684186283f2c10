"""The one traffic generator: a token stream and the batches cut from
it, from a traffic file's parameters and the seed.

``zipf_bigram`` is chip_smoke.py's ``synthetic_tokens`` (copied; the
original is listed in PERF.md for a later PR to delete): Zipfian
unigrams with a deterministic bigram mixed in, so the loss falls
within a few steps from a random init. Every seed gives the same
number of sequences of the same length, in another order and with
other tokens: the seed never changes the amount of work.
"""

from __future__ import annotations

import numpy as np


def token_stream(stream: dict, vocab: int, seed: int) -> np.ndarray:
    if stream["generator"] != "zipf_bigram":
        raise ValueError(f"unknown generator {stream['generator']!r}")
    n = int(stream["tokens"])
    rng = np.random.default_rng(seed)
    base = rng.zipf(float(stream["zipf_a"]), size=n).astype(np.int64) % vocab
    mix = rng.random(n) < float(stream["bigram_share"])
    return np.where(mix, (np.roll(base, 1) * 7 + 3) % vocab, base).astype(
        np.int32
    )


def batch_stream(data: np.ndarray, seq_len: int, rows: int, index_iter):
    """Endless (tokens, targets) host batches of ``rows`` sequences,
    starting where ``index_iter`` (the program's sampler) says."""
    while True:
        idx = np.fromiter((next(index_iter) for _ in range(rows)), np.int64, rows)
        yield (
            np.stack([data[i: i + seq_len] for i in idx]),
            np.stack([data[i + 1: i + seq_len + 1] for i in idx]),
        )


def reference_batch(data: np.ndarray, seq_len: int, rows: int):
    """The seeded sequences the reference check uses: the stream's
    first ``rows``."""
    idx = np.arange(rows) * (seq_len + 1)
    return (
        np.stack([data[i: i + seq_len] for i in idx]),
        np.stack([data[i + 1: i + seq_len + 1] for i in idx]),
    )
