"""The benchmark's traffic generator: seeded non-iid n-gram token rows.

A copy of the program's ``TokenStream`` (``src/repro/data/tokens.py``),
kept here so that the traffic a cell trains on stays fixed while the
program changes.  Each client's stream skews the n-gram table by its
node id, so shards are non-iid; a stream is a pure function of
``(vocab, batch, seq_len, seed, client)``.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Tuple

import numpy as np


@dataclasses.dataclass
class TokenStream:
    vocab_size: int
    batch: int
    seq_len: int
    seed: int = 0
    client: int = 0
    #: share of positions whose token follows the n-gram rule
    dependency: float = 0.7

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        rng = np.random.default_rng(self.seed * 1000003 + self.client)
        mult = int(rng.integers(3, 64)) * 2 + 1
        add = int(rng.integers(1, self.vocab_size))
        while True:
            base = rng.integers(0, self.vocab_size,
                                size=(self.batch, self.seq_len + 1))
            dep = (base[:, :-1] * mult + add) % self.vocab_size
            gate = rng.random((self.batch, self.seq_len)) < self.dependency
            nxt = np.where(gate, dep, base[:, 1:])
            full = np.concatenate([base[:, :1], nxt], axis=1)
            yield (full[:, :-1].astype(np.int32),
                   full[:, 1:].astype(np.int32))


def stream(traffic: dict, vocab_size: int, seed: int, client: int):
    """The iterator of one client's (tokens, labels) rows for a mix."""
    return iter(TokenStream(vocab_size, traffic["batch"], traffic["seq_len"],
                            seed=seed, client=client,
                            dependency=traffic["ngram_dependency"]))
