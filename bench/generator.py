"""The one traffic generator: turns a traffic file's parameters and a seed
into the inputs a cell feeds the program and its reference.

Training traffic of kind ``zipf_documents`` is a stream of documents of
``doc_tokens`` tokens, which the program's ``PrefetchLoader`` cuts into
rows of ``seq + 1`` (one document a row when ``doc_tokens`` is
``seq + 1``).  A document's tokens follow a Zipf law over ranks: the
``shared_ranks`` most frequent are the same ids in every document
(function words), the rest are rotated by an offset of the document's own
(its content words), so documents differ in which rarer tokens they use.
The stream is a concatenation of shards of ``shard_docs`` documents, each
drawn from its own generator seeded by ``(seed, shard)``: every seed gives
the same sizes and different tokens.
"""
from __future__ import annotations

import numpy as np


class TokenStream:
    """Token shards: ``load_shard(i)`` is the corpus interface the program's
    loader reads."""

    def __init__(self, spec: dict, vocab: int, seed: int):
        if spec["kind"] != "zipf_documents":
            raise ValueError(f"unknown token stream kind {spec['kind']!r}")
        self.exponent = float(spec["exponent"])
        self.doc_tokens = int(spec["doc_tokens"])
        self.shard_docs = int(spec["shard_docs"])
        self.shared = int(spec["shared_ranks"])
        self.vocab = int(vocab)
        self.seed = int(seed)

    def load_shard(self, idx: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, int(idx)])
        rank = (rng.zipf(self.exponent, size=(self.shard_docs,
                                              self.doc_tokens)) - 1) \
            % self.vocab
        content = self.vocab - self.shared
        offset = rng.integers(0, content, size=(self.shard_docs, 1))
        ids = np.where(rank < self.shared, rank,
                       self.shared + (rank - self.shared + offset) % content)
        return ids.reshape(-1).astype(np.int32)


def train_rows(stream: TokenStream, batch: int, seq: int, n_batches: int):
    """The first ``n_batches`` batches of ``stream`` as (tokens, labels)
    pairs of shape (batch, seq): row r of batch i is tokens
    ``[(i * batch + r) * (seq + 1), ... + seq + 1)`` of the stream."""
    need = batch * (seq + 1) * n_batches
    shards, have, idx = [], 0, 0
    while have < need:
        shards.append(stream.load_shard(idx))
        have += shards[-1].size
        idx += 1
    flat = np.concatenate(shards)[:need].reshape(n_batches, batch, seq + 1)
    return [(np.ascontiguousarray(b[:, :-1]), np.ascontiguousarray(b[:, 1:]))
            for b in flat]
