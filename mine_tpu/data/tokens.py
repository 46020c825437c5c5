"""Seeded documents packed into rows of tokens, behind the loaders'
`batch_iterator(...)` contract (data/common.py -> data/pipeline.py), so the
train loop stages them like any other batch.

A corpus is a stream of documents: lengths lognormal (median and sigma of
the log given, clipped to [min_len, seq_len]), token ids Zipf(exponent) over
the whole vocabulary, both from the seed, so the loss has unigram statistics
to learn. The stream is cut into rows of seq_len + 1 ids that overlap by one
(a row's labels are its tokens shifted by one): a document that reaches a
row's end is cut there and continues in the next row, as pre-training
pipelines pack. Only the corpus's last row can have empty slots (under 1%
of all slots); they are masked.

Attention over a packed row is plain causal attention: no document mask.

The packer counts what it emits: `data.pack.tokens` (slots that hold a
token) and `data.pack.slots` (all slots of the rows emitted).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from mine_tpu import telemetry


def zipf_ids(rng: np.random.RandomState, n: int, vocab: int,
             exponent: float) -> np.ndarray:
    """n ids with P(rank r) ~ r^-exponent, ranks mapped to ids by a seeded
    permutation (so frequent ids are spread over the embedding's rows)."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
    cdf = np.cumsum(p / p.sum())
    ranks = np.minimum(np.searchsorted(cdf, rng.random_sample(n)), vocab - 1)
    return rng.permutation(vocab)[ranks].astype(np.int32)


class PackedTokenDataset:
    """`num_rows` packed rows of `seq_len` tokens, RAM-resident."""

    def __init__(self, num_rows: int, seq_len: int, vocab_size: int,
                 seed: int = 0, doc_len_median: float = 600.0,
                 doc_len_sigma: float = 1.2, doc_len_min: int = 16,
                 zipf_exponent: float = 1.0):
        rng = np.random.RandomState(seed % (1 << 32))
        want = num_rows * seq_len + 1
        lengths, total = [], 0
        while total < want:
            draw = np.exp(rng.normal(np.log(doc_len_median), doc_len_sigma,
                                     size=max(16, want // 256)))
            for n in np.clip(np.rint(draw), doc_len_min, seq_len).astype(int):
                lengths.append(int(n))
                total += int(n)
                if total >= want:
                    break
        # the corpus ends with a whole document where that leaves under 1%
        # of the slots empty, else its last document is cut like a row's
        if total > want and want - (total - lengths[-1]) < 0.01 * want:
            total -= lengths.pop()
        else:
            lengths[-1] -= total - want
            total = want
        self.doc_lengths = np.asarray(lengths)
        stream = np.zeros(want, np.int32)
        stream[:total] = zipf_ids(rng, total, vocab_size, zipf_exponent)
        self.seq_len = seq_len
        # rows overlap by one id: row r = stream[r*S : r*S + S + 1]
        idx = (np.arange(num_rows)[:, None] * seq_len
               + np.arange(seq_len + 1)[None, :])
        self.rows = stream[idx]
        # a slot counts where it and its label hold a token
        self.valid = (idx[:, 1:] < total).astype(np.float32)

    def __len__(self):
        return len(self.rows)

    def get_row(self, index: int, rng=None) -> Dict[str, np.ndarray]:
        row = self.rows[index]
        return {"tokens": row[:-1], "labels": row[1:],
                "mask": self.valid[index]}

    def collate(self, rows) -> Dict[str, np.ndarray]:
        batch = {k: np.stack([r[k] for r in rows]) for k in rows[0]}
        telemetry.counter("data.pack.tokens").inc(int(batch["mask"].sum()))
        telemetry.counter("data.pack.slots").inc(int(batch["mask"].size))
        return batch

    def batch_iterator(self, batch_size, shuffle, seed=0, epoch=0,
                       drop_last=True, shard_index=0, num_shards=1,
                       workers=0, prefetch_batches=2):
        from mine_tpu.data.common import iterate_pair_batches
        yield from iterate_pair_batches(
            len(self.rows), self.get_row, batch_size, shuffle, seed=seed,
            epoch=epoch, drop_last=drop_last, shard_index=shard_index,
            num_shards=num_shards, workers=workers,
            prefetch_batches=prefetch_batches, collate=self.collate)


CORPUS_ROWS = 64   # the seeded stand-in corpus train_cli.py trains on


def dataset_from_config(config, seed: int = 0) -> PackedTokenDataset:
    return PackedTokenDataset(num_rows=CORPUS_ROWS,
                              seq_len=int(config["data.seq_len"]),
                              vocab_size=int(config["lm.vocab_size"]),
                              seed=seed)
