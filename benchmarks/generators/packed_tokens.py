"""Packed rows of token ids for language-model training.

Every row is ``seq_len`` ids drawn uniformly over the vocabulary from the
seed, so no row repeats and none is padded; the label row is the ids
shifted by one. Batches are made on the host, as a user's iterator makes
them, float32 as ``mx.io.NDArrayIter`` hands them over.
"""
import numpy as np


class _Packed:
    def __init__(self, mx, traffic, cfg, seed, rows):
        self._mx = mx
        self._rng = np.random.Generator(np.random.PCG64(int(seed)))
        self._shape = (rows, int(traffic["seq_len"]))
        self._vocab = int(cfg["vocab_size"])
        self.batch_size = rows
        self.provide_data = [mx.io.DataDesc("data", self._shape)]
        self.provide_label = [mx.io.DataDesc("softmax_label", self._shape)]

    def next(self):
        x = self._rng.integers(0, self._vocab, self._shape, dtype=np.int64)
        nd = self._mx.nd
        return self._mx.io.DataBatch(
            data=[nd.array(x.astype(np.float32))],
            label=[nd.array(np.roll(x, -1, axis=1).astype(np.float32))],
            pad=0, provide_data=self.provide_data,
            provide_label=self.provide_label)


def make_iter(mx, traffic, cfg, seed, rows, root):
    return _Packed(mx, traffic, cfg, seed, rows)


def units_per_batch(traffic, rows):
    return rows * int(traffic["seq_len"])
