"""The one generator of training batches, driven by a traffic file.

Each node draws its sequences from a noisy affine token process
``next = (a_i * cur + b_i) mod V``; ``heterogeneity`` moves each node's
``(a_i, b_i)`` away from a shared pair, so nodes see different data
(non-IID shards), and ``noise`` is the share of uniformly random tokens.
Everything is a function of (seed, step): the same seed gives the same
batches, and every step's rows differ from every other step's.

This is the same process as the program's ``data/synthetic.SyntheticLM``,
kept here so that the benchmark's inputs cannot change with the program.
"""

from __future__ import annotations

import numpy as np


class TrafficLM:
    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        self.v = int(vocab_size)
        self.n = int(traffic["nodes"])
        self.b = int(traffic["per_node_batch"])
        self.s = int(traffic["seq_len"])
        self.noise = float(traffic["noise"])
        self.seed = int(seed)
        rng = np.random.default_rng(self.seed)
        h = float(traffic["heterogeneity"])
        a0 = int(rng.integers(3, self.v - 1)) | 1
        b0 = int(rng.integers(1, self.v - 1))
        self.a = np.empty(self.n, np.int64)
        self.c = np.empty(self.n, np.int64)
        for i in range(self.n):
            span = max(1, int(h * self.v))
            da = int(rng.integers(0, span)) if h > 0 else 0
            db = int(rng.integers(0, span)) if h > 0 else 0
            self.a[i] = ((a0 + 2 * da) % self.v) | 1
            self.c[i] = (b0 + db) % self.v

    @property
    def tokens_per_step(self) -> int:
        return self.n * self.b * self.s

    def batch(self, step: int) -> dict[str, np.ndarray]:
        """{tokens, targets}: int32 (nodes * per_node_batch, seq_len), node
        after node."""
        rng = np.random.default_rng((self.seed, int(step)))
        shape = (self.n, self.b, self.s)
        seqs = np.empty((self.n, self.b, self.s + 1), np.int64)
        cur = rng.integers(0, self.v, (self.n, self.b))
        seqs[:, :, 0] = cur
        noise = rng.random(shape) < self.noise
        rand = rng.integers(0, self.v, shape)
        a, c = self.a[:, None], self.c[:, None]
        for t in range(self.s):
            cur = np.where(noise[:, :, t], rand[:, :, t], (a * cur + c) % self.v)
            seqs[:, :, t + 1] = cur
        flat = seqs.reshape(self.n * self.b, self.s + 1)
        return {"tokens": flat[:, :-1].astype(np.int32),
                "targets": flat[:, 1:].astype(np.int32)}
