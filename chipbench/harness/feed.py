"""Training input: synthetic token batches made on the host from the seed,
by a thread that runs ahead of the training loop (the input pipeline).

The generator is an order-1 Markov chain with Zipf-ish sparse transitions
(copied from ``repro.data.MarkovLM``: ``branching`` successors per token,
probabilities ∝ rank^-``zipf``), vectorised over rows. Batch ``i`` of a
seed is always the same array, whether the feed or the reference asks."""
from __future__ import annotations

import queue
import threading

import numpy as np


class Markov:
    def __init__(self, vocab: int, branching: int, zipf: float, seed: int):
        r = np.random.default_rng([int(seed), 7])
        self.next = r.integers(0, vocab, (vocab, branching))
        p = 1.0 / (np.arange(1, branching + 1) ** zipf)
        self.cum = np.cumsum(p / p.sum())
        self.vocab, self.seed = vocab, int(seed)

    def batch(self, i: int, rows: int, seq: int) -> np.ndarray:
        r = np.random.default_rng([self.seed, 11, int(i)])
        x = np.empty((rows, seq), np.int32)
        x[:, 0] = r.integers(0, self.vocab, rows)
        u = r.random((seq - 1, rows))
        k = np.minimum(np.searchsorted(self.cum, u), len(self.cum) - 1)
        for t in range(1, seq):
            x[:, t] = self.next[x[:, t - 1], k[t - 1]]
        return x


class Feed:
    """Batches 0, 1, 2, ... made ``depth`` ahead by one thread."""

    def __init__(self, gen: Markov, rows: int, seq: int, depth: int = 4):
        self.gen, self.rows, self.seq = gen, rows, seq
        self.q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def _run(self):
        i = 0
        while not self._stop.is_set():
            b = self.gen.batch(i, self.rows, self.seq)
            while not self._stop.is_set():
                try:
                    self.q.put((i, b), timeout=0.1)
                    break
                except queue.Full:
                    continue
            i += 1

    def next(self):
        return self.q.get()

    def close(self):
        self._stop.set()
        self._t.join()
