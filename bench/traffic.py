"""One generator for every traffic mix.

A mix file (``bench/traffic/<mix>.json``) gives the shape of the traffic:
question lengths, passages per prompt, output lengths, open or closed loop.
A cell file (``bench/cells/<cell>.json``) gives what the deployment fixes:
the offered rate of an open loop, the clients of a closed one, the decode
slots.  The seed changes the order of the work and the token ids, never
the work itself.  The requests come in blocks of ``shuffle_block`` (the
mix's; all of them in one block when it has none).  Every block holds the
same work whatever the seed: output lengths and inter-arrival gaps at
stratified quantiles of their distributions, the gaps scaled to last
exactly ``shuffle_block / rate``, and its share of question lengths; the
seed shuffles each block.  So two seeds differ only as two orderings of one
workload, and the load over any stretch of a few blocks is the same.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


@dataclass
class Item:
    q_len: int
    n_out: int
    gap_s: float            # open loop: time since the previous arrival


def _stratified_ints(lo: int, hi: int, n: int) -> np.ndarray:
    """n integers spread evenly over [lo, hi], both ends among them."""
    if n == 1:
        return np.asarray([(lo + hi) // 2], np.int64)
    return np.round(lo + (hi - lo) * np.arange(n) / (n - 1)).astype(np.int64)


def _stratified_exponential(rate: float, n: int) -> np.ndarray:
    """n quantiles of an exponential with mean 1/rate: Poisson gaps."""
    return -np.log1p(-(np.arange(n) + 0.5) / n) / rate


class Traffic:
    """Requests of one run: ``items`` in order, and the question tokens of
    request ``i`` from ``question(i)``."""

    def __init__(self, mix: dict, cell: dict, seed: int, vocab: int,
                 horizon_s: float):
        self.mix, self.cell, self.vocab = mix, cell, vocab
        self.open = mix["loop"] == "open"
        if self.open:
            self.rate = float(cell["rate_per_s"])
            n = int(math.ceil(self.rate * horizon_s)) + 1
        else:
            self.rate = None
            n = int(mix["pool_requests"])
        rng = np.random.default_rng(np.random.SeedSequence([seed, 11]))
        q_all = np.resize(np.asarray(mix["question_tokens"], np.int64), n)
        lo, hi = mix["output_tokens"]
        block = int(mix.get("shuffle_block", n))
        self.items = []
        for k in range(0, n, block):
            m = min(block, n - k)
            gaps = np.zeros(m)
            if self.open:
                gaps = _stratified_exponential(self.rate, m)
                gaps *= (m / self.rate) / gaps.sum()
            q_len, n_out, gaps = (rng.permutation(a) for a in (
                q_all[k:k + m], _stratified_ints(lo, hi, m), gaps))
            self.items += [Item(int(a), int(b), float(c))
                           for a, b, c in zip(q_len, n_out, gaps)]
        self.seed = seed

    def __len__(self) -> int:
        return len(self.items)

    def item(self, i: int) -> Item:
        return self.items[i % len(self.items)]

    def question(self, i: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 13, i])
        return rng.integers(0, self.vocab, self.item(i).q_len,
                            dtype=np.int64).astype(np.int32)

    def offsets(self) -> np.ndarray:
        """Open loop: arrival offsets (s) from the start of the traffic."""
        return np.cumsum([it.gap_s for it in self.items])


def max_output(mix: dict) -> int:
    return int(mix["output_tokens"][1])


def prompt_budget(mix: dict) -> int:
    """Longest prompt the engine admits: s_max less the longest output
    and the position of the last token."""
    return int(mix["s_max"]) - max_output(mix) - 1
