"""The one general generator of serving requests.

A mix file (``traffic/<name>.json``) gives two clipped log-normal
length distributions (prompt, output) and the arrivals: closed loop
(``clients_per_lane``) or open loop at ``rate_per_s``.

Every seed gets THE SAME stream of sizes and gaps, in the same order;
the seed draws the token values (and, in the driver, the weights).
Requests come in blocks of ``block`` (default 64).  A block holds the
``block`` mid-quantiles of each distribution; the pairing of prompt
with output length and the order within block ``b`` are drawn from
``b`` alone.  So a window of a run holds the same work whatever the
seed, and the offered rate is exact over each block.

Why not another order per seed: it was tried (chip, PR 23).  Requests
of the ``repo_batch`` mix live some 20 s of a 51 s window, and which
lanes prefill while which decode follows from the order: tokens per
second were 0.01-0.45 % apart between two runs of one seed and 8 %
apart between seeds; the 90th percentile of the time to first token
of an open loop 1 % and 35 %.  An order drawn from the seed makes the
seed change the work.

The open loop is STRATIFIED, not Poisson: the gaps are the
mid-quantiles of the exponential distribution in a drawn order, so
their histogram is the exponential's, but the number of arrivals in a
block never varies and the longest gap is bounded (about 4.9 mean gaps
at 64 a block).  It has less burstiness than a Poisson process, and a
tail measured under it is a floor for the tail under one.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def lognormal_quantiles(spec, n):
    """The ``n`` mid-quantiles of a log-normal with the given median
    and sigma, clipped to ``[min, max]``, as whole numbers."""
    mu = math.log(spec["median"])
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        out.append(int(round(min(max(math.exp(mu + spec["sigma"] * z),
                                     spec["min"]), spec["max"]))))
    return np.asarray(out, np.int64)


def exponential_quantiles(rate, n):
    """``n`` mid-quantiles of the exponential gap at ``rate``, scaled
    so that they sum to exactly ``n / rate`` seconds."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q * (n / rate) / q.sum()


class Requests:
    """``get(i)`` -> ``(due_s, prompt tokens, max_new)`` for request
    ``i`` of an endless stream; ``due_s`` is None in a closed loop."""

    def __init__(self, mix, seed, vocab_size):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab_size)
        self.block = int(mix.get("block", 64))
        self.prompt_q = lognormal_quantiles(mix["prompt_len"], self.block)
        self.output_q = lognormal_quantiles(mix["output_len"], self.block)
        self.rate = mix.get("rate_per_s")
        self.gaps_q = (exponential_quantiles(self.rate, self.block)
                       if self.rate else None)
        self._blocks = {}

    @property
    def open_loop(self):
        return self.rate is not None

    def _block(self, b):
        blk = self._blocks.get(b)
        if blk is None:
            order = np.random.default_rng(b)      # not the seed's business
            prompts = order.permutation(self.prompt_q)
            outputs = order.permutation(self.output_q)
            due = None
            if self.gaps_q is not None:
                due = (b * self.block / self.rate
                       + np.cumsum(order.permutation(self.gaps_q)))
            rng = np.random.default_rng([self.seed, b])
            toks = [rng.integers(0, self.vocab, int(p)).astype(np.int32)
                    for p in prompts]
            blk = self._blocks[b] = (due, toks, outputs)
            self._blocks.pop(b - 4, None)       # the stream only moves on
        return blk

    def get(self, i):
        due, toks, outputs = self._block(i // self.block)
        j = i % self.block
        return (None if due is None else float(due[j]), toks[j],
                int(outputs[j]))

    def warmup(self, buckets, chunk):
        """One short request per admission width the mix's prompts can
        reach, and one prompt longer than the chunk (the continuation
        program): ``(prompt tokens, max_new)`` pairs, the same for
        every seed."""
        rng = np.random.default_rng(0)
        longest = int(self.prompt_q.max())
        lens = [w for w in sorted(buckets) if w <= longest]
        if chunk and longest > chunk + 1:
            lens.append(min(longest, chunk + min(buckets) + 1))
        lens = lens or [int(self.prompt_q.min())]
        return [(rng.integers(0, self.vocab, n).astype(np.int32), 4)
                for n in lens]


def make(mix, seed, vocab_size):
    return Requests(mix, seed, vocab_size)
