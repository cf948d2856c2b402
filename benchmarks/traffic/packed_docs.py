"""The one general generator of training rows: documents of clipped
log-normal length, packed into rows of ``seq_len`` by the program's own
``pack_documents`` (the path a user's data takes).

As in ``requests.py`` every seed gets the same set of document lengths
(the mid-quantiles of the distribution, in blocks), in another order,
with other token values.  A document counts up from a random start
with a random small stride, modulo the vocabulary: a language the
model can learn in a few steps, so "the loss falls" is a check.
"""

from __future__ import annotations

import numpy as np


def make(mix, seed, vocab_size, n_rows):
    """``(rows [n_rows, seq_len+1] int32, segments alike)``."""
    from distkeras_tpu.data.packing import pack_documents
    from traffic.requests import lognormal_quantiles

    seq = int(mix["seq_len"])
    block = int(mix.get("block", 64))
    lens_q = lognormal_quantiles(mix["doc_len"], block)
    rows, segs, b = [], [], 0
    n_have = 0
    while n_have < n_rows:
        rng = np.random.default_rng([int(seed), b])
        docs = []
        for n in rng.permutation(lens_q):
            start = rng.integers(0, vocab_size)
            stride = rng.integers(1, 4)
            docs.append((start + stride * np.arange(int(n))) % vocab_size)
        r, s = pack_documents(docs, seq_len=seq)
        # The last row of a block is a padded partial row: keep it, as
        # a user's corpus has one too; its padding trains nothing and
        # counts for nothing.
        rows.append(r)
        segs.append(s)
        n_have += len(r)
        b += 1
    rows = np.concatenate(rows)[:n_rows].astype(np.int32)
    segs = np.concatenate(segs)[:n_rows].astype(np.int32)
    return rows, segs


def target_tokens(segs):
    """Non-padding target tokens per row: positions whose target lies
    in the same document (the trainer's own loss mask)."""
    return ((segs[:, 1:] == segs[:, :-1]) & (segs[:, :-1] != 0)).sum(axis=1)
