"""Operations and bytes an algorithm REQUIRES, from shapes alone.

``transformer_config`` keys as in the configuration files.  Nothing
here asks a compiler: ``cost_analysis()`` counts rematerialised work
and a scan body once, which is why the benchmark keeps its own.
A multiply-add is two operations.
"""

from __future__ import annotations

import numpy as np


def attended_pairs(segs, window=None):
    """Query-key pairs of the causal, windowed, within-document area of
    packed rows ``segs [rows, S]`` (0 = padding): a position attends
    itself and the earlier positions of its own document, the last
    ``window`` at most."""
    total = 0
    for row in np.asarray(segs):
        change = np.flatnonzero(np.diff(row)) + 1
        for part in np.split(row, change):
            n = len(part)
            if not n or part[0] == 0:
                continue
            w = n if window is None else min(window, n)
            # positions 0..w-1 attend 1..w keys, the rest attend w.
            total += w * (w + 1) // 2 + (n - w) * w
    return int(total)


def matmul_params_per_token(tc):
    """Weights a token multiplies in one forward pass: the four
    attention projections, the two FFN matrices, the tied head."""
    d, h = tc["d_model"], tc["n_heads"]
    kv = tc.get("n_kv_heads") or h
    hd = d // h
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 2 * d * tc["d_ff"]
    return tc["n_layers"] * per_layer + tc["vocab_size"] * d


def train_flops(tc, input_tokens, pairs):
    """Forward + backward over ``input_tokens`` positions of which
    ``pairs`` query-key pairs are attended: 6 operations per weight per
    token, and per pair per layer 4·head_dim·heads forward (QK^T, PV)
    and twice that backward.  Recomputation is not counted."""
    d = tc["d_model"]
    dense = 6 * matmul_params_per_token(tc) * input_tokens
    attn = 12 * d * tc["n_layers"] * pairs
    return dense + attn


# The three Pallas kernels (ops/attention.py), per call over rows with
# ``pairs`` attended pairs: matrix products each makes, x 2·head_dim
# per pair per head.
KERNEL_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def attention_kernel_flops(tc, pairs, kind):
    return KERNEL_MATMULS[kind] * 2 * tc["d_model"] * pairs


def attention_kernel_bytes(tc, tokens, kind, itemsize=2):
    """q, k, v (k, v already repeated to the query heads, as the
    kernels are called) and what each kernel also reads and writes."""
    d = tc["d_model"]
    arrays = {"fwd": 4, "dq": 5, "dkv": 6}[kind]    # q k v o | +do dq | +do dk dv
    return arrays * tokens * d * itemsize
