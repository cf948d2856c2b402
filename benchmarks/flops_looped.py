"""Operations and bytes a LOOPED transformer REQUIRES, from shapes
alone (``flops.py`` counts two feed-forward matrices, a tied head and
one pass).  ``tc`` is a configuration file's ``transformer_config``:
``n_passes`` passes over the same ``n_layers`` layers, a gated
feed-forward of three matrices where ``ffn_gated``, the head once.  A
multiply-add is two operations.
"""

from __future__ import annotations


def layer_matmul_params(tc):
    """Weights a position multiplies in ONE layer of ONE pass: the four
    attention projections and the feed-forward's two or three
    matrices."""
    d, h = tc["d_model"], tc["n_heads"]
    kv = tc.get("n_kv_heads") or h
    hd = d // h
    ffn = (3 if tc.get("ffn_gated") else 2) * d * tc["d_ff"]
    return 2 * d * h * hd + 2 * d * kv * hd + ffn


def stack_matmul_params(tc):
    """Weights of the layer stack, counted once (they are shared by
    the passes)."""
    return tc["n_layers"] * layer_matmul_params(tc)


def head_params(tc):
    return tc["vocab_size"] * tc["d_model"]


def position_flops(tc, decoded):
    """Operations one position requires outside attention: every pass
    of every layer's products, and the head if the position's logits
    are used (a decoded token; an admitted prompt position's are not)."""
    passes = int(tc.get("n_passes", 1))
    return 2 * (passes * stack_matmul_params(tc)
                + (head_params(tc) if decoded else 0))


def attention_flops(tc, pairs):
    """QK^T and PV over ``pairs`` attended (query, key) pairs, in every
    layer of every pass: 4 * heads * head_dim a pair."""
    passes = int(tc.get("n_passes", 1))
    return 4 * tc["d_model"] * tc["n_layers"] * passes * pairs


def kv_bytes_per_slot(tc, itemsize=2):
    """Keys and values one cached position holds: a plane per pass and
    layer."""
    d, h = tc["d_model"], tc["n_heads"]
    kv = tc.get("n_kv_heads") or h
    passes = int(tc.get("n_passes", 1))
    return 2 * passes * tc["n_layers"] * kv * (d // h) * itemsize


def decode_step_bytes(tc, live_slots, bytes_per_slot, itemsize=2):
    """Bytes one decode step has to read: the stack's weights once a
    PASS (nothing keeps 4.9 GB on the chip between passes), the head's
    table once, and the live cache slots' keys and values once."""
    passes = int(tc.get("n_passes", 1))
    return (passes * stack_matmul_params(tc) * itemsize
            + head_params(tc) * itemsize
            + live_slots * bytes_per_slot)
