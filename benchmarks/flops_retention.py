"""Operations and bytes a stack of POWER-RETENTION layers (degree 2,
``distkeras_tpu/ops/retention.py``) REQUIRES, from a configuration
file's ``transformer_config`` alone.  A multiply-add is two operations.

A retention layer's running state is, a K/V head, the matrix ``S
[D_phi, head]`` and the vector ``z [D_phi]`` with ``D_phi = head (head
+ 1) / 2`` — the LEAST a layout can hold: the symmetric products ``k_a
k_b``, ``a <= b`` (8,256 rows at a head of 128; the program's cyclic
layout spends 8,320).  By the recurrent form a position costs, a
layer, the update ``2 x kv x D_phi x head`` and the query ``2 x heads x
D_phi x head``; a prefill chunk adds its own (query, key) pairs, ``4 x
heads x head`` a pair (the products ``q k^T`` and ``A v``).
"""

from __future__ import annotations


def head_dim(tc):
    return tc.get("d_head") or tc["d_model"] // tc["n_heads"]


def kv_heads(tc):
    return tc.get("n_kv_heads") or tc["n_heads"]


def phi_rows(tc):
    d = head_dim(tc)
    return d * (d + 1) // 2


def layer_params(tc):
    """Weights every position multiplies in one layer: q, k, v, o, the
    gate, and the gated feed-forward's three matrices."""
    d, hd = tc["d_model"], head_dim(tc)
    return (2 * d * tc["n_heads"] * hd + 2 * d * kv_heads(tc) * hd
            + d * kv_heads(tc) + 3 * d * tc["d_ff"])


def head_params(tc):
    return tc["vocab_size"] * tc["d_model"]


def state_flops(tc):
    """The recurrent form's operations a position, over all layers:
    the state's update and its query."""
    return 2 * tc["n_layers"] * phi_rows(tc) * head_dim(tc) * (
        kv_heads(tc) + tc["n_heads"])


def position_flops(tc, decoded):
    """Operations one position requires: the products by parameters,
    the state's update and query, and the head if its logits are used
    (a decoded token)."""
    return (2 * (tc["n_layers"] * layer_params(tc)
                 + (head_params(tc) if decoded else 0)) + state_flops(tc))


def pair_flops(tc, pairs):
    """``q k^T`` and ``A v`` over a chunk's own attended pairs, in
    every layer."""
    return 4 * tc["n_heads"] * head_dim(tc) * tc["n_layers"] * pairs


def state_bytes(tc, layers=None, itemsize=4):
    """One lane's state at the least layout: ``S`` and ``z`` of every
    K/V head of ``layers`` layers (default: all)."""
    layers = tc["n_layers"] if layers is None else layers
    return (layers * kv_heads(tc) * phi_rows(tc) * (head_dim(tc) + 1)
            * itemsize)


def weight_bytes(tc, itemsize=2):
    """The layers' weights and the head's table: what a decode step
    reads once (the embedding's rows are not read whole)."""
    return (tc["n_layers"] * layer_params(tc) + head_params(tc)) * itemsize


def decode_step_bytes(tc, state_lanes, itemsize=2, state_itemsize=4):
    """Bytes one decode step has to move: the weights once, and the
    state of every lane it decodes read and written once."""
    return (weight_bytes(tc, itemsize)
            + 2 * state_lanes * state_bytes(tc, itemsize=state_itemsize))


# ------------------------------- what the program's counters say of it

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def decoding_lanes(record):
    """``state_lanes`` of the ``serving.round`` spans that dispatched a
    decode step while the profiler ran: the lanes whose state each of
    those steps read and wrote."""
    lo, hi = record["profile_window"]
    return [r["fields"]["state_lanes"] for r in record.get("obs_events", ())
            if r.get("kind") == "span" and r["name"] == "serving.round"
            and lo <= r["t0"] < hi and r["fields"].get("state_lanes")]


def state_itemsize(record):
    """Of the engine's state planes (``serving.kv_layout``'s
    ``state_dtype``), or None: the program has none."""
    layout = [r["fields"] for r in record.get("obs_events", ())
              if r.get("name") == "serving.kv_layout"
              and r.get("fields", {}).get("planes_state")]
    return ITEMSIZE[layout[-1]["state_dtype"]] if layout else None
