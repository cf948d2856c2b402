"""Operations and bytes a TYPED stack REQUIRES — window and full
attention mixed, a routed feed-forward of which a share of the experts
is held — from a configuration file's ``transformer_config`` alone
(``flops_looped.py`` counts a dense looped stack).  A multiply-add is
two operations.

What a position multiplies in a sparse layer is what the cut has here:
the router over all experts, the shared expert, and an expert's three
matrices for each of its assignments that fell on a HELD expert —
``held_share`` of its ``moe_top_k``, the measured ``moe_held /
moe_assigned`` of ``serving.round`` (1/8 is what to expect of 16 held
of 128).  Attention is counted by attended pairs, a window layer's
capped at the window.
"""

from __future__ import annotations


def _kinds(tc):
    n = tc["n_layers"]
    return list(zip(tc.get("layer_types") or ["full"] * n,
                    tc.get("ffn_types") or ["dense"] * n))


def head_dim(tc):
    return tc.get("d_head") or tc["d_model"] // tc["n_heads"]


def attn_params(tc):
    """The four attention projections of one layer."""
    kv = tc.get("n_kv_heads") or tc["n_heads"]
    return 2 * tc["d_model"] * head_dim(tc) * (tc["n_heads"] + kv)


def expert_params(tc):
    return 3 * tc["d_model"] * (tc.get("moe_d_ff") or tc["d_ff"])


def held_experts(tc):
    held = tc.get("moe_held")
    return tc.get("num_experts", 0) if held is None else len(held)


def sparse_layers(tc):
    return sum(1 for _, f in _kinds(tc) if f == "sparse")


def layer_fixed_params(tc, ffn):
    """Weights EVERY position multiplies in a layer of that
    feed-forward kind: attention, and the dense feed-forward or the
    router and the shared experts."""
    if ffn == "dense":
        return attn_params(tc) + 3 * tc["d_model"] * tc["d_ff"]
    return (attn_params(tc) + tc["d_model"] * tc["num_experts"]
            + tc.get("moe_shared", 0) * expert_params(tc))


def head_params(tc):
    return tc["vocab_size"] * tc["d_model"]


def position_flops(tc, decoded, held_share):
    """Operations one position requires outside attention: every
    layer's fixed products, its held assignments' experts, and the head
    if its logits are used (a decoded token)."""
    fixed = sum(layer_fixed_params(tc, f) for _, f in _kinds(tc))
    routed = (sparse_layers(tc) * tc.get("moe_top_k", 0) * held_share
              * expert_params(tc))
    return 2 * (fixed + routed + (head_params(tc) if decoded else 0))


def attention_flops(tc, pairs_full, pairs_window):
    """QK^T and PV: 4 * heads * head_dim a pair, over the full layers'
    attended pairs and the window layers' (capped by the caller)."""
    per_pair = 4 * tc["n_heads"] * head_dim(tc)
    kinds = [a for a, _ in _kinds(tc)]
    return per_pair * (kinds.count("full") * pairs_full
                       + kinds.count("window") * pairs_window)


def weight_bytes(tc, itemsize=2):
    """Bytes of weights one decode step has to read once: every layer's
    fixed weights, the held experts of every sparse layer (the program
    reads each in every product, reached by a token or not:
    ``transformer.moe_held_experts`` keeps a row tile a group), the
    head."""
    fixed = sum(layer_fixed_params(tc, f) for _, f in _kinds(tc))
    held = sparse_layers(tc) * held_experts(tc) * expert_params(tc)
    return (fixed + held + head_params(tc)) * itemsize


def decode_step_bytes(tc, live_full, live_window, per_slot_full,
                      per_slot_window, itemsize=2):
    """Bytes one decode step has to read: the weights once, and the
    live cache slots by kind — a full plane's at every live position,
    a window plane's at the last ``window`` of them."""
    return (weight_bytes(tc, itemsize) + live_full * per_slot_full
            + live_window * per_slot_window)
