"""Operations and bytes a stack of LATENT layers (multi-head latent
attention over a dense or routed feed-forward) REQUIRES, from a
configuration file's ``transformer_config`` alone (``flops_moe.py``
counts the experts; this file the attention around them).  A
multiply-add is two operations.

Counted by the MODEL's own equations, whatever form the program takes:
a position's products by parameters — the low-rank query pair, the
joint projection to the latent, ``c · wkv_b`` ONCE a position (what
rebuilds its keys and values), the output projection — and attention
``2 * heads * (nope + rope + v)`` an attended pair a layer.  The
absorbed form the program serves multiplies ``2 * heads * (2 * rank +
rope)`` a pair, 3.4x that at JoyAI's widths, and is credited with none
of it: a share of the peak counted so cannot pass 100.

Bytes at the LEAST layout: a cached position is ``rank + rope`` values
a layer (the program pads its rows to whole lane tiles and is charged
for it in its rooflines).
"""

from __future__ import annotations

import flops_moe


def latent_layers(tc):
    return list(tc.get("layer_types") or ()).count("latent")


def attn_params(tc):
    """The five attention matrices of one latent layer."""
    d, h = tc["d_model"], tc["n_heads"]
    rq, rkv = tc["q_lora_rank"], tc["kv_lora_rank"]
    nope, rope, v = (tc["qk_nope_head_dim"], tc["qk_rope_head_dim"],
                     tc["v_head_dim"])
    return (d * rq + rq * h * (nope + rope) + d * (rkv + rope)
            + rkv * h * (nope + v) + h * v * d)


def layer_fixed_params(tc, ffn):
    """Weights EVERY position multiplies in a latent layer of that
    feed-forward kind (``flops_moe.layer_fixed_params`` with this
    attention)."""
    return (flops_moe.layer_fixed_params(tc, ffn) - flops_moe.attn_params(tc)
            + attn_params(tc))


def fixed_params(tc):
    return sum(layer_fixed_params(tc, f) for f in (
        tc.get("ffn_types") or ["dense"] * tc["n_layers"]))


def position_flops(tc, decoded, held_share):
    """Operations one position requires outside attention: every
    layer's fixed products, its held assignments' experts, and the head
    over the rows held if its logits are used (a decoded token)."""
    routed = (flops_moe.sparse_layers(tc) * tc.get("moe_top_k", 0)
              * held_share * flops_moe.expert_params(tc))
    return 2 * (fixed_params(tc) + routed
                + (flops_moe.head_params(tc) if decoded else 0))


def attention_flops(tc, pairs):
    """QK^T over ``nope + rope`` and PV over ``v``, every head, every
    latent layer, an attended pair."""
    return (2 * tc["n_heads"] * (tc["qk_nope_head_dim"]
                                 + tc["qk_rope_head_dim"] + tc["v_head_dim"])
            * latent_layers(tc) * pairs)


def slot_bytes(tc, layers=None, itemsize=2):
    """Bytes a cached position REQUIRES: the latent and the shared
    rotary key, ``layers`` layers (every latent layer by default)."""
    n = latent_layers(tc) if layers is None else layers
    return n * (tc["kv_lora_rank"] + tc["qk_rope_head_dim"]) * itemsize


def weight_bytes(tc, itemsize=2):
    """Bytes of weights one decode step has to read once: every
    layer's fixed weights, the held experts of every sparse layer (the
    program reads each in every product), the head's rows held."""
    held = (flops_moe.sparse_layers(tc) * flops_moe.held_experts(tc)
            * flops_moe.expert_params(tc))
    return (fixed_params(tc) + held + flops_moe.head_params(tc)) * itemsize


def decode_step_bytes(tc, live, itemsize=2):
    """Bytes one decode step has to read: the weights once and the
    ``live`` cached positions at the least layout."""
    return weight_bytes(tc, itemsize) + live * slot_bytes(tc, None, itemsize)


def decode_kernel_least_s(tc, slots, peaks, itemsize=2):
    """Least time of ONE call of the absorbed decode kernel (a layer of
    a step) over ``slots`` live positions: the larger of their bytes at
    the least layout and the absorbed form's own products — every head
    against a row for the score, the row's latent part for the value."""
    rank, rope = tc["kv_lora_rank"], tc["qk_rope_head_dim"]
    t_bytes = slots * slot_bytes(tc, 1, itemsize) / peaks["hbm_bytes_per_s"]
    t_ops = (slots * 2 * tc["n_heads"] * (2 * rank + rope)
             / peaks["bf16_flops_per_s"])
    return t_bytes, t_ops


def rounds_between(record, lo, hi):
    """The fields of the decoding ``serving.round`` spans that began in
    ``[lo, hi)`` (not ``idle``, a decode dispatched: ``kv_live``)."""
    return [r["fields"] for r in record.get("obs_events", ())
            if r.get("kind") == "span" and r["name"] == "serving.round"
            and lo <= r["t0"] < hi and not r["fields"].get("idle")
            and "kv_live" in r["fields"]]


def latent_layout(record):
    """The engine's ``serving.kv_layout`` fields where it names latent
    planes, or None (a program older than them)."""
    found = [r["fields"] for r in record.get("obs_events", ())
             if r.get("name") == "serving.kv_layout"
             and r.get("fields", {}).get("planes_latent")]
    return found[-1] if found else None


def prefix_kernel_flops(tc, new, start):
    """Operations ONE call of the chunk's kernel (a latent layer of an
    admission) cannot do without, by the model's own equations: the
    chunk's ``new`` rows' keys and values from their latent (``c ·
    wkv_b``, once a position), and the ``new * (start + new / 2)``
    pairs they attend after ``start`` earlier positions at ``2 * heads
    * (nope + rope + v)``.  A kernel that keeps no keys or values and
    rebuilds the earlier positions' too (:func:`prefix_rebuilt_flops`)
    is credited with none of that."""
    h, nope, v = tc["n_heads"], tc["qk_nope_head_dim"], tc["v_head_dim"]
    pairs = new * (start + new / 2)
    return (2 * new * tc["kv_lora_rank"] * h * (nope + v)
            + 2 * h * (nope + tc["qk_rope_head_dim"] + v) * pairs)


def prefix_rebuilt_flops(tc, start):
    """Operations a call of the expanded chunk kernel spends on ``c ·
    wkv_b`` for the ``start`` positions BEFORE its chunk: work the form
    chooses (it keeps none of them), not work the model requires."""
    return (2 * start * tc["kv_lora_rank"] * tc["n_heads"]
            * (tc["qk_nope_head_dim"] + tc["v_head_dim"]))
