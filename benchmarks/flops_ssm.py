"""Operations and bytes a stack of SELECTIVE STATE-SPACE (Mamba) layers
among full-attention layers (``distkeras_tpu/ops/ssm.py``) REQUIRES,
from a configuration file's ``transformer_config`` alone.  A
multiply-add is two operations.

Counted by the model's own equations, whatever form the program takes.
A position costs, in an ssm layer, the products by parameters (``w_in``,
``w_x``, ``w_dt``, ``w_out``, the feed-forward) and the scan's ``N x
E`` updates — ``s = exp(Delta A) s + Delta B x`` and ``y = C . s``, a
multiply-add each: ``4 N E`` — with the conv's ``2 K E``; in an
attention layer the products by parameters and ``4 x heads x head`` an
attended pair (``q k^T`` and ``p v``).  The ``exp`` of every update,
``N E`` a position, is a transcendental and counted apart
(:func:`scan_elementwise`).

Bytes: a lane's state is, an ssm layer, ``s [N, E]`` float32 and the
conv's last ``K - 1`` inputs in the weights' dtype; a cached position of
an attention layer its keys and values of every K/V head.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def kinds(tc):
    return list(tc.get("layer_types") or ())


def ssm_layers(tc):
    return kinds(tc).count("ssm")


def attn_layers(tc):
    return kinds(tc).count("full")


def head_dim(tc):
    return tc.get("d_head") or tc["d_model"] // tc["n_heads"]


def inner(tc):
    return tc["ssm_expand"] * tc["d_model"]


def mixer_params(tc):
    """Every parameter of one ssm layer's mixer: ``w_in``, the conv and
    its bias, ``w_x``, ``w_dt`` and its bias, ``A_log``, ``D``, ``w_out``
    and the three inner norms' scales."""
    d, e = tc["d_model"], inner(tc)
    n, k, r = tc["ssm_state"], tc["ssm_conv"], tc["ssm_dt_rank"]
    return (2 * d * e + (k + 1) * e + e * (r + 2 * n) + (r + 1) * e
            + (n + 1) * e + e * d + r + 2 * n)


def mixer_product_params(tc):
    """The mixer's weights a position multiplies: ``w_in``, ``w_x``,
    ``w_dt``, ``w_out``."""
    d, e = tc["d_model"], inner(tc)
    return 2 * d * e + e * (tc["ssm_dt_rank"] + 2 * tc["ssm_state"]) \
        + tc["ssm_dt_rank"] * e + e * d


def attn_params(tc):
    d, hd = tc["d_model"], head_dim(tc)
    kv = tc.get("n_kv_heads") or tc["n_heads"]
    return 2 * d * tc["n_heads"] * hd + 2 * d * kv * hd


def ffn_params(tc):
    return 3 * tc["d_model"] * tc["d_ff"]


def head_params(tc):
    return tc["vocab_size"] * tc["d_model"]


def total_params(tc):
    """The whole model: layers, norms, and the embedding (which is the
    head where it is tied)."""
    norms = 2 * tc["d_model"]
    head = 0 if tc.get("tie_head", True) else head_params(tc)
    return (ssm_layers(tc) * (mixer_params(tc) + ffn_params(tc) + norms)
            + attn_layers(tc) * (attn_params(tc) + ffn_params(tc) + norms)
            + head_params(tc) + head + tc["d_model"])


def scan_flops(tc):
    """The recurrence's operations a position, over all ssm layers: the
    update and ``C . s`` (a multiply-add each a state) and the conv."""
    e = inner(tc)
    return ssm_layers(tc) * (4 * tc["ssm_state"] * e + 2 * tc["ssm_conv"] * e)


def scan_elementwise(tc, positions):
    """``(vector operations, transcendentals)`` of the scan over
    ``positions`` positions of ONE ssm layer: ``exp(Delta A)``, the two
    products and the sum of the update, ``C s`` and its sum, a state; the
    ``exp`` a state."""
    n, e = tc["ssm_state"], inner(tc)
    return 6 * positions * n * e, positions * n * e


def position_flops(tc, decoded):
    """Operations one position requires outside attention's pairs: the
    products by parameters, the scan, and the head if its logits are
    used (a decoded token)."""
    products = (ssm_layers(tc) * (mixer_product_params(tc) + ffn_params(tc))
                + attn_layers(tc) * (attn_params(tc) + ffn_params(tc))
                + (head_params(tc) if decoded else 0))
    return 2 * products + scan_flops(tc)


def pair_flops(tc, pairs):
    """``q k^T`` and ``p v`` over attended pairs, in every attention
    layer."""
    return 4 * tc["n_heads"] * head_dim(tc) * attn_layers(tc) * pairs


def state_bytes(tc, layers=None, itemsize=2):
    """One lane's state: ``s`` (float32) and the conv's tail (in
    ``itemsize``) of ``layers`` ssm layers (default: all)."""
    layers = ssm_layers(tc) if layers is None else layers
    e = inner(tc)
    return layers * (4 * tc["ssm_state"] * e + itemsize * (tc["ssm_conv"] - 1) * e)


def slot_bytes(tc, itemsize=2):
    """One cached position over the attention layers: keys and values
    of every K/V head."""
    kv = tc.get("n_kv_heads") or tc["n_heads"]
    return attn_layers(tc) * 2 * kv * head_dim(tc) * itemsize


def weight_bytes(tc, itemsize=2):
    """What a decode step reads once: every weight (the tied embedding
    once, as the head)."""
    return total_params(tc) * itemsize


def decode_step_bytes(tc, state_lanes, kv_live, itemsize=2):
    """Bytes one decode step has to move: the weights once, the state
    and conv tail of every lane it decodes read and written once, and
    the attention layers' live keys and values read once."""
    return (weight_bytes(tc, itemsize)
            + 2 * state_lanes * state_bytes(tc, itemsize=itemsize)
            + kv_live * slot_bytes(tc, itemsize))


def chunk_kernel_least(tc, rows, peaks):
    """``(seconds bound by bytes, seconds bound by operations)`` of ONE
    ssm layer's scan over a chunk of ``rows`` real positions: ``x`` and
    ``Delta`` in and ``y`` out (float32, ``E`` each), ``B`` and ``C``
    in, the state in and out once; the update's and ``C . s``'s
    multiply-adds."""
    n, e = tc["ssm_state"], inner(tc)
    nbytes = rows * 4 * (3 * e + 2 * n) + 2 * 4 * n * e
    ops = rows * 4 * n * e
    return nbytes / peaks["hbm_bytes_per_s"], ops / peaks["bf16_flops_per_s"]


# ------------------------------- what the program's counters say of it


def ssm_layout(record):
    """The engine's ``serving.kv_layout`` fields where they name ssm
    planes, or None: the program has none."""
    layout = [r["fields"] for r in record.get("obs_events", ())
              if r.get("name") == "serving.kv_layout"
              and r.get("fields", {}).get("planes_ssm")]
    return layout[-1] if layout else None


def rounds_between(record, lo, hi):
    """Fields of the ``serving.round`` spans that began in ``[lo, hi)``
    and dispatched a decode step (their ``state_lanes``)."""
    return [r["fields"] for r in record.get("obs_events", ())
            if r.get("kind") == "span" and r["name"] == "serving.round"
            and lo <= r["t0"] < hi and r["fields"].get("state_lanes")]
