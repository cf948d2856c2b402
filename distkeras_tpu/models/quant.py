"""int8 weight quantization for the decode path.

The sequential decode loop re-reads every matmul weight from HBM once
per generated token at batch sizes far too small to amortize it —
decode is weight-bandwidth-bound, the opposite regime from training.
Storing the weights as int8 with per-output-channel scales halves the
bytes vs bf16 (4x vs f32); the dequantize (one multiply) happens
*inside* the decode step so XLA fuses it into the consuming matmul's
operand read — int8 comes off HBM, full-precision math happens in
registers.

This is a decode-time serving optimization (lossy: ~1/254 relative
rounding per channel); training is untouched.  The reference has no
inference-optimization story at all (its ModelPredictor runs the
training forward, reference: distkeras/predictors.py) — this module is
TPU-first surplus.

Usage::

    qparams = quantize_params(params)           # host-side, once
    out = generate(qparams, prompt, cfg, ...)   # decode reads int8

``generate`` detects quantized leaves and keeps the sequential path
(prefill would run the batched training forward, which wants the
full-precision weights; pass the f32 params for prompt-heavy work).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QTensor:
    """int8 values + f32 per-output-channel scales.

    ``q * s`` reconstructs the weight; ``s`` broadcasts against ``q``
    (kept at the same rank, size 1 on contraction axes).
    """

    q: jax.Array  # int8
    s: jax.Array  # f32, broadcastable to q.shape

    @property
    def shape(self):
        return self.q.shape

    def dequant(self, dtype=jnp.float32):
        return (self.q.astype(jnp.float32) * self.s).astype(dtype)

    def tree_flatten(self):
        return (self.q, self.s), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _quantize(w, contract_axes: tuple[int, ...]) -> QTensor:
    """Symmetric absmax int8 over the contraction axes.

    Scales are per *output* channel: the max is taken over the axes the
    consuming matmul sums over, so each output channel rounds
    independently (the standard weight-only scheme).
    """
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
    return QTensor(q=q, s=s)


# Weight name -> axes the decode-step matmul contracts over (leading
# [L] stack axis excluded; it is never contracted).
_CONTRACT = {
    "wq": (1,), "wk": (1,), "wv": (1,),   # [L, d, h, hd]: sum over d
    "wo": (1, 2),                          # [L, h, hd, d]: sum over h, hd
    "w1": (1,), "w2": (1,),                # [L, d, f] / [L, f, d]
}


def quantize_params(params):
    """Quantize the decode-relevant matmul weights of a transformer
    parameter tree (models/transformer.init_params layout) to int8.

    Quantized: attention projections, dense-FFN mats, and ``tok_emb``
    (per-vocab-row scales — the unembedding's output channel, which is
    also exactly what a gathered embedding row needs).  Left in f32:
    RMSNorm scales (tiny, precision-critical) and MoE tensors (the
    decode MoE path gathers per-token expert slabs; quantizing those is
    future work).  Returns a tree of the same structure with
    :class:`QTensor` leaves where quantized.
    """
    params = dict(params)
    layers = dict(params["layers"])
    if "moe" in layers:
        raise ValueError(
            "quantize_params supports dense-FFN configs only (decode-time "
            "MoE gathers per-token expert slabs; see module docstring)")
    attn = {k: _quantize(v, _CONTRACT[k])
            for k, v in layers["attn"].items()}
    ffn = {k: _quantize(v, _CONTRACT[k])
           for k, v in layers["ffn"].items()}
    layers["attn"] = attn
    layers["ffn"] = ffn
    params["layers"] = layers
    # tok_emb [V, d]: scale per vocab row (axis 1 is contracted by the
    # unembed x @ emb^T; a gathered row dequants with its own scale).
    params["tok_emb"] = _quantize(params["tok_emb"], (1,))
    return params


def is_quantized(params) -> bool:
    return isinstance(params.get("tok_emb"), QTensor)


def deq(w, dtype=None):
    """Dequantize-if-needed: QTensor -> dense (f32 or ``dtype``),
    anything else passes through.  The decode step routes every weight
    read through here, so quantized and plain trees share one code
    path and the multiply sits next to its consuming matmul for XLA to
    fuse."""
    if isinstance(w, QTensor):
        return w.dequant(dtype or jnp.float32)
    return w if dtype is None else jnp.asarray(w).astype(dtype)


def unembed_logits(x, tok_emb, dtype):
    """Unembedding head ``x [..., d] @ tok_emb^T [V, d] -> [..., V]``.

    Quantized path: contract against the raw int8 table and apply the
    per-vocab-row scale to the [B, V] *result* — algebraically identical
    (the scale is constant over the contracted ``d`` axis) but the [V, d]
    HBM operand is int8 **by construction**: the only op between the
    table and the MXU is a dtype convert, which XLA always fuses into
    the operand read.  The alternative (dequantize then einsum) leaves a
    full-precision [V, d] temporary unless XLA happens to fuse the
    multiply — for the usually-dominant vocab head we don't want to
    depend on that.  int8 values are exact in bf16 (|q| <= 127 < 2^8),
    so converting q to the compute dtype loses nothing.
    """
    if isinstance(tok_emb, QTensor):
        out = jnp.einsum("...d,vd->...v", x, tok_emb.q.astype(x.dtype))
        return out.astype(jnp.float32) * tok_emb.s[:, 0]
    return jnp.einsum("...d,vd->...v", x,
                      jnp.asarray(tok_emb).astype(dtype))


def embed_rows(tok_emb, tokens, dtype):
    """Embedding lookup that gathers int8 rows THEN dequantizes (the
    gather touches B rows, not the whole [V, d] table)."""
    if isinstance(tok_emb, QTensor):
        rows = tok_emb.q[tokens].astype(jnp.float32)
        return (rows * tok_emb.s[tokens]).astype(dtype)
    return tok_emb[tokens].astype(dtype)


def quantize_kv(x):
    """Per-token, per-kv-head symmetric int8 quantization of decode-time
    K/V rows ``[..., kv_heads, head_dim]`` -> ``(int8, scale)`` with
    ``scale [..., kv_heads]`` = absmax / 127 over head_dim.

    The int8 KV cache halves the cache-byte term that dominates batched
    decode once the loop is at the HBM roofline, where only byte
    reduction goes faster (measured 2026-07-31 on one v5e, not
    re-measured since).  Scales stay f32: they are head_dim x smaller
    than the data.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return q.astype(jnp.int8), scale
