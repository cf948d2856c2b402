"""Ring attention: sequence/context parallelism over the mesh ``seq`` axis.

The reference caps sequence length at single-device memory (its longest
sequence model is the IMDB LSTM at maxlen=128; reference: examples).
Here long context is first-class: the sequence dimension is sharded over
the mesh ``seq`` axis and attention runs as a ring — each device holds
its Q shard permanently plus a rotating KV shard, updates flash-style
online-softmax state (distkeras_tpu.ops.attention.attention_chunk), and
``ppermute``s the KV block to its ring neighbour.  After ``seq`` hops
every Q row has attended to the full global sequence while per-device
memory stays O(L/seq).  The KV transfer rides the ICI ring concurrently
with the chunk matmuls (XLA overlaps the ppermute DMA with compute).

This is the Ring Attention construction (Liu et al., 2023 — see
PAPERS.md); the blockwise core it rotates is shared with the Pallas
flash kernel so single-device and ring numerics match by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu.ops.attention import (
    attention_chunk,
    online_finish,
    online_init,
    _check_window,
    _scale_for,
)


def ring_attention(q, k, v, axis_name: str = "seq", causal: bool = False,
                   scale: float | None = None, window: int | None = None,
                   segment_ids=None):
    """Per-shard ring attention body; call inside ``shard_map``.

    ``q/k/v: [B, L_local, H, D]`` — the local shard of a sequence of
    global length ``L_local * axis_size``.  Returns the local shard of
    the attention output.

    ``window`` (causal sliding window) masks on *global* positions via
    the per-hop offsets, so ring + local attention composes exactly
    with the single-device result; hops whose KV shard lies entirely
    beyond the lookback contribute nothing (masked, still rotated —
    the ring must complete for the other devices).

    ``segment_ids [B, L_local]`` (the local shard of packed-document
    ids): the query-side shard stays put and a KV-side copy rotates
    around the ring WITH its K/V shard, so every hop masks exactly the
    cross-document pairs the single-device computation would — packed
    long-context training over the seq axis.
    """
    _check_window(window, causal)
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    s = _scale_for(q, scale)
    qf = q.astype(jnp.float32)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    # Segmented-ness is static at trace time: the unsegmented carry
    # simply has no segment slot (no dead ppermute per hop).
    segmented = segment_ids is not None

    def update(m, l, o, kc, vc, sc, hop):
        # After `hop` rotations we hold the KV shard originally on
        # (my_idx - hop) mod axis_size; offsets make causal masking
        # global-position-correct.
        src = (my_idx - hop) % axis_size
        return attention_chunk(
            qf, kc.astype(jnp.float32), vc.astype(jnp.float32), m, l, o,
            causal, s, q_offset=my_idx * lq, kv_offset=src * lk,
            window=window, seg_q=segment_ids, seg_k=sc)

    def body(carry, hop):
        m, l, o, kc, vc, *sc = carry
        m, l, o = update(m, l, o, kc, vc, sc[0] if segmented else None,
                         hop)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        if segmented:
            sc = [jax.lax.ppermute(sc[0], axis_name, perm)]
        return (m, l, o, kc, vc, *sc), None

    # The last hop consumes its KV shard without rotating it onward —
    # scanning all `axis_size` hops would send one extra KV shard per
    # device over the ICI for nothing.
    init = (*online_init(b, h, lq, d), k, v) + (
        (segment_ids,) if segmented else ())
    (m, l, o, kc, vc, *sc), _ = jax.lax.scan(
        body, init, jnp.arange(axis_size - 1))
    m, l, o = update(m, l, o, kc, vc, sc[0] if segmented else None,
                     axis_size - 1)
    return online_finish(m, l, o).astype(q.dtype)


def make_ring_attention(mesh: Mesh, axis_name: str = "seq",
                        batch_axis: str | None = "data",
                        causal: bool = False, scale: float | None = None,
                        window: int | None = None):
    """Wrap :func:`ring_attention` in shard_map over ``mesh``.

    Returns ``f(q, k, v) -> out`` taking/returning global arrays of
    shape [B, L, H, D]; batch is sharded over ``batch_axis``, sequence
    over ``axis_name``, heads/dim replicated.  Composes under an outer
    jit/pjit — tensor parallelism on the H axis can be layered by
    sharding the projection weights, not this function.

    Do NOT call this wrapper inside another shard_map (a nested
    shard_map does not transpose under AD): code that is already manual
    over ``axis_name`` — the PP x SP pipeline — calls the raw
    :func:`ring_attention` body directly instead
    (transformer.apply_pipelined's ``seq_axis``).
    """
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           causal=causal, scale=scale, window=window)
    spec = P(batch_axis, axis_name, None, None)
    mapped = shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    seg_spec = P(batch_axis, axis_name)
    mapped_seg = shard_map(
        lambda q, k, v, seg: fn(q, k, v, segment_ids=seg),
        mesh=mesh, in_specs=(spec, spec, spec, seg_spec),
        out_specs=spec, check_vma=False)

    def ring_fn(q, k, v, segment_ids=None):
        if segment_ids is None:
            return mapped(q, k, v)
        return mapped_seg(q, k, v, segment_ids)

    # Tells apply_hidden's window guard WHICH window this attention_fn
    # implements; the guard requires it to equal cfg.attention_window
    # (a mismatched band would silently diverge train from decode).
    ring_fn.handles_window = window
    # Tells _resolve_attention_fn this fn accepts packed segment_ids
    # (it wraps the per-call segments in; fns without the attribute
    # are rejected rather than silently skipping the attention mask).
    ring_fn.handles_segments = True
    return ring_fn


def sequence_sharding(mesh: Mesh, batch_axis: str | None = "data",
                      axis_name: str = "seq") -> NamedSharding:
    """NamedSharding for [B, L, ...] activations under ring attention."""
    return NamedSharding(mesh, P(batch_axis, axis_name))
