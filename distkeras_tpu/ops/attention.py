"""Attention kernels: naive, blockwise (online-softmax), and Pallas flash.

The reference has no attention anywhere (its largest model is an LSTM —
reference: examples, IMDB config); this module exists because the TPU
rebuild treats long-context training as first-class.  Three tiers share
one set of semantics so tests can pin them against each other:

- :func:`naive_attention` — O(L^2) materialized logits; the numerics
  oracle for tests.
- :func:`blockwise_attention` — online-softmax over KV chunks
  (`lax.scan`), O(block) memory; pure jnp so it runs on any backend and
  is the differentiable reference for the flash kernel's VJP.  Its
  chunk-update core (:func:`attention_chunk`) is also the per-hop step
  of ring attention (distkeras_tpu.parallel.ring).
- :func:`flash_attention` — Pallas TPU kernel (MXU-tiled, VMEM-resident
  online softmax) on TPU backends at kernel-legal shapes; blockwise
  elsewhere (a dispatch on backend and shape, decided before the
  kernel is built — a kernel that fails to lower or compile raises).
  On the Pallas path the backward is the FA2 construction (dQ and
  dK/dV kernels rebuilding probabilities per tile from the forward's
  saved log-sum-exp); the blockwise backward recomputes through the
  blockwise implementation under ``jax.vjp``.  O(L) residuals either
  way.  Inside a multi-device ``jit`` the kernels run per batch/head
  shard (:func:`_per_shard`).
- :func:`flash_prefix_attention` — Pallas TPU forward kernel
  (``flash_prefix_fwd``) for a chunk of queries at a TRACED offset
  against a KV cache it reads only as far as the chunk's last position:
  chunked prefill's attention (models/generate.py::_decode_chunk takes
  it where :func:`use_flash_prefix` says so, and keeps its dense body
  everywhere else).  Inference only; grouped and multi-query heads
  read their K/V head through the block index, no ``repeat``.
- :func:`flash_decode_attention` — Pallas TPU forward kernel
  (``flash_decode_fwd``) for the decode step: each lane's few query
  rows against the live prefix of ITS row of one plane of the KV slab,
  which it is handed whole with the plane's index and the lanes'
  positions (models/generate.py::_chunk_in_place takes it where
  :func:`use_flash_decode` says so).  Bytes follow the lanes' lengths.

All but the last two take ``q: [B, Lq, H, D]``, ``k/v: [B, Lkv, H, D]`` and return
``[B, Lq, H, D]``.  ``q_offset``/``kv_offset`` give the global positions
of the local chunks so causal masking works when sequences are sharded
(ring attention).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

# Finite stand-in for -inf: keeps exp()/max() NaN-free when a whole row
# or chunk is masked (e.g. ring hops entirely in the causal future).
NEG_INF = -1e30


def _scale_for(q, scale):
    return (1.0 / math.sqrt(q.shape[-1])) if scale is None else scale


def _causal_mask(lq: int, lk: int, q_offset, kv_offset, window=None):
    """[lq, lk] bool mask: True where q position >= k position (global);
    with ``window`` also requires q - k < window (causal sliding
    window: each query sees its last ``window`` positions, self
    included)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0) + q_offset
    cols = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1) + kv_offset
    mask = rows >= cols
    if window is not None:
        mask = mask & (rows - cols < window)
    return mask


def _check_window(window, causal) -> None:
    if window is None:
        return
    if not causal:
        raise ValueError(
            "window (sliding-window attention) requires causal=True — "
            "the window is defined over the causal past")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")


def naive_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    q_offset: int = 0, kv_offset: int = 0,
                    window: int | None = None, segment_ids=None):
    """Materialized-logits attention; the test oracle.

    ``segment_ids [B, L]`` (packed sequences): positions attend only
    within their own segment — the mask composes with causal/window.
    """
    _check_window(window, causal)
    scale = _scale_for(q, scale)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = _causal_mask(q.shape[1], k.shape[1], q_offset, kv_offset,
                            window)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    if segment_ids is not None:
        seg = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        logits = jnp.where(seg, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# --------------------------------------------------------------- online core


def attention_chunk(q, k, v, m, l, o, causal: bool, scale: float,
                    q_offset, kv_offset, window: int | None = None,
                    seg_q=None, seg_k=None):
    """One online-softmax update with a KV chunk.

    Running state (per q row): ``m`` max logit ``[B,H,Lq]``, ``l``
    normalizer ``[B,H,Lq]``, ``o`` unnormalized output ``[B,H,Lq,D]``.
    This is the flash-attention recurrence; ring attention replays it
    once per hop with the offsets of whichever shard's KV it holds.
    ``seg_q [B, Lq]`` / ``seg_k [B, Lk]``: segment (packed-document)
    masking — cross-segment pairs are dead.
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = _causal_mask(q.shape[1], k.shape[1], q_offset, kv_offset,
                            window)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    if seg_q is not None:
        seg = seg_q[:, None, :, None] == seg_k[:, None, None, :]
        logits = jnp.where(seg, logits, NEG_INF)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new[..., None])
    l_new = l * correction + p.sum(axis=-1)
    o_new = o * correction[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v)
    return m_new, l_new, o_new


def online_init(batch, heads, lq, dim, dtype=jnp.float32):
    """Fresh (m, l, o) state for the online-softmax recurrence."""
    return (jnp.full((batch, heads, lq), NEG_INF, dtype),
            jnp.zeros((batch, heads, lq), dtype),
            jnp.zeros((batch, heads, lq, dim), dtype))


def online_finish(m, l, o):
    """Normalize accumulated output -> [B, Lq, H, D].

    Fully-masked rows return the uniform average of V — identical to
    softmax over an all-``NEG_INF`` row, i.e. exactly what the naive
    oracle computes (finite NEG_INF keeps every tier NaN-free and
    mutually consistent).  The ``l == 0`` guard only protects against
    catastrophic exp-underflow, not the masked case.
    """
    out = o / jnp.where(l == 0, 1.0, l)[..., None]
    return out.transpose(0, 2, 1, 3)


def blockwise_attention(q, k, v, causal: bool = False,
                        scale: float | None = None, block_k: int = 512,
                        q_offset: int = 0, kv_offset: int = 0,
                        window: int | None = None, segment_ids=None):
    """Online-softmax attention scanning KV in chunks; O(block_k) logits.

    Pure jnp: the differentiable any-backend reference for
    :func:`flash_attention`, and the single-device semantics that ring
    attention distributes.  ``segment_ids [B, L]`` masks attention to
    within-segment pairs (packed sequences); requires lq == lkv.
    """
    _check_window(window, causal)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    # Clamp to the largest divisor of lk <= block_k so any length works
    # (e.g. lk=1000 -> 500).  Prime lk degenerates to block_k=1 — pick
    # a composite sequence length if that matters.
    block_k = min(block_k, lk)
    while lk % block_k:
        block_k -= 1
    scale = _scale_for(q, scale)
    n_blocks = lk // block_k
    # [n, B, block, H, D] chunk-major for lax.scan.
    ks = k.reshape(b, n_blocks, block_k, h, d).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(b, n_blocks, block_k, h, d).transpose(1, 0, 2, 3, 4)
    if segment_ids is not None:
        if segment_ids.shape != (b, lk) or lq != lk:
            raise ValueError(
                f"segment_ids must be [batch, seq] = ({b}, {lk}) with "
                f"lq == lkv, got {segment_ids.shape} (lq={lq})")
        segs = segment_ids.reshape(b, n_blocks, block_k).transpose(1, 0, 2)
    else:
        segs = jnp.zeros((n_blocks, b, 1), jnp.int32)  # unused
    qf = q.astype(jnp.float32)

    def body(carry, chunk):
        m, l, o = carry
        kc, vc, sc, idx = chunk
        m, l, o = attention_chunk(
            qf, kc.astype(jnp.float32), vc.astype(jnp.float32), m, l, o,
            causal, scale, q_offset, kv_offset + idx * block_k, window,
            seg_q=None if segment_ids is None else segment_ids,
            seg_k=None if segment_ids is None else sc)
        return (m, l, o), None

    (m, l, o), _ = jax.lax.scan(
        body, online_init(b, h, lq, d),
        (ks, vs, segs, jnp.arange(n_blocks)))
    return online_finish(m, l, o).astype(q.dtype)


# ------------------------------------------------------------- Pallas kernel


def _flash_kernel(*refs, causal: bool, scale: float, with_lse: bool,
                  window: int | None = None, segmented: bool = False,
                  tiles: tuple | None = None, heads: int = 1):
    """Flash-attention forward for one (batch*head, q-block, kv-block) cell.

    KV streams through the grid's innermost dimension so VMEM holds only
    one [block_k, D] tile at a time — sequence length is HBM-bound, not
    VMEM-bound.  Online-softmax state (m, l, acc) lives in VMEM scratch,
    which persists across the sequential kv-block iterations; it is
    initialized at j == 0 and the normalized output is written at the
    last j.  ``m``/``l`` are stored lane-broadcast ([block_q, 128]) to
    respect the f32 (8, 128) tile.

    With ``with_lse`` (the training path) it also writes the per-row
    log-sum-exp (``lse = m + log l``), the residual the FA2-style
    backward kernels need to rebuild softmax probabilities tile-by-tile
    without O(L^2) memory; inference omits the output (and its HBM
    writes) entirely.

    ``segmented``: two extra int32 inputs (q/k segment-id tiles, laid
    out by :func:`_segment_operands`) gate the logits to within-segment
    pairs — packed-document masking.

    ``tiles`` (:func:`_tile_bounds`; a segmented launch): two leading
    scalar-prefetch refs give, per (row, q tile), the first and last k
    tile that holds an attended pair.  The block is then walked as
    ``tiles = (tile_q, tile_k)`` sub-tiles and one outside its q
    tile's range is not multiplied.  ``heads``: grid index -> row.
    """
    bounds = None
    if tiles is not None:
        bounds, refs = refs[:2], refs[2:]
    q_ref, k_ref, v_ref, *refs = refs
    if segmented:
        qseg_ref, kseg_ref, *refs = refs
    if with_lse:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    j = pl.program_id(2)
    n_kb = pl.num_programs(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    tile_q, tile_k = tiles or (block_q, block_k)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: kv blocks strictly above the diagonal contribute nothing;
    # predicate the whole update away (restores the ~2x causal saving).
    # A window switches to a BANDED grid (see _banded_kv): the inner
    # dimension walks only the ~window/block_k blocks inside the
    # lookback, so K/V HBM traffic — not just compute — is O(window).
    row0 = pl.program_id(1) * block_q
    if window is None:
        col0 = j * block_k
        live = (not causal) or (col0 <= row0 + block_q - 1)
    else:
        col0, live = _banded_cols(row0, j, n_kb, block_q, block_k, window)

    def _update(r, u):
        rows, cols = _tile_at(r, tile_q), _tile_at(u, tile_k)
        qi = jax.lax.convert_element_type(q_ref[0, rows], jnp.float32) * scale
        kj = jax.lax.convert_element_type(k_ref[0, cols], jnp.float32)
        vj = jax.lax.convert_element_type(v_ref[0, cols], jnp.float32)
        logits = jax.lax.dot_general(
            qi, kj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [tile_q, tile_k]
        if causal:
            logits = jnp.where(
                _keep_mask(logits.shape, _plus(row0, r * tile_q),
                           _plus(col0, u * tile_k), window),
                logits, NEG_INF)
        if segmented:
            logits = jnp.where(_same_segment(qseg_ref, kseg_ref, rows, u),
                               logits, NEG_INF)
        m = m_scr[rows, :1]
        l = l_scr[rows, :1]
        m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[rows] = acc_scr[rows] * corr + jax.lax.dot_general(
            p, vj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[rows] = jnp.broadcast_to(m_new, (tile_q, m_scr.shape[1]))
        l_scr[rows] = jnp.broadcast_to(l_new, (tile_q, l_scr.shape[1]))

    _walk_tiles(_update, live, bounds, heads,
                resident=(row0, block_q, tile_q),
                streamed=(col0, block_k, tile_k))

    @pl.when(j == n_kb - 1)
    def _finish():
        l = l_scr[:, :1]
        out = acc_scr[:] / jnp.where(l == 0, 1.0, l)
        o_ref[0] = out.astype(o_ref.dtype)
        if with_lse:
            # Lane-broadcast [block_q, 128]: rank-2 (1, block_q) blocks
            # break the TPU (8, 128) tiling; a trailing lane dim is the
            # idiom.
            lse_ref[0] = jnp.broadcast_to(
                m_scr[:, :1] + jnp.log(jnp.where(l == 0, 1.0, l)),
                lse_ref.shape[1:])


# The TPU (sublane, lane) tile: the last two dims of every block must
# divide by it (or span the whole array), which a (1, block) tile of a
# rank-2 [rows, S] array does not — the Mosaic lowering refuses it.
_SUBLANES, _LANES = 8, 128


def _segment_operands(segment_ids, h: int, q_at, k_at, tiles=None):
    """Segment ids as kernel operands: ``(in_specs, args)``.

    The q side is lane-broadcast ``[B, S, 128]`` (the kernel reads a
    ``[tile_q, 1]`` column), the k side one sublane-broadcast
    ``[8, tile_k]`` slab a k tile, ``[B, S / tile_k * 8, tile_k]``
    (read as a ``[1, tile_k]`` row; a tile of the block is a row slice,
    never a lane slice), so both blocks respect the TPU tile and the
    comparison broadcasts with no in-kernel transpose.  Heads share
    their batch row: the index maps divide the flattened batch*head
    grid index by ``h`` instead of repeating the ids per head.
    ``q_at``/``k_at`` are the ``(block, index_map)`` pairs of the
    rank-3 q/k tiles; the segment tiles ride the SAME sequence block
    index, so banded walks stay in lockstep.  ``tiles``: the launch's
    ``(tile_q, tile_k)`` (None: a block is one tile).
    """
    seg = segment_ids.astype(jnp.int32)
    b, s = seg.shape
    (_, block_q, _), q_map = q_at
    (_, block_k, _), k_map = k_at
    tile_k = tiles[1] if tiles else block_k
    specs = [
        pl.BlockSpec((1, block_q, _LANES),
                     lambda bh, *g: (bh // h, q_map(bh, *g)[1], 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k // tile_k * _SUBLANES, tile_k),
                     lambda bh, *g: (bh // h, k_map(bh, *g)[1], 0),
                     memory_space=pltpu.VMEM),
    ]
    by_tile = jnp.broadcast_to(seg.reshape(b, s // tile_k, 1, tile_k),
                               (b, s // tile_k, _SUBLANES, tile_k))
    return specs, [jnp.broadcast_to(seg[:, :, None], (b, s, _LANES)),
                   by_tile.reshape(b, -1, tile_k)]


def _same_segment(qseg_ref, kseg_ref, rows, u):
    """[tile_q, tile_k] within-segment mask of q rows ``rows`` and k
    tile ``u`` of the block, from the tiles of
    :func:`_segment_operands` — the ONE definition all three kernels
    share."""
    return qseg_ref[0, rows][:, :1] == kseg_ref[0, _tile_at(u, _SUBLANES)][:1]


# ------------------------------------------ tiles no document spans
#
# A packed row's documents make most of the causal band dead: a tile
# whose q rows and k columns share no segment id holds no attended
# pair.  A segmented launch finds those tiles from the ids (plain
# jax.numpy, inside the jitted step) and hands each kernel, as scalar
# prefetch, the first and last live tile of the streamed side for every
# tile of the resident side.  The kernels' predicate and the streamed
# operands' index maps read the same two arrays.


def _tile_band(n_q: int, n_k: int, tile_q: int, tile_k: int, causal: bool,
               window: int | None):
    """``[n_q, n_k]`` bool (numpy): tiles that hold a pair the causal,
    windowed mask keeps."""
    row0 = np.arange(n_q)[:, None] * tile_q
    col0 = np.arange(n_k)[None, :] * tile_k
    band = np.ones((n_q, n_k), bool)
    if causal:
        band &= col0 <= row0 + tile_q - 1
    if window is not None:
        band &= col0 + tile_k - 1 >= row0 - (window - 1)
    return band


def _live_tiles(seg, tile_q: int, tile_k: int, causal: bool,
                window: int | None):
    """``[B, S/tile_q, S/tile_k]`` bool: tiles inside the band whose q
    rows' and k columns' segment-id ranges overlap.  Safe for any ids
    (no equal pair exists where the ranges do not meet), exact for ids
    that do not decrease along a row before its padding
    (``pack_documents``).  numpy in, numpy out."""
    b, s = seg.shape
    # Padding (id 0) trails a packed row: ranked last, the row's ids do
    # not decrease and the test stays exact in its last tiles too.
    seg = seg + (seg == 0) * jnp.iinfo(jnp.int32).max
    qt = seg.reshape(b, s // tile_q, tile_q)
    kt = seg.reshape(b, s // tile_k, tile_k)
    overlap = ((qt.min(-1)[:, :, None] <= kt.max(-1)[:, None, :])
               & (kt.min(-1)[:, None, :] <= qt.max(-1)[:, :, None]))
    return overlap & _tile_band(s // tile_q, s // tile_k, tile_q, tile_k,
                                causal, window)


def _first_last(live, axis: int, xp=jnp):
    """First and last True along ``axis`` (every resident tile has its
    diagonal tile live; were one empty it would read 0 .. n-1)."""
    n = live.shape[axis]
    return (xp.argmax(live, axis=axis),
            n - 1 - xp.argmax(xp.flip(live, axis), axis=axis))


def _tile_bounds(segment_ids, tile_q: int, tile_k: int, causal: bool,
                 window: int | None, resident: str):
    """The kernels' scalar-prefetch operands: int32 ``(lo, hi)``, each
    ``[B * tiles a row]`` — per (row, tile of the ``resident`` side:
    ``"q"`` forward and dQ, ``"k"`` dK/dV) the first and last live tile
    of the streamed side.  A tile between them is computed."""
    live = _live_tiles(segment_ids.astype(jnp.int32), tile_q, tile_k,
                       causal, window)
    lo, hi = _first_last(live, 2 if resident == "q" else 1)
    return (lo.astype(jnp.int32).reshape(-1),
            hi.astype(jnp.int32).reshape(-1))


def live_tile_share(segments, block_q: int, block_k: int,
                    window: int | None = None) -> float:
    """Tiles the segmented kernels compute / tiles of the causal
    (windowed) band, at ``block_q x block_k`` tiles over packed rows
    ``segments [rows, S]``: the kernels' predicate, in numpy on the
    host (``train.attn_live_tile_share``)."""
    seg = np.asarray(segments)
    live = _live_tiles(seg, block_q, block_k, True, window)
    lo, hi = _first_last(live, 2, xp=np)
    at = np.arange(live.shape[2])
    computed = (lo[..., None] <= at) & (at <= hi[..., None])
    band = _tile_band(*live.shape[1:], block_q, block_k, True, window)
    return float(computed.sum() / (len(seg) * band.sum()))


def _skip_dead(index_map, segment_ids, tiles, blocks, causal: bool,
               window: int | None, resident: str, heads: int):
    """``(index_map, bounds)`` of a launch's streamed operands.  With
    ``tiles``: the bounds of :func:`_tile_bounds`, and the map under
    scalar prefetch — a step whose block holds no live tile maps to the
    nearest block that does, so the pipeline sees an unchanged index
    and issues no copy (as the banded maps' clamped duplicates already
    do).  Without: the map as it is, and None."""
    if tiles is None:
        return index_map, None
    side = 0 if resident == "q" else 1
    per_block = blocks[side] // tiles[side]         # resident tiles a block
    per_row = segment_ids.shape[1] // tiles[side]
    streamed = blocks[1 - side] // tiles[1 - side]  # streamed tiles a block

    def bounded(bh, i, j, lo_ref, hi_ref):
        b_, block, z = index_map(bh, i, j)
        at = (bh // heads) * per_row + i * per_block
        lo, hi = lo_ref[at], hi_ref[at]
        for r in range(1, per_block):
            lo = jnp.minimum(lo, lo_ref[at + r])
            hi = jnp.maximum(hi, hi_ref[at + r])
        return b_, jnp.clip(block, lo // streamed, hi // streamed), z

    return bounded, _tile_bounds(segment_ids, *tiles, causal, window,
                                 resident)


def _walk_tiles(update, live, bounds, heads: int, resident, streamed,
                resident_is_q: bool = True):
    """Run ``update(r, u)`` (q tile ``r``, k tile ``u`` of the block) for
    a live block.  ``bounds`` None: the block is one tile.  Else
    ``(lo_ref, hi_ref)``: every tile that lies inside its resident
    tile's live range — ONE body in a loop over the block's tiles, so a
    kernel's program does not grow with them.  ``resident`` /
    ``streamed``: ``(first position, block, tile)`` of either side."""
    if bounds is None:
        pl.when(live)(functools.partial(update, 0, 0))
        return
    lo_ref, hi_ref = bounds
    (res0, res_block, res_tile), (str0, str_block, str_tile) = \
        resident, streamed
    n_res, n_str = res_block // res_tile, str_block // str_tile
    per_row = pl.num_programs(1) * n_res
    first = (pl.program_id(0) // heads) * per_row + res0 // res_tile

    def tile(a, c):
        at = str0 // str_tile + c
        inside = (lo_ref[first + a] <= at) & (at <= hi_ref[first + a])
        pl.when(inside)(functools.partial(
            update, *((a, c) if resident_is_q else (c, a))))

    @pl.when(live)
    def _block():
        if n_res * n_str == 1:
            tile(0, 0)
        else:
            jax.lax.fori_loop(
                0, n_res * n_str,
                lambda t, carry: (tile(t // n_str, t % n_str), carry)[1],
                None)


# The mesh axes (parallel/mesh.py::AXES) that carry the batch and the
# heads of an attention operand: data parallelism and FSDP shard the
# batch over ``data``, Megatron TP (transformer.tp_rules,
# serving_plan) shards the heads over ``model``.
_SHARD_AXIS = {"b": "data", "h": "model"}


def _auto_axes(x):
    """``(mesh, axes)``: the mesh ``x`` is computed under — recorded on
    its type, else the ambient ``jax.set_mesh`` — and those of its axes
    the compiler still partitions over (not made manual by a caller's
    ``shard_map``)."""
    mesh = jax.typeof(x).sharding.mesh
    if mesh.empty:
        mesh = jax.sharding.get_abstract_mesh()
    return mesh, [a for a in mesh.axis_names if a not in mesh.manual_axes]


def _per_shard(fn, in_dims, out_dims, *arrays):
    """``fn(*arrays)`` with every device running it on its own
    batch/head shard.

    A Mosaic kernel cannot be partitioned automatically: inside a
    multi-device ``jit`` it has to sit in a ``shard_map``, and a
    ``shard_map`` needs a mesh ``flash_attention`` is never given.  It
    reads the mesh where JAX already records it: on the operands'
    types (a ``jit`` argument committed to a ``NamedSharding`` hands
    its abstract mesh to everything computed from it), else the
    ambient ``jax.set_mesh``.  Attention is independent across batch
    and heads, so nothing is gathered: the batch splits over ``data``
    and the heads over ``model`` wherever the axis divides the
    dimension, and every other axis sees whole operands —
    sequence-sharded attention is ring attention's job
    (parallel/ring.py).  On one device, and where the caller's own
    ``shard_map`` already made every axis manual, this is ``fn``.

    ``in_dims``/``out_dims`` name each array's dimensions, one letter
    each; ``fn`` returns a tuple.
    """
    mesh, auto = _auto_axes(arrays[0])
    if math.prod(mesh.shape[a] for a in auto) == 1:
        return fn(*arrays)
    size = dict(zip(in_dims[0], arrays[0].shape))
    axis = {x: a for x, a in _SHARD_AXIS.items()
            if a in auto and size[x] % mesh.shape[a] == 0}
    spec = lambda dims: P(*(axis.get(x) for x in dims))
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(spec(d) for d in in_dims),
        out_specs=tuple(spec(d) for d in out_dims),
        axis_names=frozenset(auto), check_vma=False)(*arrays)


def _banded_cols(row0, j, n_inner: int, block_q: int, block_k: int,
                 window: int):
    """(col0, live) for the kv-streaming banded kernels (forward and
    dQ) — the ONE mirror of _banded_kv's index_map: raw < 0 are clamped
    duplicates of block 0 and predicated dead."""
    raw = (row0 + block_q - 1) // block_k - (n_inner - 1) + j
    col0 = jnp.maximum(raw, 0) * block_k
    live = ((raw >= 0)
            & (col0 <= row0 + block_q - 1)
            & (col0 + block_k - 1 >= row0 - (window - 1)))
    return col0, live


def _plus(base, offset):
    """``base + offset`` that traces nothing for a tile at its block's
    start: the launch without tiles is the program it always was."""
    return base if isinstance(offset, int) and offset == 0 else base + offset


def _tile_at(index, size: int):
    """Rows of tile ``index``: static for a Python ``index``, a dynamic
    slice aligned to ``size`` for a traced one (the loop over tiles)."""
    if isinstance(index, int):
        return pl.ds(index * size, size)
    return pl.ds(pl.multiple_of(index * size, size), size)


def _keep_mask(shape, row0, col0, window):
    """Causal (optionally banded) keep-mask for a [block_q, block_k]
    logits tile at global offsets (row0, col0) — shared by all three
    kernels so forward and backward masks cannot drift."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row0
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + col0
    keep = rows >= cols
    if window is not None:
        keep = keep & (rows - cols < window)
    return keep


def _banded_kv(window: int, block_q: int, block_k: int, n_kb: int):
    """Banded inner-grid spec for windowed kernels: (extent, index_map).

    A q block's live kv blocks span floor((row0-window+1)/bk) ..
    floor((row0+bq-1)/bk); the extent bounds that count over any
    alignment, and the map walks them ascending so the last j is the
    diagonal block.  Raw indices below 0 clamp to block 0 and the
    kernels predicate them dead (they would otherwise double-count)."""
    extent = min((window - 1 + block_q - 1) // block_k + 2, n_kb)

    def index_map(bh, i, j):
        last = (i * block_q + block_q - 1) // block_k
        return (bh, jnp.maximum(last - (extent - 1) + j, 0), 0)

    return extent, index_map


def _banded_q(window: int, block_q: int, block_k: int, n_qb: int):
    """Banded inner grid for the dkv kernel (q streams): a kv block's
    live q blocks span floor(col0/bq) .. floor((col0+bk-1+window-1)/bq);
    raw indices above the last block clamp down and are predicated
    dead."""
    extent = min((block_k - 1 + window - 1) // block_q + 2, n_qb)

    def index_map(bh, i, j):
        first = (i * block_k) // block_q
        return (bh, jnp.minimum(first + j, n_qb - 1), 0)

    return extent, index_map


def _flash_pallas(q, k, v, causal, scale, block_q, block_k, interpret=False,
                  with_lse=True, window=None, segment_ids=None):
    """Returns (out, lse ``[B, H, Lq]``) with ``with_lse`` (training),
    else (out, None) — inference skips the lse buffer's HBM writes
    entirely."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    block_q, block_k = _require_fit(block_q, lq), _require_fit(block_k, lk)
    local = functools.partial(
        _flash_fwd_local if segment_ids is None else _flash_fwd_segmented,
        causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, with_lse=with_lse,
        window=window, tiles=_segment_tiles("flash_fwd", block_q, block_k))
    seg = [] if segment_ids is None else [segment_ids]
    res = _per_shard(
        local,
        ["bqhd", "bkhd", "bkhd"] + ["bq" for _ in seg],
        ["bqhd", "bhq"] if with_lse else ["bqhd"],
        q, k, v, *seg)
    return res if with_lse else (res[0], None)


def _launch(kernel, name, grid, in_specs, out_specs, out_shape,
            scratch_shapes, cost_estimate, args, bounds=None):
    """One ``pallas_call``: today's plain grid, or — ``bounds``, the
    ``(lo, hi)`` of :func:`_tile_bounds` — the same grid under scalar
    prefetch, the bounds first among the kernel's and the index maps'
    arguments."""
    if bounds is None:
        return pl.pallas_call(
            kernel, name=name, grid=grid, in_specs=in_specs,
            out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=scratch_shapes,
            cost_estimate=cost_estimate)(*args)
    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        out_shape=out_shape, cost_estimate=cost_estimate,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=SEGMENT_VMEM_BYTES))(*bounds, *args)


def _flash_fwd_local(q, k, v, segment_ids=None, *, causal, scale, block_q,
                     block_k, interpret, with_lse, window, tiles=None,
                     skip_dead=True):
    """The forward launch on ONE device's operands (the whole arrays, or
    its batch/head shard under :func:`_per_shard`): ``(out,)`` or
    ``(out, lse)``.  With ``segment_ids`` the launch skips the tiles no
    document spans, at ``tiles`` (None: whole blocks); ``skip_dead``
    False (tests only) masks them instead, as a launch did before
    the skip."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    segmented = segment_ids is not None
    tiles = (tiles or (block_q, block_k)) if segmented and skip_dead else None
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    kernel = functools.partial(_flash_kernel, causal=causal, scale=scale,
                               with_lse=with_lse, window=window,
                               segmented=segmented, tiles=tiles, heads=h)

    q_at = ((1, block_q, d), lambda bh, i, j, *_: (bh, i, 0))
    o_spec = pl.BlockSpec(*q_at, memory_space=pltpu.VMEM)
    o_shape = jax.ShapeDtypeStruct((b * h, lq, d), q.dtype)
    lse_spec = pl.BlockSpec((1, block_q, _LANES), q_at[1],
                            memory_space=pltpu.VMEM)
    lse_shape = jax.ShapeDtypeStruct((b * h, lq, _LANES), jnp.float32)
    out_bytes = o_shape.size * q.dtype.itemsize + (
        lse_shape.size * 4 if with_lse else 0)

    n_kb = lk // block_k
    if window is not None:
        inner, kv_map = _banded_kv(window, block_q, block_k, n_kb)
    else:
        inner, kv_map = n_kb, (lambda bh, i, j: (bh, j, 0))
    kv_map, bounds = _skip_dead(kv_map, segment_ids, tiles,
                                (block_q, block_k), causal, window, "q", h)
    kv_at = ((1, block_k, d), kv_map)

    in_specs = [
        pl.BlockSpec(*q_at, memory_space=pltpu.VMEM),
        pl.BlockSpec(*kv_at, memory_space=pltpu.VMEM),
        pl.BlockSpec(*kv_at, memory_space=pltpu.VMEM),
    ]
    args = [qf, kf, vf]
    if segmented:
        seg_specs, seg_args = _segment_operands(segment_ids, h, q_at, kv_at,
                                                tiles)
        in_specs += seg_specs
        args += seg_args

    def call(): return _launch(
        kernel, "flash_fwd", (b * h, lq // block_q, inner), in_specs,
        (o_spec, lse_spec) if with_lse else o_spec,
        (o_shape, lse_shape) if with_lse else o_shape,
        [
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # m (lane-broadcast)
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, d), jnp.float32),       # acc
        ],
        pl.CostEstimate(
            flops=4 * b * h * lq * lk * d,
            bytes_accessed=(qf.nbytes + kf.nbytes + vf.nbytes + out_bytes),
            transcendentals=b * h * lq * lk,
        ), args, bounds)

    if interpret:
        # The TPU-semantics interpreter: validates the kernel (incl.
        # program_id, memory spaces) on CPU in tests.  The mode is
        # captured at pallas_call *construction*, hence the thunk.
        with pltpu.force_tpu_interpret_mode():
            res = call()
    else:
        res = call()
    out, lse = res if with_lse else (res, None)
    out = out.reshape(b, h, lq, d).transpose(0, 2, 1, 3)
    return (out, lse[:, :, 0].reshape(b, h, lq)) if with_lse else (out,)


# The trainer unrolls its layers, and the segmented kernels (bounds,
# loop, maps) take twice as long to trace and lower as the plain ones:
# jitted, a step traces and lowers them once, not once a layer and
# pass (4 layers, a CPU's seconds: 0.8 the first call and 0.3 after,
# against 2.1 unjitted and 1.2 without segments; as
# flash_prefix_attention).  The launch without segments stays as it
# was, call for call.
_flash_fwd_segmented = jax.jit(
    _flash_fwd_local,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret",
                     "with_lse", "window", "tiles", "skip_dead"))


def _flash_bwd_dq_kernel(*refs, causal: bool, scale: float,
                         window: int | None = None,
                         segmented: bool = False,
                         tiles: tuple | None = None, heads: int = 1):
    """dQ for one (batch*head, q-block, kv-block) cell.

    FA2 backward: probabilities are rebuilt per tile from the saved
    log-sum-exp (p = exp(s - lse)); ``delta = rowsum(dO * O)`` folds the
    softmax normalizer's gradient.  dq accumulates across the inner
    kv-block dimension in VMEM scratch.  Segment masking re-applies to
    the rebuilt logits (masked pairs rebuild p = 0, so their gradient
    contribution vanishes exactly as in the forward).  ``tiles``: as
    :func:`_flash_kernel`.
    """
    bounds = None
    if tiles is not None:
        bounds, refs = refs[:2], refs[2:]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs = refs
    if segmented:
        qseg_ref, kseg_ref, dq_ref, dq_scr = refs
    else:
        dq_ref, dq_scr = refs
    j = pl.program_id(2)
    n_kb = pl.num_programs(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    tile_q, tile_k = tiles or (block_q, block_k)
    row0 = pl.program_id(1) * block_q

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    if window is None:
        col0 = j * block_k
        live = (not causal) or (col0 <= row0 + block_q - 1)
    else:
        col0, live = _banded_cols(row0, j, n_kb, block_q, block_k, window)

    def _update(r, u):
        rows, cols = _tile_at(r, tile_q), _tile_at(u, tile_k)
        qi = jax.lax.convert_element_type(q_ref[0, rows], jnp.float32)
        kj = jax.lax.convert_element_type(k_ref[0, cols], jnp.float32)
        vj = jax.lax.convert_element_type(v_ref[0, cols], jnp.float32)
        do = jax.lax.convert_element_type(do_ref[0, rows], jnp.float32)
        s = jax.lax.dot_general(qi, kj, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_keep_mask(s.shape, _plus(row0, r * tile_q),
                                     _plus(col0, u * tile_k), window),
                          s, NEG_INF)
        if segmented:
            s = jnp.where(_same_segment(qseg_ref, kseg_ref, rows, u),
                          s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, rows][:, :1])
        dp = jax.lax.dot_general(do, vj, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, rows][:, :1]) * scale
        dq_scr[rows] += jax.lax.dot_general(
            ds, kj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _walk_tiles(_update, live, bounds, heads,
                resident=(row0, block_q, tile_q),
                streamed=(col0, block_k, tile_k))

    @pl.when(j == n_kb - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, causal: bool,
                          scale: float, window: int | None = None,
                          n_qb_total: int = 0, segmented: bool = False,
                          tiles: tuple | None = None, heads: int = 1):
    """dK/dV for one (batch*head, kv-block, q-block) cell; q streams on
    the inner grid dimension, accumulating into the kv block's scratch.
    ``tiles``: as :func:`_flash_kernel`, with the sides swapped — the
    prefetched bounds are, per (row, k tile), the first and last live
    q tile."""
    bounds = None
    if tiles is not None:
        bounds, refs = refs[:2], refs[2:]
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs = refs
    if segmented:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_scr, dv_scr = refs
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = refs
    jq = pl.program_id(2)
    n_qb = pl.num_programs(2)
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]
    tile_q, tile_k = tiles or (block_q, block_k)
    col0 = pl.program_id(1) * block_k

    @pl.when(jq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # Causal: a q block contributes unless entirely above the diagonal;
    # with a window the inner grid is banded (mirror _banded_q): only
    # the q blocks inside this kv block's horizon stream through, and
    # clamped duplicates past the last block are predicated dead.
    if window is None:
        row0 = jq * block_q
        live = (not causal) or (row0 + block_q - 1 >= col0)
    else:
        raw = col0 // block_q + jq
        clamped = jnp.minimum(raw, n_qb_total - 1)
        row0 = clamped * block_q
        live = ((raw <= n_qb_total - 1)
                & (row0 + block_q - 1 >= col0)
                & (row0 - (col0 + block_k - 1) < window))

    def _update(r, u):
        rows, cols = _tile_at(r, tile_q), _tile_at(u, tile_k)
        qi = jax.lax.convert_element_type(q_ref[0, rows], jnp.float32)
        kj = jax.lax.convert_element_type(k_ref[0, cols], jnp.float32)
        vj = jax.lax.convert_element_type(v_ref[0, cols], jnp.float32)
        do = jax.lax.convert_element_type(do_ref[0, rows], jnp.float32)
        s = jax.lax.dot_general(qi, kj, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_keep_mask(s.shape, _plus(row0, r * tile_q),
                                     _plus(col0, u * tile_k), window),
                          s, NEG_INF)
        if segmented:
            s = jnp.where(_same_segment(qseg_ref, kseg_ref, rows, u),
                          s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, rows][:, :1])  # [tile_q, tile_k]
        dv_scr[cols] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vj, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, rows][:, :1]) * scale
        dk_scr[cols] += jax.lax.dot_general(
            ds, qi, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _walk_tiles(_update, live, bounds, heads,
                resident=(col0, block_k, tile_k),
                streamed=(row0, block_q, tile_q), resident_is_q=False)

    @pl.when(jq == n_qb - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_pallas_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                      interpret=False, window=None, segment_ids=None):
    """Pallas dQ/dK/dV from the saved (out, lse ``[B, H, Lq]``)
    residuals."""
    block_q = _require_fit(block_q, q.shape[1])
    block_k = _require_fit(block_k, k.shape[1])
    local = functools.partial(
        _flash_bwd_local if segment_ids is None else _flash_bwd_segmented,
        causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, window=window,
        tiles=_segment_tiles("flash_bwd_dq", block_q, block_k),
        dkv_tiles=_segment_tiles("flash_bwd_dkv", block_q, block_k))
    seg = [] if segment_ids is None else [segment_ids]
    return _per_shard(
        local,
        ["bqhd", "bkhd", "bkhd", "bqhd", "bhq", "bqhd"] + ["bq" for _ in seg],
        ["bqhd", "bkhd", "bkhd"],
        q, k, v, out, lse, g, *seg)


def _flash_bwd_local(q, k, v, out, lse, g, segment_ids=None, *, causal,
                     scale, block_q, block_k, interpret, window, tiles=None,
                     dkv_tiles=None, skip_dead=True):
    """The two backward launches on ONE device's operands (see
    :func:`_flash_fwd_local`): ``(dq, dk, dv)``.  ``tiles`` are the dQ
    launch's, ``dkv_tiles`` the dK/dV launch's (None: ``tiles``)."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    segmented = segment_ids is not None
    if segmented and skip_dead:
        tiles = tiles or (block_q, block_k)
        dkv_tiles = dkv_tiles or tiles
    else:
        tiles = dkv_tiles = None
    flat = lambda a, L: a.transpose(0, 2, 1, 3).reshape(b * h, L, d)
    qf, kf, vf = flat(q, lq), flat(k, lk), flat(v, lk)
    dof, of = flat(g, lq), flat(out, lq)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32), axis=-1)
    # Lane-broadcast row vectors (TPU tiling; see _flash_kernel note).
    lane = lambda a: jnp.broadcast_to(a[:, :, None], (*a.shape, _LANES))
    lse_l, delta_l = lane(lse.reshape(b * h, lq)), lane(delta)

    vspec = lambda f: pl.BlockSpec(*f, memory_space=pltpu.VMEM)
    resident = lambda bh, i, j, *_: (bh, i, 0)
    inner_map = lambda bh, i, j: (bh, j, 0)
    q_at = ((1, block_q, d), resident)
    row_at = ((1, block_q, _LANES), resident)
    cost = pl.CostEstimate(
        flops=6 * b * h * lq * lk * d,
        bytes_accessed=(qf.nbytes + kf.nbytes + vf.nbytes
                        + dof.nbytes + lse_l.nbytes + delta_l.nbytes),
        transcendentals=b * h * lq * lk)
    args = [qf, kf, vf, dof, lse_l, delta_l]
    kernel_kw = dict(causal=causal, scale=scale, window=window,
                     segmented=segmented, heads=h)
    blocks = (block_q, block_k)

    n_kb = lk // block_k
    if window is not None:
        dq_inner, dq_kv_map = _banded_kv(window, block_q, block_k, n_kb)
    else:
        dq_inner, dq_kv_map = n_kb, inner_map

    def call_dq():
        kv_map, bounds = _skip_dead(dq_kv_map, segment_ids, tiles, blocks,
                                    causal, window, "q", h)
        kv_in = ((1, block_k, d), kv_map)
        in_specs = [vspec(q_at), vspec(kv_in), vspec(kv_in),
                    vspec(q_at), vspec(row_at), vspec(row_at)]
        seg_args = []
        if segmented:
            seg_specs, seg_args = _segment_operands(segment_ids, h, q_at,
                                                    kv_in, tiles)
            in_specs += seg_specs
        return _launch(
            functools.partial(_flash_bwd_dq_kernel, tiles=tiles, **kernel_kw),
            "flash_bwd_dq", (b * h, lq // block_q, dq_inner), in_specs,
            vspec(q_at), jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
            [pltpu.VMEM((block_q, d), jnp.float32)], cost,
            args + seg_args, bounds)

    kv_at = ((1, block_k, d), resident)
    n_qb = lq // block_q
    if window is not None:
        dkv_inner, dkv_q_map = _banded_q(window, block_q, block_k, n_qb)
    else:
        dkv_inner, dkv_q_map = n_qb, inner_map

    def call_dkv():
        q_map, bounds = _skip_dead(dkv_q_map, segment_ids, dkv_tiles, blocks,
                                   causal, window, "k", h)
        q_in = ((1, block_q, d), q_map)
        row_in = ((1, block_q, _LANES), q_map)
        in_specs = [vspec(q_in), vspec(kv_at), vspec(kv_at),
                    vspec(q_in), vspec(row_in),
                    vspec(row_in)]
        seg_args = []
        if segmented:
            seg_specs, seg_args = _segment_operands(segment_ids, h, q_in,
                                                    kv_at, dkv_tiles)
            in_specs += seg_specs
        return _launch(
            functools.partial(_flash_bwd_dkv_kernel, n_qb_total=n_qb,
                              tiles=dkv_tiles, **kernel_kw),
            "flash_bwd_dkv", (b * h, lk // block_k, dkv_inner), in_specs,
            (vspec(kv_at), vspec(kv_at)),
            (jax.ShapeDtypeStruct((b * h, lk, d), k.dtype),
             jax.ShapeDtypeStruct((b * h, lk, d), v.dtype)),
            [pltpu.VMEM((block_k, d), jnp.float32),
             pltpu.VMEM((block_k, d), jnp.float32)], cost,
            args + seg_args, bounds)

    if interpret:
        with pltpu.force_tpu_interpret_mode():
            dq = call_dq()
            dk, dv = call_dkv()
    else:
        dq = call_dq()
        dk, dv = call_dkv()
    unflat = lambda a, L: a.reshape(b, h, L, d).transpose(0, 2, 1, 3)
    return unflat(dq, lq), unflat(dk, lk), unflat(dv, lk)


_flash_bwd_segmented = jax.jit(
    _flash_bwd_local,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret",
                     "window", "tiles", "dkv_tiles", "skip_dead"))


def _fit_block(requested: int, length: int,
               strict: bool = False) -> int | None:
    """Kernel block size <= ``requested`` that tiles ``length`` exactly.

    The min-clamp alone covers short rows (one block == the row) and
    explicit blocks that already divide the row; otherwise pick the
    largest lane-aligned (x128) divisor of ``length``, so raising the
    tuned defaults never pushes a length that used to tile off the
    Pallas path (e.g. seq 1536 under the (1024, 1024) defaults fits
    768).  None = nothing tiles; the caller falls back to blockwise.

    ``strict`` (explicitly requested blocks): never substitute a
    different divisor — a sweep/benchmark caller asking for block 512
    at length 768 must not silently time a 384-block kernel.  The
    min-clamp still applies (one block == the whole row is the same
    grid point); anything else returns None so the caller takes the
    blockwise fallback, the pre-fitting behavior for such shapes.
    """
    b = min(requested, length)
    if length % b == 0:
        return b
    if strict:
        return None
    return max((c for c in range(128, b + 1, 128) if length % c == 0),
               default=None)


def _require_fit(requested: int, length: int) -> int:
    """_fit_block for the kernel launchers: a grid whose block does not
    divide the length would silently leave tail rows unwritten, so an
    unfittable request is an error, never a clamp."""
    b = _fit_block(requested, length)
    if b is None:
        raise ValueError(
            f"no kernel block <= {requested} tiles sequence length "
            f"{length}; pick a length divisible by 128 or a block that "
            "divides it (flash_attention's fallback handles any length)")
    return b


# The (block_q, block_k) a launch without segments keeps.  On the
# tiles they compute its three kernels run at 58-82 % of the bf16 peak
# (TPU v5e, PR 32, PERF.md §6) and smaller blocks only lose (512 x 512:
# +12 %, 256 x 256: 2.6 x, over packed rows), so a block size has
# little to find; a sweep WITHOUT segments has not run on this code.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# A launch with ``segment_ids`` walks larger blocks as tiles and skips
# the tiles no document spans (see _tile_bounds): a tile's logits, not
# a block's, have to fit VMEM, and a grid step costs ~0.4 us whatever
# it multiplies.  Fastest of some 40 (block, tile) points over the
# benchmark's packed rows of 4096 at 24 heads of 128, float32 operands
# (TPU v5e, PR 32, scripts/sweep_attention_blocks.py; ms a call of 8
# rows, masking launch -> this): flash_fwd 9.31 -> 7.96, flash_bwd_dq
# 9.34 -> 6.63, flash_bwd_dkv 15.99 -> 11.58.  The forward pays its
# online-softmax bookkeeping (running max and sum, the accumulator's
# rescale) once a tile, so it wants its k tile wide: 512 x 512 reads
# 10.15, any 256-wide k tile 15-20 ms.
SEGMENT_BLOCK_Q = 2048
SEGMENT_BLOCK_K = 2048
# Blocks of 2048 of float32 operands (the trainer's q, k, v: bf16
# activations times float32 weights), double-buffered, pass the
# compiler's 16 MB default; a v5e core has 128 MB.
SEGMENT_VMEM_BYTES = 64 * 1024 * 1024
SEGMENT_TILES = {"flash_fwd": (512, 1024), "flash_bwd_dq": (512, 512),
                 "flash_bwd_dkv": (512, 512)}


def _segment_tiles(kernel: str, block_q: int, block_k: int):
    """The segmented launch's ``(tile_q, tile_k)`` for ``kernel`` inside
    fitted blocks: the tuned tile where it divides the block into
    lane-aligned parts, else the block whole."""
    def fit(tile, block):
        t = math.gcd(tile, block)
        return t if t % _LANES == 0 else block
    tile_q, tile_k = SEGMENT_TILES[kernel]
    return fit(tile_q, block_q), fit(tile_k, block_k)


def segment_tiles_for(seq_len: int) -> dict:
    """``{kernel: (tile_q, tile_k)}`` at which a defaulted segmented
    launch over rows of ``seq_len`` skips dead tiles (the granularity
    :func:`live_tile_share` is asked about)."""
    bq = _fit_block(SEGMENT_BLOCK_Q, seq_len) or seq_len
    bk = _fit_block(SEGMENT_BLOCK_K, seq_len) or seq_len
    return {kernel: _segment_tiles(kernel, bq, bk)
            for kernel in SEGMENT_TILES}


def _pallas_blocks(lq, lk, d, block_q, block_k, gate_small_bk=False,
                   strict_q=False, strict_k=False):
    """Pure tiling/quality decision (backend-independent, unit-tested):
    the fitted (bq, bk) the kernel would launch with, or None for the
    blockwise fallback.  ``strict_*`` marks explicitly requested blocks
    (see _fit_block): honored exactly or not at all."""
    # Tiling constraints: last dim 128-aligned, seq divisible into blocks.
    if d % 128 != 0 or min(lq, lk) < 8:
        return None
    bq = _fit_block(block_q, lq, strict=strict_q)
    bk = _fit_block(block_k, lk, strict=strict_k)
    if bq is None or bk is None:
        return None
    # Defaulted callers only (``gate_small_bk``): tiny fitted KV tiles
    # lose — the kernels get slower with every halving of the block
    # (256 x 256 blocks: 2.6 x the time of 1024 x 1024 over the same
    # rows; TPU v5e, PR 32, PERF.md §6), and a 128-wide block was not
    # measured against the blockwise path on this code — so keep bk=128
    # only when bq fitted to >=1024.  An EXPLICIT small block_k is
    # always honored — a sweep must be able to time any point of its
    # grid.
    if gate_small_bk and bk < 256 and bk != lk and bq < 1024:
        return None
    return bq, bk


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(q, k, block_q, block_k, gate_small_bk=False,
                strict_q=False, strict_k=False) -> bool:
    """Kernel or blockwise: decided from the backend and the shapes
    alone, before any kernel is built.  Not a rescue — once this says
    kernel, a kernel that fails to lower or compile raises."""
    if not _on_tpu():
        return False
    return _pallas_blocks(q.shape[1], k.shape[1], q.shape[-1],
                          block_q, block_k, gate_small_bk,
                          strict_q=strict_q, strict_k=strict_k) is not None


def _resolve_blocks(block_q, block_k, segmented=False):
    """None -> tuned default (the segmented launch has its own); the
    small-bk gate and divisor refitting apply only to defaulted blocks —
    explicit blocks are honored exactly or fall back (strict
    _fit_block).  The ONE definition shared by flash_attention and its
    custom_vjp fwd/bwd so primal and vjp can never disagree."""
    q_explicit, k_explicit = block_q is not None, block_k is not None
    gate = not k_explicit
    default_q, default_k = ((SEGMENT_BLOCK_Q, SEGMENT_BLOCK_K) if segmented
                            else (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K))
    bq = block_q if q_explicit else default_q
    bk = block_k if k_explicit else default_k
    return bq, bk, gate, q_explicit, k_explicit


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = False, scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    window: int | None = None, segment_ids=None):
    """Fused attention: Pallas kernel on TPU, blockwise jnp elsewhere.

    Differentiable with O(L) residuals both ways: on the Pallas path
    the backward is the FA2 construction — dQ/dK/dV kernels that
    rebuild probabilities per tile from the forward's saved
    log-sum-exp; on the fallback path the backward re-runs the
    blockwise forward under ``jax.vjp``.

    ``window`` (with ``causal=True``) is sliding-window attention: each
    query attends its last ``window`` positions (self included).  The
    kernels skip kv blocks entirely beyond the lookback, so compute per
    query is O(window), not O(L) — the long-context local-attention
    primitive (Mistral-style).

    ``segment_ids [B, L]`` int32 (packed sequences): attention is
    masked to within-segment pairs on every tier, forward and backward
    — the packed-document training primitive.  An integer input: its
    cotangent is None.

    ``block_q``/``block_k`` default (None) to ``DEFAULT_BLOCK_*``
    (1024 x 1024), and with ``segment_ids`` to ``SEGMENT_BLOCK_*``
    walked as ``SEGMENT_TILES``, the fastest point of
    `scripts/sweep_attention_blocks.py` over the benchmark's packed
    rows (seq 4096, 24 heads of 128; TPU v5e, PR 32; see the
    constants).  With segments a tile no document spans is neither
    fetched nor multiplied (:func:`_tile_bounds`); explicit blocks are
    walked as the same tiles where they divide.  Defaulted blocks are
    fitted per
    call (``_fit_block``): shorter sequences clamp to one block, and
    lengths the default doesn't divide (e.g. 1536) drop to their
    largest lane-aligned divisor instead of leaving the Pallas path —
    except that a *defaulted* call never fits below a 256 KV tile
    (measured slower than the fallback); pass block_k explicitly to
    force a small-tile kernel.  EXPLICIT blocks are honored exactly:
    a requested block that does not divide the length (beyond the
    whole-row min-clamp) takes the blockwise fallback rather than
    silently launching a different grid point — sweep callers measure
    the block they asked for.
    """
    _check_window(window, causal)
    s = _scale_for(q, scale)
    bq, bk, gate, xq, xk = _resolve_blocks(block_q, block_k,
                                           segment_ids is not None)
    if _use_pallas(q, k, bq, bk, gate_small_bk=gate,
                   strict_q=xq, strict_k=xk):
        return _flash_pallas(q, k, v, causal, s, bq, bk,
                             with_lse=False, window=window,
                             segment_ids=segment_ids)[0]
    return blockwise_attention(q, k, v, causal=causal, scale=s,
                               block_k=bk, window=window,
                               segment_ids=segment_ids)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, window=None,
               segment_ids=None):
    _check_window(window, causal)
    s = _scale_for(q, scale)
    bq, bk, gate, xq, xk = _resolve_blocks(block_q, block_k,
                                           segment_ids is not None)
    if _use_pallas(q, k, bq, bk, gate_small_bk=gate,
                   strict_q=xq, strict_k=xk):
        out, lse = _flash_pallas(q, k, v, causal, s, bq, bk,
                                 window=window, segment_ids=segment_ids)
        return out, (q, k, v, out, lse, segment_ids)
    out = blockwise_attention(q, k, v, causal=causal, scale=s,
                              block_k=bk, window=window,
                              segment_ids=segment_ids)
    return out, (q, k, v, None, None, segment_ids)


def _flash_bwd(causal, scale, block_q, block_k, window, res, g):
    q, k, v, out, lse, segment_ids = res
    s = _scale_for(q, scale)
    bq, bk, _, _, _ = _resolve_blocks(block_q, block_k,
                                      segment_ids is not None)
    if lse is not None:
        dq, dk, dv = _flash_pallas_bwd(q, k, v, out, lse, g, causal, s,
                                       bq, bk, window=window,
                                       segment_ids=segment_ids)
        return dq, dk, dv, None
    _, vjp = jax.vjp(
        lambda q, k, v: blockwise_attention(
            q, k, v, causal=causal, scale=s, block_k=bk,
            window=window, segment_ids=segment_ids),
        q, k, v)
    return (*vjp(g), None)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------- prefix (chunked prefill)


def _flash_prefix_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                         acc_scr, *, scale: float):
    """One (batch, kv-head, q-block, kv-block) cell of chunk-against-cache
    attention: the ``groups`` query heads that share this K/V head, a
    ``block_q`` span of the chunk each, folded into the rows of ONE
    ``[groups * block_q, block_k]`` logits tile, so a K/V block is
    fetched once for all of them.

    Query row ``t`` of the chunk sits at global position ``off + t``
    (``off_ref``: scalar prefetch, traced) and keeps key slot ``s`` iff
    ``s <= off + t`` — the chunk's own K/V are already in the cache, so
    in-chunk causality is the same comparison.  K/V blocks are aligned
    to the cache; only the mask reads ``off``.  Blocks past the q
    block's last position are predicated away here and never fetched
    (:func:`flash_prefix_attention` clamps their block index).  Block 0 is
    live for every row (slot 0 is kept), so the running max is finite
    from the first update on and a later all-masked row adds exp(-1e30
    - m) = 0.

    Operands reach the MXU in their own dtype (bf16 from a bf16 model
    and cache) with float32 accumulation; max, sum and accumulator are
    float32.  The logits are scaled AFTER the product, in float32, as
    the dense body does.
    """
    groups, block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    j = pl.program_id(3)
    row0 = off_ref[0] + pl.program_id(2) * block_q
    col0 = j * block_k

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(col0 <= row0 + block_q - 1)
    def _update():
        logits = jax.lax.dot_general(
            q_ref[...].reshape(groups * block_q, d), k_ref[...],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        rows = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(row0 + rows % block_q >= col0 + cols,
                           logits, NEG_INF)
        m = m_scr[:, :1]
        m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l_scr[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        o_ref[...] = (acc_scr[:] / l_scr[:, :1]).reshape(
            o_ref.shape).astype(o_ref.dtype)


# Rows of the logits tile (query heads of a group x block_q) and its
# columns (block_k); the tile and its exponentials are float32 in VMEM,
# 8 MB each at these sizes.  Fastest of ten (block_q, block_k) points at
# 16 heads on one K/V head, 512 queries, 8192 slots (TPU v5e, PR 26):
# 0.13 ms at offset 2283 and 0.28 ms at 7680, against 0.22 and 0.55 at
# (1024, 512).
PREFIX_TILE_ROWS = 2048
PREFIX_BLOCK_K = 1024
# The kernel's VMEM allowance: float32 operands at these tiles need
# 17.8 MB, over the compiler's 16 MB default; a v5e core has 128 MB.
PREFIX_VMEM_BYTES = 64 * 1024 * 1024


def _sublane_rows(dtype) -> int:
    """Rows of one sublane tile of ``dtype`` (8 of 32 bits, 16 of 16)."""
    return _SUBLANES * 4 // jnp.dtype(dtype).itemsize


def prefix_blocks(t_len: int, s_len: int, head_dim: int, groups: int,
                  dtype) -> tuple[int, int] | None:
    """Tile rule of :func:`flash_prefix_attention` (backend-independent):
    the ``(block_q, block_k)`` it launches with for a ``t_len``-wide
    chunk against ``s_len`` cache slots, or None where no legal tiling
    exists.  ``block_q`` tiles the chunk in whole sublane tiles of
    ``dtype`` (the q block's leading dims merge into rows), at most
    ``PREFIX_TILE_ROWS / groups`` of them; ``block_k`` tiles the cache
    in lane multiples."""
    sub = _sublane_rows(dtype)
    if head_dim % _LANES or t_len % sub or s_len % _LANES:
        return None
    cap = max(sub, PREFIX_TILE_ROWS // groups)
    bq = max(c for c in range(sub, min(cap, t_len) + 1, sub)
             if t_len % c == 0)
    bk = max(c for c in range(_LANES, min(PREFIX_BLOCK_K, s_len) + 1, _LANES)
             if s_len % c == 0)
    return bq, bk


def is_partitioned(x) -> bool:
    """Whether ``x`` is computed under a mesh the compiler still
    partitions over more than one device (see :func:`_auto_axes`)."""
    mesh, auto = _auto_axes(x)
    return math.prod(mesh.shape[a] for a in auto) > 1


def use_flash_prefix(t_len: int, s_len: int, head_dim: int, groups: int,
                     dtype, sharded: bool = False) -> bool:
    """Kernel or the caller's dense body: decided from the backend, the
    placement and the shapes alone, before any kernel is built (as
    :func:`_use_pallas`).  ``sharded`` (:func:`is_partitioned`) says
    no: a Mosaic kernel cannot be partitioned automatically, and the
    head-sharded engines keep the dense body until this launch learns
    to sit in a ``shard_map``."""
    return (_on_tpu() and not sharded
            and prefix_blocks(t_len, s_len, head_dim, groups,
                              dtype) is not None)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret", "head_major"))
def flash_prefix_attention(q, k, v, off, block_q: int | None = None,
                           block_k: int | None = None,
                           interpret: bool = False,
                           head_major: bool = False):
    """Attention of a chunk's queries against the live prefix of a KV
    cache: ``q [B, T, H, D]`` at global positions ``off .. off + T - 1``
    (``off``: int32 scalar, may be traced, need not be block-aligned),
    ``k``/``v [B, S, KV, D]`` the cache with the chunk's own K/V already
    written at ``[off, off + T)``.  Key slot ``s`` is kept iff ``s <=
    off + t``; slots past ``off + T - 1`` are neither fetched nor
    computed, so bytes and operations follow ``off + T``, not ``S``.
    Returns ``[B, T, H, D]`` in ``q``'s dtype.  Forward only.
    ``head_major``: the cache is ``[B, KV, S, D]`` (a K/V head's slots
    one matrix: a typed stack's planes) and a K/V block is a row block
    of its head's.
    ``block_q``/``block_k`` default to :func:`prefix_blocks`' choice
    (shapes it refuses need them given: the interpreter's tests).

    Jitted, so that a program calling it once a layer traces and
    lowers the kernel ONCE: unwrapped, 24 layers cost an admission
    program 1.4 s more of Python before the compile cache is even
    asked, 13 s over an engine's ten programs (chip, PR 26)."""
    b, t_len, h, d = q.shape
    kv, s_len = k.shape[1:3] if head_major else k.shape[2:0:-1]
    groups = h // kv
    if block_q is None or block_k is None:
        fit = prefix_blocks(t_len, s_len, d, groups, q.dtype)
        if fit is None:
            raise ValueError(
                f"no kernel tiling for a chunk of {t_len} x head {d} "
                f"against {s_len} cache slots (see prefix_blocks)")
        block_q, block_k = block_q or fit[0], block_k or fit[1]
    if t_len % block_q or s_len % block_k:
        raise ValueError(
            f"blocks ({block_q}, {block_k}) do not tile a chunk of "
            f"{t_len} against {s_len} cache slots")
    rows = groups * block_q
    # Head-major queries [B, KV, G, T, D]: a q block's leading dims
    # merge into the rows of one tile.  The cache keeps its layout; a
    # K/V head is a lane-aligned column block of [B, S, KV * D].
    qg = q.reshape(b, t_len, kv, groups, d).transpose(0, 2, 3, 1, 4)
    kf, vf = (k, v) if head_major else (
        a.reshape(b, s_len, kv * d) for a in (k, v))

    def kv_map(bi, c, i, j, off_ref):
        last = (off_ref[0] + (i + 1) * block_q - 1) // block_k
        if head_major:
            return bi, c, jnp.minimum(j, last), 0
        return bi, jnp.minimum(j, last), c

    q_spec = pl.BlockSpec((None, None, groups, block_q, d),
                          lambda bi, c, i, j, off_ref: (bi, c, 0, i, 0))
    kv_spec = pl.BlockSpec(
        (None, None, block_k, d) if head_major else (None, block_k, d),
        kv_map)
    live = b * h * t_len * s_len // 2     # an estimate: off is traced

    def call(): return pl.pallas_call(
        functools.partial(_flash_prefix_kernel, scale=_scale_for(q, None)),
        name="flash_prefix_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kv, t_len // block_q, s_len // block_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, _LANES), jnp.float32),   # m
                pltpu.VMEM((rows, _LANES), jnp.float32),   # l
                pltpu.VMEM((rows, d), jnp.float32),        # acc
            ]),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=PREFIX_VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=4 * live * d, transcendentals=live,
            bytes_accessed=2 * qg.nbytes + (kf.nbytes + vf.nbytes) // 2),
    )(jnp.reshape(off, (1,)).astype(jnp.int32), qg, kf, vf)

    if interpret:
        with pltpu.force_tpu_interpret_mode():
            out = call()
    else:
        out = call()
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t_len, h, d)


# ------------------------------------------- decode (per-lane live prefix)


def _flash_decode_kernel(plane_ref, pos_ref, q_ref, *refs, scale: float,
                         kv: int, block_k: int, parts: int):
    """One lane of the decode step's attention over the cache: the
    lane's ``rows`` queries (``T`` tokens x all query heads) against the
    slots STRICTLY BEFORE ``pos_ref[lane]`` of plane ``plane_ref[0]``.

    The slab stays in HBM (``k_hbm``/``v_hbm``: ``[P, B, S * KV, D]``,
    slot ``s`` of K/V head ``c`` at row ``s * KV + c``).  The lane walks
    its ``ceil(pos / block_k)`` live blocks in one grid step, each a
    contiguous ``[block_k * KV, D]`` copy into one of two VMEM buffers,
    the next block's copy in flight while this one is computed; its
    last block starts block 0 of the next lane that has any (lanes run
    in order: the grid is ``arbitrary``), so no lane begins with an
    empty pipe.  Dead blocks cost nothing: they are neither fetched
    nor stepped over.  A lane's LAST block is brought in by the
    ``1 / parts`` of a block, as many as hold a live slot; what the
    buffer keeps beyond them is an earlier block's (finite) or the
    zeros it started with, under the position mask either way.

    Every query head multiplies the whole block: a row's logits for the
    other K/V heads' rows are masked (``bias_ref``: 0 where the row's
    K/V head is the column's, -1e30 elsewhere; absent for one K/V
    head), so their probabilities are exact zeros in the second
    product.  The MXU's time goes by the block's bytes, not by the
    tile's few rows, so the surplus products are free and the slab
    needs no other layout than its own.

    Arithmetic as :func:`_flash_prefix_kernel`'s: operands in the
    cache's dtype, float32 accumulation, max, sum and accumulator,
    logits scaled after the product.  Outputs the normalised result
    and the log-sum-exp of the logits (float32); a lane with nothing
    before it (``pos == 0``) yields zeros and ``NEG_INF``: weight 0
    when merged with the chunk's own term."""
    if kv > 1:
        bias_ref, refs = refs[0], refs[1:]
    (k_hbm, v_hbm, o_ref, lse_ref, kbuf, vbuf, sems, slot_ref,
     m_scr, l_scr, acc_scr) = refs
    lane, lanes = pl.program_id(0), pl.num_programs(0)
    plane, pos = plane_ref[0], pos_ref[lane]
    rows_k = block_k * kv
    rows_p = rows_k // parts
    n = (pos + block_k - 1) // block_k

    def transfer(which, ln, i, slot, go):
        """``go`` (start or wait) the copies that bring block ``i`` of
        lane ``ln``'s K (0) or V (1) into ``slot``: the block whole, or
        the parts of a last block that hold a slot before ``pos``."""
        hbm, buf = ((k_hbm, kbuf), (v_hbm, vbuf))[which]

        def rows(r0, count):
            go(pltpu.make_async_copy(
                hbm.at[plane, ln, pl.ds(i * rows_k + r0, count)],
                buf.at[slot, pl.ds(r0, count)], sems.at[which, slot]))

        live = jnp.minimum(
            (pos_ref[ln] * kv - i * rows_k + rows_p - 1) // rows_p, parts)
        pl.when(live == parts)(lambda: rows(0, rows_k))
        for j in range(parts - 1):
            pl.when(jnp.logical_and(j < live, live < parts))(
                functools.partial(rows, j * rows_p, rows_p))

    def start(ln, i, slot):
        for which in (0, 1):
            transfer(which, ln, i, slot, lambda c: c.start())

    def start_next_lane(slot):
        nxt = jax.lax.while_loop(
            lambda c: jnp.logical_and(
                c < lanes, pos_ref[jnp.minimum(c, lanes - 1)] == 0),
            lambda c: c + 1, lane + 1)
        pl.when(nxt < lanes)(lambda: start(nxt, 0, slot))

    @pl.when(lane == 0)
    def _first():
        slot_ref[0] = 0
        vbuf[...] = jnp.zeros_like(vbuf)
        pl.when(n > 0)(lambda: start(0, 0, 0))
        pl.when(n == 0)(lambda: start_next_lane(0))

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    slot0 = slot_ref[0]

    def block(i, carry):
        slot = (slot0 + i) % 2
        pl.when(i + 1 < n)(lambda: start(lane, i + 1, 1 - slot))
        pl.when(i + 1 == n)(lambda: start_next_lane(1 - slot))
        transfer(0, lane, i, slot, lambda c: c.wait())
        logits = jax.lax.dot_general(
            q_ref[...], kbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if kv > 1:
            logits = logits + bias_ref[...]
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(i * rows_k + cols < pos * kv, logits, NEG_INF)
        m = m_scr[:, :1]
        m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l_scr[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        transfer(1, lane, i, slot, lambda c: c.wait())
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(vbuf.dtype), vbuf[slot], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        return carry

    jax.lax.fori_loop(0, n, block, 0)
    slot_ref[0] = (slot0 + n) % 2
    some = l_scr[...] > 0
    l_safe = jnp.where(some, l_scr[...], 1.0)
    o_ref[...] = acc_scr[...] / l_safe[:, :1]
    lse_ref[...] = jnp.where(some, m_scr[...] + jnp.log(l_safe), NEG_INF)


# A copy brings in ``block_k`` slots of every K/V head: the most that
# keeps it at DECODE_BLOCK_BYTES; a lane's LAST block comes by the
# 1 / DECODE_TAIL_PARTS of a block.  A block costs ~0.4 us besides its
# bytes, a copy under ~128 KiB more than it saves, and a lane reads
# half a copy past its position on average (TPU v5e, PR 28, lanes 54 %
# / 43 % full, us a layer with the merge; whole blocks | quartered
# tail): one K/V head of 128 over 8192 slots, blocks of 2048 slots
# (512 KiB) 130 | 116, of 1024 127 | 130, of 512 174 | -; 16 K/V heads
# over 512 slots, blocks of 128 slots (512 KiB) 43 | 39, of 64 42 | 44,
# of 256 53 | -.
DECODE_BLOCK_BYTES = 512 * 1024
DECODE_TAIL_PARTS = 4
# Rows of the logits tile (T x query heads): past a lane tile of rows
# the surplus products of :func:`_flash_decode_kernel` stop being free.
DECODE_MAX_ROWS = 128


@functools.lru_cache(maxsize=None)
def decode_block(t_len: int, s_len: int, head_dim: int, heads: int,
                 kv: int, dtype) -> int | None:
    """Tile rule of :func:`flash_decode_attention` (backend-independent):
    the ``block_k`` (cache slots a copy) it launches with, or None where
    the kernel has no legal tiling — a head that is no lane multiple;
    K/V heads that neither are one nor fill whole sublane tiles of
    ``dtype`` (the slab's ``[S, KV, D]`` planes are then no ``[S * KV,
    D]`` matrix without a copy); more than ``DECODE_MAX_ROWS`` query
    rows; a cache that no block of whole lane tiles of ``[S * KV]``
    rows divides.  What a lane reads is its position rounded up to
    ``block_k // DECODE_TAIL_PARTS``."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = _sublane_rows(dtype)
    if (head_dim % _LANES or (kv > 1 and kv % sub)
            or t_len * heads > DECODE_MAX_ROWS):
        return None
    # A block's rows [block_k * KV] are whole lane tiles (the logits'
    # columns), each of its parts whole sublane tiles.
    rows = math.lcm(_LANES, DECODE_TAIL_PARTS * sub)
    step = rows // math.gcd(rows, kv)
    cap = max(step, DECODE_BLOCK_BYTES // (kv * head_dim * itemsize))
    return max((c for c in range(step, min(cap, s_len) + 1, step)
                if s_len % c == 0), default=None)


def use_flash_decode(t_len: int, s_len: int, head_dim: int, heads: int,
                     kv: int, dtype, sharded: bool = False) -> bool:
    """Kernel or the caller's dense body, as :func:`use_flash_prefix`:
    the backend, the placement and the shapes decide."""
    return (_on_tpu() and not sharded
            and decode_block(t_len, s_len, head_dim, heads, kv,
                             dtype) is not None)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode_attention(q, k_all, v_all, plane, pos0,
                           block_k: int | None = None,
                           interpret: bool = False):
    """Attention of each lane's queries against the live prefix of ITS
    row of one plane of a KV slab: ``q [B, T, H, D]``, ``k_all``/``v_all
    [P, B, S, KV, D]`` (the whole slab: nothing is cut out of it),
    ``plane`` (int32 scalar) and ``pos0 [B]`` (int32), both may be
    traced.  Lane ``b`` attends slots ``s < pos0[b]`` — STRICTLY before
    its chunk, whose own K/V the caller holds and merges — and neither
    fetches nor computes blocks past them, so bytes follow the lanes'
    lengths, not ``S``.

    Returns ``(out [B, T, H, D], lse [B, T, H])``, float32: the
    attention normalised over the attended slots and the log-sum-exp of
    their scaled logits; ``pos0[b] == 0`` gives zeros and ``NEG_INF``.
    Forward only.  ``block_k`` defaults to :func:`decode_block`'s
    choice (the interpreter's tests give shapes it refuses).  Jitted for
    the reason :func:`flash_prefix_attention` is."""
    b, t_len, h, d = q.shape
    planes, _, s_len, kv, _ = k_all.shape
    if block_k is None:
        block_k = decode_block(t_len, s_len, d, h, kv, k_all.dtype)
        if block_k is None:
            raise ValueError(
                f"no kernel tiling for {t_len} x {h} query rows of head "
                f"{d} against {s_len} slots of {kv} K/V heads (see "
                "decode_block)")
    if s_len % block_k:
        raise ValueError(f"block {block_k} does not tile {s_len} slots")
    sub = _sublane_rows(k_all.dtype)
    rows = -(-t_len * h // sub) * sub
    rows_k = block_k * kv
    q2 = jnp.pad(q.astype(k_all.dtype).reshape(b, t_len * h, d),
                 ((0, 0), (0, rows - t_len * h), (0, 0)))
    kf, vf = (a.reshape(planes, b, s_len * kv, d) for a in (k_all, v_all))
    row_spec = lambda width: pl.BlockSpec(
        (None, rows, width), lambda ln, plane_ref, pos_ref: (ln, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    operands, in_specs = [q2], [row_spec(d)]
    if kv > 1:
        # Row r is query head r % H, of K/V head (r % H) // groups;
        # column j of a block is K/V head j % KV.
        head = jnp.arange(rows)[:, None] % h // (h // kv)
        bias = jnp.where(head == jnp.arange(rows_k)[None, :] % kv,
                         0.0, NEG_INF).astype(jnp.float32)
        operands.append(bias)
        in_specs.append(pl.BlockSpec(
            (rows, rows_k), lambda ln, plane_ref, pos_ref: (0, 0)))
    live = b * s_len // 2 * kv            # an estimate: pos0 is traced

    def call(): return pl.pallas_call(
        functools.partial(_flash_decode_kernel, scale=_scale_for(q, None),
                          kv=kv, block_k=block_k, parts=DECODE_TAIL_PARTS),
        name="flash_decode_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=in_specs + [hbm, hbm],
            out_specs=[row_spec(d), row_spec(_LANES)],
            scratch_shapes=[
                pltpu.VMEM((2, rows_k, d), k_all.dtype),
                pltpu.VMEM((2, rows_k, d), v_all.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),                # live buffer
                pltpu.VMEM((rows, _LANES), jnp.float32),    # m
                pltpu.VMEM((rows, _LANES), jnp.float32),    # l
                pltpu.VMEM((rows, d), jnp.float32),         # acc
            ]),
        out_shape=[jax.ShapeDtypeStruct((b, rows, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, rows, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=PREFIX_VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * live * d, transcendentals=rows * live,
            bytes_accessed=2 * live * d * k_all.dtype.itemsize),
    )(jnp.reshape(plane, (1,)).astype(jnp.int32), pos0.astype(jnp.int32),
      *operands, kf, vf)

    if interpret:
        with pltpu.force_tpu_interpret_mode():
            out, lse = call()
    else:
        out, lse = call()
    return (out[:, :t_len * h].reshape(b, t_len, h, d),
            lse[:, :t_len * h, 0].reshape(b, t_len, h))
