"""Latent attention (MLA), the served form: a decode step's queries
against a plane of latent rows.

A latent layer caches ONE row a position — the normed latent ``c``
and the rotated key ``k_pe`` every head shares, side by side, padded
to whole lane tiles (``TransformerConfig.latent_width``) — and no K or
V of any head.  With ``wkv_b`` folded into the queries (``q_lat[h] =
Wk[h]^T q_nope[h]``) a head's score against a position is one dot
product with that row, and its value is the row's first
``kv_lora_rank`` columns: multi-query attention with a wide key that
is also the value.  So a row is read ONCE, for every head, for scores
and values both — which no kernel of ``ops/attention.py`` does: they
take keys and values as two operands of one head width.

:func:`mla_decode_attention` is the kernel (name ``mla_decode_fwd``),
:func:`mla_decode_twin` the same arithmetic in ``jax.numpy`` — its
oracle, and the path off the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.attention import (NEG_INF, PREFIX_VMEM_BYTES, _LANES,
                                         _on_tpu, _sublane_rows)

# Slots a copy brings in, and the parts a lane's LAST block comes by
# (as ``ops.attention.DECODE_TAIL_PARTS``: a lane reads its position
# rounded up to a part).  512 rows of 640 bf16 are 640 KiB.
MLA_BLOCK_K = 512
MLA_TAIL_PARTS = 4


def mla_decode_twin(q, lat_all, plane, pos, scale: float, values: int):
    """``q [B, H, W]`` against the slots strictly before ``pos[b]`` of
    row ``b`` of plane ``plane`` of ``lat_all [P, B, S, W]``: ``(out
    [B, H, values] float32, lse [B, H] float32)`` — the attention over
    the rows' first ``values`` columns normalised over the attended
    slots, and the log-sum-exp of their scaled scores; ``pos[b] == 0``
    gives zeros and ``NEG_INF``.  Operands in the slab's dtype, float32
    accumulation and softmax: the kernel's arithmetic."""
    lat = jax.lax.dynamic_index_in_dim(lat_all, plane, 0, keepdims=False)
    f32 = dict(preferred_element_type=jnp.float32)
    score = jnp.einsum("bhw,bsw->bhs", q.astype(lat.dtype), lat, **f32) * scale
    keep = (jnp.arange(lat.shape[1])[None, :] < pos[:, None])[:, None, :]
    score = jnp.where(keep, score, NEG_INF)
    m = score.max(axis=-1)
    p = jnp.where(keep, jnp.exp(score - m[..., None]), 0.0)
    total = p.sum(axis=-1)
    some = total > 0
    safe = jnp.where(some, total, 1.0)
    out = jnp.einsum("bhs,bsv->bhv", p.astype(lat.dtype),
                     lat[..., :values], **f32) / safe[..., None]
    return out, jnp.where(some, m + jnp.log(safe), NEG_INF)


def _mla_decode_kernel(plane_ref, pos_ref, q_ref, lat_hbm, o_ref, lse_ref,
                       buf, sems, slot_ref, m_scr, l_scr, acc_scr, *,
                       scale: float, block_k: int, parts: int):
    """One lane: its ``rows`` queries (the heads) against the slots
    before ``pos_ref[lane]`` of plane ``plane_ref[0]``.

    ``ops.attention._flash_decode_kernel``'s walk with ONE operand: the
    slab stays in HBM, the lane's ``ceil(pos / block_k)`` live blocks
    come one contiguous ``[block_k, W]`` copy each into one of two VMEM
    buffers, the next in flight while this one is computed, a lane's
    last block by the ``1 / parts`` that hold a live slot, its copy
    started by the lane before (lanes run in order).  A lane at
    position 0 — nothing before it, or a lane the caller said does not
    decode — is neither fetched nor stepped over.  A block in VMEM
    serves twice: the scores are the queries against its rows, the
    values its first ``acc_scr.shape[1]`` columns under the
    probabilities."""
    lane, lanes = pl.program_id(0), pl.num_programs(0)
    plane, pos = plane_ref[0], pos_ref[lane]
    rows_p = block_k // parts
    n = (pos + block_k - 1) // block_k
    values = acc_scr.shape[1]

    def transfer(ln, i, slot, go):
        def rows(r0, count):
            go(pltpu.make_async_copy(
                lat_hbm.at[plane, ln, pl.ds(i * block_k + r0, count)],
                buf.at[slot, pl.ds(r0, count)], sems.at[slot]))

        live = jnp.minimum(
            (pos_ref[ln] - i * block_k + rows_p - 1) // rows_p, parts)
        pl.when(live == parts)(lambda: rows(0, block_k))
        for j in range(parts - 1):
            pl.when(jnp.logical_and(j < live, live < parts))(
                functools.partial(rows, j * rows_p, rows_p))

    def start(ln, i, slot):
        transfer(ln, i, slot, lambda c: c.start())

    def start_next_lane(slot):
        nxt = jax.lax.while_loop(
            lambda c: jnp.logical_and(
                c < lanes, pos_ref[jnp.minimum(c, lanes - 1)] == 0),
            lambda c: c + 1, lane + 1)
        pl.when(nxt < lanes)(lambda: start(nxt, 0, slot))

    @pl.when(lane == 0)
    def _first():
        slot_ref[0] = 0
        # What a buffer holds past a last block's live parts is masked
        # in the scores but multiplied (by exact zeros) as values: it
        # has to be finite from the start.
        buf[...] = jnp.zeros_like(buf)
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
        transfer(lane, i, slot, lambda c: c.wait())
        rows = buf[slot]
        logits = jax.lax.dot_general(
            q_ref[...], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(i * block_k + cols < pos, logits, NEG_INF)
        m = m_scr[:, :1]
        m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l_new = l_scr[:, :1] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :values], (((1,), (0,)), ((), ())),
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


def mla_decode_block(heads: int, s_len: int, width: int, values: int,
                     dtype) -> int | None:
    """Tile rule of :func:`mla_decode_attention` (backend-independent):
    the slots a copy brings in, or None where the kernel has no legal
    tiling — rows or values that are no whole lane tiles, heads that
    are no whole sublane tiles of ``dtype``, a plane that ``MLA_BLOCK_K``
    slots (or, a shorter plane, its whole length in lane tiles) do not
    divide.  What a lane reads is its position rounded up to the
    block's ``1 / MLA_TAIL_PARTS``."""
    block, sub = min(MLA_BLOCK_K, s_len), _sublane_rows(dtype)
    if (width % _LANES or values % _LANES or heads % sub or block % _LANES
            or block // MLA_TAIL_PARTS % sub or s_len % block):
        return None
    return block


def use_mla_decode(heads: int, s_len: int, width: int, values: int, dtype,
                   sharded: bool = False) -> bool:
    """Kernel or twin, as ``ops.attention.use_flash_decode``: the
    backend, the placement and the shapes decide."""
    return (_on_tpu() and not sharded
            and mla_decode_block(heads, s_len, width, values,
                                 dtype) is not None)


@functools.partial(jax.jit, static_argnames=("scale", "values", "block_k",
                                             "interpret"))
def mla_decode_attention(q, lat_all, plane, pos, scale: float, values: int,
                         block_k: int | None = None, interpret: bool = False):
    """:func:`mla_decode_twin` as a kernel straight from the slab:
    ``q [B, H, W]``, ``lat_all [P, B, S, W]`` (nothing is cut out of
    it), ``plane`` (int32 scalar) and ``pos [B]`` (int32), both may be
    traced.  Lane ``b`` reads the blocks that hold slots ``< pos[b]``,
    each once for scores and values, and nothing where ``pos[b] == 0``:
    bytes follow the lanes' lengths, not ``S``.  Returns ``(out [B, H,
    values], lse [B, H])``, float32.  Forward only.  ``block_k``
    defaults to :func:`mla_decode_block`'s choice (the interpreter's
    tests give shapes it refuses).  Jitted for the reason
    ``flash_prefix_attention`` is."""
    b, h, w = q.shape
    planes, _, s_len, _ = lat_all.shape
    if block_k is None:
        block_k = mla_decode_block(h, s_len, w, values, lat_all.dtype)
        if block_k is None:
            raise ValueError(
                f"no kernel tiling for {h} heads of {w} against {s_len} "
                f"slots, values {values} (see mla_decode_block)")
    if s_len % block_k or block_k % MLA_TAIL_PARTS:
        raise ValueError(f"block {block_k} does not tile {s_len} slots")
    row_spec = lambda width: pl.BlockSpec(
        (None, h, width), lambda ln, plane_ref, pos_ref: (ln, 0, 0))
    live = b * s_len // 2                 # an estimate: pos is traced

    def call(): return pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=scale, block_k=block_k,
                          parts=MLA_TAIL_PARTS),
        name="mla_decode_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[row_spec(w), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[row_spec(values), row_spec(_LANES)],
            scratch_shapes=[
                pltpu.VMEM((2, block_k, w), lat_all.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),                # live buffer
                pltpu.VMEM((h, _LANES), jnp.float32),       # m
                pltpu.VMEM((h, _LANES), jnp.float32),       # l
                pltpu.VMEM((h, values), jnp.float32),       # acc
            ]),
        out_shape=[jax.ShapeDtypeStruct((b, h, values), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=PREFIX_VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * h * live * (w + values), transcendentals=h * live,
            bytes_accessed=live * w * lat_all.dtype.itemsize),
    )(jnp.reshape(plane, (1,)).astype(jnp.int32), pos.astype(jnp.int32),
      q.astype(lat_all.dtype), lat_all)

    if interpret:
        with pltpu.force_tpu_interpret_mode():
            out, lse = call()
    else:
        out, lse = call()
    return out, lse[..., 0]


# ----------------------------------------------- prefill chunk, expanded


def mla_prefix_twin(q_nope, q_pe, wkv_b, lat_all, plane, lane, off,
                    scale: float, rank: int):
    """A chunk's attention in the EXPANDED form, in ``jax.numpy``:
    ``q_nope [T, H, nope]``, ``q_pe [T, H, rope]`` at positions ``off ..
    off + T - 1`` against row ``lane`` of plane ``plane`` of ``lat_all
    [P, B, S, W]``, the chunk's own rows already written at ``[off, off
    + T)``.  Every head's keys and values are rebuilt from the rows'
    latent part (``wkv_b [rank, H * (nope + v)]``), rounded to the
    slab's dtype as a K/V cache would hold them; key slot ``s`` is kept
    iff ``s <= off + t``.  ``[T, H, v]`` float32: the kernel's oracle,
    over all ``S`` slots."""
    rows = jax.lax.dynamic_slice(
        lat_all, (plane, lane, 0, 0), (1, 1) + lat_all.shape[2:])[0, 0]
    t, h, nope = q_nope.shape
    rope = q_pe.shape[-1]
    f32 = dict(preferred_element_type=jnp.float32)
    kv = jnp.einsum("sr,rhk->shk", rows[:, :rank],
                    wkv_b.reshape(rank, h, -1), **f32).astype(rows.dtype)
    score = (jnp.einsum("thn,shn->hts", q_nope.astype(rows.dtype),
                        kv[..., :nope], **f32)
             + jnp.einsum("thr,sr->hts", q_pe.astype(rows.dtype),
                          rows[:, rank:rank + rope], **f32)) * scale
    keep = (jnp.arange(rows.shape[0])[None, :]
            <= off + jnp.arange(t)[:, None])
    p = jax.nn.softmax(jnp.where(keep[None], score, NEG_INF), axis=-1)
    return jnp.einsum("hts,shv->thv", p.astype(rows.dtype), kv[..., nope:],
                      **f32)


# Heads a step of the chunk kernel's head loop (scripts/sweep_mla.py
# chunk: a 512-row chunk at P = 4,096 / 10,240 / 30,208 took 0.98 /
# 2.17 / 6.05 ms one head at a time, 0.81 / 1.78 / 4.93 two, 0.73 /
# 1.57 / 4.34 four, 0.70 / 1.49 / 4.05 eight — eight at 1.6x four's
# code and 2x its compile time; chip, PR 38).
MLA_PREFIX_HEAD_GROUP = 4


def _mla_prefix_kernel(plane_ref, lane_ref, off_ref, qn_ref, qp_ref, w_ref,
                       lat_ref, o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                       rank: int):
    """One (query block, key block) cell of a chunk's attention against
    its lane's latent rows, in the EXPANDED form: the block's rows come
    from the slab once (``lat_ref [block_k, W]``: the index map names
    plane and lane), and every head's keys and values for them are
    rebuilt HERE, in VMEM — ``c · wkv_b[h]``, one product a head a
    block — so nothing of them ever reaches HBM and a block is expanded
    once a chunk, not once a query.  A head's score is its ``nope``
    part against the rebuilt keys plus its rotary part against the
    rows' shared key (the columns after the latent, zero past it);
    online softmax a head in float32 (``ops.attention.
    _flash_prefix_kernel``'s arithmetic, its mask, its dead blocks
    predicated away).

    The heads go ``MLA_PREFIX_HEAD_GROUP`` at a time, every product of
    a group issued before its first softmax, so one head's vector work
    runs under the next heads' products (a head's chain alone leaves the
    MXU idle through its softmax); heads past a whole number of groups
    go one at a time."""
    heads, block_q, nope = qn_ref.shape
    block_k = lat_ref.shape[0]
    group = min(MLA_PREFIX_HEAD_GROUP, heads)
    j = pl.program_id(1)
    row0 = off_ref[0] + pl.program_id(0) * block_q
    col0 = j * block_k
    f32 = dict(preferred_element_type=jnp.float32)
    nn, nt = (((1,), (0,)), ((), ())), (((1,), (1,)), ((), ()))

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(col0 <= row0 + block_q - 1)
    def _update():
        # What every head shares, once a block.
        c = lat_ref[:, :rank]
        k_pe = lat_ref[:, rank:rank + qp_ref.shape[2]]
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        keep = row0 + rows >= col0 + cols

        def products(h):          # the MXU's: rebuild, then the scores
            kv = jax.lax.dot_general(c, w_ref[h], nn, **f32).astype(c.dtype)
            logits = (jax.lax.dot_general(qn_ref[h], kv[:, :nope], nt, **f32)
                      + jax.lax.dot_general(qp_ref[h], k_pe, nt, **f32)
                      ) * scale
            return logits, kv[:, nope:]

        def softmax(h, logits, v):
            logits = jnp.where(keep, logits, NEG_INF)
            m = m_scr[h][:, :1]
            m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(logits - m_new)
            l_new = l_scr[h][:, :1] * corr + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, nn, **f32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

        def heads_from(h0, n):
            made = [products(h0 + u) for u in range(n)]
            for u, (logits, v) in enumerate(made):
                softmax(h0 + u, logits, v)

        def step(g, carry):
            heads_from(g * group, group)
            return carry

        whole = heads // group
        jax.lax.fori_loop(0, whole, step, 0)
        for h in range(whole * group, heads):
            heads_from(h, 1)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] / l_scr[...][:, :, :1]).astype(o_ref.dtype)


MLA_PREFIX_BLOCK_K = 512
MLA_PREFIX_VMEM_BYTES = 100 * 1024 * 1024


def mla_prefix_blocks(t_len: int, s_len: int, nope: int, rope: int, v: int,
                      width: int, rank: int,
                      dtype) -> tuple[int, int] | None:
    """Tile rule of :func:`mla_prefix_attention` (backend-independent):
    ``(block_q, block_k)``, or None where the kernel has no legal
    tiling — heads' parts that are no lane tiles (the rotary part may
    be half of one: the rows carry zeros after it), a chunk that is no
    whole sublane tiles, a plane no block of lane tiles divides.  The
    chunk is ONE query block up to 512 rows, so that a key block is
    expanded once."""
    sub = _sublane_rows(dtype)
    block_k = min(MLA_PREFIX_BLOCK_K, s_len)
    if (nope % _LANES or v % _LANES or rank % _LANES or width % _LANES
            or width - rank < _LANES or rope > _LANES or t_len % sub
            or block_k % _LANES or s_len % block_k):
        return None
    block_q = max(c for c in range(sub, min(512, t_len) + 1, sub)
                  if t_len % c == 0)
    return block_q, block_k


def use_mla_prefix(t_len: int, s_len: int, nope: int, rope: int, v: int,
                   width: int, rank: int, dtype,
                   sharded: bool = False) -> bool:
    """Kernel or the caller's absorbed path, as :func:`use_mla_decode`."""
    return (_on_tpu() and not sharded and mla_prefix_blocks(
        t_len, s_len, nope, rope, v, width, rank, dtype) is not None)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "block_q",
                                             "block_k", "interpret"))
def mla_prefix_attention(q_nope, q_pe, wkv_b, lat_all, plane, lane, off,
                         scale: float, rank: int, block_q: int | None = None,
                         block_k: int | None = None,
                         interpret: bool = False):
    """:func:`mla_prefix_twin` as a kernel straight from the slab
    (name ``mla_prefix_fwd``): slots past ``off + T - 1`` are neither
    fetched nor computed, so bytes and operations follow the attended
    prefix, not ``S``, and no key or value of any head reaches HBM.
    ``plane``, ``lane``, ``off``: int32 scalars, may be traced.
    Returns ``[T, H, v]`` in the slab's dtype.  Forward only."""
    t_len, heads, nope = q_nope.shape
    rope = q_pe.shape[-1]
    planes, _, s_len, width = lat_all.shape
    v = wkv_b.shape[1] // heads - nope
    if block_q is None or block_k is None:
        fit = mla_prefix_blocks(t_len, s_len, nope, rope, v, width, rank,
                                lat_all.dtype)
        if fit is None:
            raise ValueError(
                f"no kernel tiling for a chunk of {t_len} x {heads} heads of "
                f"{nope} + {rope} against {s_len} rows of {width} (see "
                "mla_prefix_blocks)")
        block_q, block_k = block_q or fit[0], block_k or fit[1]
    if t_len % block_q or s_len % block_k:
        raise ValueError(f"blocks ({block_q}, {block_k}) do not tile a "
                         f"chunk of {t_len} against {s_len} rows")
    dtype = lat_all.dtype
    pe_w = min(width - rank, -(-rope // _LANES) * _LANES)
    qn = q_nope.astype(dtype).transpose(1, 0, 2)
    qp = jnp.pad(q_pe.astype(dtype).transpose(1, 0, 2),
                 ((0, 0), (0, 0), (0, pe_w - rope)))
    w = wkv_b.astype(dtype).reshape(rank, heads, nope + v).transpose(1, 0, 2)

    def lat_map(i, j, plane_ref, lane_ref, off_ref):
        last = (off_ref[0] + (i + 1) * block_q - 1) // block_k
        return plane_ref[0], lane_ref[0], jnp.minimum(j, last), 0

    q_map = lambda i, j, *_: (0, i, 0)
    live = heads * t_len * s_len // 2      # an estimate: off is traced
    scalar = lambda x: jnp.reshape(x, (1,)).astype(jnp.int32)

    def call(): return pl.pallas_call(
        functools.partial(_mla_prefix_kernel, scale=scale, rank=rank),
        name="mla_prefix_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(t_len // block_q, s_len // block_k),
            in_specs=[
                pl.BlockSpec((heads, block_q, nope), q_map),
                pl.BlockSpec((heads, block_q, pe_w), q_map),
                pl.BlockSpec((heads, rank, nope + v), lambda i, j, *_: (0, 0, 0)),
                pl.BlockSpec((None, None, block_k, width), lat_map)],
            out_specs=pl.BlockSpec((heads, block_q, v), q_map),
            scratch_shapes=[
                pltpu.VMEM((heads, block_q, _LANES), jnp.float32),   # m
                pltpu.VMEM((heads, block_q, _LANES), jnp.float32),   # l
                pltpu.VMEM((heads, block_q, v), jnp.float32),        # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((heads, t_len, v), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=MLA_PREFIX_VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * live * (nope + rope + v)
            + 2 * s_len // 2 * rank * heads * (nope + v),
            transcendentals=live,
            bytes_accessed=s_len // 2 * width * dtype.itemsize),
    )(scalar(plane), scalar(lane), scalar(off), qn, qp, w, lat_all)

    if interpret:
        with pltpu.force_tpu_interpret_mode():
            out = call()
    else:
        out = call()
    return out.transpose(1, 0, 2)
