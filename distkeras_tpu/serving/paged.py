"""Paged KV: block-granular cache with per-lane page tables.

The monolithic engines allocate every lane a full ``[max_len]`` KV
row, so HBM — not compute — caps the lane count, and two requests
sharing a common stem share nothing unless it was pre-registered in a
:class:`~distkeras_tpu.serving.prefix.PrefixPool`.  This module is the
vLLM-style fix (round 12):

- **One slab, fixed-size blocks.**  The whole cache is ONE device
  allocation of ``n_blocks`` blocks of ``block`` positions each
  (``[L, n_blocks, block, kv_heads, head_dim]`` per K/V leaf — i.e.
  ``init_cache`` with ``batch=n_blocks, max_len=block``).  Block 0 is
  the reserved TRASH block: unallocated page-table entries point at
  it, so idle/done/parked lanes' clamped garbage writes land there and
  admission pad writes are redirected there — allocated memory tracks
  *live tokens*, not bucket roundup.
- **Per-lane page tables.**  Each lane carries a ``[max_blocks]``
  int32 row mapping logical block k to a physical slab block.  The
  host owns the authoritative numpy copy (the allocator is host-side
  bookkeeping); the device copy is re-pushed on change — a transfer,
  never a compile.
- **The paged step gathers by page table inside the compiled
  program** and then runs the EXACT monolithic per-token body
  (:meth:`ContinuousBatcher._build_one_step` — one definition) over
  the gathered contiguous view, scattering the window's new K/V back
  into the slab afterwards.  Because ``block`` must divide
  ``max_len``, the gathered view is exactly ``[lanes, max_len]`` with
  the same mask arithmetic, so greedy AND seeded-sampled tokens are
  bit-identical to the monolithic engine (pinned by
  tests/test_serving_paged.py).
- **Content-hash stem sharing at admission.**  Every full block of
  warm prompt tokens is chain-hashed; a new request whose prompt
  prefix hashes to resident blocks refcounts them instead of
  re-prefilling — the :class:`PrefixPool` generalized to ANY common
  stem, with pinned prefixes (:meth:`PagedBatcher.pin_prefix`) just
  refcount-held block runs in the same slab: one allocator, one slab,
  one mechanism.  Hashes register only once the block's content has
  actually been dispatched (chunked prefill lands over several
  steps), so a concurrent admission can never share an unwritten
  block.
- **Copy-on-write fork** (:meth:`PagedBatcher.fork`): beam branches
  and speculative checkpoints fork the page table — full blocks are
  refcount-shared, only the divergent tail block is copied — instead
  of copying whole lane caches.

Safety invariant the whole design leans on: a block becomes shared
(by stem hit, pin, or fork) only when it lies wholly BELOW its
owner's write frontier, and every device write lands at or above the
writer's frontier (or in trash), so a shared block is immutable for
as long as it is shared.

Allocator exhaustion is backpressure, not corruption: admission
declines (``enqueue`` queues, then raises
:class:`~distkeras_tpu.serving.QueueFull`); a lane that cannot grow
mid-decode is evicted with a structured ``"error"`` result and its
private blocks return to the free list (shared blocks survive — the
chaos leg in tests/test_serving_paged.py).

When monolithic still wins: the XLA gather materializes a
``[lanes, max_len]`` working view per step, so per-step HBM *traffic*
is higher than the monolithic read — the paged win is *resident*
bytes (lane count at fixed slab), sharing, and O(block) forks.  See
docs/serving_guide.md#paged-kv.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import obs
from distkeras_tpu.models.generate import _decode_chunk, init_cache, prefill
from distkeras_tpu.models.quant import is_quantized
from distkeras_tpu.models.transformer import (TransformerConfig,
                                               reject_extended)
from distkeras_tpu.serving.engine import _Lane
from distkeras_tpu.serving.lanes import ContinuousBatcher
from distkeras_tpu.serving.prefix import PinnedStems
from distkeras_tpu.serving.disagg import BlockShipment
from distkeras_tpu.serving.residency import chain_hash as _chain_hash
from distkeras_tpu.serving.residency import stem_hashes as _stem_hashes
from distkeras_tpu.utils.locks import TracedRLock

# Physical block 0 is never handed out: unallocated page-table entries
# read it (masked anyway) and redirected pad/clamp writes land in it.
TRASH_BLOCK = 0

# kv_int8="prefill" parity bound: max |logit delta| of the first
# decode step after a prefill-BUILT int8 admission vs the exact
# decode-built cache.  Measured 0.005-0.017 across seeds on the d32/L2
# test config (argmax preserved everywhere); pinned at ~3x the worst
# measurement by tests/test_serving_paged.py::
# test_kv_int8_prefill_admission_tolerance — if this grows, the
# prefill-built write path regressed, not the tolerance.
KV_INT8_PREFILL_LOGIT_TOL = 0.05


def _gather_view(leaf, tables):
    """``leaf [L, N, B, ...]`` gathered through ``tables [rows, mb]``
    into the contiguous per-lane view ``[L, rows, mb*B, ...]`` the
    shared decode body expects."""
    g = jnp.take(leaf, tables, axis=1)
    return g.reshape(g.shape[:2] + (g.shape[2] * g.shape[3],)
                     + g.shape[4:])


class BlockAllocator:
    """Host-side refcounted block allocator with content-hash
    residency.

    Blocks live in one of two states: **live** (refcount > 0 — some
    lane's page table, a pinned stem, or a fork holds them) or on the
    **free list** (refcount 0).  A freed block keeps its content hash
    until the free list recycles it, so a later request can revive it
    by hash — cross-request stem sharing even when the requests never
    overlap in time (the vLLM cached-allocator idea).  ``alloc`` pops
    the oldest free block and purges its hash; ``share_by_hash``
    revives or refcounts a resident block.

    Thread-safe leaf lock (engines call under their admission lock —
    the same admission -> pool ordering docs/concurrency.md pins).
    """

    def __init__(self, n_blocks: int, block: int, reserved: int = 1):
        if n_blocks <= reserved:
            raise ValueError(
                f"n_blocks ({n_blocks}) must exceed the {reserved} "
                "reserved trash block(s)")
        self.block = int(block)
        self.n_blocks = int(n_blocks)
        self.capacity = self.n_blocks - reserved
        # dict-as-ordered-set: FIFO free list with O(1) revival.
        self._free: dict[int, None] = dict.fromkeys(
            range(reserved, n_blocks))
        self._refs: dict[int, int] = {}
        self._hash_of: dict[int, bytes] = {}
        self._by_hash: dict[bytes, int] = {}
        self._lock = TracedRLock("serving.kv_allocator")

    # ------------------------------------------------------ lifecycle

    def alloc(self) -> int | None:
        """Pop the oldest free block (purging any resident hash) with
        one reference, or None when exhausted — the backpressure
        signal, never an exception (the engine decides the policy)."""
        with self._lock:
            if not self._free:
                return None
            bid = next(iter(self._free))
            del self._free[bid]
            h = self._hash_of.pop(bid, None)
            if h is not None and self._by_hash.get(h) == bid:
                del self._by_hash[h]
            self._refs[bid] = 1
            return bid

    def free(self, bid: int) -> None:
        """Drop one reference; at zero the block moves to the free
        list (its hash stays resident until recycled)."""
        with self._lock:
            r = self._refs.get(bid)
            if r is None:
                raise ValueError(f"block {bid} is not live (double "
                                 "free, or never allocated)")
            if r > 1:
                self._refs[bid] = r - 1
            else:
                del self._refs[bid]
                self._free[bid] = None

    def share(self, bid: int) -> None:
        """One more reference to a LIVE block (fork/pin)."""
        with self._lock:
            if bid not in self._refs:
                raise ValueError(f"block {bid} is not live")
            self._refs[bid] += 1

    def share_by_hash(self, digest: bytes) -> int | None:
        """Refcount the resident block holding ``digest``'s content
        (reviving it off the free list if unreferenced); None on a
        miss."""
        with self._lock:
            bid = self._by_hash.get(digest)
            if bid is None:
                return None
            if bid in self._free:
                del self._free[bid]
                self._refs[bid] = 1
            else:
                self._refs[bid] += 1
            return bid

    def register(self, bid: int, digest: bytes) -> None:
        """Publish a live block's content hash for future sharing.
        First writer wins: if the digest is already mapped (a
        concurrent identical admission that both missed), the second
        block simply stays private — same content either way."""
        with self._lock:
            if bid not in self._refs:
                raise ValueError(f"block {bid} is not live")
            if digest in self._by_hash:
                return
            old = self._hash_of.pop(bid, None)
            if old is not None and self._by_hash.get(old) == bid:
                del self._by_hash[old]
            self._hash_of[bid] = digest
            self._by_hash[digest] = bid

    # ----------------------------------------------------- inspection

    def refs_of(self, bid: int) -> int:
        with self._lock:
            return self._refs.get(bid, 0)

    def resident_hashes(self) -> list[bytes]:
        """Every digest currently resident (live OR free-but-not-yet-
        recycled — both hit on :meth:`share_by_hash`): the paged half
        of the engine's residency digest (round 13)."""
        with self._lock:
            return list(self._by_hash)

    def stats(self) -> dict:
        """``used``/``free``/``shared`` block counts (shared = live
        with more than one reference) + hash residency."""
        with self._lock:
            return {
                "capacity": self.capacity,
                "used": len(self._refs),
                "free": len(self._free),
                "shared": sum(1 for r in self._refs.values() if r > 1),
                "resident_hashes": len(self._by_hash),
            }


class PagedBatcher(ContinuousBatcher):
    """:class:`ContinuousBatcher` on block-granular paged KV storage.

    Same host API (``submit``/``enqueue``/``step``/``drain``, the full
    admission-control surface, ``per_request_sampling``, chunked
    prefill) and the same exact-parity contract — every request's
    emitted tokens are bit-identical to the monolithic engine's and to
    solo ``generate`` — plus:

    - ``block`` / ``n_blocks``: the slab geometry.  ``block`` must
      divide ``cfg.max_len``; ``n_blocks`` defaults to the
      monolithic-equivalent ``lanes * max_len/block + 1`` — shrink it
      to serve more lanes than monolithic HBM would allow (memory is
      consumed by actual tokens, not ``max_len`` rows), at the price
      of ``QueueFull`` backpressure when the allocator runs dry and
      structured ``"error"`` eviction if a lane cannot grow mid-decode.
    - **stem sharing** is automatic: a prompt whose full-block prefix
      was already prefilled (by any resident request, or a pinned
      prefix) refcounts those blocks and prefills only the remainder.
    - :meth:`pin_prefix` / :meth:`unpin_prefix`: the prefix-pool story
      on the one slab — pinned block runs any matching prompt hits by
      hash, no ``prefix_id`` plumbing at submit.
    - :meth:`fork`: copy-on-write lane fork (beam branching,
      speculative checkpoint/rollback) — shares full blocks, copies
      only the divergent tail block.
    - ``kv_int8``: ``True`` is the exact-parity decode-built int8
      cache (vs the monolithic ``kv_int8=True`` engine); ``"prefill"``
      additionally builds from-scratch single-chunk admissions through
      the batched ``prefill(kv_int8=True)`` forward — faster
      admission at a measured, test-pinned parity tolerance
      (full-precision in-chunk attention, quantized once at the end).

    - ``plan=``/``mesh=`` (round 14): pod-sharded paging — the block
      slab's kv-heads dimension shards over the plan-derived mesh
      axis exactly like the monolithic cache (the slab layout ends
      ``[..., kv_heads, head_dim]`` too), page tables and the
      allocator stay host-side/replicated, so stem sharing, pinned
      stems, and CoW forks work unchanged on a slab that spans the
      mesh.  Same bit-parity/bytes/zero-compile contract as the
      sharded ContinuousBatcher (docs/serving_guide.md "Pod-sharded
      serving").

    - ``lane_tiers=`` (round 17): elastic paging — the slab and the
      block allocator are lane-count-independent, so a tier move is a
      rows-only gather plus a host-side page-table remap: zero KV
      bytes move and zero serve-phase compiles (every tier's programs
      and the inter-tier row gathers warm at construction, sharded
      engines included).  ``n_blocks`` defaults to covering the TOP
      tier.  :meth:`fork` is rejected (lane ids are not stable across
      a resize).

    Not supported (structurally): ``attention_window`` (ring slots
    have no stable block identity), ``prompt_cache=`` / ``prefix_pool=``
    (subsumed by pinned stems).

    Every program — the step windows, one admission program per
    bucket, the CoW block copy and row fork — compiles at
    construction; the ``serving_paged`` / ``serving_paged_cow``
    compile sessions pin a zero-recompile serve phase.
    """

    _always_warm = True

    def __init__(self, params, cfg: TransformerConfig, lanes: int = 8,
                 block: int = 16, n_blocks: int | None = None,
                 temperature: float = 0.0, top_k=None, top_p=None,
                 min_p=None, eos_token=None, exact_top_k: bool = False,
                 prompt_buckets=(8, 32, 128, 512), kv_int8=False,
                 per_request_sampling: bool = False,
                 max_queue: int = 0, clock=None,
                 lane_tiers=None, scale_up_after: int = 2,
                 scale_down_after: int = 8, step_windows=(1,),
                 prefill_chunk: int | None = None, plan=None,
                 mesh=None):
        reject_extended(cfg, "PagedBatcher (the paged KV store)")
        if cfg.attention_window is not None:
            raise ValueError(
                "paged KV needs a full-cache config (no "
                "attention_window): a ring slot has no stable block "
                "identity to share or fork")
        block = int(block)
        if block < 1:
            raise ValueError(f"block must be >= 1, got {block}")
        if cfg.max_len % block:
            raise ValueError(
                f"block ({block}) must divide max_len ({cfg.max_len}): "
                "the page-table gather must tile the position axis "
                "exactly or the step's mask arithmetic (and the "
                "bit-parity contract) would drift from the monolithic "
                "engine")
        if kv_int8 not in (False, True, "prefill"):
            raise ValueError(
                f'kv_int8 must be False, True, or "prefill", got '
                f"{kv_int8!r}")
        self.kv_int8_prefill = kv_int8 == "prefill"
        if self.kv_int8_prefill and is_quantized(params):
            raise ValueError(
                'kv_int8="prefill" runs the batched prefill forward '
                "at admission, which needs full-precision params "
                "(decode-built kv_int8=True composes with int8 "
                "weights)")
        self.block = block
        self._mb = cfg.max_len // block
        if n_blocks is None:
            # Monolithic-equivalent default: every lane can hold
            # max_len tokens.  The paged WIN comes from shrinking it.
            # Elastic engines size for the TOP tier — the slab never
            # resizes (rows and tables do), so the default must cover
            # the widest lane count a scale-up can reach.
            cap = max(int(t) for t in lane_tiers) if lane_tiers \
                else lanes
            n_blocks = cap * self._mb + 1
        self.n_blocks = int(n_blocks)
        self._alloc = BlockAllocator(self.n_blocks, block)
        # Per-lane block lists are built in _init_device_state (sized
        # to the STARTING lane count — elastic engines start at the
        # smallest tier and remap them on every resize).  Admission
        # bookkeeping keyed by lane: the warm frontier the
        # pad-redirect uses, and hashes awaiting their block's content
        # to be dispatched before they may be shared.
        self._lane_limit: dict[int, int] = {}
        self._pending_hashes: dict[int, list] = {}
        self._stems = PinnedStems()
        # Cumulative admission stem hits (blocks refcounted instead of
        # re-prefilled) — host-visible without an obs session; the
        # ``serving.stem_hit_blocks`` counter mirrors it into
        # /metrics.
        self.stem_hit_blocks = 0
        super().__init__(params, cfg, lanes=lanes,
                         temperature=temperature, top_k=top_k,
                         top_p=top_p, min_p=min_p, eos_token=eos_token,
                         exact_top_k=exact_top_k,
                         prompt_buckets=prompt_buckets,
                         kv_int8=bool(kv_int8),
                         per_request_sampling=per_request_sampling,
                         max_queue=max_queue, clock=clock,
                         lane_tiers=lane_tiers,
                         scale_up_after=scale_up_after,
                         scale_down_after=scale_down_after,
                         step_windows=step_windows,
                         prefill_chunk=prefill_chunk, plan=plan,
                         mesh=mesh)

    # ------------------------------------------------ storage layout

    def _fresh_cache(self, lanes: int):
        # The slab's capacity is n_blocks — independent of lane count
        # (that decoupling IS the feature).  init_cache with
        # batch=n_blocks, max_len=block is exactly the block layout,
        # scale leaves included.
        del lanes
        slab_cfg = dataclasses.replace(self.cfg, max_len=self.block)
        # _place_kv: pod-sharded engines shard the slab's kv-heads
        # dimension exactly like the monolithic cache (the block
        # layout ends [..., kv_heads, head_dim] too) — the per-lane
        # gather/scatter stays lane-and-position-local, so sharding
        # composes with paging for free.
        return self._place_kv(init_cache(slab_cfg, self.n_blocks,
                                         kv_int8=self.kv_int8))

    def _init_device_state(self, lanes: int) -> None:
        super()._init_device_state(lanes)
        self._lane_blocks: list[list[int]] = [[] for _ in range(lanes)]
        self._tables_np = np.zeros((lanes, self._mb), np.int32)
        self.tables = self._put_host(self._tables_np.copy())

    def _push_tables(self) -> None:
        # Authoritative copy is host-side numpy; the device array is
        # re-materialized on change (replicated over the mesh on
        # sharded engines).  An explicit copy: device_put may
        # alias host memory on CPU, and the host copy keeps mutating.
        self.tables = self._put_host(self._tables_np.copy())

    # ------------------------------------------------- elastic tiers

    def _make_resize(self):
        # Rows-only: the slab is lane-count-independent (that
        # decoupling IS the feature), so a tier move gathers just the
        # per-lane row metadata — no KV byte moves, and the page
        # tables remap host-side in _resize_state.
        def resize(cur, pos, keys, temps, tps, mps, idx):
            g = lambda a: jnp.take(a, idx, axis=0)
            return (g(cur), g(pos), g(keys), g(temps), g(tps), g(mps))

        return jax.jit(resize)

    def _warm_resize(self, frm: int, to: int) -> None:
        # The post-resize table push reuses _warm_steps' per-tier
        # [tier, _mb] device_put — nothing extra to warm here.
        _, cur, pos, keys, temps, tps, mps = self._tier_state(frm)
        self._resize(cur, pos, keys, temps, tps, mps,
                     jnp.zeros((to,), jnp.int32))

    def _resize_state(self, idx) -> None:
        idx = np.asarray(idx, np.int32)
        tier = int(idx.shape[0])
        (self.cur, self.pos, self.keys, self.temps, self.tps,
         self.mps) = self._resize(self.cur, self.pos, self.keys,
                                  self.temps, self.tps, self.mps, idx)
        # Host bookkeeping follows the same compaction _resize_to is
        # about to apply to _lane_state: occupied lanes move to the
        # low slots in index order; fresh lanes arrive with empty
        # block lists and all-TRASH page tables (their stale rows are
        # masked until admission overwrites them, the lane-reuse
        # contract).  Block refcounts are untouched — lanes keep their
        # blocks, only the lane ids naming them change.
        keep = [i for i, s in enumerate(self._lane_state)
                if s is not None]
        blocks: list[list[int]] = [[] for _ in range(tier)]
        tables = np.full((tier, self._mb), TRASH_BLOCK, np.int32)
        limits: dict[int, int] = {}
        pending: dict[int, list] = {}
        for j, i in enumerate(keep):
            blocks[j] = self._lane_blocks[i]
            tables[j] = self._tables_np[i]
            if i in self._lane_limit:
                limits[j] = self._lane_limit[i]
            if i in self._pending_hashes:
                pending[j] = self._pending_hashes[i]
        self._lane_blocks = blocks
        self._tables_np = tables
        self._lane_limit = limits
        self._pending_hashes = pending
        self._push_tables()

    # ---------------------------------------------- compiled programs

    def _make_step(self, n: int):
        one_step = self._one_step
        B, s_len = self.block, self.cfg.max_len
        constrain = self._kv_constraint

        def step_n(slab, tables, cur, pos, keys, temps, tps, mps):
            if constrain is not None:
                slab = constrain(slab)
            # Gather every lane's contiguous [max_len] view through its
            # page table, run the SHARED monolithic window body on it,
            # then scatter only the window's new K/V back to the slab.
            view = jax.tree.map(lambda a: _gather_view(a, tables), slab)

            def body(carry, _):
                view, cur, pos = carry
                view, cur, pos, _ = one_step(view, cur, pos, keys, temps,
                                             tps, mps)
                return (view, cur, pos), cur

            (view, cur2, pos2), toks = jax.lax.scan(
                body, (view, cur, pos), None, length=n)
            # Positions this window wrote: pos..pos+n-1, clamped like
            # the body's own advance (duplicates at the clamp carry
            # identical final-view values, so scatter order is moot).
            q = jnp.minimum(pos[:, None] + jnp.arange(n)[None, :],
                            s_len - 1)                   # [lanes, n]
            blk = jnp.take_along_axis(tables, q // B, axis=1)
            off = q % B

            def write_back(s, vw):
                idx = q.reshape((1,) + q.shape
                                + (1,) * (vw.ndim - 3))
                vals = jnp.take_along_axis(vw, idx, axis=2)
                return s.at[:, blk, off].set(vals.astype(s.dtype))

            slab = jax.tree.map(write_back, slab, view)
            if constrain is not None:
                slab = constrain(slab)
            return slab, cur2, pos2, toks.T
        return jax.jit(step_n, donate_argnums=0)

    def _build_admission_programs(self) -> None:
        params, cfg, B = self.params, self.cfg, self.block
        constrain = self._kv_constraint

        def admit(slab, table_row, rows, start, limit):
            if constrain is not None:
                slab = constrain(slab)
            # One program per bucket width (start/limit traced): the
            # lane's view is gathered, the chunk runs the SAME
            # uniform-pos _decode_chunk as monolithic admission, and
            # the chunk span scatters back — pad positions past the
            # warm frontier ``limit`` redirect to the trash block, so
            # allocated blocks hold live tokens only.
            view = jax.tree.map(
                lambda a: _gather_view(a, table_row[None]), slab)
            _, view = _decode_chunk(
                params, view, rows,
                jnp.reshape(start, (1,)).astype(jnp.int32), cfg,
                uniform_pos=True)
            w = rows.shape[1]
            q = start + jnp.arange(w)
            blk = jnp.where(q < limit, table_row[q // B], TRASH_BLOCK)
            off = q % B

            def write_back(s, vw):
                seg = jax.lax.dynamic_slice_in_dim(vw, start, w,
                                                   axis=2)[:, 0]
                return s.at[:, blk, off].set(seg.astype(s.dtype))
            out = jax.tree.map(write_back, slab, view)
            return constrain(out) if constrain is not None else out

        self._admit = jax.jit(admit, donate_argnums=0)
        # The chunked-prefill continuation IS the same program (no
        # seed/continuation split: fresh blocks need no zeroing — a
        # vacated lane's table is reset to trash, and stale block
        # content is masked until overwritten, the same staleness
        # argument as monolithic lane reuse).
        self._admit_cont = None
        self._reseed = self._reseed_pool = None

        self._admit_prefill = None
        if self.kv_int8_prefill:
            def admit_prefill(slab, table_row, rows, limit):
                # Prefill-built int8 admission (round-12 satellite):
                # the batched prefill forward attends the chunk in
                # FULL precision and quantizes once at the end —
                # cheaper than the masked full-cache chunk for a
                # from-scratch prompt, at a bounded parity cost
                # (pinned by test_kv_int8_prefill_tolerance).
                cache, _ = prefill(params, rows, cfg,
                                   last_logits=False, kv_int8=True)
                w = rows.shape[1]
                q = jnp.arange(w)
                blk = jnp.where(q < limit, table_row[q // B],
                                TRASH_BLOCK)
                off = q % B

                def write_back(s, c):
                    return s.at[:, blk, off].set(
                        c[:, 0, :w].astype(s.dtype))
                out = jax.tree.map(write_back, slab, cache)
                return (constrain(out) if constrain is not None
                        else out)
            self._admit_prefill = jax.jit(admit_prefill,
                                          donate_argnums=0)

        def copy_block(slab, src, dst):
            # The CoW fork's divergent-tail copy: O(block) bytes, the
            # whole point vs copying a max_len lane cache.
            out = jax.tree.map(
                lambda a: jax.lax.dynamic_update_slice_in_dim(
                    a, jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1),
                    dst, axis=1),
                slab)
            return constrain(out) if constrain is not None else out
        self._copy_block = jax.jit(copy_block, donate_argnums=0)

        def extract_block(slab, src):
            # Disagg export (round 17): read ONE block off the slab —
            # all layers, scale leaves included.  No donation: the
            # slab keeps serving.
            return jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, src, 1,
                                                       axis=1),
                slab)
        self._extract_block = jax.jit(extract_block)

        def adopt_block(slab, blk, dst):
            # Disagg import: splice a shipped block's content into the
            # slab at ``dst`` — the write half of _copy_block with the
            # source coming off the wire instead of the slab.
            out = jax.tree.map(
                lambda a, b: jax.lax.dynamic_update_slice_in_dim(
                    a, b.astype(a.dtype), dst, axis=1),
                slab, blk)
            return constrain(out) if constrain is not None else out
        self._adopt_block = jax.jit(adopt_block, donate_argnums=0)

        def fork_rows(cur, pos, keys, temps, tps, mps, src, dst,
                      token):
            g = lambda x: x.at[dst].set(x[src])
            return (cur.at[dst].set(token), g(pos), g(keys), g(temps),
                    g(tps), g(mps))
        self._fork_rows = jax.jit(fork_rows)

        def fork_rows_key(cur, pos, keys, temps, tps, mps, src, dst,
                          token, key):
            g = lambda x: x.at[dst].set(x[src])
            return (cur.at[dst].set(token), g(pos),
                    keys.at[dst].set(key), g(temps), g(tps), g(mps))
        self._fork_rows_key = jax.jit(fork_rows_key)

    # ------------------------------------------------------- warm-up

    def _warm_live(self) -> None:
        # The paged programs take page tables and their warmers make
        # their own (small) block slabs: untiered, the one tier.
        self._warm_tier(self.lanes)

    def _warm_steps(self, tier: int) -> None:
        for n in self._step_windows:
            if n not in self._steps:
                self._steps[n] = self._make_step(n)
        tabs = self._put_host(np.zeros((tier, self._mb), np.int32))
        for n in self._step_windows:
            cache, cur, pos, keys, temps, tps, mps = \
                self._tier_state(tier)
            self._steps[n](cache, tabs, cur, pos, keys, temps, tps,
                           mps)

    def _warm_admission(self, tier: int) -> None:
        row = self._put_host(np.zeros((self._mb,), np.int32))
        for width in self._buckets:
            rows = jnp.zeros((1, width), jnp.int32)
            self._admit(self._fresh_cache(tier), row, rows,
                        jnp.int32(0), jnp.int32(0))
            if self._admit_prefill is not None:
                self._admit_prefill(self._fresh_cache(tier), row, rows,
                                    jnp.int32(0))
        # CoW programs (block copy + row fork, keyed variant too).
        self._copy_block(self._fresh_cache(tier), jnp.int32(0),
                         jnp.int32(0))
        # Disagg block-transfer programs (export read + import
        # splice): warm with a template block placed exactly like a
        # live import places wire payloads, so adoption never
        # compiles (the ``serving_disagg`` session pins it).
        self._extract_block(self._fresh_cache(tier), jnp.int32(0))
        self._adopt_block(self._fresh_cache(tier),
                          self._place_kv(self._block_template()),
                          jnp.int32(0))
        cache, cur, pos, keys, temps, tps, mps = self._tier_state(tier)
        z = jnp.int32(0)
        self._fork_rows(cur, pos, keys, temps, tps, mps, z, z, z)
        if self._keyed:
            self._fork_rows_key(cur, pos, keys, temps, tps, mps, z, z,
                                z, jax.random.key(0))

    # ----------------------------------------------------- admission

    def _stage_blocks(self, tokens, warm: int):
        """The ONE stem-share + allocate staging path (admission AND
        pin_prefix — duplicating it is how rollback bugs breed):
        chain-hash the full blocks of ``tokens[:warm]``, refcount the
        longest resident hashed prefix, resolve the chunk plan for the
        remainder, and allocate fresh blocks for it.  Returns
        ``(blocks, shared, hashes, plan)``, or None when the allocator
        is exhausted — with every reference this attempt took rolled
        back either way on failure.

        A resident stem hit must never make a valid request
        UNPLANNABLE: if no admission bucket fits the unshared span at
        the skip offset, shared blocks are handed back (longest prefix
        first shrinking from the end) until the plan fits — skip=0 was
        already validated by ``_validate_budget``."""
        B = self.block
        full = warm // B
        hashes, digest = [], b""
        for k in range(full):
            digest = _chain_hash(digest, tokens[k * B:(k + 1) * B])
            hashes.append(digest)
        shared_blocks = []
        for h in hashes:
            bid = self._alloc.share_by_hash(h)
            if bid is None:
                break
            shared_blocks.append(bid)
        while shared_blocks:
            try:
                plan = self._chunk_plan(0, warm,
                                        skip=len(shared_blocks) * B)
                break
            except ValueError:
                # No bucket fits the span at this offset: give back
                # the last shared block and retry with a smaller skip.
                self._alloc.free(shared_blocks.pop())
        else:
            plan = self._chunk_plan(0, warm)
        shared = len(shared_blocks)
        need = (-(-warm // B) - shared) if warm else 0
        fresh = []
        for _ in range(need):
            bid = self._alloc.alloc()
            if bid is None:
                # Exhausted: no half-staged lane, no leak.
                for b in fresh:
                    self._alloc.free(b)
                for b in shared_blocks:
                    self._alloc.free(b)
                return None
            fresh.append(bid)
        return shared_blocks + fresh, shared, hashes, plan

    def _admission_plan(self, lane, prompt, off: int, warm: int):
        assert off == 0, "paged engines carry no engine-level prefix"
        staged = self._stage_blocks(prompt, warm)
        if staged is None:
            # DECLINE — the caller surfaces kv_blocks backpressure.
            return None
        blocks, shared, hashes, plan = staged
        self._lane_blocks[lane] = blocks
        self._lane_limit[lane] = warm
        # Fresh full blocks become shareable only once their content
        # has been dispatched (_register_written) — chunked prefill
        # lands over several steps and an unwritten block must never
        # hash-hit.
        self._pending_hashes[lane] = [(k, hashes[k])
                                      for k in range(shared,
                                                     warm // self.block)]
        row = self._tables_np[lane]
        row[:] = TRASH_BLOCK
        row[:len(blocks)] = blocks
        self._push_tables()
        if shared:
            self.stem_hit_blocks += shared
            obs.count("serving.stem_hit_blocks", shared)
            obs.event("serving.stem_hit", lane=lane,
                      shared_blocks=shared,
                      shared_tokens=shared * self.block)
        self._obs_blocks()
        return plan

    def _abort_admission(self, lane) -> None:
        if self._lane_state[lane] is not None:
            return  # committed; the failure happened later
        for bid in self._lane_blocks[lane]:
            self._alloc.free(bid)
        self._lane_blocks[lane] = []
        self._pending_hashes.pop(lane, None)
        self._lane_limit.pop(lane, None)
        self._tables_np[lane, :] = TRASH_BLOCK
        self._push_tables()

    def _exec_admit(self, lane, start, rows, slot):
        assert slot is None  # no prefix pool on paged engines
        return self._exec_chunk(lane, start, rows)

    def _exec_chunk(self, lane, start, rows):
        limit = self._lane_limit[lane]
        row = self._put_host(self._tables_np[lane].copy())
        w = rows.shape[1]
        if (self._admit_prefill is not None and start == 0
                and w >= limit):
            # From-scratch single-chunk admission under
            # kv_int8="prefill": the batched prefill forward.  Chunked
            # continuations and stem-shared tails keep the decode-built
            # path (they must attend PRIOR cache, which prefill
            # cannot).
            fn = self._admit_prefill
            self.cache = fn(
                self.cache, row, jnp.asarray(rows), jnp.int32(limit))
        else:
            fn = self._admit
            self.cache = fn(
                self.cache, row, jnp.asarray(rows), jnp.int32(start),
                jnp.int32(limit))
        self._register_written(lane, min(start + w, limit))
        return fn

    def _register_written(self, lane, end: int) -> None:
        pend = self._pending_hashes.get(lane)
        if not pend:
            return
        blocks = self._lane_blocks[lane]
        keep = []
        for k, h in pend:
            if (k + 1) * self.block <= end:
                self._alloc.register(blocks[k], h)
            else:
                keep.append((k, h))
        self._pending_hashes[lane] = keep

    # -------------------------------------------------- decode growth

    # The page tables grow from the transcript's length and a lane the
    # allocator cannot grow is evicted: the next dispatch DOES need the
    # last round's tokens, so each round is read at once.
    _overlap = False

    def _dispatch_step(self, n: int):
        self._ensure_growth(n)
        if n not in self._steps:
            self._steps[n] = self._make_step(n)
        self.cache, self.cur, self.pos, toks = self._steps[n](
            self.cache, self.tables, self.cur, self.pos, self.keys,
            self.temps, self.tps, self.mps)
        return toks

    def _ensure_growth(self, n: int) -> None:
        """Allocate the blocks this window's writes need, per live
        lane — memory tracks live tokens.  A lane the allocator cannot
        grow is evicted with a structured ``"error"`` result; its
        private blocks return to the free list immediately (possibly
        unblocking the remaining lanes), shared blocks survive."""
        changed = False
        for lane, st in enumerate(self._lane_state):
            if st is None or st.done or st.chunks is not None:
                continue
            pos = st.off + len(st.tokens) - 1
            # The last K/V write this REQUEST can ever need: its final
            # emitted token is never processed, so the frontier stops
            # at prompt + max_new - 2.  Window positions past it (or
            # past max_len) are discarded garbage that redirects to
            # trash — allocating for them would turn step-window
            # roundup into spurious OOM evictions.
            last = min(pos + n - 1, self.cfg.max_len - 1,
                       st.off + st.prompt_len + st.max_new - 2)
            hi = last // self.block
            blocks = self._lane_blocks[lane]
            while len(blocks) <= hi:
                bid = self._alloc.alloc()
                if bid is None:
                    obs.count("serving.kv_oom_evictions")
                    obs.event("serving.kv_oom_evict", lane=lane,
                              request_id=st.request_id,
                              live_tokens=len(st.tokens))
                    self._finish(
                        st.request_id, st.tokens, "error",
                        st.prompt_len,
                        error="KV block allocator exhausted mid-"
                              "growth: raise n_blocks, lower lane "
                              "count, or bound request budgets",
                        born=st.born)
                    self._vacate(lane)
                    break
                blocks.append(bid)
                self._tables_np[lane, len(blocks) - 1] = bid
                changed = True
        if changed:
            self._push_tables()
            self._obs_blocks()

    def _release_lane_storage(self, lane, st) -> None:
        del st
        for bid in self._lane_blocks[lane]:
            self._alloc.free(bid)
        self._lane_blocks[lane] = []
        self._pending_hashes.pop(lane, None)
        self._lane_limit.pop(lane, None)
        self._tables_np[lane, :] = TRASH_BLOCK
        self._push_tables()
        self._obs_blocks()

    # -------------------------------------------------- CoW forking

    def fork(self, lane: int, token: int, key=None):
        """Copy-on-write fork of a live lane into a free lane; returns
        the new lane id, or None under backpressure (no free lane /
        no free block).

        The fork diverges at the source's CURRENT position: its
        transcript is the source's with the LAST token replaced by
        ``token`` (pass ``st.tokens[-1]`` back for an exact replica —
        the speculative checkpoint/rollback shape; pass the runner-up
        token for a beam branch).  Full blocks below the write
        frontier are refcount-shared; only the partially-written tail
        block is copied (O(block) device bytes — vs O(max_len) for a
        monolithic cache fork).  ``key`` replaces the per-request PRNG
        key on sampling engines (a fork replaying its source's key
        and positions would replay its draws).

        The forked lane is a bare-submit-style occupant: poll it with
        ``running()`` and collect with ``drain()``.  Rejected on
        elastic (``lane_tiers=``) engines: a tier resize compacts
        lane ids, so the id this returns could silently dangle.
        """
        if self.lane_tiers is not None:
            raise ValueError(
                "fork() is not available on elastic (lane_tiers=) "
                "paged engines: a tier resize compacts lane ids, so "
                "the lane id fork returns could silently dangle — "
                "use a fixed lanes= engine to fork")
        with self._admission_lock:
            self._check_open()
            st = self._lane_state[lane]
            if st is None:
                raise ValueError(f"lane {lane} is empty")
            if st.chunks is not None:
                raise ValueError(
                    f"lane {lane} is still admitting (fork after its "
                    "prefill chunks land)")
            if st.done:
                raise ValueError(
                    f"lane {lane} already finished; drain it instead")
            token = int(token)
            if not 0 <= token < self.cfg.vocab_size:
                raise ValueError(
                    f"fork token {token} outside vocab "
                    f"[0, {self.cfg.vocab_size})")
            if key is not None and not self._keyed:
                raise ValueError(
                    "fork key= needs a sampling engine (greedy "
                    "engines carry no per-lane keys)")
            free = self.free_lanes()
            if not free:
                self._decline_full()
                return None
            dst = free[0]
            frontier = st.off + len(st.tokens) - 1  # written slots
            j = frontier // self.block
            src_blocks = self._lane_blocks[lane]
            shared = src_blocks[:min(j, len(src_blocks))]
            for bid in shared:
                self._alloc.share(bid)
            new_blocks = list(shared)
            if frontier % self.block and j < len(src_blocks):
                # Divergent tail: both lanes will write into block j's
                # position range — copy it for the fork.
                bid = self._alloc.alloc()
                if bid is None:
                    for b in shared:
                        self._alloc.free(b)
                    self._decline("kv_blocks")
                    return None
                try:
                    self.cache = self._copy_block(
                        self.cache, jnp.int32(src_blocks[j]),
                        jnp.int32(bid))
                except Exception:
                    # The fresh block and the refcount bumps are not
                    # yet reachable from any table row — roll them
                    # back or they leak for the engine's lifetime.
                    self._alloc.free(bid)
                    for b in shared:
                        self._alloc.free(b)
                    raise
                new_blocks.append(bid)
            self._lane_blocks[dst] = new_blocks
            row = self._tables_np[dst]
            row[:] = TRASH_BLOCK
            row[:len(new_blocks)] = new_blocks
            self._push_tables()
            args = (self.cur, self.pos, self.keys, self.temps,
                    self.tps, self.mps, jnp.int32(lane),
                    jnp.int32(dst), jnp.int32(token))
            if key is not None:
                out = self._fork_rows_key(*args, key)
            else:
                out = self._fork_rows(*args)
            (self.cur, self.pos, self.keys, self.temps, self.tps,
             self.mps) = out
            rid = self._next_id
            self._next_id += 1
            self._lane_state[dst] = _Lane(
                request_id=rid, prompt_len=st.prompt_len,
                max_new=st.max_new,
                key=key if key is not None else st.key,
                tokens=st.tokens[:-1] + [token], eos=st.eos,
                deadline=st.deadline, born=self._clock(), off=st.off,
                launched=st.launched)
            self.last_request_id = rid
            obs.count("serving.cow_forks")
            obs.event("serving.fork", src=lane, dst=dst,
                      request_id=rid, shared_blocks=len(shared),
                      copied_blocks=len(new_blocks) - len(shared))
            self._obs_blocks()
            return dst

    # ------------------------------------- disaggregated block transfer

    def _block_template(self):
        """Zero tree shaped like ONE slab block (``[L, 1, block, ...]``
        per leaf) — the adopt program's wire-side operand aval."""
        slab_cfg = dataclasses.replace(self.cfg, max_len=self.block)
        return init_cache(slab_cfg, 1, kv_int8=self.kv_int8)

    def export_blocks(self, tokens) -> BlockShipment:
        """Prefill ``tokens``' full blocks and read them off the slab
        into a host-side :class:`BlockShipment` — the prefill half of
        disaggregated serving (round 17).

        Staging goes through :meth:`pin_prefix` (the ONE share+alloc
        path): resident stems are reused, only the cold remainder
        prefills.  The pin is released before returning — the
        shipment owns host copies, and the blocks stay hash-resident
        locally until the free list recycles them, so back-to-back
        exports of a common stem prefill once.  Raises ``ValueError``
        for spans below one block and ``RuntimeError`` when the
        allocator cannot hold the run (the router's fallback
        signals).
        """
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        pid = self.pin_prefix(tokens)
        try:
            with self._admission_lock:
                blocks = self._stems.blocks_of(pid)
                span = self._stems.length_of(pid)
                hashes = _stem_hashes(tokens[:span], self.block)
                runs = []
                for bid in blocks:
                    blk = self._extract_block(self.cache,
                                              jnp.int32(bid))
                    runs.append(tuple(np.asarray(a) for a in
                                      jax.tree.leaves(blk)))
        finally:
            self.unpin_prefix(pid)
        ship = BlockShipment(block=self.block, hashes=tuple(hashes),
                             blocks=tuple(runs))
        obs.count("serving.disagg.blocks_out", len(ship))
        obs.count("serving.disagg.bytes_out", ship.nbytes)
        obs.event("serving.block_export", blocks=len(ship),
                  bytes=ship.nbytes, span=span)
        return ship

    def import_blocks(self, shipment: BlockShipment) -> dict | None:
        """Adopt a shipped block run by page-table splice and PIN it
        (refcount held through :class:`PinnedStems`, exactly like
        :meth:`pin_prefix`) — the decode half of disaggregated
        serving.

        Blocks whose chain digest is already resident are refcounted
        in place — zero device writes for warm stems (the
        adoption-hit counter the router's transfer-skip leans on);
        cold blocks are allocated, spliced in by the pre-compiled
        adopt program, and hash-registered so later prompts (and
        re-imports) hit them.

        Returns ``{"prefix_id", "blocks", "hits", "bytes"}`` — the
        caller owns the pin and MUST :meth:`unpin_prefix` it when the
        consuming request goes terminal — or ``None`` when the
        allocator cannot hold the run (backpressure, never an
        exception: the router falls back to routing the raw prompt).
        Any failure mid-adopt hands back every reference this import
        took — a torn transfer leaks nothing (the chaos contract).
        """
        with self._admission_lock:
            self._check_open()
            if shipment.block != self.block:
                raise ValueError(
                    f"shipment carries {shipment.block}-token blocks; "
                    f"this slab is paged at {self.block}")
            if not len(shipment):
                raise ValueError("refusing to adopt an empty shipment")
            if shipment.span > self.cfg.max_len - 2:
                raise ValueError(
                    f"shipment spans {shipment.span} tokens; pinned "
                    f"runs must leave room for a tail token and one "
                    f"generated token under max_len={self.cfg.max_len}")
            slab_leaves = jax.tree.leaves(self.cache)
            treedef = jax.tree.structure(self.cache)
            taken: list[int] = []
            hits = 0
            try:
                for h, leaves in zip(shipment.hashes,
                                     shipment.blocks):
                    bid = self._alloc.share_by_hash(h)
                    if bid is not None:
                        # Content already resident: refcount, no
                        # device write.
                        taken.append(bid)
                        hits += 1
                        continue
                    if len(leaves) != len(slab_leaves):
                        raise ValueError(
                            f"shipment blocks carry {len(leaves)} "
                            f"leaves; this slab has "
                            f"{len(slab_leaves)}")
                    for a, s in zip(leaves, slab_leaves):
                        want = (s.shape[0], 1) + tuple(s.shape[2:])
                        if (tuple(a.shape) != want
                                or a.dtype != s.dtype):
                            raise ValueError(
                                f"shipment leaf {a.shape}/{a.dtype} "
                                f"does not match slab block "
                                f"{want}/{s.dtype} (model config or "
                                "kv_int8 mode mismatch)")
                    bid = self._alloc.alloc()
                    if bid is None:
                        for b in taken:
                            self._alloc.free(b)
                        obs.count("serving.disagg.import_declines")
                        return None
                    taken.append(bid)
                    blk = self._place_kv(
                        jax.tree.unflatten(treedef, list(leaves)))
                    self.cache = self._adopt_block(self.cache, blk,
                                                   jnp.int32(bid))
                    self._alloc.register(bid, h)
                pid = self._stems.add(taken, shipment.span)
            except Exception:
                for b in taken:
                    self._alloc.free(b)
                raise
            obs.count("serving.disagg.blocks_in", len(taken))
            obs.count("serving.disagg.adopt_hits", hits)
            obs.count("serving.disagg.bytes_in", shipment.nbytes)
            obs.event("serving.block_import", prefix_id=pid,
                      blocks=len(taken), hits=hits,
                      bytes=shipment.nbytes)
            self._obs_blocks()
            return {"prefix_id": pid, "blocks": len(taken),
                    "hits": hits, "bytes": shipment.nbytes}

    # ------------------------------------------------ pinned prefixes

    def pin_prefix(self, tokens) -> int:
        """Prefill ``tokens``' full blocks into the slab and PIN them
        (refcount held by the registry): the prefix-pool story on the
        one allocator.  Any later prompt starting with those tokens
        hash-hits the blocks through ordinary stem sharing — zero
        prefill work for the pinned span, no id plumbing at submit.
        The prefix length rounds DOWN to a block multiple (the
        partial tail block would be mutable, so it can't be shared);
        returns the ``prefix_id`` for :meth:`unpin_prefix`.  Raises
        ``RuntimeError`` when the allocator cannot hold the run
        (operator-paced — no silent shed)."""
        with self._admission_lock:
            self._check_open()
            tokens = np.asarray(tokens, np.int32).reshape(-1)
            B = self.block
            span = (tokens.size // B) * B
            if span < B:
                raise ValueError(
                    f"a pinned prefix needs at least one full block "
                    f"({B} tokens); got {tokens.size}")
            if span > self.cfg.max_len - 2:
                raise ValueError(
                    f"pinned prefix of {span} tokens must leave room "
                    f"for a tail token and one generated token under "
                    f"max_len={self.cfg.max_len}")
            full = span // B
            staged = self._stage_blocks(tokens, span)
            if staged is None:
                raise RuntimeError(
                    "no free KV blocks to pin the prefix; grow "
                    "n_blocks, or drain/unpin first")
            blocks, shared, hashes, plan = staged
            try:
                if shared < full:
                    row = np.full((self._mb,), TRASH_BLOCK, np.int32)
                    row[:len(blocks)] = blocks
                    row_j = self._put_host(row)
                    # _chunk_rows reads warm = prompt.size - 1 tokens;
                    # the pseudo prompt makes the pinned span exactly
                    # the warm region.
                    pseudo = np.zeros((span + 1,), np.int32)
                    pseudo[:span] = tokens[:span]
                    for start, w in plan:
                        rows = jnp.asarray(
                            self._chunk_rows(pseudo, 0, start, w))
                        if (self._admit_prefill is not None
                                and start == 0 and len(plan) == 1):
                            # Same mode choice as request admission: a
                            # from-scratch single chunk may
                            # prefill-build.
                            self.cache = self._admit_prefill(
                                self.cache, row_j, rows,
                                jnp.int32(span))
                        else:
                            self.cache = self._admit(
                                self.cache, row_j, rows,
                                jnp.int32(start), jnp.int32(span))
                    for k in range(shared, full):
                        self._alloc.register(blocks[k], hashes[k])
                pid = self._stems.add(blocks, span)
            except Exception:
                # A failure after staging (a dispatch fault, a chaos
                # probe) must hand every staged reference back — the
                # pin was never published, so a leak here would shrink
                # the slab forever.
                for b in blocks:
                    self._alloc.free(b)
                raise
            obs.event("serving.pin_prefix", prefix_id=pid,
                      length=span, shared_blocks=shared)
            self._obs_blocks()
            return pid

    def unpin_prefix(self, prefix_id: int) -> None:
        """Release a pinned prefix's block references.  In-flight
        lanes sharing the blocks keep their own references; the
        blocks stay hash-resident until the free list recycles them,
        so recently-unpinned prefixes may still hit."""
        with self._admission_lock:
            for bid in self._stems.pop(prefix_id):
                self._alloc.free(bid)
            self._obs_blocks()

    def residency(self) -> dict:
        """The paged residency digest: the base load/pool fields plus
        the slab geometry and every resident stem hash (hex, JSON-
        safe) — the ground truth a cache-aware router's affinity
        table is built from, matching
        :func:`distkeras_tpu.serving.residency.stem_hexes` digests by
        construction (one chain-hash definition)."""
        out = super().residency()
        out["block"] = self.block
        out["stem_hashes"] = [h.hex()
                              for h in self._alloc.resident_hashes()]
        out["prefix_ids"] = self._stems.ids()
        out["kv_blocks_free"] = self._alloc.stats()["free"]
        return out

    @property
    def pinned(self) -> PinnedStems:
        return self._stems

    @property
    def allocator(self) -> BlockAllocator:
        return self._alloc

    # -------------------------------------------------------- obs

    def _obs_blocks(self) -> None:
        if obs.active() is None:
            return
        st = self._alloc.stats()
        obs.gauge("serving.kv_blocks_used", st["used"])
        obs.gauge("serving.kv_blocks_free", st["free"])
        obs.gauge("serving.kv_shared_blocks", st["shared"])

    # ---------------------------------------------------- analysis

    def traced_for_analysis(self):
        """Trace targets for the IR lint: the paged decode step (page-
        table gather + the shared window body + slab scatter), the
        paged admission program at the smallest bucket, and the
        round-17 disaggregated block-transfer pair — the export read
        (one block off the slab, no donation: the slab keeps serving)
        and the import splice (the decode-side adoption write, shaped
        exactly like a wire payload placement)."""
        from distkeras_tpu.analysis.ir_lint import TraceSpec

        if 1 not in self._steps:
            self._steps[1] = self._make_step(1)
        mode = ("per_request" if self.per_request_sampling
                else "sampled" if self.temperature > 0 else "greedy")
        if self._kv_axis is not None:
            mode += f"_tp{int(self.mesh.shape[self._kv_axis])}"
        rows = jnp.zeros((1, self._buckets[0]), jnp.int32)
        row = self._put_host(np.zeros((self._mb,), np.int32))
        return [
            TraceSpec(
                name=f"pagedbatcher_{mode}/decode_step",
                fn=self._steps[1],
                args=(self.cache, self.tables, self.cur, self.pos,
                      self.keys, self.temps, self.tps, self.mps),
                donate_argnums=(0,)),
            TraceSpec(
                name=f"pagedbatcher_{mode}/admit_b{self._buckets[0]}",
                fn=self._admit,
                args=(self.cache, row, rows, jnp.int32(0),
                      jnp.int32(0)),
                donate_argnums=(0,)),
            TraceSpec(
                name=f"pagedbatcher_{mode}/disagg_extract",
                fn=self._extract_block,
                args=(self.cache, jnp.int32(0))),
            TraceSpec(
                name=f"pagedbatcher_{mode}/disagg_adopt",
                fn=self._adopt_block,
                args=(self.cache,
                      self._place_kv(self._block_template()),
                      jnp.int32(0)),
                donate_argnums=(0,)),
        ]


__all__ = ["PagedBatcher", "BlockAllocator", "TRASH_BLOCK",
           "KV_INT8_PREFILL_LOGIT_TOL"]
