"""Multi-prefix KV pool: N prefilled prefix segments, device-resident.

The single-prefix engines (``prompt_cache=`` — one shared system
prompt compiled into admission) cover exactly one deployment shape.
Real fleets serve MANY prefixes at once: a handful of system prompts,
per-tenant few-shot preambles, tool schemas.  :class:`PrefixPool`
holds up to ``slots`` prefilled prefix segments stacked in ONE device
slab; requests carry ``prefix_id`` at ``submit``/``enqueue`` and the
admission program GATHERS the right segment into the lane — so a
request reusing a pooled prefix runs **zero prefill work for the
prefix tokens** (only its tail's admission chunk executes), and one
compiled admission program serves every prefix.

Bookkeeping is host-side and deliberately boring:

- **refcounts**: a lane occupying a prefix pins it
  (``acquire``/``release`` are called by the engines at admission and
  lane vacation); a pinned entry is never evicted.
- **LRU eviction**: ``put`` on a full pool evicts the
  least-recently-used entry with zero references; if every entry is
  pinned, ``put`` raises instead of corrupting an in-flight lane.
- **ids are never reused**: a stale ``prefix_id`` fails loudly at
  submit instead of silently serving someone else's prefix.

Segments are what :func:`~distkeras_tpu.models.generate.prefill`
returns — a full-``max_len`` batch-1 cache with the prefix slots
filled and the rest zero, exactly the fresh-lane seed admission needs
(``kv_int8`` segments must come from ``prefill(..., kv_int8=True)``,
the same quantization-match contract as ``prompt_cache``).  For
:class:`~distkeras_tpu.serving.SpeculativeBatcher` pools
(``draft_cfg=`` given), a segment is the ``(target_cache,
draft_cache)`` pair — the same prefix prefilled through both models.

The slab write is ONE pre-compiled program (warmed at construction,
slot traced), so populating or rotating prefixes never recompiles —
pinned by ``scripts/check_compile_counts.py``'s ``serving_prefix_pool``
and ``spec_prefix`` sessions.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from distkeras_tpu.models.generate import init_cache
from distkeras_tpu.models.transformer import (TransformerConfig,
                                               reject_extended)
from distkeras_tpu.utils.locks import TracedRLock


@dataclasses.dataclass
class _Entry:
    slot: int
    length: int
    refs: int = 0
    tick: int = 0
    last_token: int | None = None


class PrefixPool:
    """Refcounted, LRU-evicting pool of prefilled prefix segments.

    ``cfg``: the serving model config (segment shape =
    ``init_cache(cfg, 1, kv_int8=kv_int8)``).  ``slots``: device
    capacity — the slab holds ``slots`` segments, ~``slots`` x one
    lane's cache bytes of HBM.  ``draft_cfg``: build a speculative
    pool instead (segments are ``(target, draft)`` cache pairs; no
    ``kv_int8`` — the speculative engines hold bf16 caches).

    Thread-safe: one lock serializes ``put``/``acquire``/``release``
    (engines call acquire/release under their own admission locks, but
    a pool may be shared across engines).

    ``mesh``/``kv_axis`` (round 14): build the pool for a pod-sharded
    engine — the slab commits with the engine's KV sharding (kv-heads
    over ``kv_axis``) so the pooled admission gather stays a sharded
    device gather with zero resharding; the engine validates the
    match at construction.
    """

    def __init__(self, cfg: TransformerConfig, slots: int = 4,
                 kv_int8: bool = False,
                 draft_cfg: TransformerConfig | None = None,
                 mesh=None, kv_axis: str | None = "model"):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        for c in (cfg, draft_cfg):
            if c is not None:
                reject_extended(c, "PrefixPool (pooled prefilled prefixes)")
        if mesh is not None and draft_cfg is not None:
            raise ValueError(
                "sharded pools serve pod-sharded ContinuousBatchers; "
                "SpeculativeBatcher has no plan= mode, so a sharded "
                "speculative pool has no consumer")
        if cfg.attention_window is not None or (
                draft_cfg is not None
                and draft_cfg.attention_window is not None):
            raise ValueError(
                "prefix pools need full-cache configs (no "
                "attention_window): a ring slot has no stable notion "
                "of 'the first P positions' to seed from")
        if draft_cfg is not None and kv_int8:
            raise ValueError(
                "speculative pools hold full-precision caches "
                "(SpeculativeBatcher has no kv_int8 mode)")
        self.cfg = cfg
        self.draft_cfg = draft_cfg
        self.kv_int8 = kv_int8
        self.slots = slots
        if draft_cfg is None:
            seg = init_cache(cfg, 1, kv_int8=kv_int8)
        else:
            seg = (init_cache(cfg, 1), init_cache(draft_cfg, 1))
        self._seg_spec = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), seg)
        self.slab = jax.tree.map(
            lambda a: jnp.zeros((slots,) + a.shape, a.dtype), seg)
        # Pod-sharded placement (round 14): a pool serving a
        # ``plan=``/``mesh=`` engine commits its slab with the SAME
        # kv-heads sharding the engine's cache uses (the shared
        # kv_slab_specs rule — the slab layout just carries a leading
        # [slots] axis), so the pooled admission gather is a sharded
        # device gather with zero resharding.  The engine validates
        # the match at construction.
        self.mesh = mesh
        self.kv_axis = kv_axis if mesh is not None else None
        constrain = None
        if mesh is not None:
            from distkeras_tpu.parallel.rules import kv_slab_shardings

            if self.kv_axis is not None \
                    and cfg.kv_heads % int(mesh.shape[self.kv_axis]):
                raise ValueError(
                    f"kv_heads={cfg.kv_heads} is not divisible by "
                    f"mesh axis {self.kv_axis!r} "
                    f"(size {int(mesh.shape[self.kv_axis])})")
            slab_sh = kv_slab_shardings(mesh, self.slab, self.kv_axis)
            self.slab = jax.device_put(self.slab, slab_sh)

            def constrain(slab):
                return jax.lax.with_sharding_constraint(
                    slab, kv_slab_shardings(mesh, slab, self.kv_axis))

        def put(slab, seg, slot):
            out = jax.tree.map(
                lambda s, g: jax.lax.dynamic_update_slice_in_dim(
                    s, g.astype(s.dtype)[None], slot, axis=0), slab, seg)
            return constrain(out) if constrain is not None else out

        # Slot is traced: ONE compiled write program for the pool's
        # lifetime, warmed here so put() never compiles at serve time.
        # NOT donated: an engine admitting on another thread may hold
        # the previous slab buffer for an in-flight gather — put() is
        # rare (operator-paced), so the copy is the safe trade.
        self._put = jax.jit(put)
        self.slab = self._put(self.slab, seg, jnp.int32(0))

        self._entries: dict[int, _Entry] = {}
        self._next_id = 0
        self._tick = 0
        # Leaf lock: engines acquire it UNDER their admission lock
        # (_pin_prefix/_vacate); nothing is acquired under this one.
        self._lock = TracedRLock("serving.prefix_pool")

    # -------------------------------------------------------- mutation

    def put(self, segment, length: int, last_token: int | None = None
            ) -> int:
        """Insert a prefilled segment; returns its ``prefix_id``.

        ``segment``: the ``prefill(prefix[None], ...)`` cache (or the
        ``(target, draft)`` pair for speculative pools) — structure,
        shapes, and dtypes must match the pool's spec exactly.
        ``length``: the prefix token count the segment holds.
        ``last_token``: the prefix's final token — optional metadata a
        :class:`SpeculativeBatcher` needs to admit a **1-token** prompt
        against this prefix (its draft chunk rewrites the position
        before the prompt).

        A full pool evicts the least-recently-used entry with zero
        references; if every entry is referenced by a lane, raises
        ``RuntimeError`` (shed the put or grow ``slots``).
        """
        if length < 1:
            raise ValueError(f"prefix length must be >= 1, got {length}")
        if length >= self.cfg.max_len:
            raise ValueError(
                f"prefix length {length} must leave room under "
                f"max_len={self.cfg.max_len}")
        spec = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                           jnp.asarray(a).dtype), segment)
        if (jax.tree.structure(spec) != jax.tree.structure(self._seg_spec)
                or jax.tree.leaves(spec) != jax.tree.leaves(
                    self._seg_spec)):
            raise ValueError(
                f"segment does not match the pool's spec "
                f"{self._seg_spec} (build it with prefill() on the "
                "pool's config, kv_int8 matching)")
        with self._lock:
            used = {e.slot for e in self._entries.values()}
            free = [s for s in range(self.slots) if s not in used]
            if free:
                slot = free[0]
            else:
                victims = [(e.tick, pid) for pid, e in
                           self._entries.items() if e.refs == 0]
                if not victims:
                    raise RuntimeError(
                        f"prefix pool full: all {self.slots} slots are "
                        "referenced by live lanes; wait for requests "
                        "to finish or grow slots")
                _, victim = min(victims)
                slot = self._entries.pop(victim).slot
            self.slab = self._put(self.slab, segment, jnp.int32(slot))
            pid = self._next_id
            self._next_id += 1
            self._tick += 1
            self._entries[pid] = _Entry(slot=slot, length=int(length),
                                        tick=self._tick,
                                        last_token=last_token)
            return pid

    def acquire(self, prefix_id: int) -> _Entry:
        """Pin the entry (a lane is about to decode against it) and
        mark it recently used; returns the entry.  Engines call this
        under their admission lock; callers use ``submit(prefix_id=)``
        instead."""
        with self._lock:
            e = self._entry(prefix_id)
            e.refs += 1
            self._tick += 1
            e.tick = self._tick
            return e

    def release(self, prefix_id: int) -> None:
        """Unpin (the referencing lane was vacated)."""
        with self._lock:
            e = self._entries.get(prefix_id)
            if e is not None and e.refs > 0:
                e.refs -= 1

    # ------------------------------------------------------ inspection

    def _entry(self, prefix_id: int) -> _Entry:
        e = self._entries.get(prefix_id)
        if e is None:
            raise KeyError(
                f"unknown prefix_id {prefix_id} (evicted or never "
                "inserted; ids are never reused)")
        return e

    def length_of(self, prefix_id: int) -> int:
        return self._entry(prefix_id).length

    def slot_of(self, prefix_id: int) -> int:
        return self._entry(prefix_id).slot

    def last_token_of(self, prefix_id: int) -> int | None:
        return self._entry(prefix_id).last_token

    def refs_of(self, prefix_id: int) -> int:
        return self._entry(prefix_id).refs

    def ids(self) -> list[int]:
        return sorted(self._entries)

    def __contains__(self, prefix_id: int) -> bool:
        return prefix_id in self._entries

    def __len__(self) -> int:
        return len(self._entries)


@dataclasses.dataclass
class _Stem:
    blocks: tuple          # block ids pinned for this prefix, in order
    length: int            # prefix token count (a multiple of block)


class PinnedStems:
    """Host-side registry of PINNED block runs on a paged KV slab —
    the :class:`PrefixPool` story re-expressed in the paged engine's
    one-allocator world (round 12).

    Where the pool holds prefix segments in its OWN device slab and
    requests name them by ``prefix_id``, a pinned stem is just a run
    of ordinary cache blocks in the engine's slab whose refcounts this
    registry holds up (so the allocator can never recycle them), each
    block hash-registered like any admission-prefilled block.
    Requests need no id at all: a prompt that starts with the pinned
    tokens hash-hits the blocks through normal stem sharing — one
    mechanism serves "registered system prompt" and "two requests
    happened to share a stem" alike.

    Pure bookkeeping: the engine
    (:meth:`~distkeras_tpu.serving.paged.PagedBatcher.pin_prefix`)
    prefills the blocks and takes the references; this class only
    records which blocks each pin holds so ``unpin`` releases exactly
    them.  Engines call it under their admission lock; the leaf lock
    keeps a shared registry safe anyway (same posture as the pool).
    """

    def __init__(self):
        self._entries: dict[int, _Stem] = {}
        self._next_id = 0
        self._lock = TracedRLock("serving.pinned_stems")

    def add(self, blocks, length: int) -> int:
        with self._lock:
            pid = self._next_id
            self._next_id += 1
            self._entries[pid] = _Stem(tuple(blocks), int(length))
            return pid

    def pop(self, prefix_id: int) -> tuple:
        """Remove the pin and return its block run (the caller
        releases the references)."""
        with self._lock:
            e = self._entries.pop(prefix_id, None)
            if e is None:
                raise KeyError(
                    f"unknown pinned prefix {prefix_id} (unpinned "
                    "already or never pinned; ids are never reused)")
            return e.blocks

    def length_of(self, prefix_id: int) -> int:
        return self._entry(prefix_id).length

    def blocks_of(self, prefix_id: int) -> tuple:
        return self._entry(prefix_id).blocks

    def _entry(self, prefix_id: int) -> _Stem:
        e = self._entries.get(prefix_id)
        if e is None:
            raise KeyError(f"unknown pinned prefix {prefix_id}")
        return e

    def ids(self) -> list[int]:
        return sorted(self._entries)

    def __contains__(self, prefix_id: int) -> bool:
        return prefix_id in self._entries

    def __len__(self) -> int:
        return len(self._entries)


__all__ = ["PrefixPool", "PinnedStems"]
