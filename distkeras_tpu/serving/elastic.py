"""Elastic lane tiers: load-driven resizing over pre-compiled programs.

Two mixins: :class:`_ElasticMixin` is the engine-level hysteresis
bookkeeping every :class:`~distkeras_tpu.serving.engine._LaneEngine`
carries (inert unless ``lane_tiers`` is set) — sustained ``enqueue``
overflow steps the lane count up one tier, sustained idle steps it
back down, and a resize compacts occupied lanes through a
pre-compiled gather.  :class:`_ElasticLanesMixin` is
:class:`~distkeras_tpu.serving.lanes.ContinuousBatcher`'s device half:
the dummy-state warmup that compiles EVERY tier's programs (decode
windows, admission buckets — chunked-prefill continuations and
prefix-pool gathers included — and the inter-tier resize gathers) at
construction, so no request ever pays a recompile
(``scripts/check_compile_counts.py``'s ``serving_elastic`` session
asserts the serve phase compiles ZERO and pins the budget).
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import obs


class _ElasticMixin:
    """Host-side tier hysteresis; inert when ``lane_tiers`` is None."""

    def _try_scale_up(self) -> bool:
        """One overflow strike; step the lane tier up once the
        backpressure is *sustained* (``scale_up_after`` consecutive
        overflowing enqueues).  Returns whether a resize happened —
        False means the caller raises QueueFull (non-elastic engine,
        top tier reached, or not sustained yet)."""
        if self.lane_tiers is None:
            return False
        i = self.lane_tiers.index(self.lanes)
        if i + 1 >= len(self.lane_tiers):
            return False
        self._bp_strikes += 1
        if self._bp_strikes < self.scale_up_after:
            return False
        self._resize_to(self.lane_tiers[i + 1])
        return True

    def _maybe_scale_down(self) -> None:
        """Hysteresis mirror of :meth:`_try_scale_up`: after
        ``scale_down_after`` consecutive steps with the queue empty and
        occupancy at or under the next tier down, shrink to it (free
        lanes burn a row of decode compute each step — the whole point
        of stepping back down).  Runs under the admission lock: the
        resize compacts ``_lane_state``, which a concurrent
        ``enqueue`` (the documented thread-safe surface) must never
        observe mid-move."""
        if self.lane_tiers is None:
            return
        with self._admission_lock:
            i = self.lane_tiers.index(self.lanes)
            if i == 0:
                return
            lower = self.lane_tiers[i - 1]
            busy = sum(1 for s in self._lane_state if s is not None)
            if busy <= lower and not self._pending:
                self._idle_strikes += 1
            else:
                self._idle_strikes = 0
                return
            if self._idle_strikes >= self.scale_down_after:
                self._resize_to(lower)

    def _resize_to(self, tier: int) -> None:
        """Move the engine to ``tier`` lanes through the pre-compiled
        resize program: occupied lanes compact into the low indices
        (their device rows gathered, their host records remapped —
        the chunked-admission queue included), new lanes arrive free
        (stale rows — masked until admission overwrites them, the same
        contract as lane reuse).  Strictly host-plus-precompiled work:
        no compile, ever (pinned by ``scripts/check_compile_counts.py``'s
        elastic session)."""
        # The round in flight names lanes by the numbers they have
        # now: read it before they change.
        self._flush_round()
        old = self.lanes
        keep = [i for i, s in enumerate(self._lane_state)
                if s is not None]
        assert len(keep) <= tier, "resize below occupancy"
        idx = keep + [0] * (tier - len(keep))
        # numpy, not jnp.asarray(list): the latter jit-compiles a
        # convert_element_type per target length — a recompile the
        # elastic session's zero-compile assertion would catch.
        self._resize_state(np.asarray(idx, np.int32))
        state: list = [None] * tier
        new_of = {}
        for j, i in enumerate(keep):
            state[j] = self._lane_state[i]
            new_of[i] = j
        self._lane_state = state
        # Parked (chunk-admitting) lanes moved with the compaction;
        # their queue entries follow, order preserved.
        self._admitting = collections.deque(
            new_of[l] for l in self._admitting)
        self.lanes = tier
        self.tier_epoch += 1
        self._bp_strikes = self._idle_strikes = 0
        obs.gauge("serving.lanes_tier", tier)
        obs.count("serving.resizes",
                  direction="up" if tier > old else "down")
        obs.event("serving.resize", from_lanes=old, to_lanes=tier,
                  tier_epoch=self.tier_epoch)

    def _resize_state(self, idx) -> None:  # pragma: no cover
        raise NotImplementedError(
            "this engine does not support lane_tiers")


class _ElasticLanesMixin:
    """ContinuousBatcher's device half of elasticity: per-tier dummy
    states, the construction-time warmup, and the resize gather."""

    def _make_resize(self):
        """Build the jitted inter-tier resize program.  The default
        gathers lanes ``idx[j] -> j`` across the WHOLE device state —
        cache (lane axis 1) plus row metadata (axis 0); jit
        specializes one program per (from, to) tier pair, all warmed
        by :meth:`_compile_tiers`.  Sharded engines re-pin the
        gathered cache with the plan's KV constraint so the output
        placement matches the live slab exactly (placement is part of
        the jit cache key — a drifting layout would surface as a
        serve-phase recompile, which the elastic compile sessions
        assert never happens).  The paged engine overrides this with a
        rows-only gather: its slab is lane-independent."""
        constrain = self._kv_constraint

        def resize(cache, cur, pos, keys, temps, tps, mps, idx):
            cache = jax.tree.map(
                lambda a: jnp.take(a, idx, axis=1), cache)
            if constrain is not None:
                cache = constrain(cache)
            g = lambda a: jnp.take(a, idx, axis=0)
            return (cache, g(cur), g(pos), g(keys), g(temps),
                    g(tps), g(mps))

        # No donation: the gathered output has a different lane
        # count, so nothing could be reused in place anyway (and
        # XLA would warn on every tier pair).
        return jax.jit(resize)

    def _tier_state(self, tier: int):
        """A dummy device state at ``tier`` lanes with EXACTLY the live
        state's avals — the warmup vehicle that populates the jit
        caches every tier will hit.  Returned in step-argument order
        ``(cache, cur, pos, keys, temps, tps, mps)`` — the cache comes
        from the engine's ``_fresh_cache`` layout hook, so the paged
        engine's warmup dummies are block slabs like its live state."""
        cache = self._fresh_cache(tier)
        cur = jnp.zeros((tier,), jnp.int32)
        pos = jnp.zeros((tier,), jnp.int32)
        keys = (jnp.stack([jax.random.key(0)] * tier) if self._keyed
                else jnp.zeros((tier,), jnp.int32))
        if self.per_request_sampling:
            temps = jnp.full((tier,), float(self.temperature),
                             jnp.float32)
            tps = jnp.full((tier,), float(self.top_p or 1.0),
                           jnp.float32)
            mps = jnp.full((tier,), float(self.min_p or 0.0),
                           jnp.float32)
        else:
            temps = tps = mps = jnp.zeros((tier,), jnp.float32)
        # Sharded engines commit rows replicated (lanes.py
        # _place_rows): dummy and live placement must agree or the
        # warm-up misses the live state's jit cache entries.
        cur, pos, keys, temps, tps, mps = self._place_rows(
            cur, pos, keys, temps, tps, mps)
        return cache, cur, pos, keys, temps, tps, mps

    def _warm_tier(self, tier: int) -> None:
        """Compile one tier's worth of programs against dummy state:
        every declared step window, every admission bucket (seeded —
        prefix-pool gather included — and, under chunked prefill, the
        continuation program per bucket), the prefix reseed, and the
        tiny host-scatter programs ``submit`` touches.  Split into the
        three stages below (round 12) so the paged engine can swap
        the step/admission halves — its programs take page tables —
        while the shell and the host-scatter warmers stay shared."""
        self._warm_steps(tier)
        self._warm_admission(tier)
        self._warm_host_writes(tier)

    def _warm_steps(self, tier: int) -> None:
        for n in self._step_windows:
            if n not in self._steps:
                self._steps[n] = self._make_step(n)
        for n in self._step_windows:
            # The step donates its cache: a fresh dummy per window.
            # Hot-swap engines pass the LIVE params (committed arrays
            # — their shardings are part of the jit cache key, so the
            # warm entry is exactly the one swap_params' replacements
            # will hit).
            self._steps[n](*self._pargs(), *self._tier_state(tier))

    def _warm_admission(self, tier: int) -> None:
        pool = self._prefix_pool
        for width in self._buckets:
            rows = jnp.zeros((1, width), jnp.int32)
            cache = self._tier_state(tier)[0]
            if pool is not None:
                self._admit(cache, rows, jnp.int32(0), jnp.int32(0),
                            pool.slab, jnp.int32(-1))
            else:
                self._admit(*self._pargs(), cache, rows, jnp.int32(0),
                            jnp.int32(self._off))
            if self._admit_cont is not None:
                self._admit_cont(*self._pargs(),
                                 self._tier_state(tier)[0], rows,
                                 jnp.int32(0), jnp.int32(0))
        if self._prefix_lane is not None:
            self._reseed(self._tier_state(tier)[0], jnp.int32(0))
        if pool is not None:
            self._reseed_pool(self._tier_state(tier)[0], jnp.int32(0),
                              pool.slab, jnp.int32(0))

    def _warm_host_writes(self, tier: int) -> None:
        # submit()'s host bookkeeping (lane-slot writes) specializes
        # per tier too — tiny scatters, but a compile is a compile.
        # Placed like the live rows (sharded engines commit them
        # replicated), or the live scatter would miss this warm entry.
        ints = self._place_replicated(jnp.zeros((tier,), jnp.int32))
        ints.at[0].set(0)
        if self._keyed:
            self._place_replicated(
                jnp.stack([jax.random.key(0)] * tier)).at[0].set(
                jax.random.key(0))
        if self.per_request_sampling:
            self._place_replicated(
                jnp.zeros((tier,), jnp.float32)).at[0].set(0.0)

    def _compile_tiers(self) -> None:
        """Compile EVERY tier's programs up front, plus the resize
        gathers between adjacent tiers (both directions).  After this,
        the elastic engine's whole lifetime — admissions, decode
        windows, tier moves — runs on warm jit caches; the
        ``serving_elastic`` budget in scripts/compile_budget.json pins
        exactly that."""
        with obs.span("serving.compile_tiers", tiers=self.lane_tiers):
            for tier in self.lane_tiers:
                self._warm_tier(tier)
            for a, b in zip(self.lane_tiers, self.lane_tiers[1:]):
                for frm, to in ((a, b), (b, a)):
                    self._warm_resize(frm, to)

    def _warm_resize(self, frm: int, to: int) -> None:
        """Trace+compile the ``frm -> to`` resize gather against dummy
        state (one jit specialization per tier pair).  Split out of
        :meth:`_compile_tiers` so the paged engine can warm its
        rows-only variant with the same loop."""
        cache, cur, pos, keys, temps, tps, mps = self._tier_state(frm)
        self._resize(cache, cur, pos, keys, temps, tps, mps,
                     jnp.zeros((to,), jnp.int32))

    def _resize_state(self, idx) -> None:
        (self.cache, self.cur, self.pos, self.keys, self.temps,
         self.tps, self.mps) = self._resize(
            self.cache, self.cur, self.pos, self.keys, self.temps,
            self.tps, self.mps, idx)


__all__ = ["_ElasticMixin", "_ElasticLanesMixin"]
