"""Bounded-staleness async parameter-serving plane (docs/async.md).

The genuinely-asynchronous host tier the source paper's DOWNPOUR/AEASGD
family promises (reference: distkeras/parameter_servers.py workers
pushing pickled deltas over TCP): each host trains locally — any
intra-host ADAG/zero/exchange configuration, compiled to one XLA
program over the host mesh — and exchanges PARAMETER DELTAS with a
central plane asynchronously, under a bounded-staleness contract:

* **Staleness bound τ** (SSP): a host may start round ``r`` only while
  ``r - min(fleet rounds) <= tau``.  Past the bound a **hard-sync
  barrier** fires (``async.hard_sync`` event) — but only for a laggard
  that is *slow and alive*.  A laggard whose heartbeat went stale
  (wedged writer, dead host) is **evicted** by the watchdog instead
  (``async.evict``), so a straggler degrades the fleet by at most the
  detection window — never a full stall.  That asymmetry is the whole
  robustness story: sync SGD's step DAG freezes on one dead peer
  (arXiv:1805.03812); here the dead peer merely leaves.
* **Aggregation tree**: cross-host deltas reduce up an explicit
  ``fanout``-ary host-level aggregator tree (the in-network-aggregation
  shape, arXiv:1903.06701) rather than a flat ring, with
  Adasum (:func:`~distkeras_tpu.parallel.exchange.adasum_combine`) as
  the default merge rule — the mean for parallel contributions, the sum
  for orthogonal ones, which is exactly the taming stale deltas need.
* **Int8 error-feedback wire**: cross-host legs ride the exchange
  layer's symmetric int8 codec with a per-host residual carried to the
  next push (same EF contract as ``compress="int8"`` gradients);
  :func:`make_wire_merge` is the compiled spelling of one aggregation
  wave (encode → s8 all-gather → decode → tree combine) that the IR
  census audits, proving the wire carries s8, not f32.
* **Elastic membership**: hosts join mid-training (bootstrap params
  from the plane at the current version) and leave gracefully (final
  delta pushed before deregistration — the "refcounted" path) or
  ungracefully (eviction drops their in-flight deltas — the staleness
  rule path).  Membership transitions bump an
  :class:`~distkeras_tpu.resilience.cluster.EpochStore` generation and
  heartbeats are real ``health.write_beat`` files when a ``coord_dir``
  is given, so the plane rides the PR-5 cluster substrate.
* **Determinism**: every schedule runs under a seeded virtual-time
  clock (:class:`VirtualClock` + :class:`AsyncSchedule`); round
  durations, stalls, joins and leaves are pure functions of the seed,
  so any staleness interleaving — including evictions and joins — is
  replayable bit-for-bit in tests.

Chaos probe sites (resilience/chaos.py): ``cluster.push`` fires BEFORE
a host's delta publishes (a ``fail`` rule there is host-death mid-push:
nothing was enqueued, the delta drops cleanly) and ``cluster.merge``
fires BEFORE the root applies an aggregation wave (a fault leaves the
center params and the pending buffer intact — the merge is atomic and
simply retries on the next push).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from distkeras_tpu import obs
from distkeras_tpu.parallel.exchange import (adasum_combine, int8_decode,
                                              int8_encode)

_MERGE_RULES = ("adasum", "sum")
_COMPRESS = (None, "int8")


@dataclasses.dataclass(frozen=True)
class AsyncConfig:
    """Knobs of the async tier (validated at construction).

    ``tau`` is the staleness bound in rounds; ``beat_window`` the
    heartbeat-staleness window in *virtual* seconds — a parked fleet
    evicts a wedged laggard after at most this long, so choose it
    well under ``tau`` round-lengths to keep the <τ degradation bound.
    """

    tau: int = 4
    merge_rule: str = "adasum"
    compress: str | None = "int8"
    fanout: int = 2
    beat_window: float = 3.0

    def __post_init__(self):
        if self.tau < 1:
            raise ValueError(f"tau must be >= 1, got {self.tau}")
        if self.merge_rule not in _MERGE_RULES:
            raise ValueError(
                f"merge_rule must be one of {_MERGE_RULES}, "
                f"got {self.merge_rule!r}")
        if self.compress not in _COMPRESS:
            raise ValueError(
                f"compress must be one of {_COMPRESS}, "
                f"got {self.compress!r}")
        if self.fanout < 2:
            raise ValueError(f"fanout must be >= 2, got {self.fanout}")
        if self.beat_window <= 0:
            raise ValueError(
                f"beat_window must be > 0, got {self.beat_window}")


class VirtualClock:
    """Monotone virtual time: the one clock every schedule, heartbeat
    and staleness decision reads.  Advancing is the event loop's job;
    nothing in the plane ever reads wall time."""

    def __init__(self, start: float = 0.0):
        self._now = float(start)

    def now(self) -> float:
        return self._now

    def advance_to(self, t: float) -> float:
        if t < self._now:
            raise ValueError(
                f"virtual time moved backwards: {t} < {self._now}")
        self._now = float(t)
        return self._now

    def __call__(self) -> float:  # health.write_beat clock= protocol
        return self._now


class AsyncSchedule:
    """Seeded, fully deterministic per-host round timing + membership
    events.  ``duration(host, rnd)`` is a pure function of
    ``(seed, host, rnd)`` (independent draws via ``SeedSequence``), so
    two runs of the same schedule produce the same interleaving.

    Fault/elasticity spellings (all return ``self`` for chaining):

    * ``stall(host, at_round, extra)`` — that round takes ``extra``
      additional virtual seconds AND the host's heartbeat wedges for
      the duration (the ``stall:cluster.heartbeat`` fault kind in
      virtual time).
    * ``join(host, at_time)`` — a new host joins the plane at ``t``.
    * ``leave(host, after_round)`` — graceful leave once the host
      completes that round (remaining data dropped).
    """

    def __init__(self, seed: int = 0, base: float = 1.0,
                 jitter: float = 0.25):
        if base <= 0:
            raise ValueError(f"base must be > 0, got {base}")
        if not 0 <= jitter < 1:
            raise ValueError(f"jitter must be in [0, 1), got {jitter}")
        self.seed = int(seed)
        self.base = float(base)
        self.jitter = float(jitter)
        self._stalls: dict[tuple[int, int], float] = {}
        self._joins: list[tuple[float, int]] = []
        self._leaves: dict[int, int] = {}

    def duration(self, host: int, rnd: int) -> float:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, int(host), int(rnd)]))
        d = self.base * (1.0 + self.jitter * (2.0 * rng.random() - 1.0))
        return d + self._stalls.get((host, rnd), 0.0)

    def stall(self, host: int, at_round: int,
              extra: float) -> "AsyncSchedule":
        if extra < 0:
            raise ValueError(f"extra must be >= 0, got {extra}")
        self._stalls[(int(host), int(at_round))] = float(extra)
        return self

    def stalled(self, host: int, rnd: int) -> bool:
        return (int(host), int(rnd)) in self._stalls

    def join(self, host: int, at_time: float) -> "AsyncSchedule":
        self._joins.append((float(at_time), int(host)))
        self._joins.sort()
        return self

    def joins(self) -> list[tuple[float, int]]:
        return list(self._joins)

    def leave_after(self, host: int) -> int | None:
        return self._leaves.get(int(host))

    def leave(self, host: int, after_round: int) -> "AsyncSchedule":
        self._leaves[int(host)] = int(after_round)
        return self


# --------------------------------------------------------- merge kernels


def _stack_leaves(trees: list) -> Any:
    """``m`` same-structure pytrees -> one pytree of ``[m, ...]`` leaves."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


@jax.jit
def _combine_adasum(stacked):
    """One aggregator node: ``[m, ...]`` leaves -> merged leaves, per
    leaf by pairwise adaptive summation over the flattened vector."""
    def leaf(x):
        flat = x.reshape((x.shape[0], -1))
        return adasum_combine(flat).reshape(x.shape[1:])

    return jax.tree.map(leaf, stacked)


@jax.jit
def _combine_sum(stacked):
    """One aggregator node under ``merge_rule="sum"``: deltas SUM up
    the tree — the DOWNPOUR commit semantic (each host's delta is
    already scaled by its own learning rate; a mean would shrink the
    effective step as the fleet grows).  Adasum lands between the two:
    the mean for parallel deltas, this sum for orthogonal ones."""
    return jax.tree.map(lambda x: jnp.sum(x, axis=0), stacked)


def combine_group(deltas: list, merge_rule: str):
    """Merge one aggregator group's deltas (``len(deltas) <= fanout``)."""
    if len(deltas) == 1:
        return deltas[0]
    stacked = _stack_leaves(deltas)
    if merge_rule == "adasum":
        return _combine_adasum(stacked)
    return _combine_sum(stacked)


def tree_reduce(deltas: list, fanout: int, merge_rule: str):
    """Reduce ``m`` host deltas up the explicit ``fanout``-ary
    aggregator tree: level 0 merges groups of ``fanout`` hosts, each
    group's result rides up to the next tier, until one delta reaches
    the root.  Deterministic: tree shape depends only on ``m``."""
    while len(deltas) > 1:
        deltas = [combine_group(deltas[i:i + fanout], merge_rule)
                  for i in range(0, len(deltas), fanout)]
    return deltas[0]


@jax.jit
def _encode_ef(delta, residual):
    """Error-feedback int8 encode of a delta pytree: quantize
    ``delta + residual`` per-leaf (one row per leaf), return
    ``(q s8 leaves, scale leaves, decoded leaves, new residual)`` —
    the decoded tree is what crosses the (simulated) wire; the
    quantization error is carried to the NEXT push, same EF contract
    as the gradient codec (docs/lowcomm.md)."""
    def leaf(d, r):
        x = jnp.asarray(d, jnp.float32) + r
        q, scale = int8_encode(x.reshape(1, -1))
        dec = int8_decode(q, scale).reshape(d.shape)
        return q, scale, dec, x - dec

    out = jax.tree.map(leaf, delta, residual)
    unzip = lambda i: jax.tree.map(lambda t: t[i], out,
                                   is_leaf=lambda t: isinstance(t, tuple))
    return unzip(0), unzip(1), unzip(2), unzip(3)


@jax.jit
def delta_of(tv_new, tv_pulled):
    """``tv_new - tv_pulled`` without donating either operand."""
    return jax.tree.map(jnp.subtract, tv_new, tv_pulled)


@jax.jit
def apply_delta(center, delta):
    return jax.tree.map(jnp.add, center, delta)


def copy_tree(tree):
    """A real copy: the trainers donate their state buffers, so the
    center must never alias anything a jitted step consumes."""
    return jax.tree.map(lambda a: jnp.array(a, copy=True), tree)


def wire_cost_bytes(q_tree, scale_tree) -> int:
    """Ring-free accounting of one push's cross-host bytes: the s8
    payload plus its f32 per-row scales."""
    qb = sum(int(np.prod(q.shape)) for q in jax.tree.leaves(q_tree))
    sb = sum(int(np.prod(s.shape)) * 4
             for s in jax.tree.leaves(scale_tree))
    return qb + sb


def make_wire_merge(mesh, config: AsyncConfig) -> Callable:
    """The compiled spelling of ONE aggregation wave for the IR census:
    a shard_map over the mesh ``data`` axis (standing in for the host
    tier — one replica per host), where each replica int8-encodes its
    delta, the s8 payload and f32 scales are all-gathered (the only
    cross-host wire legs, and the census proves the payload dtype is
    s8), every aggregator decodes and tree-combines, and the merged
    delta comes back replicated.

    ``wire_merge(stacked_delta)`` with leaves ``[n_hosts, ...]``
    sharded ``P("data")`` -> merged delta leaves, replicated.
    """
    axis = "data"
    rule = config.merge_rule
    fanout = config.fanout
    compress = config.compress
    n = int(mesh.shape[axis])

    def body(stacked):
        def leaf(x):
            # x: [1, ...] — this replica's delta leaf.
            flat = x.reshape(1, -1).astype(jnp.float32)
            if compress == "int8":
                q, scale = int8_encode(flat)
                gq = jax.lax.all_gather(q, axis, axis=0)        # s8 wire
                gs = jax.lax.all_gather(scale, axis, axis=0)
                stack = int8_decode(gq, gs).reshape(n, -1)
            else:
                stack = jax.lax.all_gather(flat, axis,
                                           axis=0).reshape(n, -1)
            rows = [stack[i] for i in range(n)]
            merged = tree_reduce(rows, fanout, rule)
            return merged.reshape(x.shape[1:])

        return jax.tree.map(leaf, stacked)

    return shard_map(body, mesh=mesh,
                     in_specs=(P(axis),), out_specs=P(),
                     check_vma=False)


# ------------------------------------------------------------- the plane


@dataclasses.dataclass
class HostSlot:
    """Per-member bookkeeping: completed round, pulled center version,
    heartbeat freeze state, and the int8 EF residual."""

    round: int = 0
    version: int = 0
    joined_at: float = 0.0
    frozen_at: float | None = None   # wedged heartbeat since t (None = fresh)
    residual: Any = None
    beats: int = 0


class AsyncPlane:
    """The parameter-serving plane: center params + elastic membership
    + the aggregation tree, all under one virtual clock.

    Invariants the chaos legs assert:

    * ``push`` probes ``cluster.push`` BEFORE anything is enqueued — a
      fault there means the delta never existed (host death mid-push,
      dropped cleanly).
    * an aggregation wave probes ``cluster.merge`` BEFORE the center
      mutates — a fault there leaves center AND the pending buffer
      intact (``version`` does not advance; the wave retries on the
      next push).  No torn merge is representable.
    """

    def __init__(self, center, config: AsyncConfig, clock: VirtualClock,
                 coord_dir: str | None = None):
        self.config = config
        self.clock = clock
        self.center = copy_tree(center)
        self.version = 0
        self.members: dict[int, HostSlot] = {}
        self.pending: list[tuple[int, Any]] = []
        self.pushes = 0
        self.merges = 0
        self.hard_syncs = 0
        self.evicted: list[int] = []
        self.dropped_deltas = 0
        self.wire_bytes = 0
        self.epoch = 0
        self._store = None
        self._hb_dir = None
        if coord_dir is not None:
            import os

            from distkeras_tpu.resilience.cluster import EpochStore

            self._store = EpochStore(coord_dir)
            self._store.request(self.epoch)
            self._hb_dir = os.path.join(coord_dir, "beats")

    # ------------------------------------------------------- membership

    def _bump_epoch(self) -> None:
        """Every membership transition is a cluster-epoch generation —
        the same monotone marker-file contract coordinated restarts use
        (resilience/cluster.py), so an external supervisor can observe
        the async fleet's composition history."""
        self.epoch += 1
        if self._store is not None:
            self._store.request(self.epoch)

    def join(self, host: int):
        """Register ``host`` and bootstrap it: returns
        ``(params, version)`` copied from the center.  The joiner
        registers at the fleet's max round so it cannot trip the
        staleness bound the instant it arrives."""
        if host in self.members:
            raise ValueError(f"host {host} is already a member")
        rnd = max((m.round for m in self.members.values()), default=0)
        self.members[host] = HostSlot(
            round=rnd, version=self.version, joined_at=self.clock.now(),
            residual=jax.tree.map(
                lambda a: jnp.zeros_like(a, jnp.float32), self.center))
        self._bump_epoch()
        self.beat(host)
        obs.event("async.join", host=host, round=rnd,
                  version=self.version, t=self.clock.now())
        obs.gauge("async.members", len(self.members))
        return copy_tree(self.center), self.version

    def leave(self, host: int, final_delta=None) -> None:
        """Graceful deregistration.  A ``final_delta`` is pushed FIRST
        — the leaver's in-flight contribution is refcounted into the
        tree before the slot disappears — so a clean leave never loses
        work; only eviction (the staleness rule) drops deltas."""
        self._require_member(host)
        if final_delta is not None:
            self.push(host, final_delta)
        self._write_beat(host, done=True)
        del self.members[host]
        self._bump_epoch()
        obs.event("async.leave", host=host, t=self.clock.now())
        obs.gauge("async.members", len(self.members))

    def evict(self, host: int, reason: str) -> None:
        """Drop a member and every in-flight delta it owns (the
        bounded-staleness rule's discard path)."""
        self._require_member(host)
        before = len(self.pending)
        self.pending = [(h, d) for h, d in self.pending if h != host]
        self.dropped_deltas += before - len(self.pending)
        del self.members[host]
        self.evicted.append(host)
        self._bump_epoch()
        obs.event("async.evict", host=host, reason=reason,
                  dropped=before - len(self.pending), t=self.clock.now())
        obs.count("async.evictions", 1, reason=reason)
        obs.gauge("async.members", len(self.members))

    def _require_member(self, host: int) -> None:
        if host not in self.members:
            raise KeyError(f"host {host} is not a member "
                           f"(members: {sorted(self.members)})")

    # -------------------------------------------------------- heartbeats

    def _write_beat(self, host: int, done: bool = False) -> None:
        if self._hb_dir is not None:
            from distkeras_tpu.resilience.health import write_beat

            write_beat(self._hb_dir, host, self.epoch,
                       self.members[host].beats, clock=self.clock,
                       done=done)

    def beat(self, host: int) -> None:
        """One virtual-time heartbeat.  A frozen writer (stalled host)
        publishes nothing — that silence is what the watchdog reads."""
        m = self.members[host]
        if m.frozen_at is not None:
            return
        m.beats += 1
        self._write_beat(host)

    def freeze_beats(self, host: int) -> None:
        """The host's heartbeat writer wedges NOW (virtual time): the
        stall fault kind.  Peers see its last beat age out."""
        self._require_member(host)
        self.members[host].frozen_at = self.clock.now()

    def thaw_beats(self, host: int) -> None:
        if host in self.members:
            self.members[host].frozen_at = None
            self.beat(host)

    def stale(self, host: int) -> bool:
        """Heartbeat-driven straggler detection: stale means the writer
        froze more than ``beat_window`` virtual seconds ago.  A healthy
        member's daemon writer beats continuously, so it is never
        stale no matter how slow its rounds are — slow-but-alive gets
        the barrier, wedged-or-dead gets evicted."""
        m = self.members.get(host)
        if m is None:
            return True
        return (m.frozen_at is not None
                and self.clock.now() - m.frozen_at > self.config.beat_window)

    # ------------------------------------------------------ delta plane

    def pull(self, host: int):
        """Fresh center params for ``host`` (a real copy — trainers
        donate their buffers into the jitted step)."""
        self._require_member(host)
        self.members[host].version = self.version
        return copy_tree(self.center), self.version

    def push(self, host: int, delta) -> None:
        """Publish one host's parameter delta into the aggregation
        tree.  The ``cluster.push`` probe fires before anything is
        enqueued; int8 EF encoding happens on the way in (the wire
        leg), and the wave merges immediately — atomically — at the
        root."""
        from distkeras_tpu.resilience import chaos

        self._require_member(host)
        chaos.probe("cluster.push", step=self.pushes + 1)
        self.pushes += 1
        m = self.members[host]
        if self.config.compress == "int8":
            q, scale, decoded, m.residual = _encode_ef(delta, m.residual)
            cost = wire_cost_bytes(q, scale)
        else:
            decoded = jax.tree.map(
                lambda d: jnp.asarray(d, jnp.float32), delta)
            cost = sum(int(np.prod(x.shape)) * 4
                       for x in jax.tree.leaves(decoded))
        self.wire_bytes += cost
        obs.count("async.push", 1, host=host)
        obs.count("async.wire_bytes", cost, host=host)
        self.pending.append((host, decoded))
        self._merge_pending()

    def _merge_pending(self) -> None:
        """One aggregation wave: tree-combine every pending delta and
        apply the result to the center.  Probed, and atomic — a fault
        before the apply leaves center/version/pending untouched."""
        from distkeras_tpu.resilience import chaos

        if not self.pending:
            return
        try:
            chaos.probe("cluster.merge", step=self.merges + 1)
        except chaos.FaultInjected:
            obs.event("async.merge_fault", pending=len(self.pending),
                      t=self.clock.now())
            return  # wave retries at the next push; nothing torn
        merged = tree_reduce([d for _, d in self.pending],
                             self.config.fanout, self.config.merge_rule)
        self.center = apply_delta(self.center, merged)
        self.version += 1
        self.merges += 1
        self.pending = []
        obs.gauge("async.version", self.version)

    def flush(self) -> None:
        """Drain any aggregation wave a ``cluster.merge`` fault
        deferred (the retry path; a no-op when nothing is pending)."""
        self._merge_pending()

    def complete(self, host: int) -> int:
        """Mark one finished local round; returns the new round."""
        self._require_member(host)
        m = self.members[host]
        m.round += 1
        self.beat(host)
        obs.gauge("async.round", m.round, host=host)
        self._lag_gauges()
        return m.round

    # -------------------------------------------------------- staleness

    def min_round(self) -> int:
        return min((m.round for m in self.members.values()), default=0)

    def laggards(self, next_round: int) -> list[int]:
        """Members whose completed round would violate the bound if
        some host started ``next_round``."""
        return sorted(h for h, m in self.members.items()
                      if next_round - m.round > self.config.tau)

    def may_start(self, host: int,
                  next_round: int) -> tuple[bool, list[int]]:
        """The SSP gate: ``host`` may start ``next_round`` iff no peer
        is more than τ rounds behind it.  Blocked starts are the
        hard-sync barrier (counted + evented once per park)."""
        self._require_member(host)
        lag = [h for h in self.laggards(next_round) if h != host]
        if lag:
            self.hard_syncs += 1
            obs.event("async.hard_sync", host=host, round=next_round,
                      laggards=",".join(map(str, lag)),
                      t=self.clock.now())
            return False, lag
        return True, []

    def _lag_gauges(self) -> None:
        if not self.members:
            return
        lo = self.min_round()
        for h, m in self.members.items():
            obs.gauge("async.round_lag", m.round - lo, host=h)
        obs.gauge("async.staleness",
                  max(m.round for m in self.members.values()) - lo)


__all__ = ["AsyncConfig", "AsyncSchedule", "AsyncPlane", "VirtualClock",
           "HostSlot", "tree_reduce", "combine_group", "make_wire_merge",
           "delta_of", "apply_delta", "copy_tree", "wire_cost_bytes"]
