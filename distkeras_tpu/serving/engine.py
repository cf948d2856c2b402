"""Shared lane machinery for the serving engines.

``_LaneEngine`` is the host-side core both engines build on: the lane
table (free/running/drain), the per-step emission loop, the chunked-
prefill scheduler, and — via the mixins it composes — admission
control (:mod:`distkeras_tpu.serving.admission`) and elastic lane
tiers (:mod:`distkeras_tpu.serving.elastic`).  The compiled-program
factories for single-lane admission live here too, shared by
:class:`~distkeras_tpu.serving.lanes.ContinuousBatcher` and
:class:`~distkeras_tpu.serving.speculative.SpeculativeBatcher`.

Everything in this module is host bookkeeping or a jit factory; the
decode-step programs themselves are each engine's own.
"""

from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import obs
from distkeras_tpu.models.generate import (_decode_chunk,
                                           chunk_attends_prefix,
                                           decode_attends_prefix,
                                           decode_read_unit,
                                           latent_chunk_expands,
                                           latent_decode_bounded,
                                           latent_read_unit)
from distkeras_tpu.serving.admission import _AdmissionMixin
from distkeras_tpu.serving.elastic import _ElasticMixin


@dataclasses.dataclass
class _Lane:
    request_id: int
    prompt_len: int
    max_new: int
    key: object          # per-request PRNG key (None for greedy)
    tokens: list         # host-side transcript, prompt included
    done: bool = False
    eos: object = None   # per-request eos token (engine default)
    deadline: float | None = None  # absolute clock() time; None = none
    managed: bool = False  # admitted via enqueue(): auto-collected
    born: float | None = None  # clock() at admission (obs latency)
    # Chunked prefill (round-10): remaining (start, rows) admission
    # chunks; non-None means the lane is still ADMITTING — parked out
    # of the emission loop until the last chunk lands.
    chunks: list | None = None
    # Cache positions the admission programs run so far have written
    # prompt tokens up to (exclusive): what a later chunk re-writes
    # below it (a backed-up tail) is not new (``positions`` of the
    # admission spans).
    filled: int = 0
    # Shared-prefix bookkeeping: the request's prefix length (0 =
    # none) and its PrefixPool id (refcount released at vacation).
    off: int = 0
    prefix_id: int | None = None
    # Engine-clock time of the lane's previous emission (TTFT/TPOT
    # telemetry; None until the first token lands).
    last_emit: float | None = None
    # Decode steps DISPATCHED for this request, read or not: with one
    # round in flight the transcript lags the device by that round, so
    # what asks where the lane stands on the device before a dispatch
    # (its budget, its written slots) asks this and not ``tokens``.
    launched: int = 0


def _program_name(jitted) -> str:
    """What the device trace calls a launch of ``jitted``: an "XLA
    Modules" event is named ``jit_<function>(<id>)`` after the function
    ``jax.jit`` wrapped.  The dispatching spans' ``program`` field, so
    that a reader finds a round's device work by what the engine says
    it launched and not by a name typed in beside it (pinned against
    the lowered module's name by tests/test_round_tracing.py)."""
    return "jit_" + jitted.__name__


def _make_lane_admit(model_params, model_cfg, prefix_lane=None,
                     pooled: bool = False, seed: bool = True,
                     constrain=None, take_params: bool = False,
                     in_place: bool = False):
    """ONE-lane admission program factory shared by both engines:
    prefill ``rows`` (bucket-padded) into a single lane's cache slice
    at traced start position ``off``, seeded from the engine's static
    ``prefix_lane``, from a :class:`PrefixPool` slab gather
    (``pooled=True`` — the program takes ``(slab, slot)``; ``slot < 0``
    means "no prefix", seeding zeros), or from zeros — a fresh
    occupant must never see the previous request's K/V beyond its own
    positions.  ``seed=False`` builds the CONTINUATION program for
    chunked prefill: the chunk lands on the lane's existing cache
    (earlier chunks) untouched.

    ``off`` is traced, so one program per bucket-padded ``rows`` shape
    serves every prefix length and every chunk offset.

    ``constrain``: sharding-constraint hook (pod-sharded engines pass
    the KV-slab constraint so GSPMD pins the cache layout inside the
    compiled program instead of inferring it per call).

    ``in_place=True`` (an engine with no prefix to seed and a state
    ``generate.base_body_only`` does not hold back): the chunk is
    written into lane ``lane`` of the slab IN PLACE — no lane cut out,
    none put back, nothing seeded (a previous occupant's slots are
    masked until overwritten) — so ``seed`` changes nothing and the
    engine uses ONE program for first and continuation chunks.

    ``take_params=True`` builds the hot-swap spelling (round 20): the
    program takes the param tree as its FIRST argument instead of
    closing over it, so a live weight push is a plain argument change
    on a warm jit cache — same avals + same committed shardings = the
    exact cache entry, zero recompiles (the ``serving_weight_push``
    compile session pins it).  The cache is still the donated buffer
    (argnums shifts to 1); params are never donated — version N must
    survive the swap for rollback.
    """
    def _admit(params, cache, rows, lane, off, *pool):
        if in_place:
            # ``pool`` is then ``(n_real,)`` or empty: how many of the
            # rows are real, where the program writes ring planes.
            return _decode_chunk(
                params, cache, rows,
                jnp.reshape(off, (1,)).astype(jnp.int32), model_cfg,
                uniform_pos=True, lane=lane,
                n_real=pool[0] if pool else None)[1]
        if constrain is not None:
            cache = constrain(cache)
        # "kv_slab": the lane cut out of the slab here and put back
        # below is the same copying the model's scope of that name
        # holds (models/transformer.py SCOPES).
        with jax.named_scope("kv_slab"):
            lane_cache = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, lane, 1,
                                                       axis=1),
                cache)
        if seed:
            if pooled:
                slab, slot = pool
                # Gather the segment; slot < 0 selects the zero seed
                # (the gather still runs — admission is off the decode
                # hot path and a branch would compile both sides
                # anyway).
                seg = jax.tree.map(
                    lambda a: jnp.take(a, jnp.maximum(slot, 0), axis=0),
                    slab)
                lane_cache = jax.tree.map(
                    lambda z, pre: jnp.where(slot >= 0,
                                             pre.astype(z.dtype),
                                             jnp.zeros_like(z)),
                    lane_cache, seg)
            elif prefix_lane is not None:
                # prefill() returns a full-max_len cache with the
                # prefix slots filled and the rest zero — exactly the
                # fresh-lane seed we need.
                lane_cache = jax.tree.map(
                    lambda z, pre: pre.astype(z.dtype),
                    lane_cache, prefix_lane)
            else:
                lane_cache = jax.tree.map(jnp.zeros_like, lane_cache)
        _, lane_cache = _decode_chunk(
            params, lane_cache, rows,
            jnp.reshape(off, (1,)).astype(jnp.int32), model_cfg,
            uniform_pos=True)
        with jax.named_scope("kv_slab"):
            out = jax.tree.map(
                lambda a, u: jax.lax.dynamic_update_slice_in_dim(
                    a, u, lane, axis=1), cache, lane_cache)
        return constrain(out) if constrain is not None else out

    if take_params:
        return jax.jit(_admit, donate_argnums=1)

    def admit(cache, rows, lane, off, *pool):
        return _admit(model_params, cache, rows, lane, off, *pool)
    return jax.jit(admit, donate_argnums=0)


def _make_lane_reseed(prefix_lane=None, pooled: bool = False,
                      constrain=None):
    """Prefix copy into one lane WITHOUT an admission chunk (1-token
    prompts skip the chunk but still need the prefix K/V)."""
    def reseed(cache, lane, *pool):
        if pooled:
            slab, slot = pool
            pre = jax.tree.map(lambda a: jnp.take(a, slot, axis=0), slab)
        else:
            pre = prefix_lane
        out = jax.tree.map(
            lambda a, p: jax.lax.dynamic_update_slice_in_dim(
                a, p.astype(a.dtype), lane, axis=1), cache, pre)
        return constrain(out) if constrain is not None else out
    return jax.jit(reseed, donate_argnums=0)


class _LaneEngine(_AdmissionMixin, _ElasticMixin):
    """Host-side lane machinery shared by the serving engines: the
    lane table, free/running/drain, the per-step emission loop (append
    to the transcript, stop at budget or the lane's eos), and the
    chunked-prefill scheduler.

    Also composes the admission-control layer (resilience subsystem —
    deadlines/TTLs, the bounded FIFO queue with :class:`QueueFull`
    backpressure, structured :class:`RequestResult` reporting, the
    drain-then-shutdown lifecycle) and the elastic-tier bookkeeping.
    All of it is host bookkeeping — the compiled decode programs and
    their exact-parity contract are untouched (an evicted lane just
    stops being read; its rows keep burning compute until admission
    reseeds them, same as any done lane)."""

    # Engines without a pool leave this None; ContinuousBatcher /
    # SpeculativeBatcher set it from their ``prefix_pool=`` argument.
    _prefix_pool = None

    # Pod-sharded serving (round 14): ``mesh``/``_kv_axis`` are set by
    # ContinuousBatcher(plan=..., mesh=...); every other engine runs
    # single-placement and these defaults keep the helpers no-ops.
    mesh = None
    plan = None
    _kv_axis = None

    # Live weight push (round 20): engines built with
    # ``hot_swap=True`` compile their decode/admission programs to
    # take the param tree as an ARGUMENT (see ``_make_lane_admit``'s
    # ``take_params``), so :meth:`swap_params` is a warm-cache
    # argument change.  ``param_version`` is 0 until the first swap —
    # every engine carries it (the router's fleet snapshot reads it
    # unconditionally).
    _hot_swap = False
    param_version = 0

    # Admission programs dispatched since the last decode dispatch —
    # first chunks (``_submit_locked``) and continuation chunks
    # (``_run_pending_chunk``), whoever asked for them; ``step()``
    # reports it as the round's ``chunks`` and zeroes it there.
    _admit_programs = 0

    # The decode round dispatched and not yet read: ``(tokens on the
    # device, [(lane, _Lane), ...] decoding at the dispatch, its
    # ``seq``)``, or None.  ``ContinuousBatcher.step()`` reads it in
    # the call AFTER the one that dispatched it.
    _inflight = None

    # Decode rounds dispatched so far: ``serving.step``'s ``seq``, and
    # ``serving.collect``'s for the round it reads.  Counted with or
    # without a session.
    _dispatch_seq = 0

    # ``wait_ms`` of the ``serving.collect`` spans since the round's
    # span opened (``serving.round``'s ``host_ms`` is its time less
    # this); kept only while a trace is written.
    _waited_ms = 0.0

    def _attended(self, cache, start: int, width: int) -> int:
        """Cache positions the attention of an admission program reads
        — the ``attended`` field of ``serving.admit`` and
        ``serving.admit_chunk``: ``start + width`` where the program
        of that width was compiled with the bounded path
        (``chunk_attends_prefix``, the question ``_decode_chunk``
        itself asks), ``max_len`` where it keeps the dense body.  Host
        integers known at dispatch; no device read."""
        cfg = self.cfg
        if not (cfg.kv_planes or cfg.kv_ring_planes or cfg.latent_planes):
            return 0        # states only: no cache position is read
        sharded = self.mesh is not None and self.mesh.size > 1
        bounded = (latent_chunk_expands if cfg.latent_planes
                   else chunk_attends_prefix)
        if bounded(cfg, width, cache, sharded=sharded):
            return start + width
        return cfg.max_len

    def _step_attended(self, n: int) -> int:
        """Cache slots the decode program's attention reads in a round
        of ``n`` steps — ``serving.step``'s ``attended``, the decode
        step's share of the question :meth:`_attended` answers for an
        admission.  On the per-lane bounded path
        (``decode_attends_prefix``) a decoding lane reads its position
        rounded up to the kernel's smallest copy; a free, done or admitting
        lane is counted as parked at ``max_len - 1``, its whole row (a
        done lane gets there a step at a time: an upper bound).  On
        the dense path every lane reads ``max_len`` slots.  Latent
        planes (``ops.latent.mla_decode_attention``): a decoding lane's
        position rounded up to that kernel's smallest copy, and NOTHING
        for a lane that does not decode (the step's ``live`` mask).  Host
        integers from the lane table (the steps dispatched so far, so
        an unread round counts); no device read."""
        cfg, cap = self.cfg, self.cfg.max_len
        if not (cfg.kv_planes or cfg.kv_ring_planes or cfg.latent_planes):
            return 0
        sharded = self.mesh is not None and self.mesh.size > 1
        # Latent planes: a lane that does not decode reads nothing (the
        # step's ``live`` mask), on the kernel's path and its twin's.
        latent = bool(cfg.latent_planes)
        if latent:
            unit = (latent_read_unit(cfg, self.cache)
                    if latent_decode_bounded(cfg, self.cache, sharded)
                    else cap)
        elif not decode_attends_prefix(cfg, 1, self.cache, sharded=sharded):
            return n * len(self._lane_state) * cap
        else:
            unit = decode_read_unit(cfg, 1, self.cache)
        total = 0
        for st in self._lane_state:
            if st is None or st.done or st.chunks is not None:
                total += 0 if latent else n * cap
                continue
            pos = st.off + st.prompt_len - 1 + st.launched
            total += sum(min(-(-(pos + j) // unit) * unit, cap)
                         for j in range(n))
        return total

    def _pargs(self) -> tuple:
        """The params-argument prefix of every compiled-program call:
        ``(params,)`` on a hot-swap engine, ``()`` otherwise — ONE
        spelling at every dispatch/warm-up site, so the two engine
        modes cannot drift."""
        return (self.params,) if self._hot_swap else ()

    def swap_params(self, new_params, version: int,
                    allow_downgrade: bool = False) -> int:
        """Replace the engine's weights BETWEEN steps (round 20): the
        new tree is placed with the live params' exact shardings, so
        every warm program is a jit cache hit — zero recompiles (the
        ``serving_weight_push`` session pins it).  In-flight requests
        continue mid-stream on the new weights over their existing
        K/V (the documented mixed-cache contract: tokens emitted
        under version N are bit-deterministic functions of version N).

        ``version`` must be strictly greater than ``param_version``
        unless ``allow_downgrade=True`` — the canary controller's
        rollback is the one legitimate downgrade.  Geometry is
        validated leaf-for-leaf; a mismatched tree raises and the
        engine keeps serving its current version.  Returns the new
        ``param_version``."""
        if not self._hot_swap:
            raise ValueError(
                "engine was built without hot_swap=True: its programs "
                "closed over the weights at compile time, so a swap "
                "would recompile everything — rebuild with "
                "hot_swap=True for live weight push")
        version = int(version)
        with self._admission_lock:
            if version <= self.param_version and not allow_downgrade:
                raise ValueError(
                    f"swap_params(version={version}) ≤ live version "
                    f"{self.param_version}: versions are monotone "
                    "(rollback passes allow_downgrade=True)")
            old_leaves, old_def = jax.tree_util.tree_flatten(
                self.params)
            new_leaves, new_def = jax.tree_util.tree_flatten(
                new_params)
            if old_def != new_def:
                raise ValueError(
                    f"swap_params: param tree structure changed "
                    f"({new_def} vs live {old_def}) — a push must "
                    "carry the exact geometry the engine compiled "
                    "for")
            for i, (o, nw) in enumerate(zip(old_leaves, new_leaves)):
                if (tuple(np.shape(nw)) != tuple(o.shape)
                        or jnp.asarray(nw).dtype != o.dtype):
                    raise ValueError(
                        f"swap_params: leaf {i} is "
                        f"[{np.shape(nw)} {jnp.asarray(nw).dtype}], "
                        f"engine compiled for [{tuple(o.shape)} "
                        f"{o.dtype}]")
            # Placement must REPRODUCE the live tree's exactly — avals
            # plus committed-ness are the jit cache key, so the swap
            # is invisible to the compiler.  Unsharded engines placed
            # via asarray (uncommitted, like every other engine; a
            # committed replacement would re-key every warm program);
            # pod-sharded engines re-commit to the live shardings.
            if self.mesh is None:
                self.params = jax.tree.map(jnp.asarray, new_params)
            else:
                self.params = jax.device_put(
                    new_params,
                    jax.tree.map(lambda l: l.sharding, self.params))
            old = self.param_version
            self.param_version = version
            obs.count("serving.param_swaps")
            obs.event("serving.param_swap", version=version,
                      from_version=old, engine=type(self).__name__)
            return version

    # ----------------------------------------- sharded-placement hooks

    def _place_replicated(self, x):
        """Commit a host/device array REPLICATED over the serving mesh
        (no-op unsharded).  Row metadata and page tables go through
        here: placement is part of the jit cache key for committed
        arrays, so warm-up dummies and live state must agree or the
        serve phase pays a recompile."""
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(x, NamedSharding(self.mesh,
                                               PartitionSpec()))

    def _put_host(self, arr):
        """Host numpy -> device array: plain ``device_put`` unsharded,
        replicated over the serving mesh when sharded (page tables and
        table rows ride this — their placement must be identical
        between warm-up and live pushes)."""
        if self.mesh is None:
            return jax.device_put(arr)
        return self._place_replicated(arr)

    def _kv_shardings(self, tree):
        """NamedShardings placing a KV cache/slab tree under the
        engine's plan: kv-heads dimension over the derived axis,
        everything else replicated (``parallel/rules.py``)."""
        from distkeras_tpu.parallel.rules import kv_slab_shardings

        return kv_slab_shardings(self.mesh, tree, self._kv_axis)

    def _place_kv(self, tree):
        """Commit a KV cache/slab with the plan-derived sharding
        (no-op unsharded)."""
        if self.mesh is None:
            return tree
        return jax.device_put(tree, self._kv_shardings(tree))

    def _constrain_kv(self, tree):
        """``with_sharding_constraint`` pinning the KV layout inside a
        compiled program, or None when the engine is unsharded — the
        program factories pass this straight to their ``constrain=``
        hooks, so GSPMD places the per-token collectives against a
        DECLARED slab layout instead of one inferred per call."""
        return jax.lax.with_sharding_constraint(
            tree, self._kv_shardings(tree))

    @property
    def _kv_constraint(self):
        return self._constrain_kv if self.mesh is not None else None

    def memory_footprint(self) -> dict:
        """Param and KV bytes, total and per device (max over
        addressable devices) — read from the LIVE arrays' addressable
        shards, the same ground-truth accounting ``zero=3`` uses for
        its per-device claim.  Replicated leaves count fully on every
        device; sharded leaves count 1/n — so the per-device figures
        ARE the claim ``plan=`` makes (bench rows and
        tests/test_serving_sharded.py assert from here)."""
        def account(tree):
            total, per_dev = 0, {}
            for leaf in jax.tree.leaves(tree):
                total += leaf.nbytes
                for sh in leaf.addressable_shards:
                    key = repr(sh.device)
                    per_dev[key] = per_dev.get(key, 0) \
                        + sh.data.nbytes
            return total, max(per_dev.values())

        p_total, p_dev = account(self.params)
        kv_total, kv_dev = account(self.cache)
        return {"param_bytes": p_total,
                "param_bytes_per_device": p_dev,
                "kv_bytes": kv_total,
                "kv_bytes_per_device": kv_dev}

    def free_lanes(self):
        return [i for i, s in enumerate(self._lane_state) if s is None]

    def running(self):
        return [i for i, s in enumerate(self._lane_state)
                if s is not None and not s.done]

    def drain(self, lane):
        """Return the finished lane's [prompt + generation] tokens and
        free the lane; raises if the lane is still running."""
        st = self._lane_state[lane]
        if st is None:
            raise ValueError(f"lane {lane} is empty")
        if not st.done:
            raise ValueError(f"lane {lane} is still decoding")
        self._vacate(lane)
        self._obs_request_done("ok", st.born, rid=st.request_id)
        return np.asarray(st.tokens, np.int32)

    def _vacate(self, lane) -> None:
        """THE one lane-release path (drain, reap, eviction, shutdown
        cancellation): frees the lane slot, drops it from the chunked-
        admission queue, releases its prefix-pool pin, and hands the
        lane's storage back through :meth:`_release_lane_storage`."""
        st = self._lane_state[lane]
        self._lane_state[lane] = None
        if st is None:
            return
        if st.chunks is not None:
            try:
                self._admitting.remove(lane)
            except ValueError:  # pragma: no cover — defensive
                pass
        if st.prefix_id is not None and self._prefix_pool is not None:
            self._prefix_pool.release(st.prefix_id)
        self._release_lane_storage(lane, st)

    def _release_lane_storage(self, lane, st) -> None:
        """Storage-layout hook of :meth:`_vacate`: monolithic engines
        own a fixed cache row per lane (nothing to release); the paged
        engine drops the lane's block references here — the ONE place,
        so no eviction path can leak a block."""

    def residency(self) -> dict:
        """The engine's residency digest (round 13): what a cache-
        aware router needs to route on — resident prefix-pool ids,
        resident paged stem hashes (the paged engine overrides to
        fill them), and the live load signals.  Ground truth, cheap
        (host counters + id lists, no device work), JSON-safe; served
        live by the ``/residency`` telemetry endpoint and consumed by
        :class:`~distkeras_tpu.serving.router.Router`.

        Mesh-agnostic by construction: the digests are host-side chain
        hashes of token content (serving/residency.py), so a
        pod-SHARDED engine publishes exactly the digests its solo twin
        would — to the router, one sharded engine is ONE replica
        handle whose mesh is an implementation detail
        (``model_shards`` is surfaced for operators only, never
        scored)."""
        with self._admission_lock:
            return {
                "engine": type(self).__name__,
                "lanes": self.lanes,
                "model_shards": (int(self.mesh.shape[self._kv_axis])
                                 if self._kv_axis is not None else 1),
                "lanes_busy": len(self.running()),
                "queue_depth": len(self._pending),
                "block": None,
                "prefix_ids": (self._prefix_pool.ids()
                               if self._prefix_pool is not None
                               else []),
                "stem_hashes": [],
                "param_version": int(self.param_version),
            }

    def _validate_request_args(self, prompt, max_new_tokens: int):
        """The prompt/budget checks every engine's submit() runs —
        one definition (ContinuousBatcher and SpeculativeBatcher must
        not drift); returns the canonicalized 1-D int32 prompt."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        return prompt

    def _emit(self, lane_tokens, lanes=None):
        """Feed each live lane's new tokens (``lane_tokens(lane)``)
        through the transcript/budget/eos bookkeeping; returns the
        ``{lane: [emitted...]}`` step result.  ``lanes``: the
        ``(lane, _Lane)`` pairs the tokens were decoded FOR (a round
        read after its dispatch names them; default: the lane table as
        it stands) — a lane that has since been vacated, or vacated and
        given to another request, gets nothing of the row.  The ONE
        site that counts emitted tokens (``serving.tokens``) — every step path
        funnels through here, so the throughput metric is
        structurally complete, and so are the per-request latency
        signals it derives: ``serving.ttft_s`` (born -> first token,
        queue wait included for managed requests) and
        ``serving.tpot_s`` (inter-token gap per emitted token), plus
        one ``serving.emit`` trace event per emitting lane carrying
        its ``request_id`` — the decode leg of the request waterfall
        (``scripts/obs_report.py --request``).  The whole loop is one
        ``serving.emit_loop`` span.  Lanes still ADMITTING
        (pending prefill chunks) are parked: their decode rows are
        burnt compute, never emission."""
        out = {}
        with obs.span("serving.emit_loop"):
            active = obs.active() is not None
            now = self._clock() if active else None
            if lanes is None:
                lanes = enumerate(self._lane_state)
            for lane, st in lanes:
                if (st is None or st is not self._lane_state[lane]
                        or st.done or st.chunks is not None):
                    continue
                emitted = []
                for tok in lane_tokens(lane):
                    st.tokens.append(int(tok))
                    emitted.append(int(tok))
                    budget = len(st.tokens) - st.prompt_len >= st.max_new
                    if budget or (st.eos is not None and tok == st.eos):
                        st.done = True
                        break
                out[lane] = emitted
                if active and emitted:
                    first = (len(st.tokens) - st.prompt_len
                             == len(emitted))
                    if first and st.born is not None:
                        obs.observe("serving.ttft_s", now - st.born)
                    elif st.last_emit is not None:
                        obs.observe("serving.tpot_s",
                                    (now - st.last_emit) / len(emitted))
                    st.last_emit = now
                    obs.event("serving.emit", request_id=st.request_id,
                              lane=lane, n=len(emitted), first=first)
            if active:
                obs.count("serving.tokens",
                          sum(len(v) for v in out.values()))
        return out

    # The routes of the round last read (a typed stack with sparse
    # layers: ``[n, sparse layers, lanes, k]``, an assignment's index
    # among the held experts), and the round's counts made of them.
    _routes = None
    _moe_round = None

    def _read_tokens(self, toks) -> np.ndarray:
        """The second of a decode round's two hooks (the first is
        ``_dispatch_step``): the round's ``[lanes, n]`` tokens on the
        host; blocks until the device has finished the round.  A
        routed step's routes come in the same read."""
        if isinstance(toks, tuple):
            toks, routes = toks
            self._routes = np.asarray(routes)
        return np.asarray(toks)

    def _count_routes(self, lanes) -> None:
        """``serving.round``'s ``moe_*`` counts of the round just read,
        over the lanes that were decoding at its dispatch: assignments
        routed, those that fell on held experts, the most one held
        expert got in one layer of one step."""
        routes, self._routes = self._routes, None
        held = len(self.cfg.experts_held)
        mine = routes[:, :, [lane for lane, _ in lanes]]
        per_expert = (mine[..., None] == np.arange(held)).sum(axis=(2, 3))
        self._moe_round = (int(mine.size), int(per_expert.sum()),
                           int(per_expert.max(initial=0)))

    def _number_dispatch(self, sp, program) -> None:
        """Count the decode round just launched and, under a trace, say
        on its ``serving.step`` span ``sp`` which launch it was
        (``seq``) and of which program (``program``).  Called once the
        launch went out: a dispatch that raised leaves no hole in the
        count."""
        self._dispatch_seq += 1
        if sp is not None:
            sp.fields.update(seq=self._dispatch_seq,
                             program=_program_name(program))

    def _timed_read(self, seq: int, read):
        """``read()`` — the device-to-host read of dispatch ``seq`` —
        inside a ``serving.collect`` span that names the dispatch and
        says how long the read blocked (``wait_ms``)."""
        with obs.span("serving.collect", seq=seq) as sp:
            t0 = time.perf_counter()
            out = read()
            if sp is not None:
                wait = (time.perf_counter() - t0) * 1e3
                sp.fields["wait_ms"] = wait
                self._waited_ms += wait
        return out

    def _collect(self, pending) -> dict:
        """Read a dispatched round and emit it: ``pending`` is the
        record ``step()`` kept at the dispatch.  The read is one
        ``serving.collect`` span whose ``wait_ms`` is the time blocked
        in it — near the device's round time where the device sets the
        pace, near zero where the host does — and whose ``seq`` names
        the dispatch it reads (``serving.step``'s of the same number)."""
        dev, lanes, seq = pending
        toks = self._timed_read(seq, lambda: self._read_tokens(dev))
        if self._routes is not None and obs.active() is not None:
            self._count_routes(lanes)
        return self._emit(lambda lane: toks[lane].tolist(), lanes)

    def _flush_round(self) -> bool:
        """Read the round in flight NOW, where something is about to
        move lanes under it (a resize renumbers them) or to stop
        calling ``step()`` (shutdown): its tokens join the transcripts
        here and ride out with the next ``step()``'s result, found
        again by ``_Lane`` identity.  Returns whether there was one."""
        pending, self._inflight = self._inflight, None
        if pending is None:
            return False
        out = self._collect(pending)
        self._flushed += [(self._lane_state[lane], toks)
                          for lane, toks in out.items()]
        return True

    def _close_round(self, rnd, out, chunks: int, idle: bool,
                     overlapped: bool = False, state_lanes: int = 0,
                     fused: bool = False) -> None:
        """The counts of one ``step()``, taken where the round ends
        (session active only): ONE pass over the lane table, set as
        the ``serving.lanes_busy`` gauge and written into the closing
        ``serving.round`` span ``rnd`` (None without a trace file).

        ``kv_live`` is positions written and live once the steps
        dispatched so far have run: a decoding lane holds its prefix,
        its prompt but for the last token and one slot for every step
        dispatched for it, up to its budget (the round in flight
        counts: the count is the dispatch's, not the transcript's), an
        admitting lane what lies before its next chunk.  ``chunks`` is the admission
        chunks dispatched since the previous decode dispatch, ``fused``
        (0 / 1) whether one of them went out INSIDE this round's decode
        program (``ContinuousBatcher._make_round_chunk``) and not as an
        admission program of its own.
        ``host_ms`` is the call's time so far (this is its last act)
        less what its ``serving.collect`` spans waited for the device:
        the host's own work in the round."""
        busy = admitting = kv_live = kv_live_window = 0
        window = self.cfg.sliding_window or 0
        for st in self._lane_state:
            if st is None or st.done:
                continue
            busy += 1
            if st.chunks is not None:
                admitting += 1
                live = st.chunks[0][0]
            else:
                live = (st.off + st.prompt_len - 1
                        + min(st.launched, st.max_new))
            kv_live += live
            kv_live_window += min(live, window)
        obs.gauge("serving.lanes_busy", busy)
        moe, self._moe_round = self._moe_round, None
        if rnd is not None:
            rnd.fields.update(
                lanes_busy=busy, lanes_admitting=admitting,
                kv_live=kv_live, chunks=chunks, fused=int(fused),
                tokens=sum(len(v) for v in out.values()))
            if self.cfg.kv_ring_planes:
                # Ring planes: what of a lane's positions a window
                # layer still holds (sum of min(position, window)).
                rnd.fields["kv_live_window"] = kv_live_window
            if self.cfg.state_planes:
                # Lanes whose state the step DISPATCHED here read and
                # wrote (its ``live`` mask): the others' was left alone.
                rnd.fields["state_lanes"] = state_lanes
            if moe is not None:
                # Of the round READ here (like ``tokens``).
                (rnd.fields["moe_assigned"], rnd.fields["moe_held"],
                 rnd.fields["moe_max"]) = moe
            if idle:
                rnd.fields["idle"] = True
            if overlapped:
                rnd.fields["overlapped"] = True
            rnd.fields["host_ms"] = (
                (time.perf_counter() - rnd.t0) * 1e3 - self._waited_ms)

    # --------------------------------------------- chunked admission

    def _run_pending_chunk(self) -> None:
        """Execute ONE pending admission chunk (FIFO across admitting
        lanes) — called at the top of every ``step()``, so a long
        prompt's prefill interleaves with decode at one chunk per step
        and the other lanes' inter-token gap stays bounded by one
        chunk.  Completing the last chunk un-parks the lane: its
        position/current-token are set and it joins THIS step's decode
        (the same "admission then the next step processes the final
        prompt token" convention as monolithic admission)."""
        if not self._admitting:
            return
        lane, st, start, rows, end, new = self._pop_chunk()
        with obs.span("serving.admit_chunk", bucket=rows.shape[1],
                      positions=new, remaining=len(st.chunks),
                      request_id=st.request_id) as sp:
            fn = self._exec_chunk(lane, start, rows,
                                  **self._real(end - start))
            if sp is not None:
                sp.fields.update(
                    program=_program_name(fn),
                    attended=self._attended(self.cache, start,
                                            rows.shape[1]))
        self._admit_programs += 1
        if not st.chunks:
            self._admitting.popleft()
            st.chunks = None
            self._finish_admission(lane, st)

    def _pop_chunk(self) -> tuple:
        """Take the next pending chunk (FIFO across admitting lanes) off
        its lane's plan and move the lane's ``filled`` frontier past
        it: ``(lane, its _Lane, start, rows, end, new)`` — the chunk's
        real tokens end at ``end``, ``new`` of its positions were not
        written before (the spans' ``positions``)."""
        lane = self._admitting[0]
        st = self._lane_state[lane]
        start, rows = st.chunks.pop(0)
        end = min(start + rows.shape[1], st.off + st.prompt_len - 1)
        new, st.filled = max(end - st.filled, 0), max(end, st.filled)
        return lane, st, start, rows, end, new

    def _real(self, n: int) -> dict:
        """``n_real=`` of an admission dispatch — how many of the
        chunk's tokens are real, which a program that writes ring
        planes or a state takes as one argument more — or nothing: every
        other engine's programs keep their signature."""
        cfg = self.cfg
        return {"n_real": n} if cfg.kv_ring_planes or cfg.state_planes else {}

    def _exec_chunk(self, lane, start, rows):  # pragma: no cover
        raise NotImplementedError(
            "this engine does not support chunked prefill")

    def _finish_admission(self, lane, st):  # pragma: no cover
        raise NotImplementedError(
            "this engine does not support chunked prefill")


__all__ = ["_Lane", "_LaneEngine", "_make_lane_admit",
           "_make_lane_reseed", "_program_name"]
