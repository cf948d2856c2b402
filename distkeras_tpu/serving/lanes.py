"""ContinuousBatcher: lane-based continuous batching over one jitted
decode step.

Static-shape serving loop for interactive workloads: requests arrive
at different times, but the chip wants one fixed-shape program.  The
engine holds ``lanes`` decode rows in ONE KV cache and ONE jitted
per-row-position decode step; a new request is admitted into any free
lane mid-flight with a bucket-padded chunked prefill of just that
lane, while the other lanes keep decoding.  No compiled shape ever
depends on arrival times.

Contract: every request's emitted tokens are EXACTLY what
``generate(params, prompt, cfg, max_new_tokens, ...)`` would emit for
it alone — the per-lane PRNG stream is position-keyed like generate's
(``fold_in(request_key, pos)``), lane-local positions start at 0 per
request, and stale cache slots from the lane's previous occupant are
masked until overwritten (the ``_decode_chunk`` staleness argument).
Pinned by tests/test_serving.py against solo ``generate`` runs,
including staggered admission and lane reuse.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import obs
from distkeras_tpu.resilience import chaos

from distkeras_tpu.models.generate import (
    _decode_chunk,
    _device_tree,
    _resolve_prompt_cache,
    base_body_only,
    init_cache,
    min_p_mask,
    rolling_eligible,
    top_k_mask,
    top_p_mask,
)
from distkeras_tpu.models.transformer import (TransformerConfig,
                                               reject_extended)
from distkeras_tpu.serving.elastic import _ElasticLanesMixin
from distkeras_tpu.serving.engine import (_Lane, _LaneEngine,
                                          _program_name,
                                          _make_lane_admit,
                                          _make_lane_reseed)

# The measured cache-bound crossover for the int8 KV cache: +33% at
# b64, -15% at b8 (docs/serving_guide.md's byte-lever table).  Engines
# built with kv_int8 below this lane count get a construction-time
# advisory — the cache-byte saving cannot pay for the dequant cost at
# batch sizes where weights, not cache, dominate the step's traffic.
KV_INT8_LANE_ADVISORY = 16


class ContinuousBatcher(_ElasticLanesMixin, _LaneEngine):
    """Lane-based continuous batching over one jitted decode step.

    Args mirror ``generate``'s sampling surface: ``temperature``,
    ``top_k`` / ``top_p`` / ``min_p``, ``eos_token``, ``exact_top_k``
    — fixed per engine (they are compiled into the step).  Per-request
    PRNG keys arrive with ``submit``.

    ``per_request_sampling=True`` compiles the vectorized step instead
    (per-lane temperature/top_p/min_p carried as [lanes] device
    arrays): ``submit`` then takes per-request ``temperature`` /
    ``top_p`` / ``min_p`` / ``eos_token`` overrides — greedy and
    sampled requests mix in one batch, each still matching its solo
    ``generate`` run exactly.  The constructor values become the
    per-request DEFAULTS.  Off by default because the general program
    pays the nucleus sort and the sampling draw every step even for a
    greedy-only workload; ``top_k`` stays engine-level either way (a
    static shape baked into the program).

    ``lanes``: decode rows held by the engine; ``prompt_buckets``:
    admission pad widths (a prompt of length P uses the smallest
    bucket >= P - 1; one admission program compiles per bucket).

    Full-cache configs, or rope + ``attention_window`` configs — the
    latter run ROLLING lanes: every lane decodes past ``max_len`` on
    the ring-buffer cache with no total-length cap (prompts still must
    fit the ring), each request matching its solo rolling
    ``generate()`` run exactly.  No quantized-tree restriction — int8
    weights decode on the same chunk path — and every engine shape
    takes ``kv_int8=True`` (int8 KV cache; parity vs
    ``generate(kv_int8=True, use_prefill=False)``), rolling ring
    lanes included (round-5: the scale slabs ride the same ring-slot
    updates as the K/V).

    **Chunked prefill** (round-10, ``prefill_chunk=``): admission of a
    prompt longer than ``prefill_chunk`` tokens no longer runs as one
    monolithic chunk that stalls every lane — it is split into
    fixed-size, bucket-padded chunks, the first executed at ``submit``
    and the rest interleaved one per ``step()`` between decode
    dispatches, so concurrently decoding lanes' inter-token gap is
    bounded by ONE chunk's compute.  The parked lane joins decode the
    step its last chunk lands; emitted tokens are identical to
    monolithic admission (the chunks write exactly the same K/V).
    Full-cache configs only, and every chunk program compiles at
    construction (the ``serving_chunked`` compile session pins a
    zero-recompile serve phase).  The ``prefill_chunk`` width is added
    to ``prompt_buckets``.  Where the stack is the plain one (untyped,
    one pass, in-place admission; one device, no tiers, ``step(1)``) a
    full-width chunk that is neither its plan's first nor its last goes
    through the layers INSIDE the round's decode program
    (:meth:`_make_round_chunk`): one launch a round, the weights
    streamed once, the same tokens.

    **Prefix pool** (round-10, ``prefix_pool=``): attach a
    :class:`~distkeras_tpu.serving.PrefixPool` and ``submit`` /
    ``enqueue`` take ``prefix_id=`` — the lane is seeded from the
    pooled prefilled segment by a device gather, so the prefix tokens
    cost ZERO prefill work per request, across N distinct prefixes on
    one engine (the generalization of the single ``prompt_cache=``
    prefix, ``kv_int8`` layouts included — the pool's quantization
    must match the engine's).  Requests pin their entry (refcount)
    until the lane is vacated; queued requests do not pin, so a prefix
    evicted while its request queues surfaces as a structured
    ``"error"`` result.  Parity: a pooled request matches
    ``generate(tail, prompt_cache=(segment, P))`` exactly, greedy and
    sampled.  Mutually exclusive with ``prompt_cache`` and with
    rolling (windowed) engines.

    **Elastic lane tiers** (round-7, resilience subsystem):
    ``lane_tiers=(2, 4, 8)`` starts the engine at 2 lanes and moves it
    between the declared tiers under load — ``scale_up_after``
    consecutive queue overflows step the tier up (the overflowing
    enqueue is absorbed instead of raising :class:`QueueFull`);
    ``scale_down_after`` consecutive steps with the queue empty and
    occupancy fitting the next tier down step it back (free lanes burn
    a decode row per step — shrinking recovers that compute).  EVERY
    tier's programs — each ``step_windows`` decode window, each
    admission bucket, the inter-tier resize gathers — compile at
    construction, so no request ever pays a recompile
    (``scripts/check_compile_counts.py``'s ``serving_elastic`` budget
    pins it).  A resize compacts occupied lanes; lane ids are
    therefore unstable, so elastic engines admit through the id-keyed
    :meth:`enqueue` surface only (bare ``submit`` rejects).
    ``serving.lanes_tier`` / ``serving.resizes`` /
    ``serving.resize`` events expose the tier trajectory through obs,
    and ``tier_epoch`` counts resizes for drain/debug correlation.

    ``step_windows`` declares the ``step(n)`` window sizes to
    pre-compile.  Elastic engines are restricted to the declared set;
    chunked-prefill and prefix-pool engines warm the declared set at
    construction (undeclared windows still compile lazily); plain
    engines ignore it beyond validation.

    **Pod-sharded serving** (round 14, ``plan=``/``mesh=``): ONE
    engine spans a whole device mesh — params placed by the plan's
    regex partition rules (``serving_plan()`` is the standard TP
    layout; ``fsdp_plan()`` works too), the KV cache's kv-heads
    dimension sharded over whatever axis the plan shards attention
    heads over (derived — ``parallel/rules.py``), row state
    replicated, every program compiled at construction under sharding
    constraints so GSPMD inserts the per-token collectives and the
    serve phase never compiles.  Emitted tokens are bit-exact vs the
    solo engine, greedy and sampled; per-device param+KV bytes drop
    ~axis-size× (see :meth:`memory_footprint`).  Composes with paged
    KV, chunked prefill, mesh-matched prefix pools, and (round 17)
    ``lane_tiers`` — every tier and resize gather compiles at
    construction under the plan's constraints; rejects
    ``prompt_cache``/rolling configs (the composition table lives in
    docs/serving_guide.md "Pod-sharded serving").

    **The slab is written in place.**  The decode step writes one
    window over all planes a row (``generate._chunk_in_place``); an
    engine with no prefix to seed admits its chunk into the lane in
    place too (first and continuation chunks are one program); and
    the construction-time warm-up of an untiered engine runs on the
    LIVE slab (:meth:`_warm_live`) — a slab can be half the chip, so
    no second one is made.  **Extended configs** (``cfg.extended``:
    the gated / sandwich-norm / untied block, ``fused_qkv``, a looped
    stack of ``n_passes`` with one KV plane per pass and layer)
    compose with ``hot_swap``, ``prefill_chunk`` and sampling;
    everything else (``plan=``, ``prompt_cache=``, ``prefix_pool=``,
    ``kv_int8=``, ``lane_tiers=``, a window) rejects them by name.

    **State planes** (a stack with retention layers, ``layer_types``
    ``"retention"``): such a layer's lane plane is a float32 state of
    fixed size, not slots — ``max_len`` is positions there, not memory
    (``serving.kv_layout``'s ``state_bytes_per_lane``;
    :meth:`memory_footprint` counts it under ``kv_bytes``).  Three
    things a K/V slab never needed: the decode step takes the lanes'
    ``live`` mask (:meth:`_live`) and leaves a free, finished or
    ADMITTING lane's state unread and unwritten; a new occupant starts
    from zero (a chunk or a step at position 0 clears the state: a
    stale state is not masked by position); and an admission's padding
    neither adds to a state nor decays it (``n_real``; a chunked tail
    stays on the grid, as with ring planes).

    **Latent planes** (a stack of latent layers, ``layer_types``
    ``"latent"``): a lane's plane holds ONE row a position — the
    latent and the rotary key every head shares, ``latent_width``
    values, no K/V-head dimension (``serving.kv_layout``'s
    ``planes_latent``, ``bytes_per_slot_latent``).  Slots like a full
    plane's: stale ones are masked by position, a new occupant clears
    nothing, an admission's padding lands past the frontier.  The
    decode step takes the ``live`` mask as with state planes, so that
    a lane which does not decode reads none of its row.

    **Live weight push** (round 20, ``hot_swap=True``): every decode
    and admission program takes the param tree as an explicit jit
    argument (never donated), so :meth:`swap_params` can replace the
    served weights BETWEEN steps with zero recompiles — the swap
    rebinds a host-side reference under the live placement
    (``jnp.asarray`` re-placement unsharded, ``device_put`` onto the
    live leaves' shardings under ``plan=``), it never re-keys the jit
    cache (the ``serving_weight_push`` compile session pins it).
    Swaps are version-monotone (``allow_downgrade=True`` is the
    canary rollback's exception), validated against the live tree's
    treedef/shapes/dtypes, and atomic under the admission lock — a
    request's next step either wholly sees version N or wholly sees
    N+1.  ``residency()`` reports ``param_version`` so the router's
    fleet snapshot carries per-replica versions.  Rejects
    ``prompt_cache``/``prefix_pool`` (prefilled K/V baked from old
    params would mix versions) and forces always-warm admission.  The
    policy layer above is :class:`~distkeras_tpu.serving.canary.
    CanaryController` over :class:`~distkeras_tpu.serving.publish.
    SnapshotReader`.
    """

    def __init__(self, params, cfg: TransformerConfig, lanes: int = 8,
                 temperature: float = 0.0, top_k=None, top_p=None,
                 min_p=None, eos_token=None, exact_top_k: bool = False,
                 prompt_buckets=(8, 32, 128, 512), prompt_cache=None,
                 kv_int8: bool = False,
                 per_request_sampling: bool = False,
                 max_queue: int = 0, clock=None,
                 lane_tiers=None, scale_up_after: int = 2,
                 scale_down_after: int = 8, step_windows=(1,),
                 prefill_chunk: int | None = None, prefix_pool=None,
                 plan=None, mesh=None, hot_swap: bool = False):
        # Windowed configs: the engine runs ROLLING lanes — each lane
        # decodes past max_len on the ring-buffer cache (the unbounded
        # streaming-chat shape), which needs rope (positions beyond
        # max_len have no learned-table embedding) and a window that
        # fits the ring.  Non-rope windowed configs have no rolling
        # semantics, so they stay rejected rather than silently
        # becoming bounded.
        # Pod-sharded serving (round 14, ``plan=``/``mesh=``): one
        # engine replica spans a whole device mesh.  Params are placed
        # by the plan's regex partition rules (the same TP/FSDP
        # spellings training uses), the KV cache's kv-heads dimension
        # shards over whatever mesh axis the plan shards attention
        # heads over (DERIVED, never authored — parallel/rules.py's
        # serving_kv_axis), row metadata replicates, and every program
        # compiles ONCE with sharding constraints so GSPMD inserts the
        # per-token collectives — emitted tokens stay bit-exact vs the
        # solo engine (tests/test_serving_sharded.py).
        if cfg.extended:
            # The extended block serves through monolithic lanes,
            # chunked prefill and per-row decode; what was not made to
            # work with it says so by name (reject_extended).
            for on, path in (
                    (plan is not None, "plan= (a tensor-parallel "
                     "serving_plan)"),
                    (cfg.attention_window is not None, "rolling lanes "
                     "(attention_window)"),
                    (prompt_cache is not None, "prompt_cache="),
                    (prefix_pool is not None, "prefix_pool="),
                    (bool(kv_int8), "kv_int8= (the int8 KV cache)"),
                    (lane_tiers is not None, "lane_tiers= (elastic "
                     "resizing gathers the slab)")):
                if on:
                    reject_extended(cfg, f"ContinuousBatcher with {path}")
        if (plan is None) != (mesh is None):
            raise ValueError(
                "pass plan= and mesh= together: the plan's rules only "
                "mean something against a concrete mesh (use "
                "parallel.sharding.serving_plan() for the standard TP "
                "layout)")
        if plan is not None:
            if cfg.attention_window is not None:
                raise ValueError(
                    "pod-sharded serving needs a full-cache config "
                    "(no attention_window): the ring slab's rolling "
                    "scatter has no stable sharded layout to pin")
            # lane_tiers composes (round 17): every tier's programs —
            # and the inter-tier resize gathers — compile at
            # construction under the same sharding constraints, so a
            # tier move on a sharded engine is still zero serve-phase
            # compiles (the serving_disagg compile session pins it).
            if prompt_cache is not None:
                raise ValueError(
                    "plan= does not compose with prompt_cache= (one "
                    "baked-in prefix); use prefix_pool= built with "
                    "the same mesh, or a PagedBatcher pinned stem")
        self.plan, self.mesh = plan, mesh
        if plan is not None:
            # Any ShardingPlan works (fsdp_plan/tp_plan/serving_plan):
            # the KV axis derives from its attention rules, with the
            # head-divisibility rejection naming the offending rule.
            from distkeras_tpu.parallel.rules import serving_kv_axis

            self._kv_axis = serving_kv_axis(plan, mesh, cfg)
        self._rolling = False
        if cfg.attention_window is not None:
            if not rolling_eligible(cfg):
                raise ValueError(
                    "windowed continuous batching runs rolling lanes, "
                    "which needs rope=True and attention_window <= "
                    "max_len (full-cache configs need no window)")
            if prompt_cache is not None:
                raise ValueError("prompt_cache requires a full-cache "
                                 "config (no attention_window)")
            if prefix_pool is not None:
                raise ValueError("prefix_pool requires a full-cache "
                                 "config (no attention_window)")
            if prefill_chunk is not None:
                raise ValueError(
                    "chunked prefill (prefill_chunk=) requires a "
                    "full-cache config: a rolling ring has no parking "
                    "slot whose garbage writes stay masked, and ring "
                    "prompts are already bounded by the ring size")
            # kv_int8 composes: the int8 ring slab is the same
            # slot-addressed slab update with scale slabs riding along.
            self._rolling = True
        # Elastic lane tiers (resilience subsystem): the engine starts
        # at the smallest tier and moves between PRE-COMPILED tiers
        # under load — every tier's programs compile at construction,
        # so no request ever pays a recompile (the admission-latency
        # analogue of the prompt-bucket contract).
        _tiers = None
        _windows = tuple(sorted({int(n) for n in step_windows}))
        if not _windows or _windows[0] < 1:
            raise ValueError(
                f"step_windows must be positive ints, got "
                f"{step_windows}")
        if lane_tiers is not None:
            _tiers = tuple(sorted({int(t) for t in lane_tiers}))
            if len(_tiers) < 2:
                raise ValueError(
                    f"lane_tiers needs >= 2 distinct tiers, got "
                    f"{lane_tiers} (a single fixed size is just lanes=)")
            if _tiers[0] < 1:
                raise ValueError(f"lane tiers must be >= 1, got {_tiers}")
            if scale_up_after < 1 or scale_down_after < 1:
                raise ValueError(
                    "scale_up_after/scale_down_after must be >= 1 "
                    f"(got {scale_up_after}, {scale_down_after})")
            if 1 not in _windows:
                raise ValueError(
                    "step_windows must include 1 — drain/shutdown "
                    "steps one token at a time")
            if max_queue < 1:
                raise ValueError(
                    "lane_tiers needs max_queue >= 1: the queue "
                    "overflow IS the scale-up signal")
            lanes = _tiers[0]
        if lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes}")
        if not isinstance(kv_int8, bool):
            # PagedBatcher validates its own tri-state and passes a
            # bool down; a string reaching a monolithic engine would
            # otherwise silently truthy-coerce into plain int8.
            raise ValueError(
                f"kv_int8 must be a bool here (got {kv_int8!r}); "
                'kv_int8="prefill" is a PagedBatcher admission mode')
        if prompt_cache is not None and prefix_pool is not None:
            raise ValueError(
                "pass prompt_cache (ONE engine-level prefix, baked "
                "into admission) OR prefix_pool (per-request pooled "
                "prefixes), not both")
        if prompt_cache is not None and prompt_cache[1] >= cfg.max_len:
            raise ValueError(
                f"shared prefix length {prompt_cache[1]} must leave "
                f"room under max_len={cfg.max_len}")
        if (temperature <= 0
                and (top_k
                     or (top_p is not None and top_p < 1.0)
                     or (min_p is not None and min_p > 0.0))
                and not per_request_sampling):
            # With per-request sampling the constructor values are only
            # DEFAULTS; a filter default alongside a greedy default
            # temperature is legal (it applies to requests that
            # override the temperature).  The explicit no-op values
            # (top_p=1.0 / min_p=0.0) are legal everywhere — the same
            # round-6 contract as generate and submit().
            raise ValueError(
                "top_k/top_p/min_p need temperature > 0 (greedy always "
                "takes the argmax)")
        # Eager range checks: the scalar step validates these lazily at
        # first trace, but the per-request path bakes them into device
        # arrays where a bad value would sample silent garbage
        # (log of a negative min_p is NaN, which masks every token).
        if temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {temperature}")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        # min_p=0.0 is the explicit "no filter" value on EVERY engine
        # mode (round-6: same contract as generate and submit()).
        if min_p is not None and not 0.0 <= min_p <= 1.0:
            raise ValueError(f"min_p must be in [0, 1], got {min_p}")
        if eos_token is not None and not 0 <= eos_token < cfg.vocab_size:
            raise ValueError(
                f"eos_token {eos_token} outside vocab [0, "
                f"{cfg.vocab_size})")
        # Live weight push (round 20, ``hot_swap=``): compile every
        # decode/admission program to take the param tree as an
        # ARGUMENT instead of closing over it, so swap_params() is a
        # warm-cache argument change — zero recompiles (the
        # serving_weight_push compile session pins it).  Prefilled
        # prefixes are rejected: their K/V was computed under the
        # params they were built with, so a swap would silently serve
        # a version mix (re-prefill and rebuild instead).
        self._hot_swap = bool(hot_swap)
        if self._hot_swap:
            if prompt_cache is not None or prefix_pool is not None:
                raise ValueError(
                    "hot_swap=True does not compose with "
                    "prompt_cache=/prefix_pool=: prefix K/V is baked "
                    "from the params it was prefilled with, so a "
                    "weight swap would silently mix param versions "
                    "mid-sequence — rebuild the prefix under the new "
                    "version instead")
            # Every program must exist before the first request: a
            # lazy serve-phase compile would land INSIDE the push
            # window the zero-compile budget pins.
            self._always_warm = True
        if plan is not None:
            # Sharded device placement per the plan's rules: the big
            # matmul operands scatter over the mesh, small leaves
            # (norm scales) replicate — per-device param bytes drop
            # ~axis-size×, asserted from addressable shards by
            # memory_footprint().  Already-placed trees re-place
            # cheaply (device_put is a no-op per unchanged leaf).
            self.params = jax.device_put(
                params, plan.tree_shardings(mesh, params))
            # Every program must exist before the first request: the
            # serving_sharded compile sessions assert a zero-compile
            # serve phase, same contract as elastic/paged engines.
            self._always_warm = True
        else:
            self.params = _device_tree(params)
        self.cfg = cfg
        self.lanes = lanes
        # Shared prefix (system prompt): every lane's request decodes
        # past a common prefilled prefix — same contract as
        # generate(prompt_cache=...); admission seeds the lane from the
        # prefix instead of zeros and all positions shift by its length.
        self._off = 0
        self._prefix_lane = None
        if prompt_cache is not None:
            # The ONE prompt_cache contract (generate's helper): batch
            # must be 1 here (b=1), the prefix quantization must match
            # the engine cache (build it with prefill(kv_int8=...)),
            # and the loosest budget (p=1, one new token) must fit;
            # per-request budgets are re-checked at submit.
            pc, self._off = _resolve_prompt_cache(
                prompt_cache, cfg, b=1, p=1, max_new_tokens=1,
                kv_int8=kv_int8, use_prefill=None)
            self._prefix_lane = jax.tree.map(jnp.asarray, pc)
        if prefix_pool is not None:
            if prefix_pool.draft_cfg is not None:
                raise ValueError(
                    "this pool holds (target, draft) speculative "
                    "pairs; build a plain PrefixPool(cfg, ...) for "
                    "ContinuousBatcher")
            if prefix_pool.kv_int8 != kv_int8:
                raise ValueError(
                    "prefix_pool quantization must match kv_int8= "
                    "(build the pool with the engine's kv_int8)")
            if getattr(prefix_pool, "mesh", None) != mesh:
                raise ValueError(
                    "prefix_pool placement must match the engine's: "
                    "build the pool with PrefixPool(..., mesh=, "
                    "kv_axis=) matching plan=/mesh= (a slab placed "
                    "differently from the cache would make every "
                    "pooled admission reshard the segment)")
            if (mesh is not None
                    and getattr(prefix_pool, "kv_axis", None)
                    != self._kv_axis):
                raise ValueError(
                    f"prefix_pool kv_axis="
                    f"{getattr(prefix_pool, 'kv_axis', None)!r} does "
                    f"not match the plan-derived KV axis "
                    f"{self._kv_axis!r}")
            want = jax.eval_shape(
                lambda: init_cache(cfg, 1, kv_int8=kv_int8))
            got = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                prefix_pool.slab)
            if (jax.tree.structure(want) != jax.tree.structure(got)
                    or jax.tree.leaves(want) != jax.tree.leaves(got)):
                raise ValueError(
                    f"prefix_pool was built for a different config "
                    f"(pool segment {got}, engine cache {want})")
        self._prefix_pool = prefix_pool
        self.eos_token = eos_token
        self.temperature = temperature
        self.top_p = top_p
        self.min_p = min_p
        if prefill_chunk is not None:
            prefill_chunk = int(prefill_chunk)
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
            if self._off + prefill_chunk > cfg.max_len:
                raise ValueError(
                    f"prefill_chunk {prefill_chunk} exceeds the cache "
                    f"slots past the prefix "
                    f"({cfg.max_len - self._off})")
        self.prefill_chunk = prefill_chunk
        # Buckets clamp to the cache slots past the shared prefix and
        # include the chunk width (chunked admission's full chunks have
        # an exact program) or, unchunked, the largest legal width.
        # Chunked, no wider program is built: a longer prompt goes in
        # chunks — unless a shared prefix offsets the lanes, where the
        # cap-wide bucket is what still fits when a narrower one's
        # padding would overflow the cache.
        cap = cfg.max_len - self._off
        widths = {min(int(w), cap) for w in prompt_buckets}
        if prefill_chunk is None or self._off:
            widths.add(cap)
        if prefill_chunk is not None:
            widths = {w for w in widths
                      if w <= prefill_chunk or (self._off and w == cap)}
            widths.add(prefill_chunk)
        self._buckets = tuple(sorted(widths))
        self._lane_state: list[_Lane | None] = [None] * lanes
        self._next_id = 0
        # Admission control (resilience subsystem): ``max_queue`` bounds
        # the enqueue() backlog (0 = no queue: enqueue needs a free
        # lane); ``clock`` is the deadline clock (monotonic seconds;
        # injectable for deterministic chaos tests).
        self._init_admission(max_queue, clock)
        if _tiers is not None:
            self.lane_tiers = _tiers
            self.scale_up_after = scale_up_after
            self.scale_down_after = scale_down_after
        self._step_windows = _windows

        # Device state: one cache, per-lane next-position, per-lane
        # current token (the one the next step processes), per-lane key.
        # ``kv_int8``: the cache stores int8 K/V + f32 scales — halves
        # the dominant HBM term at batch where cache bytes rule (a
        # gain at b64, a LOSS at b8; measured 2026-07-31 on one v5e,
        # not re-measured since) — and every request still matches its
        # solo ``generate(kv_int8=True, use_prefill=False)`` run
        # exactly: both the admission chunk and the sequential path
        # attend the ALREADY-QUANTIZED cache position by position,
        # unlike prefill() which attends the prompt in full precision.
        # (Stored for introspection only, like ``lanes``; the runtime
        # switch is the ``k_scale`` leaf in ``self.cache``.)
        self.kv_int8 = bool(kv_int8)
        if kv_int8 and max(_tiers or (lanes,)) < KV_INT8_LANE_ADVISORY:
            # Construction-time advisory (round-10 satellite): at small
            # lane counts decode is weight-bound and the int8 cache is
            # a measured LOSS (-15% at b8); the lever pays only where
            # cache bytes dominate.  See docs/serving_guide.md's
            # byte-lever table for the regime boundary.
            msg = (f"kv_int8=True with {max(_tiers or (lanes,))} lanes:"
                   f" the int8 KV cache is a measured loss below "
                   f"~{KV_INT8_LANE_ADVISORY} lanes (-15% at b8; "
                   "docs/serving_guide.md byte-lever table) — decode "
                   "is weight-bound there, so the cache-byte saving "
                   "cannot pay for the dequant")
            warnings.warn(msg, RuntimeWarning, stacklevel=2)
            obs.event("serving.advisory", kind="kv_int8_small_lanes",
                      lanes=max(_tiers or (lanes,)), detail=msg)
        self.per_request_sampling = per_request_sampling
        # Engine-level sampling statics the compiled step closes over
        # (stored so the paged subclass's step factory reuses the ONE
        # per-token body — see _build_one_step).
        self.top_k = top_k
        self.exact_top_k = exact_top_k
        self._init_device_state(lanes)
        self._kv_layout_event()
        self._one_step = self._build_one_step()
        self._steps = {}
        self._build_admission_programs()

        if self.lane_tiers is not None:
            self._resize = self._make_resize()
            self._compile_tiers()
        elif (prefill_chunk is not None or self._prefix_pool is not None
                or self._always_warm):
            # Chunked/pooled engines make the elastic construction-time
            # promise too: every admission bucket (seeded + chunk
            # continuation + pool gather) and every DECLARED step
            # window compiles here, so the serve phase is recompile-
            # free (the serving_chunked / serving_prefix_pool compile
            # sessions assert it).  Undeclared step(n) windows still
            # compile lazily, as on a plain engine.  Engines that set
            # ``_always_warm`` (the paged engine) take this path
            # unconditionally — every one of their programs is built
            # here or nowhere.
            with obs.span("serving.compile_warm", lanes=lanes):
                self._warm_live()

    # ----------------------------------------- device-state factories
    #
    # Split out of __init__ (round 12) so the paged engine
    # (serving/paged.py) can swap the STORAGE — a block slab + page
    # tables instead of the monolithic [lanes, max_len] cache — while
    # the host machinery, the per-token sampling body, and therefore
    # the exact-parity contract stay literally shared.

    # Engines that must compile every program at construction even
    # without chunked prefill / a pool / tiers (the paged engine).
    _always_warm = False

    def _fresh_cache(self, lanes: int):
        """A zeroed KV store for ``lanes`` decode rows — the ONE
        cache-layout decision point (monolithic here; the paged
        engine overrides with its block slab).  Sharded engines place
        it with the plan-derived kv-heads sharding (``_place_kv`` is a
        no-op unsharded) — warm-up dummies come through here too, so
        they always carry the live layout."""
        return self._place_kv(
            init_cache(self.cfg, lanes, kv_int8=self.kv_int8))

    def _init_device_state(self, lanes: int) -> None:
        self.cache = self._fresh_cache(lanes)
        self._init_lane_rows(lanes)

    def _kv_layout_event(self) -> None:
        """One ``serving.kv_layout`` event at construction: what a
        reader needs to turn ``serving.round``'s ``kv_live`` slots
        into bytes without knowing the model."""
        cfg = self.cfg
        slab = sum(int(a.nbytes) for a in jax.tree.leaves(self.cache))
        if cfg.typed:
            # Two kinds of plane in one slab: a lane's slot costs the
            # full planes' bytes at every position and the rings' at
            # its last ``window`` positions only (``kv_live`` and
            # ``kv_live_window`` of ``serving.round`` count the two).
            # ``bytes_per_slot`` keeps its meaning: the slab over the
            # lanes' max_len positions.  (Keywords spelled out: the
            # contract lint reads an event's labels off its call.)
            slots = self.lanes * cfg.max_len
            per = 2 * cfg.kv_heads * cfg.head_dim * self.cache["k"].itemsize
            # A third kind where the stack has retention layers: one
            # float32 state a layer, of a fixed size at every position
            # (``max_len`` is positions there, not memory); 0 planes
            # and "" say the stack has none.
            state = ([self.cache["s"], self.cache["z"]]
                     if cfg.state_planes else [])
            # A fourth kind where the layers are latent: one row of
            # ``latent_width`` values a position a layer (the latent,
            # the shared rotary key, zeros up to whole lane tiles), at
            # every live position like a full plane's slot; 0 planes
            # and width 0 say the stack has none.
            lat = self.cache.get("lat")
            obs.event("serving.kv_layout", passes=cfg.n_passes,
                      layers=cfg.n_layers, planes=cfg.kv_planes,
                      bytes_per_slot=slab // slots, slots=slots,
                      slab_bytes=slab, planes_full=cfg.kv_planes,
                      planes_window=cfg.kv_ring_planes,
                      window=cfg.sliding_window,
                      ring_slots=cfg.sliding_window,
                      bytes_per_slot_full=per * cfg.kv_planes,
                      bytes_per_slot_window=per * cfg.kv_ring_planes,
                      planes_state=cfg.state_planes,
                      state_bytes_per_lane=sum(
                          int(a.nbytes) for a in state) // self.lanes,
                      state_dtype=str(state[0].dtype) if state else "",
                      planes_latent=cfg.latent_planes,
                      latent_width=0 if lat is None else int(lat.shape[-1]),
                      bytes_per_slot_latent=0 if lat is None else int(
                          lat.nbytes) // slots)
            return
        # [planes, lanes, max_len, ...] (the paged store: [planes,
        # blocks, block, ...]): slots are rows x positions.
        slots = int(np.prod(self.cache["k"].shape[1:3]))
        obs.event("serving.kv_layout", passes=cfg.n_passes,
                  layers=cfg.n_layers, planes=cfg.kv_planes,
                  bytes_per_slot=slab // slots, slots=slots,
                  slab_bytes=slab)

    def _warm_live(self) -> None:
        """Compile an untiered engine's programs by running each ONCE
        on the LIVE state — no dummy slab beside the live one (a slab
        can be half the chip; a second does not fit).  Every program
        writes the cache in place and hands it back; what the warm-up
        leaves in lane 0 and at slot 0 is stale K/V like a previous
        occupant's, masked until overwritten or seeded over.  The row
        state is made anew afterwards.  (Tiered engines warm every
        tier on dummies of its size: ``_compile_tiers``.)"""
        pool = self._prefix_pool
        for n in self._step_windows:
            self._dispatch_step(n)
        for width in self._buckets:
            rows = np.zeros((1, width), np.int32)
            self._exec_admit(0, self._off, rows, None, **self._real(width))
            if self._admit_cont not in (None, self._admit):
                self._exec_chunk(0, self._off, rows)
        if self._round_chunk is not None:
            self._exec_round_chunk(0, self._off, np.zeros(
                (1, self.prefill_chunk), np.int32))
        if pool is not None:
            self._exec_reseed(0, 0)
        elif self._prefix_lane is not None:
            self._exec_reseed(0, None)
        self._warm_host_writes(self.lanes)
        self._init_lane_rows(self.lanes)

    def _place_rows(self, cur, pos, keys, temps, tps, mps):
        """Commit per-lane row state REPLICATED over the serving mesh
        (identity unsharded).  Shared by the live init and the warm-up
        dummies: for committed arrays the sharding is part of the jit
        cache key, so the two must agree or the serve phase pays a
        recompile."""
        if self.mesh is None:
            return cur, pos, keys, temps, tps, mps
        return tuple(self._place_replicated(x)
                     for x in (cur, pos, keys, temps, tps, mps))

    def _init_lane_rows(self, lanes: int) -> None:
        """Per-lane row state shared by every storage layout: next
        position, current token, PRNG key, per-request sampling
        params."""
        self.pos = jnp.zeros((lanes,), jnp.int32)
        self.cur = jnp.zeros((lanes,), jnp.int32)
        sampling = self.temperature > 0 or self.per_request_sampling
        self.keys = (jnp.stack([jax.random.key(0)] * lanes)
                     if sampling else None)
        # Per-lane sampling params (per_request_sampling only):
        # constructor values are the defaults; submit() overrides the
        # admitted lane's slots.  top_p 1.0 / min_p 0.0 are exact
        # no-ops in the row-wise masks.
        if self.per_request_sampling:
            # Explicit dtype: weak-typed f32 and plain f32 are distinct
            # jit avals, and the elastic warmup's dummy states must hit
            # the exact programs the live state will use.
            self.temps = jnp.full((lanes,), float(self.temperature),
                                  jnp.float32)
            self.tps = jnp.full((lanes,), float(self.top_p or 1.0),
                                jnp.float32)
            self.mps = jnp.full((lanes,), float(self.min_p or 0.0),
                                jnp.float32)
        else:
            # Placeholder args keep one step signature across modes
            # (allocated once — step() is the latency-floor hot loop).
            self.temps = self.tps = self.mps = jnp.zeros((lanes,),
                                                         jnp.float32)
        if self.keys is None:
            self.keys = jnp.zeros((lanes,), jnp.int32)  # unused filler
            self._keyed = False
        else:
            self._keyed = True
        (self.cur, self.pos, self.keys, self.temps, self.tps,
         self.mps) = self._place_rows(self.cur, self.pos, self.keys,
                                      self.temps, self.tps, self.mps)

    def _build_one_step(self):
        """The per-token decode body over a CONTIGUOUS [lanes, S]
        cache tree: attention + sampling + position advance.  ONE
        definition for every storage layout — the monolithic step
        scans it over the live cache, the paged step scans it over
        the page-table-gathered view — so emitted tokens cannot drift
        between the two engines."""
        cfg = self.cfg
        per_request_sampling = self.per_request_sampling
        temperature, top_p, min_p = (self.temperature, self.top_p,
                                     self.min_p)
        top_k, exact_top_k = self.top_k, self.exact_top_k

        def pick(k, row, q):
            return jax.random.categorical(
                jax.random.fold_in(k, q), row)

        # A typed stack with sparse layers: the step also yields each
        # lane's routes ([sparse layers, lanes, k]: the index among the
        # held experts of every assignment), the round's moe_* counts.
        routed = cfg.typed and "sparse" in cfg.ffn_types

        def one_step_p(params, cache, cur, pos, keys, temps, tps, mps,
                       live=None, chunk=None):
            # ``chunk`` (``_make_round_chunk`` only): an admission
            # chunk that goes through the layers with this step's rows.
            routes = None
            if routed:
                logits, cache, routes = _decode_chunk(
                    params, cache, cur[:, None], pos, cfg, with_routes=True,
                    live=live, chunk=chunk)
                routes = routes[:, :, 0]
            else:
                logits, cache = _decode_chunk(
                    params, cache, cur[:, None], pos, cfg, live=live,
                    chunk=chunk)
            logits = logits[:, 0]                      # [lanes, V]
            if per_request_sampling:
                # Vectorized per-lane params: greedy lanes (t <= 0)
                # take the argmax of the RAW logits; the sampled draw
                # is computed for every lane (one static program) and
                # selected per lane.
                safe_t = jnp.where(temps > 0, temps, 1.0)
                scaled = logits / safe_t[:, None]
                if top_k is not None:
                    scaled = top_k_mask(scaled, top_k, exact=exact_top_k)
                # tps == 1.0 rows bypass the nucleus mask entirely:
                # float cumsum can overshoot 1.0 and mask an
                # underflowed-tail token that solo generate (which
                # skips the mask when top_p is None) could sample —
                # the bypass keeps the exact-parity contract.
                # min_p's 0.0 no-op is exact as-is (log 0 = -inf).
                scaled = jnp.where(tps[:, None] >= 1.0, scaled,
                                   top_p_mask(scaled, tps[:, None]))
                scaled = min_p_mask(scaled, mps[:, None])
                nxt = jnp.where(temps > 0,
                                jax.vmap(pick)(keys, scaled, pos),
                                logits.argmax(axis=-1))
            elif temperature > 0:
                scaled = logits / temperature
                if top_k is not None:
                    scaled = top_k_mask(scaled, top_k, exact=exact_top_k)
                # top_p >= 1.0 bypasses the mask, like the per-request
                # path and generate's scalar path (round-6 parity fix):
                # the sorted cumsum can float-overshoot 1.0 and mask an
                # underflowed tail token "no filter" could sample.
                if top_p is not None and top_p < 1.0:
                    scaled = top_p_mask(scaled, top_p)
                # min_p 0.0 likewise means "no filter" (and the scalar
                # mask rejects a concrete 0.0 outright).
                if min_p is not None and min_p > 0.0:
                    scaled = min_p_mask(scaled, min_p)
                nxt = jax.vmap(pick)(keys, scaled, pos)
            else:
                nxt = logits.argmax(axis=-1)
            # Device-side invariant (full-cache engines): pos NEVER
            # exceeds max_len - 1.  Free/done lanes keep decoding (the
            # price of one static program) and would otherwise advance
            # unboundedly; the clamp pins them to re-processing the
            # last slot — their outputs are discarded and admission
            # reseeds the lane, so correctness no longer leans on
            # dynamic_update_slice's start-clamping.  Live lanes are
            # unaffected: submit() budgets guarantee they finish at
            # pos <= max_len - 1.  Chunk-ADMITTING lanes park here too:
            # their garbage writes pin to the last slot, which the
            # request's own final decode step rewrites.  ROLLING
            # (windowed) engines are the exception by design: pos is
            # unbounded (the ring slot is pos % max_len), for idle
            # lanes too — harmless, since their writes land in slots
            # admission reseeds and the all-idle early-out in step()
            # stops the clock entirely.
            nxt_pos = (pos + 1 if self._rolling
                       else jnp.minimum(pos + 1, cfg.max_len - 1))
            return cache, nxt.astype(jnp.int32), nxt_pos, routes

        if self._hot_swap:
            # Hot-swap engines thread the params through as the first
            # step argument (the swap is then a warm-cache argument
            # change); the default spelling below bakes self.params in
            # at trace time — its jaxpr, and therefore every recorded
            # compile budget and IR census, is byte-identical to the
            # pre-round-20 one.
            return one_step_p

        def one_step(cache, cur, pos, keys, temps, tps, mps, *live,
                     chunk=None):
            return one_step_p(self.params, cache, cur, pos, keys,
                              temps, tps, mps, *live, chunk=chunk)
        return one_step

    def _make_step(self, n: int):
        one_step = self._one_step
        constrain = self._kv_constraint

        if self._hot_swap:
            def step_n_p(params, cache, cur, pos, keys, temps, tps,
                         mps, *live):
                # ``live`` (a stack with state planes only: ``_live``):
                # the lanes this round decodes; the step leaves every
                # other lane's state as it is.
                if constrain is not None:
                    cache = constrain(cache)

                def body(carry, _):
                    cache, cur, pos = carry
                    cache, cur, pos, routes = one_step(
                        params, cache, cur, pos, keys, temps, tps, mps,
                        *live)
                    return (cache, cur, pos), (cur, routes)
                (cache, cur, pos), (toks, routes) = jax.lax.scan(
                    body, (cache, cur, pos), None, length=n)
                if constrain is not None:
                    cache = constrain(cache)
                toks = toks.T                     # [lanes, n]
                return cache, cur, pos, (toks if routes is None
                                         else (toks, routes))
            # Donate the cache (now argument 1); params are NOT
            # donated — version N must survive the swap for rollback.
            return jax.jit(step_n_p, donate_argnums=1)

        def step_n(cache, cur, pos, keys, temps, tps, mps, *live):
            if constrain is not None:
                # Pod-sharded engines pin the cache layout here: GSPMD
                # then inserts the per-token collectives (psum per
                # block + the unembed gather) against the DECLARED
                # kv-heads sharding — compiled once, zero steady-state
                # compiles (the serving_sharded session asserts it).
                cache = constrain(cache)

            def body(carry, _):
                cache, cur, pos = carry
                cache, cur, pos, routes = one_step(cache, cur, pos, keys,
                                                   temps, tps, mps, *live)
                return (cache, cur, pos), (cur, routes)
            (cache, cur, pos), (toks, routes) = jax.lax.scan(
                body, (cache, cur, pos), None, length=n)
            if constrain is not None:
                cache = constrain(cache)
            toks = toks.T                         # [lanes, n]
            return cache, cur, pos, (toks if routes is None
                                     else (toks, routes))
        return jax.jit(step_n, donate_argnums=0)

    def _make_round_chunk(self):
        """The program of a round that holds a continuation chunk: ONE
        decode step (``_build_one_step``'s: the same attention,
        sampling and position advance as ``_make_step(1)``) whose layer
        body also takes ``rows [1, prefill_chunk]`` into lane ``lane``
        at ``off`` (``generate._chunk_in_place``'s ``chunk=``), so the
        round streams the weights once where ``_admit`` + ``step_n``
        stream them twice.  Returns the step's ``(cache, cur, pos,
        toks [lanes, 1])``.  The slab is read-only inside the layers, so
        the chunk's lane must stay parked through this round:
        :meth:`_fusable_chunk` never hands it a plan's last chunk.  Its
        name matches neither ``step_n`` nor ``_admit``: the by-name
        readers of those two programs do not take this one for theirs."""
        one_step = self._one_step

        if self._hot_swap:
            def round_chunk_p(params, cache, cur, pos, keys, temps, tps,
                              mps, rows, lane, off):
                cache, cur, pos, _ = one_step(
                    params, cache, cur, pos, keys, temps, tps, mps,
                    chunk=(rows, lane, off))
                return cache, cur, pos, cur[:, None]
            return jax.jit(round_chunk_p, donate_argnums=1)

        def round_chunk(cache, cur, pos, keys, temps, tps, mps, rows, lane,
                        off):
            cache, cur, pos, _ = one_step(cache, cur, pos, keys, temps, tps,
                                          mps, chunk=(rows, lane, off))
            return cache, cur, pos, cur[:, None]
        return jax.jit(round_chunk, donate_argnums=0)

    def _build_admission_programs(self) -> None:
        # Admission: prefill `width` positions of ONE lane (lane-sliced
        # cache write; padded tail slots stay masked until the decode
        # loop overwrites them).  ONE jitted program per bucket shape —
        # the start offset and pool slot are traced, so every prefix
        # length and chunk offset shares it.
        pooled = self._prefix_pool is not None
        constrain = self._kv_constraint
        # Nothing to seed and nothing that needs the base body: the
        # chunk is written into the slab in place, and a first chunk
        # and a continuation are ONE program.
        in_place = (self._prefix_lane is None and not pooled
                    and base_body_only(self.cfg, self.params,
                                       self.cache) is None)
        self._admit = _make_lane_admit(self.params, self.cfg,
                                       prefix_lane=self._prefix_lane,
                                       pooled=pooled,
                                       constrain=constrain,
                                       take_params=self._hot_swap,
                                       in_place=in_place)
        # Chunked prefill: the continuation program lands chunk k > 0
        # on the lane's existing cache (no reseed — that would erase
        # the earlier chunks).
        self._admit_cont = (
            None if self.prefill_chunk is None
            else self._admit if in_place
            else _make_lane_admit(self.params, self.cfg, seed=False,
                                  constrain=constrain,
                                  take_params=self._hot_swap))
        self._reseed = (_make_lane_reseed(prefix_lane=self._prefix_lane,
                                          constrain=constrain)
                        if self._prefix_lane is not None else None)
        self._reseed_pool = (_make_lane_reseed(pooled=True,
                                               constrain=constrain)
                             if pooled else None)
        # A round with a continuation chunk as ONE program — where the
        # stack is the plain one (untyped, one pass, nothing the base
        # body holds back), the chunk goes into the slab in place, the
        # engine is on one device with no tiers, and the round is this
        # class's own (the paged engine reads each round at once, the
        # speculative one's rounds are its own).  Everything else keeps
        # its two programs.
        cfg = self.cfg
        if (in_place and self.prefill_chunk is not None and not cfg.typed
                and cfg.n_passes == 1 and self._overlap
                and self.lane_tiers is None and self.mesh is None
                and type(self).step is ContinuousBatcher.step):
            self._round_chunk = self._make_round_chunk()

    # ------------------------------------------------------------ API

    def _validate_budget(self, p: int, max_new_tokens: int,
                         off: int | None = None) -> None:
        off = self._off if off is None else off
        if (not self._rolling
                and off + p + max_new_tokens > self.cfg.max_len):
            # Rolling engines have no total-length cap: lanes decode
            # past max_len on the ring (the admission bucket check
            # below still caps the PROMPT at the ring size — a longer
            # prompt's chunk would wrap mid-write).
            raise ValueError(
                f"prefix ({off}) + prompt ({p}) + "
                f"max_new_tokens ({max_new_tokens}) exceeds "
                f"max_len={self.cfg.max_len}")
        warm = p - 1
        if warm:
            # Every chunk of the admission plan must have a padded
            # write that fits the cache (dynamic_update_slice would
            # otherwise clamp the start and clobber earlier slots).
            self._chunk_plan(off, warm)

    def _bucket_for(self, width: int, start: int) -> int:
        """Smallest admission bucket >= ``width`` whose padded write at
        ``start`` stays inside the cache."""
        b = next((w for w in self._buckets
                  if w >= width and start + w <= self.cfg.max_len),
                 None)
        if b is None:
            raise ValueError(
                f"no admission bucket fits {width} prompt tokens at "
                f"cache offset {start} (buckets {self._buckets}, "
                f"max_len={self.cfg.max_len}); raise prompt_buckets "
                "or add a finer width")
        return b

    def _chunk_plan(self, off: int, warm: int, skip: int = 0) -> list:
        """The admission plan for ``warm`` prompt tokens decoding past
        ``off`` cached positions: a list of ``(start, width)`` — rows
        are materialized at execution.  Monolithic (one bucket-padded
        chunk at ``off``) unless chunked prefill is on and the warm
        length exceeds the chunk width; then full ``W``-wide chunks on
        the ``off + skip + k*W`` grid plus a bucket-padded tail whose
        start backs up so its padded end lands exactly at the warm
        frontier (re-prefilling the overlap is idempotent — same
        tokens, same cache prefix, same K/V).  ``skip`` drops the
        first ``skip`` warm tokens from the plan — the paged engine's
        stem-sharing admission, whose shared blocks already hold those
        positions' K/V (the backed-up tail can never reach into the
        skipped region: its width is at most one chunk, and the
        chunked branch only runs when more than a chunk remains).
        Raises if any padded write would overflow the cache."""
        if warm <= skip:
            return []
        w_chunk = self.prefill_chunk
        lo, span = off + skip, warm - skip
        if self._rolling or w_chunk is None or span <= w_chunk:
            return [(lo, self._bucket_for(span, lo))]
        m, rem = divmod(span, w_chunk)
        plan = [(lo + k * w_chunk, w_chunk) for k in range(m)]
        if plan[-1][0] + w_chunk > self.cfg.max_len:
            raise ValueError(
                f"chunked admission grid overflows the cache (chunk at "
                f"{plan[-1][0]} + {w_chunk} > {self.cfg.max_len})")
        if rem and (self.cfg.kv_ring_planes or self.cfg.state_planes):
            # Ring planes: the tail stays on the grid and its padding
            # goes unwritten (``n_real``) — a backed-up tail would ask a
            # ring for positions the chunk before has rolled out of it
            # (and would enter a state a second time).
            plan.append((lo + m * w_chunk,
                         self._bucket_for(rem, lo + m * w_chunk)))
        elif rem:
            # The chunk width is always a bucket (the constructor adds
            # it), so the smallest bucket >= rem is <= w_chunk < span:
            # the backed-up start always lands inside the grid, never
            # before lo, and its end off + warm fits by budget.
            b = next(w for w in self._buckets if w >= rem)
            plan.append((off + warm - b, b))
        return plan

    def _admission_plan(self, lane, prompt, off: int, warm: int):
        """Stage lane storage for an admission and return its chunk
        plan, or None to DECLINE for lack of KV storage (the paged
        engine's allocator-exhausted signal — surfaced as ``kv_blocks``
        backpressure by enqueue/pump).  The monolithic engine's storage
        is the lane row itself, so it never declines here."""
        del lane, prompt
        return self._chunk_plan(off, warm)

    def _abort_admission(self, lane) -> None:
        """Failure between storage staging and lane commit: release
        whatever _admission_plan staged (no-op for monolithic lanes;
        the paged engine frees the staged blocks)."""

    def _exec_admit(self, lane, start, rows, slot, n_real=None):
        """Execute the FIRST admission chunk (the one that seeds the
        lane) — ``slot`` is the pinned prefix-pool slot or None.
        Returns the jitted program it launched (``serving.admit``'s
        ``program`` is its name)."""
        real = () if n_real is None else (jnp.int32(n_real),)
        if slot is not None:
            self.cache = self._admit(
                self.cache, jnp.asarray(rows), jnp.int32(lane),
                jnp.int32(start), self._prefix_pool.slab,
                jnp.int32(slot))
        elif self._prefix_pool is not None:
            # Pooled engine, plain request: the gather program takes
            # slot -1 = "seed zeros".
            self.cache = self._admit(
                self.cache, jnp.asarray(rows), jnp.int32(lane),
                jnp.int32(start), self._prefix_pool.slab,
                jnp.int32(-1))
        else:
            self.cache = self._admit(*self._pargs(), self.cache,
                                     jnp.asarray(rows),
                                     jnp.int32(lane), jnp.int32(start),
                                     *real)
        return self._admit

    def _exec_reseed(self, lane, slot) -> None:
        """No admission chunk ran (1-token prompt) but the lane still
        needs its prefix K/V seeded."""
        if slot is not None:
            # 1-token prompt on a pooled prefix: no admission chunk
            # runs, but the lane still needs the prefix K/V.
            self.cache = self._reseed_pool(
                self.cache, jnp.int32(lane), self._prefix_pool.slab,
                jnp.int32(slot))
        elif self._prefix_lane is not None:
            # 1-token prompt: no admission chunk runs, but the lane
            # still needs the shared prefix's K/V (code-review
            # regression: skipping this read zeros where the prefix
            # belongs).
            self.cache = self._reseed(self.cache, jnp.int32(lane))
        # else: 1-token prompt, no prefix — stale slots stay masked
        # until the decode loop overwrites them.

    def _chunk_rows(self, prompt, off: int, start: int,
                    width: int) -> np.ndarray:
        """Bucket-padded token rows for the chunk covering positions
        ``[start, start + width)`` (real tokens up to the warm
        frontier, zero pad beyond — masked until overwritten)."""
        warm = prompt.size - 1
        rows = np.zeros((1, width), np.int32)
        lo = start - off
        hi = min(lo + width, warm)
        rows[0, :hi - lo] = prompt[lo:hi]
        return rows

    def _exec_chunk(self, lane, start, rows, n_real=None):
        real = () if n_real is None else (jnp.int32(n_real),)
        self.cache = self._admit_cont(*self._pargs(), self.cache,
                                      jnp.asarray(rows),
                                      jnp.int32(lane), jnp.int32(start),
                                      *real)
        return self._admit_cont

    def _exec_round_chunk(self, lane, start, rows):
        """DISPATCH the decode step that also admits ``rows`` into
        ``lane`` at ``start`` (:meth:`_make_round_chunk`); returns the
        round's tokens ``[lanes, 1]`` still on the device, as
        :meth:`_dispatch_step` does."""
        self.cache, self.cur, self.pos, toks = self._round_chunk(
            *self._pargs(), self.cache, self.cur, self.pos, self.keys,
            self.temps, self.tps, self.mps, jnp.asarray(rows),
            jnp.int32(lane), jnp.int32(start))
        return toks

    def _finish_admission(self, lane, st):
        """Last chunk landed: un-park the lane — set its decode
        position past the warm prompt and hand it the final prompt
        token, exactly where monolithic admission leaves a lane."""
        self.pos = self.pos.at[lane].set(st.off + st.prompt_len - 1)
        self.cur = self.cur.at[lane].set(
            int(st.tokens[st.prompt_len - 1]))

    def submit(self, prompt, max_new_tokens: int, key=None,
               temperature=None, top_p=None, min_p=None, eos_token=None,
               ttl=None, deadline=None, prefix_id=None):
        """Admit one request; returns its lane id, or None if the
        engine is full.  ``prompt``: 1-D int tokens; ``key``: per-
        request PRNG key (required iff THIS request samples).

        ``temperature`` / ``top_p`` / ``min_p`` / ``eos_token``:
        per-request overrides of the engine defaults — engines built
        with ``per_request_sampling=True`` only (``eos_token`` is
        host-side bookkeeping and works on every engine).  Pass
        ``top_p=1.0`` / ``min_p=0.0`` (the explicit no-op values) for
        an unfiltered request on an engine whose default filters.
        ``top_p=1.0`` means "no nucleus filter" EVERYWHERE — here,
        the engine scalar path, and solo ``generate`` all bypass the
        mask at >= 1.0 (round-6 parity fix), so a request copying its
        solo call's ``top_p=1.0`` replays that run exactly.

        ``ttl`` (seconds from now) / ``deadline`` (absolute ``clock()``
        time): the request's deadline.  A request that is already
        expired never occupies a lane — its structured timeout result
        is recorded (see :meth:`results`) and None is returned; one
        that expires mid-decode is evicted at the next ``step()`` the
        same way.  Deadline-carrying requests report through
        ``poll``/``take``/``results``, not ``drain``; this request's id
        is exposed as ``self.last_request_id`` (the queue-level
        :meth:`enqueue` API wraps all of this and returns the request
        id directly).

        ``prefix_id``: decode past a pooled prefilled prefix
        (``prefix_pool=`` engines) — the lane is seeded from the
        pool's device slab, the prefix tokens run no prefill work, and
        the output matches ``generate(prompt, cfg, n,
        prompt_cache=(segment, P))`` exactly.  The entry is pinned
        until the lane is vacated.

        On a ``prefill_chunk=`` engine, a prompt longer than the chunk
        width returns its lane immediately but PARKED: the remaining
        prefill chunks run one per ``step()`` interleaved with decode,
        and the lane starts emitting when the last chunk lands.

        Elastic engines (``lane_tiers=``) reject bare ``submit``: lane
        indices are not stable across tier resizes, so requests must go
        through the id-keyed :meth:`enqueue` surface.

        The whole admission runs under the engine lock, so a submit
        racing ``begin_shutdown`` either lands its lane before the
        drain looks (and is drained) or raises EngineClosed — the same
        contract :meth:`enqueue` documents.
        """
        with self._admission_lock:
            return self._submit_locked(prompt, max_new_tokens, key,
                                       temperature, top_p, min_p,
                                       eos_token, ttl, deadline,
                                       prefix_id)

    def _submit_locked(self, prompt, max_new_tokens, key, temperature,
                       top_p, min_p, eos_token, ttl, deadline,
                       prefix_id=None):
        if self.lane_tiers is not None and not self._admitting_internal:
            raise ValueError(
                "elastic engines (lane_tiers=...) admit through "
                "enqueue(): a tier resize compacts lanes, so the lane "
                "id submit() would return can dangle")
        self._check_open()
        prompt = self._validate_request_args(prompt, max_new_tokens)
        p = prompt.size
        if ((temperature is not None or top_p is not None
             or min_p is not None) and not self.per_request_sampling):
            raise ValueError(
                "per-request temperature/top_p/min_p need "
                "ContinuousBatcher(per_request_sampling=True) — the "
                "default engine compiles the constructor's sampling "
                "params into the step")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if min_p is not None and not 0.0 <= min_p <= 1.0:
            # 0.0 is the explicit "no min-p filter" override.
            raise ValueError(f"min_p must be in [0, 1], got {min_p}")
        if temperature is not None and temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {temperature}")
        if eos_token is not None and not (
                0 <= eos_token < self.cfg.vocab_size):
            raise ValueError(
                f"eos_token {eos_token} outside vocab [0, "
                f"{self.cfg.vocab_size})")
        eff_t = self.temperature if temperature is None else temperature
        if eff_t <= 0 and ((top_p is not None and top_p < 1.0)
                           or (min_p is not None and min_p > 0.0)):
            # The explicit no-op values (top_p=1.0 / min_p=0.0) stay
            # legal on greedy requests — they turn a default filter OFF.
            raise ValueError(
                "per-request top_p/min_p need a sampling temperature "
                f"(effective temperature is {eff_t})")
        off, slot, lane = self._off, None, None
        if prefix_id is not None:
            # Pin FIRST (see _pin_prefix): from here on, a concurrent
            # pool.put can never evict this entry, so the slot stays
            # ours through the slab gather below.  Every non-admission
            # exit must release the pin.
            off, slot, _ = self._pin_prefix(prefix_id)
        try:
            self._validate_budget(p, max_new_tokens, off=off)
            if (key is None) == (eff_t > 0):
                raise ValueError(
                    "pass a per-request key iff this request samples "
                    f"(effective temperature={eff_t})")
            dl = self._deadline_of(ttl, deadline)
            if self._expired_on_arrival(dl, prompt, p):
                # The acceptance contract: an already-dead request
                # never occupies a lane; its timeout is a structured
                # result.
                if prefix_id is not None:
                    self._prefix_pool.release(prefix_id)
                return None
            free = self.free_lanes()
            if not free:
                self._decline_full()
                if prefix_id is not None:
                    self._prefix_pool.release(prefix_id)
                return None
            lane = free[0]
            chaos.probe("serving.admit")
            # The request's id (enqueue-assigned for internal
            # admission, fresh otherwise) — claimed BEFORE the
            # admission chunk so every span/event below carries it.
            rid = self._claim_rid()
            if not self._admitting_internal:
                obs.event("serving.submit", request_id=rid,
                          prompt_len=p, max_new=int(max_new_tokens))

            warm = p - 1
            plan = self._admission_plan(lane, prompt, off, warm)
            if plan is None:
                # KV-storage decline (the paged allocator is out of
                # blocks): no lane is occupied; enqueue/pump treat it
                # as backpressure, not a timeout.
                self._decline("kv_blocks")
                if prefix_id is not None:
                    self._prefix_pool.release(prefix_id)
                return None
            chunks, filled = None, off
            if plan:
                start0, width0 = plan[0]
                rows = self._chunk_rows(prompt, off, start0, width0)
                filled = min(start0 + width0, off + warm)
                with obs.span("serving.admit", bucket=width0,
                              positions=filled - start0,
                              chunks=len(plan), lane=lane,
                              request_id=rid) as sp:
                    fn = self._exec_admit(lane, start0, rows, slot,
                                          **self._real(filled - start0))
                    if sp is not None:
                        sp.fields.update(
                            program=_program_name(fn),
                            attended=self._attended(self.cache, start0,
                                                    width0))
                self._admit_programs += 1
                if len(plan) > 1:
                    chunks = [(s, self._chunk_rows(prompt, off, s, w))
                              for s, w in plan[1:]]
            else:
                self._exec_reseed(lane, slot)
            if chunks is None:
                self.pos = self.pos.at[lane].set(off + warm)
                self.cur = self.cur.at[lane].set(int(prompt[-1]))
            else:
                # Parked: the lane burns decode rows at the clamp slot
                # until its last chunk lands (one_step's clamp note).
                self.pos = self.pos.at[lane].set(self.cfg.max_len - 1)
                self.cur = self.cur.at[lane].set(0)
            if self._keyed and key is not None:
                self.keys = self.keys.at[lane].set(key)
            if self.per_request_sampling:
                self.temps = self.temps.at[lane].set(float(eff_t))
                self.tps = self.tps.at[lane].set(float(
                    (self.top_p or 1.0) if top_p is None else top_p))
                self.mps = self.mps.at[lane].set(float(
                    (self.min_p or 0.0) if min_p is None else min_p))

            # The pin taken above becomes the lane's reference here.
            self._lane_state[lane] = _Lane(
                request_id=rid, prompt_len=p,
                max_new=max_new_tokens, key=key, tokens=list(prompt),
                eos=self.eos_token if eos_token is None else eos_token,
                deadline=dl, born=self._clock(), chunks=chunks,
                filled=filled, off=off, prefix_id=prefix_id)
            if not self._admitting_internal:
                self.last_request_id = rid
        except Exception:
            # Any failure between pin and lane commit (validation, a
            # chaos-injected admit fault, a dispatch error) must not
            # leak the prefix reference — nor, on the paged engine,
            # the KV blocks the admission plan staged.
            if prefix_id is not None:
                self._prefix_pool.release(prefix_id)
            if lane is not None:
                self._abort_admission(lane)
            raise
        if chunks is not None:
            self._admitting.append(lane)
        return lane

    def traced_for_analysis(self):
        """Trace targets for the IR lint (analysis/ir_lint.py): the
        jitted single-token decode step over the engine's live lane
        state, plus the admission chunk program at the smallest bucket
        (the round-10 engine builds — chunked continuations and pool
        gathers ride the same program shape).  Nothing executes — the
        lint traces and lowers only."""
        from distkeras_tpu.analysis.ir_lint import TraceSpec

        if 1 not in self._steps:
            self._steps[1] = self._make_step(1)
        mode = ("per_request" if self.per_request_sampling
                else "sampled" if self.temperature > 0 else "greedy")
        if self._prefix_pool is not None:
            mode += "_pooled"
        if self._kv_axis is not None:
            # Pod-sharded engine: the census pins this step's per-token
            # collectives (scripts/comm_budget.json).
            mode += f"_tp{int(self.mesh.shape[self._kv_axis])}"
        rows = jnp.zeros((1, self._buckets[0]), jnp.int32)
        pargs = self._pargs()  # hot-swap engines take params first
        d = len(pargs)
        admit_args = pargs + (self.cache, rows, jnp.int32(0),
                              jnp.int32(self._off)) + tuple(
            jnp.int32(n) for n in self._real(rows.shape[1]).values())
        if self._prefix_pool is not None:
            admit_args += (self._prefix_pool.slab, jnp.int32(0))
        return [
            TraceSpec(
                name=f"continuousbatcher_{mode}/decode_step",
                fn=self._steps[1],
                args=pargs + (self.cache, self.cur, self.pos,
                              self.keys, self.temps, self.tps,
                              self.mps) + self._live(),
                donate_argnums=(d,)),
            TraceSpec(
                name=f"continuousbatcher_{mode}/admit_b"
                     f"{self._buckets[0]}",
                fn=self._admit, args=admit_args, donate_argnums=(d,)),
        ]

    # An engine whose next dispatch needs nothing of the last round's
    # tokens keeps one round in flight.  The paged engine's does (page
    # tables grow from the transcript) and reads each round at once.
    _overlap = True

    # The program of a round that holds a continuation chunk
    # (:meth:`_make_round_chunk`), on the engines that fuse it
    # (``_build_admission_programs`` says which); None: two programs.
    _round_chunk = None

    def step(self, n: int = 1):
        """Advance every lane ``n`` tokens in ONE device round;
        returns ``{lane: [tokens...]}`` for lanes that emitted.

        **One decode round stays in flight.**  A call dispatches its
        round and then reads the round the call BEFORE dispatched, so
        while the host blocks in that read, walks its tokens, returns
        to its caller and comes back, the device already holds the next
        programs: nothing the next dispatch needs is on the host (the
        current tokens, positions and cache are device arrays, a
        budget is a host count of steps dispatched).  For a caller: a
        token is returned by the call AFTER the one that dispatched it
        — a request's first token comes one round later, a finished
        lane is freed one round later — and a call with nothing left to
        dispatch returns the round still unread.  Every returned token
        exists on the host; ``running()``, ``free_lanes()``, ``take``,
        ``drain``, ``partial`` and ``results`` speak of what has been
        RETURNED.  A lane that ended (eos, budget, deadline) while a
        further round of it was in flight has that round's row
        dropped, as ``n > 1`` drops a window's surplus.  (The paged
        engine, whose dispatch grows page tables from the transcript,
        reads each round at once: ``_overlap``.)

        ``n > 1`` amortizes the per-dispatch host latency at the cost
        of admission granularity: new requests wait for the window to
        finish, and a lane that hits its eos/budget mid-window keeps
        decoding privately — the surplus tokens are discarded here,
        identical to truncating generate()'s sticky-fill output.
        Emitted tokens are EXACTLY step(1)'s.

        Chunked prefill runs here too: at most ONE pending admission
        chunk executes per call (FIFO across parked lanes) before the
        decode dispatch.  A request admitted since the last decode
        dispatch (by ``enqueue``, by the caller's ``pump()`` or by this
        call's) has run its FIRST chunk too, so two chunks can stand
        between two decode rounds: the round's ``chunks`` field counts
        them.

        With a telemetry session active the call is one
        ``serving.round`` span — children ``serving.pump``,
        ``serving.admit`` / ``serving.admit_chunk``, ``serving.step``
        (the dispatch), ``serving.collect`` (the read of the round
        before), ``serving.emit_loop``, ``serving.reap`` — closed with
        the round's counts (:meth:`_close_round`;
        docs/observability.md).  A dispatching span names the
        ``program`` it launched; ``serving.step`` numbers its launch
        (``seq``, the engine's count of decode dispatches) and
        ``serving.collect`` says which launch it reads.

        Runs under the engine lock end to end: a concurrent
        ``enqueue`` can trigger a tier resize (scale-up), and the
        device state this step captures must not be swapped and
        compacted under it mid-dispatch.
        """
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        if self.lane_tiers is not None and n not in self._step_windows:
            raise ValueError(
                f"elastic engines pre-compile their decode windows; "
                f"step({n}) is not in step_windows={self._step_windows}"
                " — declare it at construction (a lazy compile here "
                "would break the no-recompile contract across tiers)")
        with self._admission_lock, obs.span("serving.round") as rnd:
            if rnd is not None:
                self._waited_ms = 0.0
            self.pump()
            # Tier hysteresis BEFORE the idle early-out: an idle
            # elastic engine must still step its lane count back down.
            self._maybe_scale_down()
            # The pending chunk goes out as a program of its own, or —
            # ``fused`` — inside this round's decode program.
            fused = self._fusable_chunk(n)
            if not fused:
                self._run_pending_chunk()
            lanes = self._decoding()
            # ``_inflight`` is rebound only after the dispatch: one that
            # raises keeps the unread round for the next call.
            unread, launched = self._inflight, None
            chunks = self._admit_programs + fused
            if lanes:
                chaos.probe("serving.step")
                self._admit_programs = 0
                with obs.span("serving.step", n=n) as sp:
                    if sp is not None:
                        sp.fields["attended"] = self._step_attended(n)
                    if fused:
                        toks = self._dispatch_round_chunk(sp)
                    else:
                        toks = self._dispatch_step(n)
                    for a in jax.tree.leaves(toks):
                        a.copy_to_host_async()
                    self._number_dispatch(
                        sp, self._round_chunk if fused else self._steps[n])
                for _, s in lanes:
                    s.launched += n
                launched = (toks, lanes, self._dispatch_seq)
                if not self._overlap:
                    unread, launched = launched, None
            self._inflight = launched
            # Nothing to dispatch and nothing read (every lane empty,
            # finished-but-undrained, or still admitting): nothing can
            # emit.  Reap all the same: a parked (admitting) lane whose
            # deadline expired must still be evicted promptly, not only
            # once decode resumes.
            idle = not (lanes or unread or self._flushed)
            out = {} if unread is None else self._collect(unread)
            if self._flushed:
                self._return_flushed(out)
            # Deadline granularity is one step window: tokens emitted
            # in the window that straddles the deadline are kept in
            # the partial result.
            self._reap()
            if obs.active() is not None:
                self._close_round(rnd, out, chunks, idle,
                                  overlapped=launched is not None
                                  and unread is not None,
                                  state_lanes=len(lanes), fused=fused)
            return out

    def _return_flushed(self, out: dict) -> None:
        """Add to ``out`` the tokens a :meth:`_flush_round` read between
        two ``step()`` results, under the lanes their requests hold NOW
        (a resize may have moved them).  A flush leaves no round
        unread, so the call that returns them has read none itself."""
        at = {id(s): i for i, s in enumerate(self._lane_state)}
        out.update((at[id(st)], toks) for st, toks in self._flushed
                   if id(st) in at)
        self._flushed = []

    def _dispatch_step(self, n: int):
        """DISPATCH the ``n``-token decode window over the engine's
        storage — the first of a round's two hooks; returns the
        emitted-token matrix ``[lanes, n]`` still on the device
        (``_read_tokens`` is the read).  The paged engine overrides
        this to grow page tables first and thread them through its
        step."""
        if n not in self._steps:
            self._steps[n] = self._make_step(n)
        self.cache, self.cur, self.pos, toks = self._steps[n](
            *self._pargs(), self.cache, self.cur, self.pos, self.keys,
            self.temps, self.tps, self.mps, *self._live())
        return toks

    def _fusable_chunk(self, n: int) -> bool:
        """Whether this round's pending chunk goes through the layers
        WITH the decode step (:meth:`_make_round_chunk`): the engine
        built that program, the round is one step, the chunk is a full
        ``prefill_chunk`` wide with more of its plan to come (a plan's
        last chunk un-parks its lane into this round's decode, which
        must find the chunk in the slab: it stays a program of its own,
        as a lane's first chunk and a bucket-padded tail do), and some
        lane decodes.  Host comparisons on what the engine holds."""
        if self._round_chunk is None or n != 1 or not self._admitting:
            return False
        chunks = self._lane_state[self._admitting[0]].chunks
        return (len(chunks) > 1
                and chunks[0][1].shape[1] == self.prefill_chunk
                and bool(self._decoding()))

    def _dispatch_round_chunk(self, sp):
        """DISPATCH the decode step with the pending chunk inside it;
        ``sp``, the round's ``serving.step`` span, takes what a
        ``serving.admit_chunk`` span would have said (no such span is
        opened: the admission readers divide the ``_admit`` programs'
        device time by those spans' ``bucket``).  Returns the round's
        tokens on the device, as :meth:`_dispatch_step` does."""
        lane, st, start, rows, _, new = self._pop_chunk()
        if sp is not None:
            sp.fields.update(
                bucket=rows.shape[1], positions=new,
                remaining=len(st.chunks), request_id=st.request_id,
                chunk_attended=self._attended(self.cache, start,
                                              rows.shape[1]))
        return self._exec_round_chunk(lane, start, rows)

    def _decoding(self) -> list:
        """``(lane, _Lane)`` of the lanes with a token still to decode:
        not empty, finished, admitting, or with their whole budget
        already dispatched."""
        return [(i, s) for i, s in enumerate(self._lane_state)
                if s is not None and not s.done and s.chunks is None
                and s.launched < s.max_new]

    def _live(self) -> tuple:
        """The decode step's last argument where the cache holds state
        planes: the mask ``[lanes]`` of the lanes that decode in the
        round about to be dispatched.  A K/V slot written for a lane
        that does not decode is a parked write, masked by position; a
        state has no positions, and a step on an ADMITTING lane would
        decay and add to the very state its chunks are building — so
        the step is told, and leaves the others' state unread.  A
        stack of latent layers takes the mask too: a parked lane stands
        at ``max_len - 1``, and its step would read the whole row for
        nothing — told, the kernel reads none of it.
        Nothing for every other engine: its programs keep their
        signature."""
        if not (self.cfg.state_planes or self.cfg.latent_planes):
            return ()
        mask = np.zeros((len(self._lane_state),), np.int32)
        mask[[i for i, _ in self._decoding()]] = 1
        return (jnp.asarray(mask),)


__all__ = ["ContinuousBatcher", "KV_INT8_LANE_ADVISORY"]
