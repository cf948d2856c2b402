"""SpeculativeBatcher: draft-assisted continuous batching.

Every lane advances up to ``n_draft + 1`` positions per device
round-trip: ``n_draft`` cheap draft proposals, ONE target verify
chunk, per-lane acceptance.  The lane/admission machinery is shared
with :class:`~distkeras_tpu.serving.lanes.ContinuousBatcher` through
:class:`~distkeras_tpu.serving.engine._LaneEngine`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import obs
from distkeras_tpu.resilience import chaos

from distkeras_tpu.models.generate import (_decode_chunk, _device_tree,
                                           init_cache, rolling_eligible)
from distkeras_tpu.models.speculative import speculative_accept
from distkeras_tpu.models.transformer import (TransformerConfig,
                                               reject_extended)
from distkeras_tpu.serving.engine import (_Lane, _LaneEngine,
                                          _make_lane_admit,
                                          _make_lane_reseed,
                                          _program_name)


class SpeculativeBatcher(_LaneEngine):
    """Draft-assisted continuous batching: every lane advances up to
    ``n_draft + 1`` positions per device round-trip.

    The lane/admission machinery is :class:`ContinuousBatcher`'s; the
    step is one iteration of :func:`speculative_generate`'s body
    vectorized over lanes at divergent positions — ``n_draft`` draft
    proposals (the draft's first chunk is T=2, closing the
    full-acceptance cache gap exactly like the solo loop), ONE target
    verify chunk, per-lane acceptance, and a per-lane advance of
    ``accepted + 1`` tokens.  Rejected-tail cache writes land
    beyond each lane's frontier and are masked until overwritten
    (the _decode_chunk staleness argument), so lanes never interact.

    Contract: every request's emitted tokens are EXACTLY its solo
    ``speculative_generate`` run's (batch 1, same key).  Greedy
    (``temperature=0``) that is ``generate``'s greedy rollout;
    sampled (engine-level ``temperature > 0``, per-request keys) it
    is the Leviathan/Chen speculative-sampling rollout — each lane
    carries its own iteration counter so its accept/corrective draws
    replay the solo run's ``fold_in(key, iteration)`` stream exactly,
    whenever the lane was admitted.  Scope: no top-k/p filters (the
    solo fn has none either); unsupported combinations reject loudly.

    **Shared prefixes** (round-10 — the v1 "no shared prefix"
    exclusion is LIFTED): attach a
    ``PrefixPool(cfg, slots, draft_cfg=draft_cfg)`` whose segments are
    ``(target_cache, draft_cache)`` pairs (the same prefix prefilled
    through BOTH models) and ``submit``/``enqueue`` take
    ``prefix_id=`` — both lane caches are seeded from the pooled
    segments by a device gather, so the prefix tokens run zero prefill
    work on either model.  Greedy pooled requests keep exact parity
    with ``generate(prompt, cfg, n, prompt_cache=(target_segment, P))``
    (greedy speculative IS the greedy target rollout); sampled pooled
    requests draw on the engine's iteration-keyed stream (valid
    target-distribution samples — there is no solo
    ``speculative_generate(prompt_cache=...)`` to replay).  A 1-token
    prompt against a prefix needs the prefix's ``last_token`` recorded
    at ``PrefixPool.put`` (the draft's first chunk rewrites the
    position before the prompt).  Full-cache configs only.

    Budget (full-cache): a request needs ``prefix + prompt +
    max_new_tokens + n_draft <= max_len`` on BOTH models (the verify
    chunk writes ``n_draft + 1`` slots past the frontier; same slack
    as the solo fn).  Finished lanes keep decoding with their frontier
    clamped at the last budget-safe position — outputs discarded,
    admission reseeds.

    ROLLING lanes (round-7): when BOTH configs are windowed
    (rope + ``attention_window``, with ``window + n_draft + 1 <=
    max_len`` each — solo speculative's ring bound), lanes decode past
    ``max_len`` on the ring caches with no total-length cap (prompts
    still must fit the ring), matching solo windowed
    ``speculative_generate`` per request; and the draft-fault FALLBACK
    is ring-compatible — it inherits the lanes' unbounded positions
    and ring slabs mid-wrap, so greedy parity with solo rolling
    ``generate`` holds past ``max_len`` through a degradation.

    **Pod-sharded** (round 17, ``plan=``/``mesh=``): the TARGET model
    shards per the plan's rules exactly like the dense engine (params
    TP-placed, ``tcache``'s kv-heads dim over the derived axis,
    GSPMD's per-token collectives compiled in) while the DRAFT model
    replicates whole — a draft is small by design, so replication
    costs little and keeps the draft chunks collective-free.  Every
    serve-phase program warms at construction
    (:meth:`_warm_sharded`); emitted tokens stay bit-exact vs the
    solo engine.  Full-cache configs only; rejects ``prefix_pool=``
    (one slab placement cannot serve a sharded target and a
    replicated draft).
    """

    def __init__(self, params, draft_params, cfg: TransformerConfig,
                 draft_cfg: TransformerConfig, lanes: int = 8,
                 n_draft: int = 4, temperature: float = 0.0,
                 eos_token=None, prompt_buckets=(8, 32, 128, 512),
                 max_queue: int = 0, clock=None, prefix_pool=None,
                 plan=None, mesh=None):
        # Windowed configs run ROLLING speculative lanes (round-7): the
        # verify chunk writes through _decode_chunk's modular ring
        # scatter under the same bound as solo speculative_generate —
        # window + n_draft + 1 <= max_len keeps every rejected tail's
        # slots outside every live query's band — and lanes decode past
        # max_len with no total-length cap, exactly like rolling
        # ContinuousBatcher lanes.  Crucially the DEGRADED path stays
        # ring-compatible too: the target-only fallback advances the
        # same unbounded per-lane positions over the same ring slabs,
        # so a draft fault mid-wrap preserves greedy solo parity past
        # max_len.  Mixed full/windowed model
        # pairs stay rejected: their caches disagree on what a
        # position IS past the smaller ring.
        for c in (cfg, draft_cfg):
            reject_extended(c, "SpeculativeBatcher (speculative decoding)")
        self._rolling = False
        if (cfg.attention_window is None) != (draft_cfg.attention_window
                                              is None):
            raise ValueError(
                "speculative serving needs the target and draft caches "
                "to agree: both full-cache or both windowed (got "
                f"target window={cfg.attention_window}, draft "
                f"window={draft_cfg.attention_window})")
        if cfg.attention_window is not None:
            if prefix_pool is not None:
                raise ValueError("prefix_pool requires full-cache "
                                 "configs (no attention_window)")
            for name, c in (("cfg", cfg), ("draft_cfg", draft_cfg)):
                if not rolling_eligible(c):
                    raise ValueError(
                        f"windowed speculative serving runs rolling "
                        f"lanes, which needs {name}.rope=True and "
                        f"attention_window <= max_len")
                if c.attention_window + n_draft + 1 > c.max_len:
                    raise ValueError(
                        f"rolling speculative lanes need "
                        f"{name}.attention_window "
                        f"({c.attention_window}) + n_draft + 1 "
                        f"({n_draft + 1}) <= max_len ({c.max_len}): "
                        "the verify chunk's rejected tail must alias "
                        "outside every live query's band")
            self._rolling = True
        if draft_cfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft vocab_size {draft_cfg.vocab_size} != target "
                f"{cfg.vocab_size} — the models must share a tokenizer")
        if n_draft < 1:
            raise ValueError(f"n_draft must be >= 1, got {n_draft}")
        # Eager impossibility check: _cap = min(max_len) - n_draft - 1
        # is the largest prompt+generation budget any request can use;
        # _cap <= 0 means NO request can ever be admitted, so fail at
        # construction naming the real culprits instead of letting
        # every submit() blame the prompt.
        if min(cfg.max_len, draft_cfg.max_len) <= n_draft + 1:
            raise ValueError(
                f"n_draft={n_draft} leaves no decode budget: the verify "
                f"chunk needs n_draft + 1 cache slots of slack, but "
                f"min(max_len)={min(cfg.max_len, draft_cfg.max_len)} "
                f"(target {cfg.max_len}, draft {draft_cfg.max_len}) <= "
                f"n_draft + 1 = {n_draft + 1}; lower n_draft or raise "
                "max_len")
        if lanes < 1:
            raise ValueError(f"lanes must be >= 1, got {lanes}")
        if temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {temperature}")
        if eos_token is not None and not 0 <= eos_token < cfg.vocab_size:
            raise ValueError(
                f"eos_token {eos_token} outside vocab [0, "
                f"{cfg.vocab_size})")
        if prefix_pool is not None:
            if prefix_pool.draft_cfg is None:
                raise ValueError(
                    "SpeculativeBatcher needs a speculative pool — "
                    "PrefixPool(cfg, slots, draft_cfg=draft_cfg), whose "
                    "segments are (target, draft) cache pairs")
            want = jax.eval_shape(lambda: (init_cache(cfg, 1),
                                           init_cache(draft_cfg, 1)))
            got = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                prefix_pool.slab)
            if (jax.tree.structure(want) != jax.tree.structure(got)
                    or jax.tree.leaves(want) != jax.tree.leaves(got)):
                raise ValueError(
                    f"prefix_pool was built for different configs "
                    f"(pool segments {got}, engine caches {want})")
        # Pod-sharded speculative serving (round 17): the TARGET model
        # shards per the plan's rules exactly like the dense engine —
        # params TP-placed, tcache's kv-heads dim over the derived
        # axis — while the DRAFT model replicates whole (a draft is
        # small by design; replicating it sidesteps any
        # head-divisibility question on its config and keeps the
        # draft chunks collective-free).  Full-cache configs only;
        # every serve-phase program warms at construction
        # (_warm_sharded), same zero-compile contract as the dense
        # engine.
        if (plan is None) != (mesh is None):
            raise ValueError(
                "pass plan= and mesh= together: the plan's rules only "
                "mean something against a concrete mesh (use "
                "parallel.sharding.serving_plan() for the standard TP "
                "layout)")
        if plan is not None:
            if cfg.attention_window is not None:
                raise ValueError(
                    "pod-sharded speculative serving needs full-cache "
                    "configs (no attention_window): the ring slab's "
                    "rolling scatter has no stable sharded layout to "
                    "pin")
            if prefix_pool is not None:
                raise ValueError(
                    "plan= does not compose with prefix_pool= on the "
                    "speculative engine: pooled segments are (target, "
                    "draft) cache pairs and the draft half replicates "
                    "while the target shards — one slab placement "
                    "cannot satisfy both; use the dense engine for "
                    "pooled sharded serving")
        self.plan, self.mesh = plan, mesh
        if plan is not None:
            from distkeras_tpu.parallel.rules import serving_kv_axis

            self._kv_axis = serving_kv_axis(plan, mesh, cfg)
        self._prefix_pool = prefix_pool
        if plan is not None:
            self.params = jax.device_put(
                params, plan.tree_shardings(mesh, params))
            self.draft_params = self._place_replicated(draft_params)
        else:
            self.params = _device_tree(params)
            self.draft_params = _device_tree(draft_params)
        self.cfg, self.draft_cfg = cfg, draft_cfg
        self.lanes, self.n_draft = lanes, n_draft
        self.temperature = temperature
        self.eos_token = eos_token
        # The verify chunk writes k+1 slots past the frontier on BOTH
        # caches; bucket admission caps prompts the same way.  Rolling
        # engines have no frontier cap (positions are unbounded on the
        # ring) — only the prompt must fit it: the admission warm
        # chunk is uniform-pos and must not wrap, so p - 1 <= ring - 1.
        if self._rolling:
            self._cap = None
            bucket_cap = min(cfg.max_len, draft_cfg.max_len) - 1
        else:
            self._cap = min(cfg.max_len, draft_cfg.max_len) - n_draft - 1
            bucket_cap = self._cap
        self._buckets = tuple(sorted(
            {min(int(w), bucket_cap) for w in prompt_buckets}
            | {bucket_cap}))
        self._lane_state: list[_Lane | None] = [None] * lanes
        self._next_id = 0
        self._init_admission(max_queue, clock)
        # Graceful degradation: when the draft half of the step faults
        # (chaos-injected, or a real dispatch failure caught with the
        # engine state intact), the engine permanently switches to a
        # plain target-only decode step — requests still complete,
        # just without the speculative speedup.  Greedy engines keep
        # exact solo-generate parity through the switch (greedy
        # speculative == greedy generate by construction); sampled
        # engines keep drawing valid samples but on a different PRNG
        # stream than the solo speculative rollout.
        self._degraded = False
        self.degraded_error = None
        self._fallback = None

        # Sharded engines commit the target cache under the plan's KV
        # sharding, the draft cache and row state replicated —
        # placement is part of the jit cache key for committed arrays,
        # so live state and warm-up dummies must agree (identity
        # placements unsharded).
        self.tcache = self._place_kv(init_cache(cfg, lanes))
        self.dcache = self._place_replicated(init_cache(draft_cfg,
                                                        lanes))
        self.pos = jnp.zeros((lanes,), jnp.int32)   # last FINAL position
        self.cur = jnp.zeros((lanes,), jnp.int32)   # token at pos
        self.prev = jnp.zeros((lanes,), jnp.int32)  # token at pos - 1
        # Sampled mode: per-lane request keys + per-lane ITERATION
        # counters — a lane's draws are keyed fold_in(key, iter) like
        # the solo loop's, so wherever the lane was admitted it
        # replays its solo b=1 run's PRNG stream exactly (RNG bits are
        # shape-row invariant: (V,) and (1, V) draws agree).
        self.keys = jnp.stack([jax.random.key(0)] * lanes)
        self.iters = jnp.zeros((lanes,), jnp.int32)
        if mesh is not None:
            (self.pos, self.cur, self.prev, self.keys, self.iters) = (
                self._place_replicated(x)
                for x in (self.pos, self.cur, self.prev, self.keys,
                          self.iters))

        k = n_draft
        idx = jnp.arange(k + 1)
        rolling = self._rolling
        cap = None if rolling else jnp.int32(self._cap)
        sampled = temperature > 0
        constrain = self._kv_constraint

        def step_fn(tcache, dcache, prev, cur, pos, keys, iters):
            if constrain is not None:
                # Pin the target cache's sharded layout inside the
                # compiled program (the draft cache is replicated —
                # replicated in, replicated out, nothing to pin).
                tcache = constrain(tcache)
            # ---- draft: first chunk T=2 rewrites [pos-1, pos] (the
            # full-acceptance gap closure, exactly the solo body's).
            pos0 = jnp.maximum(pos - 1, 0)
            first = jnp.where(
                (pos == 0)[:, None],
                jnp.stack([cur, jnp.zeros_like(cur)], axis=1),
                jnp.stack([prev, cur], axis=1))
            lg2, dcache = _decode_chunk(self.draft_params, dcache,
                                        first, pos0, draft_cfg)
            lg = jnp.take_along_axis(
                lg2, (pos - pos0)[:, None, None], axis=1)[:, 0]
            kit = jax.vmap(jax.random.fold_in)(keys, iters)
            d_toks, q_logps = [], []
            for j in range(k):
                if sampled:
                    logp = jax.nn.log_softmax(lg / temperature, axis=-1)
                    nxt = jax.vmap(
                        lambda kk, row, _j=j: jax.random.categorical(
                            jax.random.fold_in(kk, _j), row))(kit, logp)
                    q_logps.append(logp)
                else:
                    nxt = lg.argmax(axis=-1)
                nxt = nxt.astype(jnp.int32)
                d_toks.append(nxt)
                if j < k - 1:
                    lgj, dcache = _decode_chunk(
                        self.draft_params, dcache, nxt[:, None],
                        pos + 1 + j, draft_cfg)
                    lg = lgj[:, 0]
            d = jnp.stack(d_toks, axis=1)               # [lanes, k]

            # ---- one target verify chunk over [cur, d_1..d_k]
            chunk = jnp.concatenate([cur[:, None], d], axis=1)
            tlog, tcache = _decode_chunk(self.params, tcache, chunk,
                                         pos, cfg)
            if sampled:
                # The Leviathan/Chen rule via the ONE shared
                # definition (speculative.speculative_accept); only
                # the draw keys differ from the solo loop — per-lane
                # iteration-keyed so each lane replays its solo run.
                p_logp = jax.nn.log_softmax(tlog / temperature, -1)
                q_logp = jnp.stack(q_logps, axis=1)
                u = jax.vmap(lambda kk: jax.random.uniform(
                    jax.random.fold_in(kk, k + 1), (k,)))(kit)
                n, corr_logits = speculative_accept(p_logp, q_logp,
                                                    d, u)
                corrective = jax.vmap(
                    lambda kk, row: jax.random.categorical(
                        jax.random.fold_in(kk, k + 2),
                        row))(kit, corr_logits).astype(jnp.int32)
            else:
                t_pred = tlog.argmax(axis=-1).astype(jnp.int32)
                match = d == t_pred[:, :k]
                n = jnp.cumprod(match, axis=1).sum(axis=1)   # [lanes]
                corrective = jnp.take_along_axis(t_pred, n[:, None],
                                                 axis=1)[:, 0]
            d_ext = jnp.concatenate([d, d[:, -1:]], axis=1)
            win = jnp.where(idx[None, :] < n[:, None], d_ext,
                            corrective[:, None]).astype(jnp.int32)

            # ---- advance: accepted + corrective.  Full-cache: the
            # frontier clamps at the budget-safe cap (live lanes never
            # reach it — submit guarantees total - 1 <= cap; clamped
            # lanes spin and the host discards their output).
            # Rolling: positions are unbounded — the ring absorbs any
            # advance (idle/done lanes keep rolling too; their writes
            # land in slots admission reseeds, like the rolling
            # ContinuousBatcher).
            if rolling:
                adv = (n + 1).astype(jnp.int32)
            else:
                adv = jnp.where(pos >= cap, 0,
                                jnp.minimum(n + 1, cap - pos)
                                ).astype(jnp.int32)
            new_pos = pos + adv
            last = jnp.take_along_axis(
                win, jnp.maximum(adv - 1, 0)[:, None], axis=1)[:, 0]
            new_cur = jnp.where(adv > 0, last, cur)
            second_last = jnp.take_along_axis(
                win, jnp.maximum(adv - 2, 0)[:, None], axis=1)[:, 0]
            new_prev = jnp.where(adv >= 2, second_last,
                                 jnp.where(adv == 1, cur, prev))
            return (tcache, dcache, new_prev, new_cur, new_pos,
                    iters + 1, win, adv)

        self._step = jax.jit(step_fn, donate_argnums=(0, 1))

        # Admission: one jitted program per MODEL (jit specializes per
        # bucket-padded rows shape); pooled engines gather the
        # per-model prefix segment inside the same program.
        pooled = prefix_pool is not None
        self._admit_t = _make_lane_admit(self.params, cfg,
                                         pooled=pooled,
                                         constrain=self._kv_constraint)
        self._admit_d = _make_lane_admit(self.draft_params, draft_cfg,
                                         pooled=pooled)
        if pooled:
            self._reseed_t = _make_lane_reseed(pooled=True)
            self._reseed_d = _make_lane_reseed(pooled=True)
        if plan is not None:
            self._warm_sharded()

    # ---------------------------------------------- sharded warm-up

    def _warm_sharded(self) -> None:
        """Compile every serve-phase program at construction (the
        sharded zero-compile contract): the speculative step and both
        per-bucket admission programs run once against dummy state
        with EXACTLY the live arrays' avals and placements, plus the
        tiny host-scatter programs ``submit`` touches.  After this the
        serve phase never compiles (the ``spec_sharded`` compile
        session asserts it); only the degraded fallback still
        compiles lazily — a draft fault is not a steady state."""
        with obs.span("serving.compile_warm", lanes=self.lanes):
            fresh = lambda: (
                self._place_kv(init_cache(self.cfg, self.lanes)),
                self._place_replicated(init_cache(self.draft_cfg,
                                                  self.lanes)))
            ints = lambda: self._place_replicated(
                jnp.zeros((self.lanes,), jnp.int32))
            keys = self._place_replicated(
                jnp.stack([jax.random.key(0)] * self.lanes))
            tc, dc = fresh()           # the step donates both caches
            self._step(tc, dc, ints(), ints(), ints(), keys, ints())
            for width in self._buckets:
                rows = jnp.zeros((1, width), jnp.int32)
                tc, dc = fresh()       # admission donates its cache
                self._admit_t(tc, rows, jnp.int32(0), jnp.int32(0))
                self._admit_d(dc, rows, jnp.int32(0), jnp.int32(0))
            # submit()'s host lane-slot writes specialize per shape
            # and placement too — tiny scatters, but a compile is a
            # compile.
            ints().at[0].set(0)
            keys.at[0].set(jax.random.key(0))

    # -------------------------------------------------------------- API

    def traced_for_analysis(self):
        """Trace targets for the IR lint: the jitted speculative
        draft+verify step over the engine's live lane state, plus the
        target-model admission chunk at the smallest bucket."""
        from distkeras_tpu.analysis.ir_lint import TraceSpec

        mode = "sampled" if self.temperature > 0 else "greedy"
        if self._prefix_pool is not None:
            mode += "_pooled"
        rows = jnp.zeros((1, self._buckets[0]), jnp.int32)
        admit_args = (self.tcache, rows, jnp.int32(0), jnp.int32(0))
        if self._prefix_pool is not None:
            admit_args += (self._prefix_pool.slab[0], jnp.int32(0))
        return [
            TraceSpec(
                name=f"speculativebatcher_{mode}/step",
                fn=self._step,
                args=(self.tcache, self.dcache, self.prev, self.cur,
                      self.pos, self.keys, self.iters),
                donate_argnums=(0, 1)),
            TraceSpec(
                name=f"speculativebatcher_{mode}/admit_b"
                     f"{self._buckets[0]}",
                fn=self._admit_t, args=admit_args,
                donate_argnums=(0,)),
        ]

    def _validate_budget(self, p: int, max_new_tokens: int,
                         off: int = 0) -> None:
        if self._rolling:
            # No total-length cap: lanes roll past max_len on the
            # ring.  Only the PROMPT is bounded — its warm chunk is
            # uniform-pos and must not wrap.
            if p - 1 > self._buckets[-1]:
                raise ValueError(
                    f"prompt length {p} exceeds the largest admission "
                    f"bucket ({self._buckets[-1]} + 1); rolling "
                    "speculative prompts must fit the ring")
            return
        if off + p + max_new_tokens - 1 > self._cap:
            raise ValueError(
                f"prefix ({off}) + prompt ({p}) + max_new_tokens "
                f"({max_new_tokens}) + n_draft ({self.n_draft}) exceeds "
                f"max_len={min(self.cfg.max_len, self.draft_cfg.max_len)}"
                " (the verify chunk needs n_draft + 1 slots of slack)")
        warm = p - 1
        if warm and next((w for w in self._buckets
                          if w >= warm
                          and off + w <= min(self.cfg.max_len,
                                             self.draft_cfg.max_len)),
                         None) is None:
            raise ValueError(
                f"no admission bucket fits {warm} prompt tokens past a "
                f"{off}-token prefix (buckets {self._buckets}); raise "
                "prompt_buckets or add a finer width")

    def submit(self, prompt, max_new_tokens: int, key=None,
               eos_token=None, ttl=None, deadline=None, prefix_id=None):
        """Admit one request; returns its lane id, or None if full.
        ``key``: per-request PRNG key (required iff the engine
        samples, i.e. ``temperature > 0``).  ``ttl``/``deadline``:
        request deadline, same contract as
        :meth:`ContinuousBatcher.submit` — including holding the
        engine lock for the whole admission, so a submit racing
        ``begin_shutdown`` is either drained or raises EngineClosed.
        ``prefix_id``: decode past a pooled (target, draft) prefix
        pair — see the class docstring."""
        with self._admission_lock:
            return self._submit_locked(prompt, max_new_tokens, key,
                                       eos_token, ttl, deadline,
                                       prefix_id)

    def _submit_locked(self, prompt, max_new_tokens, key, eos_token,
                       ttl, deadline, prefix_id=None):
        self._check_open()
        prompt = self._validate_request_args(prompt, max_new_tokens)
        p = prompt.size
        if (key is None) == (self.temperature > 0):
            raise ValueError(
                "pass a per-request key iff the engine samples "
                f"(temperature={self.temperature})")
        off, slot, pre_last = 0, None, None
        if prefix_id is not None:
            # Pin FIRST (engine._pin_prefix): a concurrent pool.put
            # can never evict a pinned entry, so the slot stays ours
            # through both slab gathers below.  Every non-admission
            # exit releases the pin.
            off, slot, pre_last = self._pin_prefix(prefix_id)
        try:
            if prefix_id is not None and p == 1 and pre_last is None:
                raise ValueError(
                    "a 1-token prompt against a pooled prefix needs "
                    "the prefix's last token recorded at "
                    "PrefixPool.put(last_token=...): the draft chunk "
                    "rewrites the position before the prompt")
            self._validate_budget(p, max_new_tokens, off=off)
            if eos_token is not None and not (
                    0 <= eos_token < self.cfg.vocab_size):
                raise ValueError(
                    f"eos_token {eos_token} outside vocab [0, "
                    f"{self.cfg.vocab_size})")
            dl = self._deadline_of(ttl, deadline)
            if self._expired_on_arrival(dl, prompt, p):
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
            rid = self._claim_rid()
            if not self._admitting_internal:
                obs.event("serving.submit", request_id=rid,
                          prompt_len=p, max_new=int(max_new_tokens))
            warm = p - 1
            if warm:
                # The budget check above bounds warm and the bucket
                # fit, so a bucket always exists.
                width = next(w for w in self._buckets
                             if w >= warm and off + w <= min(
                                 self.cfg.max_len,
                                 self.draft_cfg.max_len))
                rows = np.zeros((1, width), np.int32)
                rows[0, :warm] = prompt[:-1]
                rows_j = jnp.asarray(rows)
                with obs.span("serving.admit", bucket=width, lane=lane,
                              request_id=rid) as sp:
                    if sp is not None:
                        # Target and draft admission are one function
                        # jitted twice: one name on the device.
                        sp.fields.update(
                            program=_program_name(self._admit_t),
                            attended=self._attended(self.tcache, off,
                                                    width))
                    if slot is not None:
                        t_slab, d_slab = self._prefix_pool.slab
                        self.tcache = self._admit_t(
                            self.tcache, rows_j, jnp.int32(lane),
                            jnp.int32(off), t_slab, jnp.int32(slot))
                        self.dcache = self._admit_d(
                            self.dcache, rows_j, jnp.int32(lane),
                            jnp.int32(off), d_slab, jnp.int32(slot))
                    elif self._prefix_pool is not None:
                        t_slab, d_slab = self._prefix_pool.slab
                        self.tcache = self._admit_t(
                            self.tcache, rows_j, jnp.int32(lane),
                            jnp.int32(0), t_slab, jnp.int32(-1))
                        self.dcache = self._admit_d(
                            self.dcache, rows_j, jnp.int32(lane),
                            jnp.int32(0), d_slab, jnp.int32(-1))
                    else:
                        self.tcache = self._admit_t(
                            self.tcache, rows_j, jnp.int32(lane),
                            jnp.int32(0))
                        self.dcache = self._admit_d(
                            self.dcache, rows_j, jnp.int32(lane),
                            jnp.int32(0))
            elif slot is not None:
                # 1-token prompt on a pooled prefix: no admission
                # chunk, but both lane caches still need the prefix
                # K/V.
                t_slab, d_slab = self._prefix_pool.slab
                self.tcache = self._reseed_t(
                    self.tcache, jnp.int32(lane), t_slab,
                    jnp.int32(slot))
                self.dcache = self._reseed_d(
                    self.dcache, jnp.int32(lane), d_slab,
                    jnp.int32(slot))
            # else: stale slots stay masked until overwritten.
            self.pos = self.pos.at[lane].set(off + p - 1)
            self.cur = self.cur.at[lane].set(int(prompt[-1]))
            # prev seeds the draft's T=2 gap-closure chunk: the token
            # at pos - 1 — the second-to-last prompt token, or
            # (1-token prompt on a prefix) the prefix's recorded last
            # token.
            self.prev = self.prev.at[lane].set(
                int(prompt[-2]) if p > 1
                else int(pre_last) if pre_last is not None else 0)
            if key is not None:
                self.keys = self.keys.at[lane].set(key)
            self.iters = self.iters.at[lane].set(0)
            # The pin taken above becomes the lane's reference here.
            self._lane_state[lane] = _Lane(
                request_id=rid, prompt_len=p,
                max_new=max_new_tokens, key=key, tokens=list(prompt),
                eos=self.eos_token if eos_token is None else eos_token,
                deadline=dl, born=self._clock(), off=off,
                prefix_id=prefix_id)
            if not self._admitting_internal:
                self.last_request_id = rid
        except Exception:
            if prefix_id is not None:
                self._prefix_pool.release(prefix_id)
            raise
        return lane

    # ------------------------------------------------- degraded mode

    @property
    def degraded(self) -> bool:
        """True once the engine fell back to the plain decode path."""
        return self._degraded

    def degrade(self, error=None) -> None:
        """Permanently switch to the target-only fallback decode step
        (see the constructor's degradation note).  Called automatically
        when the draft half of a step faults; callable directly by an
        operator who knows the draft model is bad."""
        if not self._degraded:
            obs.count("serving.degraded")
            # Event name differs from the counter: one name must map
            # to one instrument kind (contract lint, metric-collision).
            obs.event("serving.degrade",
                      error=None if error is None else repr(error))
        self._degraded = True
        if error is not None and self.degraded_error is None:
            self.degraded_error = error

    def _note_draft_fault(self, e: BaseException) -> None:
        intact = not any(
            getattr(leaf, "is_deleted", lambda: False)()
            for leaf in jax.tree.leaves(
                (self.tcache, self.cur, self.pos, self.keys)))
        if not intact:
            raise RuntimeError(
                "draft fault surfaced after the speculative step "
                "consumed its donated state; the fallback path has "
                "nothing valid to decode from") from e
        self.degrade(e)

    def _make_fallback(self):
        """Plain target-only decode step over the SAME engine state
        (tcache/cur/pos): one token per lane per call, frontier clamped
        at the budget-safe cap exactly like the speculative step —
        except on ROLLING engines, where the fallback preserves the
        ring-slot arithmetic instead: positions stay unbounded and each
        row keeps writing slot ``pos % max_len``, so a draft fault
        mid-wrap hands the plain path a cache whose implied positions
        it continues exactly (greedy parity past max_len; pinned by
        tests/test_speculative.py's chaos regression)."""
        cfg = self.cfg
        temperature = self.temperature
        rolling = self._rolling
        cap = None if rolling else jnp.int32(self._cap)
        constrain = self._kv_constraint

        def pick(k, row, q):
            return jax.random.categorical(jax.random.fold_in(k, q), row)

        def one(tcache, cur, pos, keys):
            if constrain is not None:
                tcache = constrain(tcache)
            logits, tcache = _decode_chunk(self.params, tcache,
                                           cur[:, None], pos, cfg)
            logits = logits[:, 0]
            if temperature > 0:
                nxt = jax.vmap(pick)(keys, logits / temperature, pos)
            else:
                nxt = logits.argmax(axis=-1)
            nxt = nxt.astype(jnp.int32)
            if rolling:
                adv = jnp.ones_like(pos)
                new_pos = pos + 1
            else:
                adv = (pos < cap).astype(jnp.int32)
                new_pos = jnp.minimum(pos + 1, cap)
            new_cur = jnp.where(adv > 0, nxt, cur)
            return tcache, new_cur, new_pos, nxt, adv

        return jax.jit(one, donate_argnums=0)

    def step(self):
        """One decode round for every lane; returns
        ``{lane: [tokens...]}`` — up to ``n_draft + 1`` tokens per
        lane per call (exactly 1 once the engine is degraded).  Runs
        under the engine lock, like :meth:`ContinuousBatcher.step`, so
        a concurrent locked ``submit``/``enqueue`` never rebinds the
        lane state mid-round-trip."""
        with self._admission_lock:
            return self._step_locked()

    def _read_round(self, *arrays) -> tuple:
        """The round's results on the host, read in the call that
        launched it (the next dispatch needs them): a
        ``serving.collect`` span INSIDE ``serving.step``, with the
        ``seq`` just given."""
        return self._timed_read(
            self._dispatch_seq,
            lambda: tuple(np.asarray(a) for a in arrays))

    def _step_locked(self):
        self.pump()
        if all(s is None or s.done for s in self._lane_state):
            return {}
        chaos.probe("serving.step")
        live = () if obs.active() is None else self.running()
        obs.gauge("serving.lanes_busy", len(live))
        if not self._degraded:
            try:
                chaos.probe("serving.draft")
                with obs.span("serving.step") as sp:
                    (tcache, dcache, prev, cur, pos, iters, win,
                     adv) = self._step(
                        self.tcache, self.dcache, self.prev, self.cur,
                        self.pos, self.keys, self.iters)
                    self._number_dispatch(sp, self._step)
                    # Force async dispatch errors to surface INSIDE the
                    # try, before the engine state is rebound: a fault
                    # arriving here finds self.* still naming the donated
                    # (now consumed) inputs, and _note_draft_fault reports
                    # the unrecoverable case with a clear error instead of
                    # leaving poisoned state behind.
                    win, adv = self._read_round(win, adv)
            except Exception as e:  # noqa: BLE001 — degrade, not die
                self._note_draft_fault(e)
            else:
                (self.tcache, self.dcache, self.prev, self.cur,
                 self.pos, self.iters) = (tcache, dcache, prev, cur,
                                          pos, iters)
                if obs.active() is not None:
                    # Speculative accept rate, host-visible for free:
                    # each live lane advanced accepted + 1 positions.
                    accepted = int(sum(max(int(adv[l]) - 1, 0)
                                       for l in live))
                    obs.count("serving.spec.proposed",
                              self.n_draft * len(live))
                    obs.count("serving.spec.accepted", accepted)
                out = self._emit(
                    lambda lane: win[lane, :adv[lane]].tolist())
                self._reap()
                return out
        # Degraded: plain target decode — requests still complete.
        if self._fallback is None:
            self._fallback = self._make_fallback()
        with obs.span("serving.step") as sp:
            self.tcache, self.cur, self.pos, nxt, adv = self._fallback(
                self.tcache, self.cur, self.pos, self.keys)
            self._number_dispatch(sp, self._fallback)
            nxt, adv = self._read_round(nxt, adv)
        out = self._emit(
            lambda lane: [int(nxt[lane])] if adv[lane] else [])
        self._reap()
        return out


__all__ = ["SpeculativeBatcher"]
