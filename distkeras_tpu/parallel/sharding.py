"""Sharding plans: variable-path rules -> PartitionSpecs.

The reference has exactly one placement policy: the full weight vector
lives on the parameter server and full copies live on every worker
(distkeras/parameter_servers.py holds the "center variable").  Here
placement is a first-class, declarative plan: regex rules over Keras
variable paths map each parameter to a ``PartitionSpec`` on the mesh.
The default plan is pure data parallelism (weights replicated, batch
split over ``data``); a tensor-parallel plan shards the big matmul
operands over ``model`` and XLA inserts the all-gathers/reduce-scatters.
"""

from __future__ import annotations


from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _augment_fsdp(spec: P, shape, axis_size: int, axis: str) -> P:
    """Add ``axis`` to the largest still-unsharded dimension of ``shape``
    that divides evenly; leave small/indivisible params replicated.

    This is the ZeRO-3 placement rule expressed as sharding: parameters
    (and, via :meth:`ShardingPlan.state_shardings`, their optimizer-state
    mirrors) live scattered over the data axis, and GSPMD materializes
    them with an all-gather at use and a reduce-scatter on the gradient
    — the XLA-native form of FSDP, no hand-written collectives.
    """
    if axis_size <= 1 or shape is None:
        return spec
    spec_t = tuple(spec) + (None,) * (len(shape) - len(spec))
    used = set()
    for s in spec_t:
        for a in (s if isinstance(s, tuple) else (s,)):
            if a is not None:
                used.add(a)
    if axis in used:
        return spec
    best, best_size = None, 0
    for i, (dim, s) in enumerate(zip(shape, spec_t)):
        if s is None and dim % axis_size == 0 and dim > best_size:
            best, best_size = i, dim
    if best is None:
        return spec
    new = list(spec_t)
    new[best] = axis
    while new and new[-1] is None:
        new.pop()
    return P(*new)


class ShardingPlan:
    """Ordered (regex, PartitionSpec) rules; first match wins.

    Unmatched variables are replicated.  Rules match against the Keras
    variable path (e.g. ``"dense_1/kernel"``).

    ``fsdp_axis`` layers fully-sharded data parallelism on top of the
    rule-derived spec: each parameter's largest still-free dimension is
    sharded over that mesh axis (see :func:`_augment_fsdp`).  Rules and
    FSDP compose — a Megatron-TP rule can claim one dimension and FSDP
    takes another.
    """

    def __init__(self, rules: Sequence[tuple[str, P]] = (),
                 batch_spec: P = P("data"), fsdp_axis: str | None = None):
        from distkeras_tpu.parallel.rules import compile_rules

        self.rules = compile_rules(rules)
        self.batch_spec = batch_spec
        self.fsdp_axis = fsdp_axis

    def spec_for(self, path: str, shape=None, mesh: Mesh | None = None) -> P:
        # First-match-wins through the shared rule engine
        # (parallel/rules.py); a plan's unmatched leaves replicate —
        # the historical ShardingPlan default (rule authors who want
        # unmatched-leaf errors use rules.match_partition_rules).
        from distkeras_tpu.parallel.rules import first_match

        matched, spec = first_match(self.rules, path)
        if not matched:
            spec = P()
        if self.fsdp_axis is not None and mesh is not None:
            spec = _augment_fsdp(spec, shape,
                                 int(mesh.shape[self.fsdp_axis]),
                                 self.fsdp_axis)
        return spec

    # ------------------------------------------------------------- builders

    def param_shardings(self, mesh: Mesh, paths: Sequence[str],
                        shapes: Sequence | None = None):
        """NamedShardings for a list-of-arrays pytree ordered like ``paths``."""
        shapes = shapes if shapes is not None else [None] * len(paths)
        return [NamedSharding(mesh, self.spec_for(p, shape=s, mesh=mesh))
                for p, s in zip(paths, shapes)]

    def state_shardings(self, mesh: Mesh, state, tv_paths: Sequence[str]):
        """Shardings pytree matching a :class:`TrainState`.

        ``tv`` (and its optimizer-state mirrors) get the plan's rules;
        ``ntv``/``step`` are replicated.  Optax states are pytrees whose
        array leaves mirror parameter shapes (mu/nu in adam etc.) or are
        scalars; we map any leaf whose shape matches a param positionally.
        """
        tv_sh = self.param_shardings(
            mesh, tv_paths, [tuple(v.shape) for v in state.tv])
        rep = NamedSharding(mesh, P())

        # Optax states embed subtrees mirroring the param pytree (our tv
        # is a flat list, so e.g. adam's mu/nu are lists in tv order).
        # Match each opt-state leaf to its param by the *index* of the
        # innermost list it sits in — positional, not shape-based, so
        # same-shaped params with different specs stay distinct.  A leaf
        # whose innermost-list index doesn't correspond to a matching
        # param shape (EmptyState internals, scalar counts) replicates.
        tv_list = list(state.tv)

        def opt_leaf_sharding(path, leaf):
            idx = None
            for key in reversed(path):
                if isinstance(key, jax.tree_util.SequenceKey):
                    idx = key.idx
                    break
            if (idx is not None and idx < len(tv_list)
                    and hasattr(leaf, "shape")
                    and tuple(leaf.shape) == tuple(tv_list[idx].shape)):
                return tv_sh[idx]
            return rep

        from distkeras_tpu.models.adapter import TrainState

        return TrainState(
            tv=tv_sh,
            ntv=jax.tree.map(lambda _: rep, state.ntv),
            opt_state=jax.tree_util.tree_map_with_path(
                opt_leaf_sharding, state.opt_state),
            step=rep,
        )

    def batch_sharding(self, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.batch_spec)

    def tree_shardings(self, mesh: Mesh, pytree):
        """NamedShardings for any pytree, rules keyed on jax key-paths.

        Paths are rendered like ``"layers/0/attn/wq"`` (keystr with the
        leading separator stripped), so the same regex rule language
        covers Keras variable paths and functional-model dicts.
        """
        def leaf(path, x):
            name = jax.tree_util.keystr(path, simple=True, separator="/")
            shape = tuple(x.shape) if hasattr(x, "shape") else None
            return NamedSharding(mesh, self.spec_for(name, shape=shape,
                                                     mesh=mesh))

        return jax.tree_util.tree_map_with_path(leaf, pytree)


class Zero1Plan(ShardingPlan):
    """Data parallelism with the *optimizer state* sharded over ``data``
    (ZeRO-1): parameters replicate exactly like :func:`dp_plan` — the
    forward/backward is untouched — but every optimizer-state leaf that
    mirrors a parameter lives as a ``[n, cols]`` shard view (see
    ``parallel/collectives.py``) placed ``P("data", None)``, so each
    device persists 1/n of the moments.  Pair with
    ``collectives.zero1_optimizer``, which produces state in exactly
    that layout; the trainers wire both through ``zero1=True``.
    """

    def __init__(self, bucket_mb: float | None = None):
        super().__init__(rules=(), batch_spec=P("data"))
        from distkeras_tpu.parallel.collectives import DEFAULT_BUCKET_MB

        self.zero1 = True
        self.bucket_mb = (DEFAULT_BUCKET_MB if bucket_mb is None
                          else bucket_mb)

    def state_shardings(self, mesh: Mesh, state, tv_paths: Sequence[str]):
        """TrainState shardings: ``tv``/``ntv``/``step`` replicated;
        optimizer-state leaves take the ZeRO-1 shard-view rule (the
        shared ``collectives.zero1_state_shardings``)."""
        from distkeras_tpu.models.adapter import TrainState
        from distkeras_tpu.parallel.collectives import (
            zero1_state_shardings)

        rep = NamedSharding(mesh, P())
        return TrainState(
            tv=[rep for _ in state.tv],
            ntv=jax.tree.map(lambda _: rep, state.ntv),
            opt_state=zero1_state_shardings(list(state.tv),
                                            state.opt_state, mesh),
            step=rep,
        )


class ExchangePlan(ShardingPlan):
    """Data parallelism under a non-default gradient-exchange policy
    (``parallel/exchange.py``): parameters replicate like
    :func:`dp_plan`, but the optimizer state may carry error-feedback
    residuals (sharded over their leading replica axis) and — when the
    int8 codec composes with ZeRO-1 — scattered ``[n, cols]`` shard
    views.  One shared sharding rule
    (``exchange.exchange_state_shardings``) covers both.
    """

    def __init__(self, config, zero1: bool = False):
        super().__init__(rules=(), batch_spec=P("data"))
        self.exchange = config
        self.zero1 = zero1
        self.bucket_mb = config.bucket_mb

    def state_shardings(self, mesh: Mesh, state, tv_paths: Sequence[str]):
        from distkeras_tpu.models.adapter import TrainState
        from distkeras_tpu.parallel.exchange import (
            exchange_state_shardings)

        rep = NamedSharding(mesh, P())
        return TrainState(
            tv=[rep for _ in state.tv],
            ntv=jax.tree.map(lambda _: rep, state.ntv),
            opt_state=exchange_state_shardings(
                list(state.tv), state.opt_state, mesh,
                zero1=self.zero1),
            step=rep,
        )


class Zero3Plan(ShardingPlan):
    """Data parallelism with parameters AND optimizer state scattered
    as ``[n, cols]`` chunk-major shard views over ``data`` (ZeRO-3,
    gather-on-use): persistent state holds 1/n of every parameter,
    gradient-moment and EMA leaf per device; the train step
    re-materializes parameters per fusion bucket just-in-time
    (``collectives.gather_bucket``) and runs the update entirely on the
    shard views — no per-step parameter all-gather of the update.

    Unlike :func:`fsdp_plan` (the GSPMD dimension-sharding spelling of
    ZeRO-3), the chunk-major layout shards EVERY leaf regardless of
    divisibility (biases, norm scales — anything `_augment_fsdp` would
    leave replicated), and the gather is bucket-granular: a handful of
    fused all-gathers per step instead of one per parameter.  Derived
    from the shared rule engine (``parallel/rules.py``): the shape-
    keyed shard-view rule ahead of a replicate catch-all.
    """

    def __init__(self, bucket_mb: float | None = None):
        super().__init__(rules=(), batch_spec=P("data"))
        from distkeras_tpu.parallel.collectives import DEFAULT_BUCKET_MB

        self.zero = 3
        self.bucket_mb = (DEFAULT_BUCKET_MB if bucket_mb is None
                          else bucket_mb)

    def state_shardings(self, mesh: Mesh, state, tv_paths: Sequence[str]):
        """TrainState shardings for a state whose ``tv`` leaves are
        shard views: ``tv`` and the view-mirroring optimizer leaves
        scatter ``P("data", None)``; ``ntv``/``step``/scalar counts
        replicate — one ordered rule list (parallel/rules.py)."""
        from distkeras_tpu.models.adapter import TrainState
        from distkeras_tpu.parallel.rules import (zero3_param_shardings,
                                                  zero_state_shardings)

        rep = NamedSharding(mesh, P())
        return TrainState(
            tv=zero3_param_shardings(list(state.tv), mesh),
            ntv=jax.tree.map(lambda _: rep, state.ntv),
            opt_state=zero_state_shardings(list(state.tv),
                                           state.opt_state, mesh),
            step=rep,
        )


class ServingPlan(ShardingPlan):
    """A :class:`ShardingPlan` for the SERVE path (round 14): regex
    rules over the functional transformer's param paths place the
    parameters, and the KV cache / paged block slab / prefix-pool slab
    placement is DERIVED from them (``parallel/rules.py``'s
    ``serving_kv_axis``/``kv_slab_specs``) — the rule that shards
    attention heads over a mesh axis is what shards the cache's
    kv-heads dimension, so plan and cache can never disagree.

    Lane/row metadata (positions, current tokens, PRNG keys, page
    tables) always replicates: it is O(lanes) host bookkeeping, and
    replicating it keeps the admission scatters collective-free.

    Built by :func:`serving_plan`; consumed by
    ``ContinuousBatcher(plan=..., mesh=...)`` and
    ``PagedBatcher(plan=..., mesh=...)`` — which derive the KV axis
    through ``rules.serving_kv_axis`` (the ONE entry point; it works
    on any ShardingPlan, so this class adds no method for it).
    """


def _override_rules(extra_rules, stock_rules) -> list:
    """Compose user overrides ahead of stock rules, first-match-wins.

    An extra rule that spells a stock pattern VERBATIM replaces it —
    the stock copy is dropped rather than left as an unreachable
    duplicate, which ``rules.compile_rules`` (round 17) rejects at
    build time.  Overrides via broader/narrower patterns compose by
    ordering alone, as before.
    """
    seen = {pat for pat, _ in extra_rules}
    return list(extra_rules) + [(pat, val) for pat, val in stock_rules
                                if pat not in seen]


def serving_plan(extra_rules: Sequence[tuple[str, P]] = (),
                 fsdp_axis: str | None = None) -> ServingPlan:
    """The pod-sharded serving plan (ROADMAP item 1, arXiv
    2004.13336 applied to the serve path): Megatron tensor-parallel
    rules over the ``model`` axis for the functional transformer's
    params — the SAME ``tp_rules()`` spellings ``fsdp=True``-era
    training shards with — so one engine replica spans a whole mesh:
    attention projections and FFN matmuls shard over ``model``, the KV
    cache's kv-heads dimension shards with them, per-device param+KV
    bytes drop ~``model``× and GSPMD inserts the per-token collectives
    (one psum pair per block + the unembed gather) when the engine
    compiles its step.

    ``extra_rules`` prepend (first-match-wins, so they override);
    ``fsdp_axis`` additionally scatters still-unsharded params over
    that axis (gather-on-use — params only; the cache follows the
    attention-head rules, never fsdp).  See docs/serving_guide.md
    "Pod-sharded serving".
    """
    from distkeras_tpu.models.transformer import tp_rules

    return ServingPlan(rules=_override_rules(extra_rules, tp_rules()),
                       batch_spec=P(), fsdp_axis=fsdp_axis)


def dp_plan() -> ShardingPlan:
    """Pure data parallelism: replicate weights, split batch on ``data``."""
    return ShardingPlan(rules=(), batch_spec=P("data"))


def zero1_plan(bucket_mb: float | None = None) -> Zero1Plan:
    """Data parallelism with a cross-replica sharded weight update
    (ZeRO-1, arXiv 2004.13336): parameters replicated — forward and
    backward are byte-identical to :func:`dp_plan` — while optimizer
    state shards over ``data`` and each replica computes only its slice
    of the update (reduce-scatter(grads) -> shard update ->
    all-gather(update)).  Communication volume is unchanged (RS+AG ==
    the all-reduce it replaces); per-device optimizer memory and update
    FLOPs drop ~num_workers x.  Compare :func:`fsdp_plan` (ZeRO-3),
    which additionally scatters the *parameters* at the cost of an
    all-gather per use; see docs/zero1.md for when to prefer which.
    """
    return Zero1Plan(bucket_mb=bucket_mb)


def zero3_plan(bucket_mb: float | None = None) -> Zero3Plan:
    """Data parallelism with chunk-major gather-on-use parameter
    sharding (ZeRO-3): persistent params, gradients and optimizer
    state all live as ``[n, cols]`` shard views over ``data`` —
    per-device bytes for all three drop ~n× — and the step all-gathers
    parameters per fusion bucket just-in-time.  The explicit-plan
    spelling of ``zero=3`` on ADAG/DynSGD; compare :func:`fsdp_plan`
    (GSPMD dimension sharding, composes with TP) and
    :func:`zero1_plan` (update-only sharding, no gather-on-use).
    """
    return Zero3Plan(bucket_mb=bucket_mb)


def fsdp_plan(extra_rules: Sequence[tuple[str, P]] = (),
              axis: str = "data") -> ShardingPlan:
    """Fully-sharded data parallelism (ZeRO-3): weights and optimizer
    state scattered over the ``data`` axis, gathered on use.

    Same batch semantics as :func:`dp_plan`; per-device parameter and
    optimizer-state memory drops by ~the data-axis size, at the cost of
    an all-gather per use and a reduce-scatter per gradient (both ride
    the ICI).  The reference cannot express this at all — every worker
    and the parameter server hold full weight copies
    (distkeras/parameter_servers.py center variable).
    """
    return ShardingPlan(rules=extra_rules, batch_spec=P("data"),
                        fsdp_axis=axis)


def tp_plan(extra_rules: Sequence[tuple[str, P]] = ()) -> ShardingPlan:
    """Data + tensor parallelism for dense/conv/embedding stacks.

    Default rules follow the Megatron layout on the ``model`` axis:
    dense kernels column-sharded ([in, out] -> out over model); embeddings
    sharded over the vocab/feature dim; conv kernels over output channels.
    XLA turns the resulting partial products into psum/reduce-scatter on
    the ICI.
    """
    rules = _override_rules(extra_rules, [
        (r"(dense|mlp|fc)[^/]*/kernel$", P(None, "model")),
        (r"embedding[^/]*/embeddings$", P(None, "model")),
        (r"conv[^/]*/kernel$", P(None, None, None, "model")),
    ])
    return ShardingPlan(rules=rules, batch_spec=P("data"))
