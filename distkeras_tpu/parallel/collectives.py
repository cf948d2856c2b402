"""Bucketed cross-replica collectives + the ZeRO-1 sharded weight update.

The data-parallel trainers' gradient exchange is compiler-inserted: the
batch shards over the mesh ``data`` axis and XLA all-reduces the
gradient of the replicated parameters.  The *update* that consumes it,
though, was fully replicated — every replica holds the whole optimizer
state and redundantly computes the whole update each round, exactly the
waste "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" (arXiv 2004.13336) identifies.  This module is
that paper's construction for this codebase:

    reduce-scatter(grads)  ->  each replica updates only its 1/n shard
                           ->  all-gather(new update)

with *identical training math* (RS+AG moves exactly the bytes the old
all-reduce did; the update is elementwise, so sharding it changes
nothing) and ~n x less optimizer-state memory per device.

Mechanics.  Gradient pytrees are flattened into ~fixed-size **fusion
buckets**: each leaf is padded to a multiple of ``n`` (the ``data``
axis size) and viewed as ``[n, cols]`` — row ``k`` is the chunk replica
``k`` owns — then same-dtype leaves are concatenated along the column
axis until a bucket reaches ``bucket_mb``.  Per-bucket issuance (rather
than one monolithic exchange) is what lets the scheduler overlap bucket
``k``'s reduce-scatter with bucket ``k+1``'s packing and the unpacked
buckets' update math — the comm/compute overlap "A DAG Model of
Synchronous SGD" (arXiv 1805.03812) formalizes.  Because every leaf's
chunk boundary lies on the bucket's *row* boundary, slicing a leaf back
out of a scattered bucket is a column slice — no resharding, no
communication.

Two spellings of each collective:

* :func:`scatter` — the jit-native reduce-scatter: a sharding
  constraint to ``P(axis, None)``.  Fed a gradient whose all-reduce is
  still pending, GSPMD emits a reduce-scatter instead (the same
  mechanism that gives ``fsdp_plan`` its gradient reduce-scatters).
* :func:`reduce_scatter` / :func:`all_gather` — the explicit
  shard_map primitives, for manual-SPMD callers and for testing the
  collective math in isolation.
  ``all_gather`` is also the hot path's parameter-update gather.

:func:`zero1_optimizer` wraps any *elementwise* optax transform (the
whole ``ops/optimizers.py`` name set; see
``ops.optimizers.zero1_compatible``) into the sharded update.  It is a
drop-in ``optax.GradientTransformation``, so every trainer that calls
``optimizer.update`` — the Keras accumulation step, LMTrainer's train
step, the EMA/clip chains — picks it up unchanged.

The bucketed layout here is also the substrate of the pluggable
**gradient-exchange layer** (``parallel/exchange.py``): Adasum merging,
local-SGD periodic sync, and error-feedback int8/top-k compression all
operate per fusion bucket, and the int8 codec composes with ZeRO-1 by
compressing exactly the reduce-scatter leg of this module's exchange
(docs/lowcomm.md).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu import obs

# ~4 MB buckets: big enough to amortize collective launch latency,
# small enough that several buckets pipeline inside one exchange.
DEFAULT_BUCKET_MB = 4.0


@dataclasses.dataclass(frozen=True)
class _Slot:
    """Where one pytree leaf lives inside the bucketed layout."""

    shape: tuple
    dtype: Any
    size: int       # prod(shape)
    cols: int       # padded size // n; the columns this leaf occupies
    bucket: int     # bucket index
    offset: int     # column offset inside the bucket


@dataclasses.dataclass(frozen=True)
class Zero1Layout:
    """Deterministic leaf -> bucket placement for one pytree geometry.

    Computed from shapes/dtypes only (works on arrays or
    ``ShapeDtypeStruct`` trees), so the optimizer wrapper can rebuild
    the identical layout at init and at every update trace.
    """

    n: int
    treedef: Any
    slots: tuple[_Slot, ...]         # in leaf order
    bucket_cols: tuple[int, ...]     # column count per bucket
    bucket_dtypes: tuple[Any, ...]
    # Per-bucket group key (all None without `groups=`): the exchange
    # layer's per-bucket codec choice buckets by (dtype, group) so a
    # bucket is always codec-homogeneous (parallel/exchange.py).
    bucket_groups: tuple[Any, ...] = ()

    @classmethod
    def for_tree(cls, tree, n: int,
                 bucket_mb: float = DEFAULT_BUCKET_MB,
                 groups=None) -> "Zero1Layout":
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if n < 1:
            raise ValueError(f"axis size must be >= 1, got {n}")
        if groups is None:
            group_of = [None] * len(leaves)
        else:
            group_of = jax.tree_util.tree_leaves(
                groups, is_leaf=lambda x: x is None)
            if len(group_of) != len(leaves):
                raise ValueError(
                    f"groups carries {len(group_of)} entries for "
                    f"{len(leaves)} leaves")
        # Group by (dtype, group) — buckets concatenate, so they must
        # be dtype-homogeneous, and a group key (e.g. a codec) must
        # never straddle a bucket — then fill ~bucket_mb buckets in
        # leaf order.  With no groups this is exactly the historical
        # dtype-only bucketing, bit-for-bit.
        order = list(range(len(leaves)))
        by_key: dict[Any, list[int]] = {}
        for i in order:
            by_key.setdefault((np.dtype(leaves[i].dtype), group_of[i]),
                              []).append(i)
        slots: list[_Slot | None] = [None] * len(leaves)
        bucket_cols: list[int] = []
        bucket_dtypes: list[Any] = []
        bucket_groups: list[Any] = []
        for (dtype, group), idxs in by_key.items():
            budget = max(1, int(bucket_mb * 2 ** 20 / dtype.itemsize))
            cur_cols, cur_bucket = 0, -1
            for i in idxs:
                size = int(math.prod(leaves[i].shape)) or 1
                cols = -(-size // n)  # ceil: pad to a multiple of n
                if cur_bucket < 0 or cur_cols * n + cols * n > budget:
                    bucket_cols.append(0)
                    bucket_dtypes.append(dtype)
                    bucket_groups.append(group)
                    cur_bucket = len(bucket_cols) - 1
                    cur_cols = 0
                slots[i] = _Slot(shape=tuple(leaves[i].shape), dtype=dtype,
                                 size=int(math.prod(leaves[i].shape)),
                                 cols=cols, bucket=cur_bucket,
                                 offset=cur_cols)
                cur_cols += cols
                bucket_cols[cur_bucket] = cur_cols
        return cls(n=n, treedef=treedef, slots=tuple(slots),
                   bucket_cols=tuple(bucket_cols),
                   bucket_dtypes=tuple(bucket_dtypes),
                   bucket_groups=tuple(bucket_groups))

    # ------------------------------------------------------------ views

    @property
    def shard_shapes(self) -> frozenset:
        """Every ``[n, cols]`` shard-view shape in this layout — the
        shapes optimizer-state leaves take under ZeRO-1 (the trainers'
        sharding rules key on membership here)."""
        return frozenset((self.n, s.cols) for s in self.slots)

    def _leaf_view(self, slot: _Slot, x):
        """One leaf -> its ``[n, cols]`` chunk-major view (pad with 0)."""
        flat = jnp.reshape(x, (-1,))
        pad = slot.cols * self.n - slot.size
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), dtype=flat.dtype)])
        return jnp.reshape(flat, (self.n, slot.cols))

    def shard_views(self, tree):
        """Pytree of original leaves -> same-structure pytree of
        ``[n, cols]`` views (row k = replica k's chunk).  Pure
        reshape/pad — no communication."""
        leaves = self.treedef.flatten_up_to(tree)
        return self.treedef.unflatten(
            [self._leaf_view(s, x) for s, x in zip(self.slots, leaves)])

    def unview(self, view_tree):
        """Inverse of :meth:`shard_views`: ``[n, cols]`` leaves back to
        their original shapes (drop the pad).  Used to read state that
        lives as shard views — e.g. the EMA shadow — back out in
        parameter layout; gathers if the views are sharded."""
        views = self.treedef.flatten_up_to(view_tree)
        return self.treedef.unflatten(
            [jnp.reshape(jnp.reshape(v, (-1,))[:s.size], s.shape)
             for s, v in zip(self.slots, views)])

    # ---------------------------------------------------------- buckets

    def pack(self, tree) -> list:
        """Pytree -> list of ``[n, C_b]`` fusion buckets."""
        return self.pack_views(self.shard_views(tree))

    def pack_views(self, view_tree) -> list:
        """Shard-view pytree (``[n, cols]`` leaves) -> bucket list.
        Column concatenation only: a sharded view stays sharded."""
        views = self.treedef.flatten_up_to(view_tree)
        groups: list[list] = [[] for _ in self.bucket_cols]
        for slot, v in zip(self.slots, views):
            groups[slot.bucket].append(v)
        return [vs[0] if len(vs) == 1 else jnp.concatenate(vs, axis=1)
                for vs in groups]

    def views_from_buckets(self, buckets: Sequence):
        """Bucket list -> shard-view pytree.  Column slices only (leaf
        boundaries sit on row boundaries by construction), so a
        scattered bucket yields scattered views with no resharding."""
        views = [buckets[s.bucket][:, s.offset:s.offset + s.cols]
                 for s in self.slots]
        return self.treedef.unflatten(views)

    def zero_buckets(self) -> list:
        """Fresh all-zero buckets in this layout — the ZeRO-2/3 step
        builders' gradient accumulator carry (kept scattered by a
        :func:`scatter` constraint per microbatch add)."""
        return [jnp.zeros((self.n, c), d)
                for c, d in zip(self.bucket_cols, self.bucket_dtypes)]

    def unpack(self, buckets: Sequence):
        """Bucket list -> pytree of original leaf shapes (drop pad)."""
        out = []
        for s in self.slots:
            flat = jnp.reshape(
                buckets[s.bucket][:, s.offset:s.offset + s.cols], (-1,))
            out.append(jnp.reshape(flat[:s.size], s.shape))
        return self.treedef.unflatten(out)


# ------------------------------------------------------------ collectives


def scatter(x, mesh: Mesh, axis: str = "data"):
    """Jit-native reduce-scatter of a ``[n, C]`` bucket: constrain it to
    ``P(axis, None)`` so replica ``k`` materializes only row ``k``.

    Fed a value whose cross-replica reduction is still pending (a
    gradient of replicated params over a data-sharded batch), GSPMD
    emits a reduce-scatter — the all-reduce never happens.  Fed an
    already-replicated value, it is a free local slice.  Outside a
    trace it is the identity (eager callers place state via
    ``device_put`` with the plan's shardings).
    """
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(axis, None)))
    return x


def reduce_scatter(x, mesh: Mesh, axis: str = "data"):
    """Explicit reduce-scatter primitive (shard_map + ``psum_scatter``).

    ``x``: ``[n, C]`` whose *rows are per-replica addends* (e.g. stacked
    partial gradients), ``n`` = the ``axis`` size and ``C`` divisible
    by ``n`` (the scattered output gives each replica a ``C/n`` chunk).
    Returns the global ``[C]`` row-sum, sharded over ``axis`` (replica
    ``k`` holds columns ``[k*C/n, (k+1)*C/n)``).

    NOTE the contract difference from :func:`scatter`: here rows are
    independent contributions to a sum; there the input is one logical
    value whose rows are chunks.  The trainers' hot path uses
    :func:`scatter` (the gradient is one logical value under jit); this
    primitive serves manual-SPMD code and validates the collective math
    in isolation.
    """
    n = int(mesh.shape[axis])
    if x.ndim != 2 or x.shape[0] != n or x.shape[1] % n:
        raise ValueError(
            f"reduce_scatter takes [n, C] with n == the {axis!r} axis "
            f"size ({n}) and C divisible by n (each replica receives a "
            f"C/n chunk); got shape {tuple(x.shape)} — pad the columns "
            "to a multiple of the axis size")

    def body(s):  # [1, C] — this replica's addend
        return jax.lax.psum_scatter(s[0], axis, scatter_dimension=0,
                                    tiled=True)

    return shard_map(body, mesh=mesh, in_specs=P(axis, None),
                     out_specs=P(axis), check_vma=False)(x)


def all_gather(x, mesh: Mesh, axis: str = "data"):
    """Explicit all-gather primitive (shard_map): ``[n, C]`` sharded
    over ``axis`` on dim 0 -> the same value replicated on every
    replica.  The ZeRO-1 step's parameter-update gather."""
    def body(s):  # [1, C] — this replica's chunk
        return jax.lax.all_gather(s, axis, axis=0, tiled=True)

    return shard_map(body, mesh=mesh, in_specs=P(axis, None),
                     out_specs=P(None, None), check_vma=False)(x)


def _replicate(x, mesh: Mesh):
    """Jit-native all-gather of a scattered ``[n, C]`` bucket: constrain
    it to replicated so GSPMD materializes every row on every replica.
    Outside a trace it is the identity (eager sharded arrays gather on
    read)."""
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None, None)))
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_bucket(x, mesh: Mesh, axis: str = "data"):
    """The ZeRO-3 gather-on-use primitive: forward re-materializes a
    scattered ``[n, C]`` parameter bucket on every replica (an
    all-gather under GSPMD), and the BACKWARD scatters the cotangent
    back to ``P(axis, None)`` — a reduce-scatter of the gradient, one
    per fusion bucket.

    The custom vjp is the point: ``with_sharding_constraint``'s own
    transpose would pin the cotangent replicated (forcing a full
    gradient all-reduce and a replicated gradient buffer); here the
    gradient of a gathered parameter only ever materializes as the
    ``1/n`` shard each replica owns.  Scopes ``zero3/param_gather`` /
    ``zero3/grad_scatter`` tag both legs for the declared-exchange
    parity proof (analysis/ir_lint.py) and profiler timelines.
    """
    with jax.named_scope("zero3/param_gather"):
        return _replicate(x, mesh)


def _gather_bucket_fwd(x, mesh, axis):
    with jax.named_scope("zero3/param_gather"):
        return _replicate(x, mesh), None


def _gather_bucket_bwd(mesh, axis, _, ct):
    with jax.named_scope("zero3/grad_scatter"):
        return (scatter(ct, mesh, axis),)


gather_bucket.defvjp(_gather_bucket_fwd, _gather_bucket_bwd)


def adasum_reduce(x, mesh: Mesh, axis: str = "data"):
    """Adasum merge primitive (shard_map): ``[n, C]`` whose *rows are
    per-replica addends* (the :func:`reduce_scatter` contract) ->
    their pairwise adaptive sum ``[C]``, replicated on every replica
    (arXiv 2006.02924; rule in ``parallel/exchange.py``).

    The standalone spelling of the bucketed exchange layer's
    ``merge_rule="adasum"`` for manual-SPMD callers and for testing
    the merge math in isolation: identical replicas reproduce the
    value itself (== mean-reduce of agreeing replicas), orthogonal
    replicas reproduce the plain sum.
    """
    from distkeras_tpu.parallel.exchange import adasum_combine

    n = int(mesh.shape[axis])
    if x.ndim != 2 or x.shape[0] != n:
        raise ValueError(
            f"adasum_reduce takes [n, C] with n == the {axis!r} axis "
            f"size ({n}); got shape {tuple(x.shape)}")

    def body(s):  # [1, C] — this replica's addend
        stacked = jax.lax.all_gather(s[0], axis, axis=0)  # [n, C]
        return adasum_combine(stacked).astype(s.dtype)

    return shard_map(body, mesh=mesh, in_specs=P(axis, None),
                     out_specs=P(None), check_vma=False)(x)


# ------------------------------------------------------------ the wrapper


def zero_validate(mesh: Mesh, spec, axis: str = "data",
                  stage: int = 1) -> None:
    """The ZeRO enablement checks, run at TRAINER CONSTRUCTION for
    every stage (1/2/3) by both trainer families, and by the exchange
    layer's zero1+int8 composition (``parallel/exchange.py``):

    * pure-``axis`` mesh — every stage here shards the update (and, at
      stage 3, the parameters) of an otherwise *replicated* layout;
    * an optimizer whose update rule is per-leaf elementwise
      (``ops.optimizers.zero1_compatible``).  A known-unsafe transform
      raises HERE, naming the offending optax transform (e.g.
      ``scale_by_trust_ratio`` inside a LAMB chain), instead of
      training to silently-diverged weights inside the scattered
      update; an uninspectable transform warns.
    """
    knob = f"zero={stage}" if stage != 1 else "zero1=True"
    for ax, size in mesh.shape.items():
        if ax != axis and int(size) > 1:
            raise ValueError(
                f"{knob} composes with the {axis} axis only, but the "
                f"mesh has {ax}={int(size)}; the ZeRO stages shard the "
                "update of *replicated* parameters — use fsdp/TP plans "
                "when a rule-driven parameter layout is wanted instead")
    from distkeras_tpu.ops.optimizers import (zero1_compatible,
                                              zero1_offender)

    compat = zero1_compatible(spec)
    if compat is False:
        offender = zero1_offender(spec)
        raise ValueError(
            f"optimizer {spec!r} is known-incompatible with the ZeRO "
            "sharded update"
            + (f": transform {offender!r} mixes elements within a leaf"
               if offender else
               " (its update rule mixes elements within a leaf)")
            + ", so sharding changes the math; train it replicated or "
            "under fsdp")
    if compat is None:
        import warnings

        warnings.warn(
            f"{knob} with a prebuilt/factory optax optimizer that "
            "cannot be verified elementwise: the sharded update is "
            "math-identical only for per-leaf elementwise update rules; "
            "transforms mixing elements within a leaf (LARS/LAMB trust "
            "ratios, Shampoo preconditioners) will silently diverge",
            stacklevel=3)


def zero1_validate(mesh: Mesh, spec, axis: str = "data") -> None:
    """Stage-1 spelling of :func:`zero_validate` (kept: the exchange
    layer and older call sites name it)."""
    zero_validate(mesh, spec, axis=axis, stage=1)


def zero1_optimizer(inner: optax.GradientTransformation, mesh: Mesh,
                    axis: str = "data",
                    bucket_mb: float = DEFAULT_BUCKET_MB
                    ) -> optax.GradientTransformation:
    """ZeRO-1 wrap of an elementwise optax transform.

    ``init`` builds the inner state over *shard views* (``[n, cols]``
    per leaf) — same pytree structure as the params, so path-keyed
    masks (weight-decay exclusions, LoRA masks) see the tree they
    expect — and the trainers place those leaves ``P(axis, None)``:
    each device persists 1/n of every moment buffer.

    ``update``:

    1. pack grads into fusion buckets, :func:`scatter` each —
       per-bucket reduce-scatter, issued as the buckets are packed;
    2. run ``inner.update`` on the scattered shard views (elementwise
       math partitions with zero communication; a chained
       ``clip_by_global_norm`` stays exact — its sum-of-squares over
       sharded leaves becomes a cheap scalar psum);
    3. pack the update shards back into buckets and :func:`all_gather`
       each; unpack to the original leaf shapes.

    Returned updates are replicated, so the caller's ``p + u`` is the
    replicated-path value bit-for-bit (modulo reduction order inside
    the collective).  Correctness requires the inner update to be
    elementwise per leaf — true of every named optimizer this package
    resolves (``ops.optimizers.zero1_compatible``); transforms that mix
    elements *within* a leaf (per-layer trust ratios a la LARS/LAMB)
    would silently change math and must not be wrapped.
    """
    n = int(mesh.shape[axis])

    def init(params):
        layout = Zero1Layout.for_tree(params, n, bucket_mb)
        return inner.init(layout.shard_views(params))

    def _record_layout(layout: Zero1Layout) -> None:
        """Bucket geometry into the obs metrics registry — runs at
        TRACE time (once per compile), so the per-step hot path is
        untouched.  Per-step *device-side* RS/AG timings are by design
        not host-observable (overlap interleaves them on the
        timeline); the ``jax.named_scope`` zero1 regions tag them on
        profiler traces, and these gauges size the exchange exactly."""
        if obs.active() is None:
            return
        bucket_bytes = [c * layout.n * np.dtype(d).itemsize
                        for c, d in zip(layout.bucket_cols,
                                        layout.bucket_dtypes)]
        pad = sum((s.cols * layout.n - s.size)
                  * np.dtype(s.dtype).itemsize for s in layout.slots)
        obs.gauge("zero1.buckets", len(bucket_bytes))
        obs.gauge("zero1.exchange_bytes", sum(bucket_bytes))
        obs.gauge("zero1.pad_bytes", pad)
        for b in bucket_bytes:
            obs.observe("zero1.bucket_bytes", b,
                        buckets=(2**18, 2**20, 2**22, 2**24, 2**26))

    def update(grads, state, params=None, **kw):
        layout = Zero1Layout.for_tree(grads, n, bucket_mb)
        _record_layout(layout)
        with jax.named_scope("zero1/reduce_scatter"):
            g_buckets = [scatter(b, mesh, axis) for b in layout.pack(grads)]
        g_views = layout.views_from_buckets(g_buckets)
        p_views = (None if params is None
                   else layout.shard_views(params))
        with jax.named_scope("zero1/update"):
            u_views, new_state = inner.update(g_views, state, p_views, **kw)
        with jax.named_scope("zero1/all_gather"):
            u_buckets = [all_gather(b, mesh, axis)
                         for b in layout.pack_views(u_views)]
        return layout.unpack(u_buckets), new_state

    return optax.GradientTransformation(init, update)


def zero1_enable(inner: optax.GradientTransformation, mesh: Mesh,
                 spec=None, bucket_mb: float | None = None,
                 axis: str = "data",
                 stage: int = 1) -> optax.GradientTransformation:
    """Validate a trainer's ZeRO configuration and return the wrapped
    optimizer — the ONE enablement path both trainer families share
    for every stage that wraps (``DistributedTrainer`` stages 1/2/3 —
    stages 2/3 consume only the wrapper's shard-view ``init`` and
    drive the raw inner from the step — and ``LMTrainer`` stage 1;
    LMTrainer stages 2/3 init over views directly and call
    :func:`zero_validate` alone).

    * Rejects meshes with any non-``axis`` dimension > 1: the ZeRO
      stages shard the update/state of *replicated* parameter layouts;
      rule-driven sharded-parameter layouts belong to fsdp/TP plans.
    * Checks ``spec`` (the user's optimizer spec, a name string or a
      prebuilt transform) against ``ops.optimizers.zero1_compatible``:
      known-unsafe raises naming the offending transform,
      uninspectable warns.
    """
    zero_validate(mesh, spec if spec is not None else inner, axis=axis,
                  stage=stage)
    return zero1_optimizer(
        inner, mesh, axis=axis,
        bucket_mb=DEFAULT_BUCKET_MB if bucket_mb is None else bucket_mb)


def zero1_shard_shapes(params, n: int) -> frozenset:
    """The ``[n, cols]`` shapes ZeRO-1 optimizer-state leaves take for
    this parameter tree — what :func:`zero1_state_shardings` matches
    against."""
    return Zero1Layout.for_tree(params, n).shard_shapes


def zero1_state_shardings(params, opt_state, mesh: Mesh,
                          axis: str = "data"):
    """Sharding tree for a ZeRO optimizer state (every stage): leaves
    whose shape is one of ``params``' shard-view shapes go
    ``P(axis, None)``; everything else replicates.

    The rule is by *shape*, structure-agnostic on purpose: it covers
    moments nested inside chains, masks, and EMA shadows uniformly —
    under a sharded update the inner optimizer only ever sees shard
    views, so every params-mirroring leaf it creates has a shard-view
    shape, and the remaining leaves are scalar counts.  Since the
    ZeRO-2/3 round it is expressed through the ONE regex rule engine
    (``parallel/rules.py``: the shape-keyed :func:`~distkeras_tpu.
    parallel.rules.shard_view_rule` ahead of a replicate-everything
    catch-all), the same ordered-rules form every other plan takes.
    ``opt_state`` may be real arrays or an ``eval_shape`` tree.
    """
    from distkeras_tpu.parallel.rules import zero_state_shardings

    return zero_state_shardings(params, opt_state, mesh, axis=axis)


__all__ = ["Zero1Layout", "scatter", "reduce_scatter", "all_gather",
           "gather_bucket", "adasum_reduce", "zero1_optimizer",
           "zero1_enable", "zero1_validate", "zero_validate",
           "zero1_shard_shapes", "zero1_state_shardings",
           "DEFAULT_BUCKET_MB"]
