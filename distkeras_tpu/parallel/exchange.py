"""Pluggable gradient-exchange layer: Adasum, local-SGD, EF codecs.

The data-parallel trainers' default exchange is the compiler-inserted
mean all-reduce (or ZeRO-1's RS+AG, ``parallel/collectives.py``).  This
module makes the exchange a *policy*, reviving the source paper's
low-communication identity (DOWNPOUR/AEASGD's "talk less, learn more")
at modern scale:

* **Adasum merge** ("Scaling Distributed Training with Adaptive
  Summation", arXiv 2006.02924): replicas' gradients combine pairwise
  with adaptive weights ``1 - <g_i, g_j> / (2 |g_i|^2)`` instead of a
  plain mean, so nearly-parallel gradients average (identical replicas
  reproduce mean-reduce exactly) while orthogonal ones *sum* — the
  property that tolerates much larger effective batches.
* **Error-feedback compression codecs** (motivated by the bandwidth
  analysis in "Scaling Distributed ML with In-Network Aggregation",
  arXiv 1903.06701): per fusion bucket, the int8 codec quantizes each
  replica's contribution (plus the carried residual), moves an int8
  wire payload through a chunked two-phase reduce (all-to-all partial
  sums, then an all-gather of the re-quantized chunks — the compressed
  spelling of reduce-scatter + all-gather), and dequantizes; the
  residual ``x - decode(encode(x))`` carries to the next step, which is
  what keeps convergence honest.  Wire bytes drop ~4x vs f32 (pinned
  exactly by the collective census in ``scripts/comm_budget.json``).
  The top-k codec keeps the ``topk_frac`` largest-magnitude entries per
  bucket instead.  ``zero1=True`` composes by compressing the
  reduce-scatter leg and leaving the all-gather of the (already
  sharded-computed) update in full precision.
* **Local-SGD / periodic sync** (``sync_every=H``): H purely-local
  optimizer steps per replica, then ONE cross-replica parameter merge
  (momentum buffers averaged too — the momentum-aware variant), cutting
  collective frequency to 1/H.  The step builders live with the trainer
  families (``models/adapter.py``, ``trainers/lm.py``); the merge rules
  here are shared.

All rules operate on **stacked local gradients**: the trainers compute
per-replica gradients inside a ``shard_map`` over the ``data`` axis and
return them with a leading replica axis (global ``[n, *leaf]``, sharded
``P("data")``), so the exchange sees the pre-reduction contributions the
compiler path never materializes.  Bucketing reuses
:class:`~distkeras_tpu.parallel.collectives.Zero1Layout` — the same
~``bucket_mb`` dtype-grouped fusion buckets ZeRO-1 overlaps.

See docs/lowcomm.md for merge-rule semantics, the codec contract, and
when local-SGD is safe.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu import obs
from distkeras_tpu.parallel.collectives import (DEFAULT_BUCKET_MB,
                                                 Zero1Layout, all_gather,
                                                 zero1_shard_shapes)

_MERGE_RULES = ("mean", "adasum")
_CODECS = (None, "int8", "topk")
# Smallest positive normal f32: the zero-norm/zero-scale guard.
_EPS = np.float32(np.finfo(np.float32).tiny)


@dataclasses.dataclass(frozen=True)
class ExchangeConfig:
    """One gradient-exchange policy (validated at construction).

    ``merge_rule``: "mean" (the baseline semantics) or "adasum".
    ``sync_every``: local-SGD period H (1 = sync every step).
    ``compress``: None, "int8" (error-feedback symmetric int8), "topk"
    (error-feedback magnitude top-k, ``topk_frac`` of each bucket) —
    or an ordered sequence of ``(regex, codec)`` RULES resolved per
    parameter leaf by the shared rule engine (``parallel/rules.py``,
    first-match-wins over flattened key paths / Keras variable paths;
    an unmatched leaf raises naming it).  Under rules the fusion
    buckets group by (dtype, codec) so every bucket is
    codec-homogeneous — e.g. ``[("emb", "topk"), (".*", "int8")]``
    sends embedding gradients top-k and dense gradients int8, and the
    compiled census pins each bucket's wire dtype separately.
    ``bucket_mb`` sizes the fusion buckets (same knob as ZeRO-1).

    Composition limits (raise here, not deep in a trace):
    ``compress`` requires ``merge_rule="mean"`` (the codecs implement a
    compressed *sum*; Adasum needs the uncompressed stacks) and
    ``sync_every=1`` (local-SGD exchanges parameters, not gradients).
    Codec RULES do not compose with the ZeRO stages (only the uniform
    ``"int8"`` codec has a chunked compressed-reduce-scatter form).
    """

    merge_rule: str = "mean"
    sync_every: int = 1
    compress: str | tuple | None = None
    topk_frac: float = 0.01
    bucket_mb: float = DEFAULT_BUCKET_MB

    def __post_init__(self):
        if self.merge_rule not in _MERGE_RULES:
            raise ValueError(
                f"merge_rule must be one of {_MERGE_RULES}, got "
                f"{self.merge_rule!r}")
        if isinstance(self.compress, (list, tuple)):
            import re

            rules = []
            for entry in self.compress:
                try:
                    pat, codec = entry
                except (TypeError, ValueError):
                    raise ValueError(
                        "compress rules must be (pattern, codec) "
                        f"pairs, got {entry!r}")
                if codec not in ("int8", "topk"):
                    raise ValueError(
                        f"compress rule {pat!r} names codec {codec!r}; "
                        "known codecs: 'int8', 'topk'")
                re.compile(pat)  # typos raise here, not mid-trace
                rules.append((str(pat), str(codec)))
            if not rules:
                raise ValueError(
                    "compress=[] is ambiguous: pass None for no codec "
                    "or at least one (pattern, codec) rule")
            object.__setattr__(self, "compress", tuple(rules))
        elif self.compress not in _CODECS:
            raise ValueError(
                f"compress must be one of {_CODECS} or a sequence of "
                f"(regex, codec) rules, got {self.compress!r}")
        if self.sync_every < 1:
            raise ValueError(
                f"sync_every must be >= 1, got {self.sync_every}")
        if not 0.0 < self.topk_frac <= 1.0:
            raise ValueError(
                f"topk_frac must be in (0, 1], got {self.topk_frac}")
        if self.compress is not None and self.merge_rule != "mean":
            raise ValueError(
                "compress composes with merge_rule='mean' only: the "
                "codecs implement a compressed sum, while adasum needs "
                "every replica's uncompressed contribution")
        if self.compress is not None and self.sync_every > 1:
            raise ValueError(
                "compress with sync_every > 1 is not supported: "
                "local-SGD exchanges parameters once per period, so "
                "there is no per-step gradient wire to compress")
        if self.sync_every > 1 and self.merge_rule == "adasum":
            # Allowed: adasum applies to the parameter DELTAS at sync.
            pass

    @property
    def is_default(self) -> bool:
        """True when this config means "the compiler-inserted mean
        exchange" — the trainers skip the whole layer then."""
        return (self.merge_rule == "mean" and self.sync_every == 1
                and self.compress is None)

    @property
    def needs_grad_exchange(self) -> bool:
        """Per-step gradient merging (vs local-SGD's parameter sync)."""
        return not self.is_default and self.sync_every == 1

    @property
    def codec_rules(self) -> tuple | None:
        """The (pattern, codec) rules when ``compress`` is rule-based,
        else None."""
        return self.compress if isinstance(self.compress, tuple) else None

    def label(self) -> str:
        parts = []
        if self.merge_rule != "mean":
            parts.append(self.merge_rule)
        if self.sync_every > 1:
            parts.append(f"localsgd{self.sync_every}")
        if self.codec_rules is not None:
            parts.append("rulesef")
        elif self.compress:
            parts.append(f"{self.compress}ef")
        return "_".join(parts) or "mean"


@flax.struct.dataclass
class ExchangeState:
    """Error-feedback carry of one exchange policy (a pytree; rides
    inside the optimizer state so checkpointing and the Supervisor's
    bit-for-bit resume cover it with zero extra machinery).

    ``e1``: per-bucket phase-1 residuals — each replica's quantization
    error on its local contribution; global ``[n, n, C_b]`` sharded
    ``P("data", None, None)`` (leading axis = replica).  ``e2``:
    per-bucket phase-2 residuals of the re-quantized reduced chunk;
    global ``[n, C_b]`` sharded ``P("data", None)``.  Both empty
    without a codec.  ``residual_norm``: replicated scalar, the global
    L2 norm of all residuals after the last update — the EF diagnostic
    the obs layer reads at end of run.
    """

    e1: Any
    e2: Any
    residual_norm: Any


# ------------------------------------------------------------- adasum


def _adasum_pair(a, b):
    """Pairwise adaptive sum of two same-shape f32 vectors.

    ``(1 - <a,b>/(2|a|^2)) a + (1 - <a,b>/(2|b|^2)) b`` — the mean for
    parallel inputs, the plain sum for orthogonal ones.  Zero-norm
    inputs fall back to the plain sum (the projection is undefined)."""
    dot = jnp.sum(a * b)
    na = jnp.sum(a * a)
    nb = jnp.sum(b * b)
    fa = jnp.where(na > 0, 1.0 - dot / (2.0 * jnp.maximum(na, _EPS)), 1.0)
    fb = jnp.where(nb > 0, 1.0 - dot / (2.0 * jnp.maximum(nb, _EPS)), 1.0)
    return fa * a + fb * b


def adasum_combine(stack):
    """Reduce ``[m, D]`` stacked contributions to ``[D]`` by pairwise
    adaptive summation up a binary tree (log2(m) levels; an odd
    leftover at any level rides up unmerged).  Deterministic: the tree
    shape depends only on ``m``."""
    stack = jnp.asarray(stack, jnp.float32)
    while stack.shape[0] > 1:
        m = stack.shape[0]
        pairs = m // 2
        merged = jax.vmap(_adasum_pair)(stack[0:2 * pairs:2],
                                        stack[1:2 * pairs:2])
        if m % 2:
            merged = jnp.concatenate([merged, stack[-1:]], axis=0)
        stack = merged
    return stack[0]


# ------------------------------------------------------------- codecs


def int8_encode(x):
    """Symmetric per-row int8 quantization of ``x [..., C]`` over its
    last axis: returns ``(q int8, scale f32[..., 1])`` with
    ``dequant = q * scale``.  scale = amax/127, guarded so an all-zero
    row encodes to zeros exactly."""
    x = jnp.asarray(x, jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, _EPS)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_decode(q, scale):
    return q.astype(jnp.float32) * scale


# ------------------------------------------------ in-shard_map merges


def _merge_bucket_mean(bucket, axis):
    """Plain mean merge of one local bucket (each replica's full
    ``[n, C]`` contribution) — explicit spelling of the compiler's
    gradient all-reduce, for the stacked-local-grad path."""
    return jax.lax.pmean(bucket, axis)


def _merge_bucket_adasum(bucket, axis):
    """Adasum merge of one local bucket: gather every replica's
    contribution, pairwise-combine up the binary tree (replicated
    math, identical on every replica)."""
    stacked = jax.lax.all_gather(bucket, axis, axis=0)      # [n, n, C]
    merged = adasum_combine(stacked.reshape(stacked.shape[0], -1))
    return merged.reshape(bucket.shape).astype(bucket.dtype)


def _merge_bucket_int8(bucket, e1, e2, axis, n, zero1):
    """Error-feedback int8 merge of one local bucket ``[n, C]`` (rows
    chunk-major: row k is the chunk replica k owns — the Zero1Layout
    contract, which is what makes the two-phase reduce a compressed
    RS+AG).

    Phase 1 (compressed reduce-scatter): quantize each row of the
    residual-corrected local contribution, all-to-all the int8 rows so
    replica k receives every peer's chunk k, dequantize and sum —
    replica k now holds the reduced chunk k.  Phase 2 (compressed
    all-gather; skipped under ``zero1``, which updates on the scattered
    chunks and gathers the f32 *update* instead): re-quantize the
    reduced chunk, all-gather the int8 chunks, dequantize into the full
    merged bucket.  Residuals carry what quantization dropped.

    Returns ``(merged, e1', e2')``: merged is the full ``[n, C]``
    mean bucket (or the ``[C]`` owned chunk under zero1).
    """
    x = jnp.asarray(bucket, jnp.float32) / n + e1   # mean semantics
    q, scale = int8_encode(x)                       # [n, C], [n, 1]
    e1_new = x - int8_decode(q, scale)
    qt = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                            tiled=True)             # rows = peers' chunk k
    st = jax.lax.all_to_all(scale, axis, split_axis=0, concat_axis=0,
                            tiled=True)             # [n, 1]
    chunk = jnp.sum(int8_decode(qt, st), axis=0)    # [C]: reduced chunk k
    if zero1:
        return chunk, e1_new, e2
    y = chunk + e2
    q2, s2 = int8_encode(y[None])                   # [1, C], [1, 1]
    e2_new = y - int8_decode(q2, s2)[0]
    qg = jax.lax.all_gather(q2[0], axis, axis=0)    # [n, C] int8
    sg = jax.lax.all_gather(s2[0], axis, axis=0)    # [n, 1]
    merged = int8_decode(qg, sg).astype(bucket.dtype)
    return merged, e1_new, e2_new


def _merge_bucket_topk(bucket, e1, axis, n, k):
    """Error-feedback top-k merge of one local bucket ``[n, C]``: keep
    the ``k`` largest-magnitude entries of the residual-corrected local
    contribution (flattened), all-gather ``(values, indices)`` and
    scatter-add into the dense merged bucket.  Wire per step is
    ``8k * n`` bytes instead of the bucket's f32 all-reduce."""
    shape = bucket.shape
    x = (jnp.asarray(bucket, jnp.float32) / n + e1).reshape(-1)
    _, idx = jax.lax.top_k(jnp.abs(x), k)
    vals = x[idx]
    e_new = x.at[idx].set(0.0).reshape(shape)
    vg = jax.lax.all_gather(vals, axis, axis=0)     # [n, k]
    ig = jax.lax.all_gather(idx, axis, axis=0)      # [n, k]
    merged = jnp.zeros(x.shape, jnp.float32).at[ig.reshape(-1)].add(
        vg.reshape(-1))
    return merged.reshape(shape).astype(bucket.dtype), e_new


# --------------------------------------------------- the optimizer wrap


def _unstacked_struct(stacked):
    """ShapeDtypeStruct tree of the un-stacked gradient (drop the
    leading replica axis) — what the bucket layout is computed over."""
    return jax.tree.map(
        lambda g: jax.ShapeDtypeStruct(tuple(g.shape)[1:], g.dtype),
        stacked)


def resolve_codecs(rules: Sequence, tree, names=None):
    """Per-leaf codec tree from ordered ``(pattern, codec)`` rules via
    the shared rule engine (``parallel/rules.py``): first-match-wins
    over the flattened key paths — or over ``names``, a same-structure
    tree of explicit leaf names (the Keras trainers pass their variable
    paths so rules read ``"dense_1/kernel"``-style, not list indices).
    An unmatched leaf raises, naming it."""
    from distkeras_tpu.parallel.rules import (UnmatchedLeafError,
                                              compile_rules, first_match,
                                              match_rules)

    if names is None:
        return match_rules(list(rules), tree, what="codec")
    compiled = compile_rules(list(rules))

    def of(name):
        matched, codec = first_match(compiled, str(name))
        if not matched:
            raise UnmatchedLeafError(str(name), "codec",
                                     [p.pattern for p, _ in compiled])
        return codec

    return jax.tree.map(of, names)


def exchange_layout(tree, n: int, config: ExchangeConfig, names=None
                    ) -> Zero1Layout:
    """The fusion-bucket layout one exchange policy uses for ``tree``:
    the plain ZeRO-1 layout, except under codec RULES the buckets
    additionally group by resolved codec (``Zero1Layout`` groups=), so
    each bucket is codec-homogeneous and ``bucket_groups[i]`` IS bucket
    i's codec."""
    if config.codec_rules is None:
        return Zero1Layout.for_tree(tree, n, config.bucket_mb)
    codecs = resolve_codecs(config.codec_rules, tree, names=names)
    return Zero1Layout.for_tree(tree, n, config.bucket_mb,
                                groups=codecs)


def _bucket_codecs(layout: Zero1Layout, config: ExchangeConfig) -> list:
    """Bucket index -> codec (or None): the rule-resolved group key
    under codec rules, the uniform ``compress`` otherwise."""
    if config.codec_rules is not None:
        return list(layout.bucket_groups)
    return [config.compress] * len(layout.bucket_cols)


def _e2_slots(layout: Zero1Layout, config: ExchangeConfig,
              zero1: bool) -> dict:
    """bucket index -> slot in the ``e2`` residual list.  Only int8
    buckets outside zero1 carry a phase-2 re-quantization residual —
    a top-k bucket in a mixed-rules layout gets NO slot (an aligned
    zero buffer would persist bucket-sized dead f32 in the optimizer
    state, donated and resharded every step)."""
    if zero1:
        return {}
    codecs = _bucket_codecs(layout, config)
    return {i: k for k, i in enumerate(
        j for j, c in enumerate(codecs) if c == "int8")}


def _residual_shapes(layout: Zero1Layout, config: ExchangeConfig,
                     zero1: bool):
    """(e1 shapes, e2 shapes) — global — for one layout.  ``e1``
    exists per bucket for every codec'd bucket; ``e2`` per int8 bucket
    only (see :func:`_e2_slots`)."""
    n = layout.n
    codecs = _bucket_codecs(layout, config)
    if not any(codecs):
        return [], []
    e1 = [(n, n, c) for c in layout.bucket_cols]
    e2 = [(n, layout.bucket_cols[i])
          for i in sorted(_e2_slots(layout, config, zero1))]
    return e1, e2


def topk_k(config: ExchangeConfig, bucket_cols: int, n: int) -> int:
    """Entries kept per bucket: ``topk_frac`` of the bucket, >= 1."""
    return max(1, int(round(config.topk_frac * bucket_cols * n)))


def wire_bytes(layout: Zero1Layout, config: ExchangeConfig,
               zero1: bool = False) -> tuple[int, int]:
    """``(baseline_bytes, wire_bytes)`` of one GRADIENT exchange under
    ``config`` for this bucket layout, ring-model per-device — the same
    accounting as the compiled collective census (all-reduce moves
    ``2(n-1)/n x payload``, one-shot collectives ``(n-1)/n``;
    scripts/comm_budget.json pins the compiled truth, this is what the
    obs gauges and the ``lowcomm_update`` bench report).

    ``baseline_bytes`` is the mean exchange's wire (the f32 gradient
    all-reduce; under ``zero1`` its reduce-scatter leg — the leg the
    int8 codec compresses).  ``wire_bytes`` counts the configured
    rule's gradient legs: int8 = int8 payload + per-row f32 scales per
    leg; top-k = the ``(values, indices)`` all-gather; adasum = the
    whole-stack all-gather (MORE than the mean — the batch-scaling
    trade, visible by design)."""
    n = layout.n
    ring = (n - 1) / n
    payloads = [c * n * np.dtype(d).itemsize
                for c, d in zip(layout.bucket_cols,
                                layout.bucket_dtypes)]
    ar_legs = 1 if zero1 else 2
    f32_bytes = int(sum(ar_legs * ring * p for p in payloads))
    codecs = _bucket_codecs(layout, config)
    wire = 0.0
    for cols, payload, codec in zip(layout.bucket_cols, payloads,
                                    codecs):
        if codec == "int8":
            legs = 1 if zero1 else 2
            wire += legs * ring * (cols * n + 4 * n)
        elif codec == "topk":
            wire += ring * 8 * topk_k(config, cols, n) * n
        elif config.merge_rule == "adasum":
            wire += ring * n * payload
        else:
            wire += ar_legs * ring * payload
    return f32_bytes, int(wire)


def _record_geometry(layout: Zero1Layout, config: ExchangeConfig,
                     zero1: bool) -> None:
    """Exchange geometry into the obs registry at TRACE time (once per
    compile) — bucket count, f32 vs wire bytes, compression ratio.
    The census (scripts/comm_budget.json) pins the compiled truth;
    these gauges make it readable on a live run."""
    if obs.active() is None:
        return
    f32_bytes, wire = wire_bytes(layout, config, zero1)
    obs.gauge("exchange.buckets", len(layout.bucket_cols))
    obs.gauge("exchange.f32_bytes", f32_bytes)
    obs.gauge("exchange.wire_bytes", wire)
    obs.gauge("exchange.compression_ratio",
              f32_bytes / max(wire, 1))
    obs.gauge("exchange.sync_every", config.sync_every)
    codecs = _bucket_codecs(layout, config)
    obs.event("exchange.geometry", merge_rule=config.merge_rule,
              codec=("rules" if config.codec_rules is not None
                     else config.compress or "none"), zero1=zero1,
              buckets=len(layout.bucket_cols),
              bucket_codecs=",".join(str(c) for c in codecs))


def exchange_optimizer(inner: optax.GradientTransformation, mesh: Mesh,
                       config: ExchangeConfig, axis: str = "data",
                       zero1: bool = False, names=None
                       ) -> optax.GradientTransformation:
    """Wrap ``inner`` so its ``update`` takes STACKED LOCAL gradients
    (leading replica axis, sharded ``P(axis)``) and performs the
    configured exchange before the inner update.

    ``state = (inner_state, ExchangeState)``.  Without ``zero1`` the
    merged gradient is replicated and ``inner`` runs replicated (its
    state mirrors the params exactly as in plain DP).  With ``zero1``
    the compressed phase-1 reduce leaves each replica its owned chunk,
    ``inner`` runs on the scattered ``[n, cols]`` shard views (the
    ZeRO-1 layout), and the f32 *update* is all-gathered — the
    "compress the reduce-scatter leg" composition.

    Under codec RULES (``config.compress`` a ``(pattern, codec)``
    sequence) each fusion bucket runs the codec its leaves resolved to;
    ``names`` optionally names the leaves for the rules (a tree of
    strings matching the parameter structure — the Keras trainers pass
    their variable paths; by default the flattened key paths name
    them).

    The returned transform's ``init`` takes the plain (un-stacked)
    parameter tree, like any optax transform.
    """
    n = int(mesh.shape[axis])
    if zero1 and config.compress != "int8":
        raise ValueError(
            "zero1 composes with compress='int8' only (the chunked "
            "two-phase codec IS a compressed reduce-scatter; adasum, "
            "top-k and per-bucket codec rules merge whole buckets)")

    def layout_for(tree) -> Zero1Layout:
        return exchange_layout(tree, n, config, names=names)

    def init(params):
        layout = layout_for(params)
        inner_state = inner.init(layout.shard_views(params) if zero1
                                 else params)
        e1_s, e2_s = _residual_shapes(layout, config, zero1)
        ex = ExchangeState(
            e1=tuple(jnp.zeros(s, jnp.float32) for s in e1_s),
            e2=tuple(jnp.zeros(s, jnp.float32) for s in e2_s),
            residual_norm=jnp.zeros((), jnp.float32))
        return inner_state, ex

    def _merge(stacked, ex: ExchangeState, layout: Zero1Layout):
        """shard_map over ``axis``: local grads -> merged grads (full
        tree, or scattered buckets under zero1) + new residuals."""
        codecs = _bucket_codecs(layout, config)
        e2_slot = _e2_slots(layout, config, zero1)

        def body(stacked_local, e1, e2):
            g = jax.tree.map(lambda v: jnp.squeeze(v, axis=0),
                             stacked_local)
            buckets = layout.pack(g)
            e1 = [jnp.squeeze(e, axis=0) for e in e1]
            e2 = [jnp.squeeze(e, axis=0) for e in e2]
            merged, e1_new, e2_new = [], [], []
            for i, b in enumerate(buckets):
                if codecs[i] == "int8":
                    m, r1, r2 = _merge_bucket_int8(
                        b, e1[i],
                        e2[e2_slot[i]] if i in e2_slot else 0.0,
                        axis, n, zero1)
                    e1_new.append(r1)
                    if i in e2_slot:  # appended in slot order
                        e2_new.append(r2)
                elif codecs[i] == "topk":
                    k = topk_k(config, layout.bucket_cols[i], n)
                    m, r1 = _merge_bucket_topk(b, e1[i], axis, n, k)
                    e1_new.append(r1)
                elif config.merge_rule == "adasum":
                    m = _merge_bucket_adasum(b, axis)
                else:
                    m = _merge_bucket_mean(b, axis)
                merged.append(m)
            if e1_new or e2_new:
                sq = sum(jnp.sum(jnp.square(e)) for e in e1_new + e2_new)
                norm = jnp.sqrt(jax.lax.psum(sq, axis))
            else:  # no codec: no residual, and no wasted scalar AR
                norm = jnp.zeros(())
            if zero1:
                # merged[i] is this replica's [C] chunk; keep a leading
                # row axis so the out_spec shards it back into the
                # scattered [n, C] bucket layout.
                out = [m[None] for m in merged]
            else:
                out = layout.unpack(merged)
            return (out,
                    [e[None] for e in e1_new],
                    [e[None] for e in e2_new],
                    norm)

        merged_spec = P(axis, None) if zero1 else P()
        return shard_map(
            body, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis)),
            out_specs=(merged_spec, P(axis), P(axis), P()),
            check_vma=False)(stacked, list(ex.e1), list(ex.e2))

    def update(stacked_grads, state, params=None, **kw):
        inner_state, ex = state
        layout = layout_for(_unstacked_struct(stacked_grads))
        _record_geometry(layout, config, zero1)
        with jax.named_scope("exchange/merge"):
            merged, e1, e2, norm = _merge(stacked_grads, ex, layout)
        ex = ExchangeState(e1=tuple(e1), e2=tuple(e2),
                           residual_norm=norm)
        if zero1:
            g_views = layout.views_from_buckets(merged)
            p_views = (None if params is None
                       else layout.shard_views(params))
            with jax.named_scope("exchange/update"):
                u_views, inner_state = inner.update(g_views, inner_state,
                                                    p_views, **kw)
            with jax.named_scope("exchange/all_gather"):
                u_buckets = [all_gather(b, mesh, axis)
                             for b in layout.pack_views(u_views)]
            updates = layout.unpack(u_buckets)
        else:
            with jax.named_scope("exchange/update"):
                updates, inner_state = inner.update(merged, inner_state,
                                                    params, **kw)
        return updates, (inner_state, ex)

    return optax.GradientTransformation(init, update)


# ----------------------------------------------------- state shardings


def exchange_state_shardings(params, opt_state, mesh: Mesh,
                             axis: str = "data", zero1: bool = False):
    """Sharding tree for an :func:`exchange_optimizer` state: residual
    leaves shard over their leading replica axis, zero1 shard views
    (when composed) take the ZeRO shard-view rule, everything else
    replicates.  Since the ZeRO-2/3 round the policy is ordered rules
    resolved by the shared engine (``parallel/rules.py``) — the path-
    keyed ``e1``/``e2`` residual rules inside the located
    :class:`ExchangeState`, the shape-keyed shard-view rule outside.
    ``opt_state`` may be real arrays or an ``eval_shape`` tree."""
    from distkeras_tpu.parallel.rules import (match_rules,
                                              shard_view_rule)

    rep = NamedSharding(mesh, P())
    ex_rules = [
        (r"(^|/)e1(/|$)", NamedSharding(mesh, P(axis, None, None))),
        (r"(^|/)e2(/|$)", NamedSharding(mesh, P(axis, None))),
        (r".*", rep),
    ]
    inner_rules = []
    if zero1:
        shapes = zero1_shard_shapes(list(jax.tree.leaves(params)),
                                    int(mesh.shape[axis]))
        inner_rules.append(shard_view_rule(shapes, mesh, axis=axis))
    inner_rules.append((r".*", rep))

    def rule(x):
        if isinstance(x, ExchangeState):
            # The residual rules match within the ExchangeState subtree
            # only — a user parameter named "e1" elsewhere can never
            # collide with them.
            return match_rules(ex_rules, x, what="exchange sharding")
        return match_rules(inner_rules, {"leaf": x},
                           what="exchange sharding")["leaf"]

    return jax.tree.map(rule, opt_state,
                        is_leaf=lambda x: isinstance(x, ExchangeState))


def residual_norm_of(opt_state):
    """The ExchangeState residual-norm scalar buried anywhere in an
    optimizer state, or None.  Host-side, end-of-run: the trainers
    record it into the obs registry as the EF diagnostic."""
    found = []

    def visit(x):
        if isinstance(x, ExchangeState):
            found.append(x.residual_norm)
        return x

    jax.tree.map(visit, opt_state,
                 is_leaf=lambda x: isinstance(x, ExchangeState))
    return float(found[0]) if found else None


# --------------------------------------------------- local-SGD merging


def _mean_buckets(tree, axis: str, n: int, bucket_mb: float):
    """pmean a pytree through the fusion-bucket layout: pack, ONE
    pmean per bucket, unpack.  This is what keeps a local-SGD sync at
    ~one collective per bucket instead of one per leaf — the whole
    point of trading per-step gradient exchange for a periodic merge."""
    layout = Zero1Layout.for_tree(
        jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype),
                     tree), n, bucket_mb)
    buckets = [jax.lax.pmean(b, axis) for b in layout.pack(tree)]
    return layout.unpack(buckets)


def merge_local_params(start, local, config: ExchangeConfig, axis: str,
                       n: int):
    """Cross-replica parameter merge at a local-SGD sync point, INSIDE
    a shard_map over ``axis``: ``start`` is the (replicated) tree the
    period began from, ``local`` the replica's diverged tree.  The
    merge applies the configured rule to the parameter DELTAS, per
    fusion bucket — ``mean`` averages them (classic local-SGD /
    federated averaging); ``adasum`` combines them adaptively, the
    Adasum paper's own suggested use beyond gradients."""
    delta = jax.tree.map(lambda a, b: b - a, start, local)
    if config.merge_rule == "mean":
        merged = _mean_buckets(delta, axis, n, config.bucket_mb)
    else:
        layout = Zero1Layout.for_tree(
            jax.tree.map(
                lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), delta),
            n, config.bucket_mb)
        buckets = [_merge_bucket_adasum(b, axis)
                   for b in layout.pack(delta)]
        merged = layout.unpack(buckets)
    return jax.tree.map(jnp.add, start, merged)


def sync_local_tree(tree, config: ExchangeConfig, axis: str, n: int):
    """Momentum-aware half of the sync: pmean every floating leaf of
    ``tree`` (an optimizer state / ntv pytree) through the fusion
    buckets, pass the rest through (int leaves — step counts —
    increment identically on every replica)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    fmask = [jnp.issubdtype(jnp.asarray(l).dtype, jnp.inexact)
             for l in leaves]
    floats = [l for l, m in zip(leaves, fmask) if m]
    if floats:
        merged = iter(_mean_buckets(floats, axis, n, config.bucket_mb))
        leaves = [next(merged) if m else l
                  for l, m in zip(leaves, fmask)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


__all__ = ["ExchangeConfig", "ExchangeState", "exchange_optimizer",
           "exchange_state_shardings", "exchange_layout",
           "resolve_codecs", "residual_norm_of",
           "adasum_combine", "int8_encode", "int8_decode",
           "merge_local_params", "sync_local_tree",
           "topk_k", "wire_bytes"]
