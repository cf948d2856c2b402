"""Regex partition-rule plans: ordered rules over flattened key paths.

The repo grew three hand-built planners — ``ShardingPlan.spec_for``'s
regex loop over Keras variable paths, ``Zero1Plan``'s shape-keyed
optimizer-state walk, and ``ExchangePlan``'s residual-aware variant —
and the ZeRO-2/3 work multiplies the plans again.  This module is the
ONE rule engine they all derive from, the ``match_partition_rules``
pattern of SNIPPETS [1] grown into a library:

* a **rule** is ``(pattern, value)``: ``pattern`` a regex matched
  (``re.search``) against the leaf's flattened key path (rendered
  ``"layers/0/attn/wq"``-style, the same language ShardingPlan always
  used), ``value`` either a concrete value (a ``PartitionSpec``, a
  codec name, ...) or a callable ``(name, leaf) -> value | None`` —
  ``None`` means "this rule declines, fall through to the next".
  Callable rules are what lets shape-keyed policies (the ZeRO shard-view
  rule) and path-keyed policies live in one ordered list.
* matching is **first-match-wins** in rule order;
* an **unmatched leaf raises**, naming the leaf path — the silent
  "unmatched means replicated" default of the old planners hid typos in
  TP rule sets.  Pass ``default=`` to restore a fallback explicitly
  (the plans append an explicit catch-all ``(".*", default)`` instead,
  so reading the rule list shows the whole policy).

Consumers: ``parallel/sharding.py`` (every ShardingPlan;
``Zero3Plan``), ``parallel/exchange.py`` (per-bucket codec rules and
the exchange-state shardings), and user code via
``distkeras_tpu.match_partition_rules``.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import keystr


class UnmatchedLeafError(ValueError):
    """No rule matched a leaf (carries the rendered leaf path).

    ``patterns`` (when the raiser has them) are the rule patterns that
    were tried: the message names the 3 nearest misses by match-prefix
    length — how far the pattern's literal spelling gets into the leaf
    path before diverging — so a plan-authoring typo ("atn/wq" for
    "attn/wq") is self-diagnosing instead of a silent fall-through.
    """

    def __init__(self, name: str, what: str, patterns: Sequence[str] = ()):
        self.leaf = name
        near = nearest_patterns(name, patterns)
        near_s = ("; nearest-miss patterns (by match-prefix length): "
                  + ", ".join(repr(p) for p in near)) if near else ""
        super().__init__(
            f"no {what} rule matched leaf {name!r}; rules are ordered "
            "(pattern, value) pairs matched first-match-wins against "
            "the flattened key path — add a rule for this leaf or a "
            f"catch-all ('.*', <default>) at the end{near_s}")


def _pattern_skeleton(pattern: str) -> str:
    """The literal spine of a regex: metacharacters stripped, escapes
    unwrapped — what the author *typed* minus the regex machinery."""
    s = re.sub(r"\\([\w/])", r"\1", pattern)
    return re.sub(r"[\^\$\.\*\+\?\(\)\[\]\{\}\|\\]", "", s)


def _miss_score(pattern: str, name: str) -> int:
    """Match-prefix length: the longest prefix of the pattern's literal
    skeleton that still occurs in ``name``.  A typo'd rule scores just
    below its intended target; an unrelated rule scores ~0."""
    skel = _pattern_skeleton(pattern)
    for k in range(len(skel), 0, -1):
        if skel[:k] in name:
            return k
    return 0


def nearest_patterns(name: str, patterns: Sequence[str], n: int = 3):
    """The ``n`` patterns nearest to ``name`` by match-prefix length
    (ties keep rule order) — the UnmatchedLeafError diagnosis.
    Patterns sharing nothing with the leaf (score 0) are omitted:
    listing unrelated rules as "nearest" would mislead, and an empty
    result drops the diagnosis line entirely."""
    pats = [p if isinstance(p, str) else p.pattern for p in patterns]
    scored = sorted((-_miss_score(p, name), i)
                    for i, p in enumerate(pats))
    return [pats[i] for s, i in scored[:n] if s < 0]


# Sentinel: "no default — unmatched leaves are an error".
_RAISE = object()


def leaf_name(path) -> str:
    """Render one jax key path the way every rule in this repo is
    written against: ``"layers/0/attn/wq"``."""
    return keystr(path, simple=True, separator="/")


def _is_concrete(val) -> bool:
    """A value that always claims a pattern match (a PartitionSpec, a
    codec name, a sharding) — as opposed to a callable rule, which may
    decline and fall through.  The shard lint's duplicate-pattern rule
    (analysis/shard_lint.py) shares this predicate so the build-time
    rejection below and the static lint can never disagree."""
    return not (callable(val) and not isinstance(val, type))


def compile_rules(rules: Sequence[tuple[str, Any]]):
    """[(pattern, value)] -> [(compiled, value)], validating patterns
    eagerly so a typo raises at plan construction, not mid-trace.

    Rejects an identical pattern repeated after an earlier occurrence
    with a *concrete* value: first-match-wins makes the later rule
    unreachable, so the duplicate is a plan-authoring bug (the same
    spelling as the shard lint's ``duplicate-pattern`` rule,
    docs/graph_lint.md).  Repeats after a *callable* occurrence remain
    legal — the decline-chain idiom ``zero_state_rules`` is built on.
    """
    claimed: dict[str, bool] = {}
    out = []
    for pat, val in rules:
        if claimed.get(pat):
            raise ValueError(
                f"duplicate pattern {pat!r}: an identical earlier rule "
                "with a concrete value already claims every match "
                "(first-match-wins), so this rule can never fire — "
                "remove one of the two (shard lint rule "
                "`duplicate-pattern`)")
        claimed[pat] = claimed.get(pat, False) or _is_concrete(val)
        out.append((re.compile(pat), val))
    return out


def first_match(compiled, name: str, leaf=None):
    """First rule whose pattern matches ``name`` and whose value
    accepts the leaf; ``(matched, value)`` — ``(False, None)`` when no
    rule claims it."""
    for pat, val in compiled:
        if pat.search(name) is None:
            continue
        if callable(val) and not isinstance(val, type):
            out = val(name, leaf)
            if out is None:
                continue  # rule declined: fall through
            return True, out
        return True, val
    return False, None


def match_rules(rules: Sequence[tuple[str, Any]], tree, *,
                default: Any = _RAISE, what: str = "partition"):
    """Pytree -> same-structure pytree of rule values.

    The generic engine: ``rules`` may map to anything (PartitionSpecs,
    codec names, shardings).  Unmatched leaves raise
    :class:`UnmatchedLeafError` naming the leaf, unless ``default`` is
    given.
    """
    compiled = compile_rules(rules)

    def visit(path, leaf):
        name = leaf_name(path)
        matched, val = first_match(compiled, name, leaf)
        if matched:
            return val
        if default is _RAISE:
            raise UnmatchedLeafError(name, what,
                                     [p.pattern for p, _ in compiled])
        return default

    return jax.tree_util.tree_map_with_path(visit, tree)


def match_partition_rules(rules: Sequence[tuple[str, P]], tree, *,
                          default: Any = _RAISE):
    """The SNIPPETS [1] ``match_partition_rules`` contract: ordered
    ``(regex, PartitionSpec)`` rules over flattened key paths, first
    match wins, **scalar leaves always replicate** (partitioning a
    scalar is never meaningful), unmatched non-scalar leaves raise
    naming the leaf."""
    def scalar_guard(name, leaf):
        shape = getattr(leaf, "shape", None)
        if shape is not None and len(shape) == 0:
            return P()
        return None

    return match_rules([(r".*", scalar_guard)] + list(rules), tree,
                       default=default)


def tree_shardings(mesh: Mesh, rules: Sequence[tuple[str, Any]], tree, *,
                   default: Any = _RAISE, what: str = "sharding"):
    """Like :func:`match_rules` but wraps plain ``PartitionSpec``
    values into ``NamedSharding(mesh, spec)`` (values that already are
    shardings pass through) — the form ``jax.device_put`` and
    ``jit(out_shardings=...)`` consume."""
    def wrap(v):
        return NamedSharding(mesh, v) if isinstance(v, P) else v

    if default is not _RAISE:
        default = wrap(default)
    specs = match_rules(rules, tree, default=default, what=what)
    return jax.tree_util.tree_map(wrap, specs)


# --------------------------------------------------- the ZeRO rule set


def shard_view_rule(shard_shapes: frozenset, mesh: Mesh,
                    axis: str = "data"):
    """The ZeRO shard-view rule as ONE engine rule: any leaf whose
    shape is a ``[n, cols]`` shard-view shape of the parameter tree
    scatters ``P(axis, None)``; every other leaf falls through to the
    next rule.  Shape-keyed on purpose (see
    ``collectives.zero1_state_shardings``): it covers moments nested in
    chains, masks and EMA shadows uniformly, because under a sharded
    update the inner optimizer only ever sees shard views."""
    sh = NamedSharding(mesh, P(axis, None))

    def rule(name, leaf):
        if hasattr(leaf, "shape") and tuple(leaf.shape) in shard_shapes:
            return sh
        return None

    return (r".*", rule)


def zero_state_rules(params, mesh: Mesh, axis: str = "data"):
    """The ordered rule list for a ZeRO-sharded optimizer state (every
    stage): shard views scatter, everything else (scalar counts,
    EmptyState internals) replicates.  ``params`` is the parameter tree
    the state mirrors (arrays or shape structs) — full layout or shard
    views, the derived shard shapes agree."""
    from distkeras_tpu.parallel.collectives import zero1_shard_shapes

    shapes = zero1_shard_shapes(jax.tree.leaves(params),
                                int(mesh.shape[axis]))
    return [shard_view_rule(shapes, mesh, axis=axis),
            (r".*", NamedSharding(mesh, P()))]


def zero_state_shardings(params, opt_state, mesh: Mesh,
                         axis: str = "data"):
    """Sharding tree for a ZeRO optimizer state, via the rule engine —
    the ONE definition every stage and both trainer families share."""
    return match_rules(zero_state_rules(params, mesh, axis=axis),
                       opt_state, what="ZeRO state sharding")


def zero3_param_shardings(view_tree, mesh: Mesh, axis: str = "data"):
    """Shardings for a ZeRO-3 parameter tree held as ``[n, cols]``
    shard views: every leaf scatters ``P(axis, None)`` (gather-on-use
    re-materializes them per fusion bucket inside the step)."""
    sh = NamedSharding(mesh, P(axis, None))
    return jax.tree.map(lambda _: sh, view_tree)


# ----------------------------------------------- the serving KV rules
#
# Pod-sharded serving (round 14): the KV cache's placement is DERIVED
# from the param rules, never authored separately — the rule that
# shards attention projections over a mesh axis determines which axis
# the cache's kv-heads dimension shards over, so plan and cache can
# never disagree (a cache sharded differently from the heads that
# write it would make GSPMD reshard the slab every token).

# Canonical attention-projection paths of the functional transformer
# (models/transformer.py init_params), with the index of the HEADS
# dimension in each kernel's [L, ...] stacked shape.  wq carries
# n_heads; wk/wv carry kv_heads — both must divide by the axis.
_ATTN_HEAD_PATHS = (
    ("layers/attn/wq", 2, "n_heads"),
    ("layers/attn/wk", 2, "kv_heads"),
    ("layers/attn/wv", 2, "kv_heads"),
)


def _axes_of(entry) -> tuple:
    """Mesh axes one PartitionSpec entry names (an entry may be an
    axis name or a tuple of them)."""
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, tuple) else (entry,)


def serving_kv_axis(plan, mesh: Mesh, cfg) -> str | None:
    """The mesh axis a serving plan shards attention HEADS over — and
    therefore the axis the KV cache/slab's kv-heads dimension must
    shard over.  None when the plan leaves attention heads whole
    (pure-FSDP / replicated plans: params gather on use, the cache
    replicates, GSPMD still compiles one program).

    Validates head divisibility eagerly and names the offending rule:
    a head count the axis cannot split would otherwise surface as an
    inscrutable GSPMD error at first trace.
    """
    _reject_latent_plane(getattr(cfg, "latent_planes", 0))
    axis, culprit = None, None
    for path, head_dim, attr in _ATTN_HEAD_PATHS:
        for pat, spec in plan.rules:
            if pat.search(path) is None:
                continue
            if callable(spec):
                # First-match-wins: a callable claiming an attention
                # path would decide the param placement at device_put
                # time, where this derivation cannot follow it —
                # skipping it silently could leave the cache placed
                # against the heads that write it.  Loud, like every
                # plan-validation failure in this module.
                raise ValueError(
                    f"serving plan rule ({pat.pattern!r}, <callable>) "
                    f"matches attention path {path!r}; the KV-cache "
                    "placement is derived from the attention rules, "
                    "which therefore must be concrete PartitionSpecs "
                    "— spell the attention rule out (callable rules "
                    "remain fine for every other path)")
            spec_t = tuple(spec)
            entries = (spec_t[head_dim]
                       if len(spec_t) > head_dim else None)
            for a in _axes_of(entries):
                n = int(mesh.shape[a])
                if n <= 1:
                    continue
                heads = int(getattr(cfg, attr))
                if heads % n:
                    raise ValueError(
                        f"serving plan rule ({pat.pattern!r}, "
                        f"{spec}) shards the head dimension of "
                        f"{path!r} over mesh axis {a!r} (size {n}), "
                        f"but {attr}={heads} is not divisible by it — "
                        "shrink the axis or pick a head count the "
                        "mesh can split")
                if axis is not None and a != axis:
                    raise ValueError(
                        f"serving plan shards attention heads over "
                        f"two different mesh axes ({axis!r} via "
                        f"{culprit!r}, {a!r} via {pat.pattern!r}); "
                        "the KV cache has ONE heads dimension — use "
                        "one axis")
                axis, culprit = a, pat.pattern
            break  # first-match-wins, like every plan lookup
    return axis


def _reject_latent_plane(found) -> None:
    """A latent plane (``lat [planes, lanes, max_len, latent_width]``:
    one row a position that every head reads) has no kv-heads
    dimension to shard: placed by the rule below it would be cut over
    its POSITIONS.  Said by name, not mis-placed."""
    if found:
        raise ValueError(
            "a latent plane (layer_types: 'latent', the cache leaf "
            "'lat') has no kv-heads dimension: serving_kv_axis / "
            "kv_slab_specs place a cache by that dimension and cannot "
            "place it (tensor-parallel heads over a cache with no heads "
            "axis is not built)")


def kv_slab_specs(tree, axis: str | None):
    """PartitionSpecs for a KV cache / paged block slab / prefix-pool
    slab: the kv-heads dimension shards over ``axis``, everything else
    replicates.  Works on every KV layout in the repo because they all
    end ``[..., kv_heads, head_dim]`` for data leaves and
    ``[..., kv_heads]`` for the int8 scale leaves — the heads dim is
    ``ndim-2`` or ``ndim-1`` keyed on the leaf name.  ``axis=None``
    replicates everything (the pure-FSDP serving layout)."""
    def leaf(path, a):
        ndim = getattr(a, "ndim", len(a.shape))
        _reject_latent_plane(leaf_name(path) == "lat")
        if axis is None:
            return P()
        hd = ndim - 1 if leaf_name(path).endswith("scale") else ndim - 2
        spec = [None] * (hd + 1)
        spec[hd] = axis
        return P(*spec)

    return jax.tree_util.tree_map_with_path(leaf, tree)


def kv_slab_shardings(mesh: Mesh, tree, axis: str | None):
    """:func:`kv_slab_specs` wrapped into ``NamedSharding`` — the form
    ``jax.device_put`` and ``with_sharding_constraint`` consume."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), kv_slab_specs(tree, axis))


__all__ = ["UnmatchedLeafError", "nearest_patterns", "leaf_name",
           "compile_rules",
           "first_match", "match_rules", "match_partition_rules",
           "tree_shardings", "shard_view_rule", "zero_state_rules",
           "zero_state_shardings", "zero3_param_shardings",
           "serving_kv_axis", "kv_slab_specs", "kv_slab_shardings"]
