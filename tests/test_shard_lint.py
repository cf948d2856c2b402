"""Shard lint: every plan-lint rule positive + negative, resharding
attribution (including the injected dropped-``with_sharding_constraint``
regression), the placement-census machinery, the shipped-plan dry-run
matrix, and the CLI mode-flag validation.

The compiled-census repo guards — placement budget vs
``scripts/shard_budget.json``, the no-unattributed-resharding
invariant, the memory-footprint cross-check — live in
``tests/test_budget_guards.py``, which compiles every standard target
once for the whole module (same split as graph lint:
test_graph_lint.py carries the rules, test_budget_guards.py the heavy
repo runs).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distkeras_tpu.analysis import shard_lint as sl
from distkeras_tpu.analysis.ir_lint import TraceSpec, trace_target
from distkeras_tpu.parallel import rules as pr
from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
from helpers import toy_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings, only_gating=False):
    return {f.rule for f in findings if f.gating or not only_gating}


def _tree():
    return {
        "layers": {"attn": {"wq": jax.ShapeDtypeStruct((2, 32, 2, 16),
                                                       jnp.float32)},
                   "ffn": {"w1": jax.ShapeDtypeStruct((2, 32, 64),
                                                      jnp.float32)}},
        "tok_emb": jax.ShapeDtypeStruct((64, 32), jnp.float32),
    }


# ------------------------------------------------------ plan-lint rules


def test_dead_rule_positive_and_negative():
    pos = sl.lint_plan([("atn/wq$", P())], _tree(), name="t")
    assert "dead-rule" in rules_of(pos, only_gating=True)
    neg = sl.lint_plan([("attn/wq$", P())], _tree(), name="t")
    assert "dead-rule" not in rules_of(neg)
    # The finding names the offending (pattern, value) pair.
    f = next(f for f in pos if f.rule == "dead-rule")
    assert "atn/wq$" in f.message and "P()" in f.message


def test_shadowed_rule_positive_and_negative():
    pos = sl.lint_plan([("attn/.*", P(None, None, "model", None)),
                        ("attn/wq$", P())], _tree(), name="t")
    assert "shadowed-rule" in rules_of(pos, only_gating=True)
    f = next(f for f in pos if f.rule == "shadowed-rule")
    # ... naming the shadowed rule, the covering rule, and the leaves.
    assert "attn/wq$" in f.message and "attn/.*" in f.message
    assert "layers/attn/wq" in f.message
    # A later broader rule that still wins SOME leaf: no shadow.
    neg = sl.lint_plan([("attn/wq$", P()), ("(attn|ffn)/.*", P())],
                       _tree(), name="t")
    assert "shadowed-rule" not in rules_of(neg)
    # Union coverage shadows too: two narrow rules together cover a
    # later broad one.
    pos2 = sl.lint_plan([("attn/wq$", P()), ("ffn/w1$", P()),
                         ("(attn/wq|ffn/w1)$", P())], _tree(), name="t")
    assert "shadowed-rule" in rules_of(pos2)


def test_callable_decliner_does_not_shadow():
    # An earlier callable that declines every leaf leaves later rules
    # reachable — the decline-chain idiom must not read as shadowing.
    fs = sl.lint_plan([(".*", lambda n, l: None), ("attn/wq$", P())],
                      _tree(), name="t")
    assert "shadowed-rule" not in rules_of(fs)
    # ... but a callable that CLAIMS everything does shadow.
    fs = sl.lint_plan([(".*", lambda n, l: P()), ("attn/wq$", P())],
                      _tree(), name="t")
    assert "shadowed-rule" in rules_of(fs)


def test_duplicate_pattern_positive_and_negative():
    # lint_plan analyzes raw (uncompiled) lists, so the duplicate that
    # compile_rules would reject at build time is reported statically.
    pos = sl.lint_plan([("attn/wq$", P()), ("attn/wq$", P("data"))],
                       _tree(), name="t")
    assert "duplicate-pattern" in rules_of(pos, only_gating=True)
    # Repeat after a CALLABLE occurrence is the legal decline chain.
    neg = sl.lint_plan([(".*", lambda n, l: None), (".*", P())],
                       _tree(), name="t")
    assert "duplicate-pattern" not in rules_of(neg)


def test_duplicate_not_double_reported_as_shadowed_or_dead():
    # One authoring bug -> ONE finding: the duplicate error, not an
    # extra shadowed-rule warn (which would inflate ratchet counts).
    fs = sl.lint_plan([("attn/wq$", P()), ("attn/wq$", P("data"))],
                      _tree(), name="t")
    by_rule = [f.rule for f in fs]
    assert by_rule.count("duplicate-pattern") == 1
    assert "shadowed-rule" not in by_rule and "dead-rule" not in by_rule


def test_verbatim_extra_rule_overrides_stock_pattern():
    """The documented `serving_plan(extra_rules=...)` override idiom,
    spelled with the stock pattern VERBATIM: the stock copy is dropped
    (not left as a rejected duplicate), the override wins, and the
    composed plan lints clean."""
    from distkeras_tpu.parallel.sharding import serving_plan, tp_plan

    plan = serving_plan(extra_rules=[(r"attn/w[qkv]$", P())])
    assert plan.spec_for("layers/attn/wq") == P()
    assert sum(1 for p, _ in plan.rules
               if p.pattern == r"attn/w[qkv]$") == 1
    assert "duplicate-pattern" not in rules_of(
        sl.lint_plan(plan, _tree(), name="t"))
    tp_plan(extra_rules=[(r"(dense|mlp|fc)[^/]*/kernel$", P())])


def test_invalid_regex():
    fs = sl.lint_plan([("([unclosed", P())], _tree(), name="t")
    assert "invalid-regex" in rules_of(fs, only_gating=True)
    # The broken rule is skipped, not fatal: later rules still lint.
    assert "dead-rule" not in rules_of(
        sl.lint_plan([("([unclosed", P()), ("attn/wq$", P())],
                     _tree(), name="t"))


def test_axis_divisibility_positive_and_negative():
    rules = [("attn/wq$", P(None, None, "model", None))]
    # heads dim = 2: divisible by 2, not by 3.
    neg = sl.lint_plan(rules, _tree(), name="t",
                       axis_sizes={"model": 2})
    assert "axis-divisibility" not in rules_of(neg)
    pos = sl.lint_plan(rules, _tree(), name="t",
                       axis_sizes={"model": 3})
    assert "axis-divisibility" in rules_of(pos, only_gating=True)
    f = next(f for f in pos if f.rule == "axis-divisibility")
    assert "attn/wq$" in f.message and "'model'" in f.message
    # Tuple entries multiply the axis sizes.
    pos = sl.lint_plan([("tok_emb$", P(("data", "model"), None))],
                       _tree(), name="t",
                       axis_sizes={"data": 3, "model": 2})
    assert "axis-divisibility" in rules_of(pos)      # 64 % 6 != 0
    # Undeclared axes — and axis_sizes=None entirely — skip the check.
    assert "axis-divisibility" not in rules_of(sl.lint_plan(
        rules, _tree(), name="t", axis_sizes={"data": 3}))
    assert "axis-divisibility" not in rules_of(sl.lint_plan(
        rules, _tree(), name="t"))


def test_axis_divisibility_rank_overflow():
    fs = sl.lint_plan([("tok_emb$", P(None, None, "model"))], _tree(),
                      name="t", axis_sizes={"model": 2})
    f = next(f for f in fs if f.rule == "axis-divisibility")
    assert "rank" in f.message


def test_replicated_giant_threshold():
    tree = {"big": jax.ShapeDtypeStruct((1024, 1024), jnp.float32),
            "small": jax.ShapeDtypeStruct((4,), jnp.float32)}
    fs = sl.lint_plan([("nothing", P())], tree, name="t",
                      giant_bytes=1 << 20)
    giants = [f for f in fs if f.rule == "replicated-giant"]
    assert len(giants) == 1 and "big" in giants[0].message
    # A rule claiming the leaf silences it; so does a catch-all.
    assert not [f for f in sl.lint_plan([("big", P("data", None)),
                                         ("nothing2", P())],
                                        tree, name="t")
                if f.rule == "replicated-giant"]


def test_replicated_giant_respects_fsdp_axis():
    """A plan with fsdp_axis scatters unmatched leaves too
    (ShardingPlan.spec_for augments the P() fallback), so a big
    unmatched-but-divisible leaf must NOT warn; one FSDP declines
    (no divisible dim) still does."""
    from distkeras_tpu.parallel.sharding import ShardingPlan

    plan = ShardingPlan(rules=[("nothing", P())], fsdp_axis="data")
    sharded = {"big": jax.ShapeDtypeStruct((1024, 1024), jnp.float32)}
    fs = sl.lint_plan(plan, sharded, name="t", axis_sizes={"data": 8})
    assert "replicated-giant" not in rules_of(fs)
    # Undeclared axis size: replication is unprovable — no warn either.
    fs = sl.lint_plan(plan, sharded, name="t")
    assert "replicated-giant" not in rules_of(fs)
    # Indivisible everywhere: FSDP declines, the leaf really replicates.
    odd = {"big": jax.ShapeDtypeStruct((1023, 1023), jnp.float32)}
    fs = sl.lint_plan(plan, odd, name="t", axis_sizes={"data": 8})
    assert "replicated-giant" in rules_of(fs)
    # Same tree without fsdp_axis warns as before.
    fs = sl.lint_plan([("nothing", P())], sharded, name="t",
                      axis_sizes={"data": 8})
    assert "replicated-giant" in rules_of(fs)


def test_callable_rules_evaluated_and_namedsharding_specs():
    """The real ZeRO rule list shape: a shape-keyed callable ahead of a
    concrete catch-all, NamedSharding values — the lint evaluates the
    callable and reads the spec out of the sharding for divisibility."""
    mesh = make_mesh(MeshSpec())
    tree = {"view": jax.ShapeDtypeStruct((8, 6), jnp.float32),
            "count": jax.ShapeDtypeStruct((), jnp.int32)}
    sh = NamedSharding(mesh, P("data", None))

    def view_rule(name, leaf):
        return sh if getattr(leaf, "shape", ()) == (8, 6) else None

    rules = [(".*", view_rule), (".*", NamedSharding(mesh, P()))]
    assert not [f for f in sl.lint_plan(rules, tree, name="t",
                                        axis_sizes={"data": 8})
                if f.gating]
    # A view shape the axis cannot split is caught through the
    # callable's returned sharding.
    bad = {"view": jax.ShapeDtypeStruct((6, 6), jnp.float32)}

    def bad_rule(name, leaf):
        return sh if getattr(leaf, "shape", ()) == (6, 6) else None

    fs = sl.lint_plan([(".*", bad_rule), (".*", P())], bad, name="t",
                      axis_sizes={"data": 8})
    assert "axis-divisibility" in rules_of(fs)


# ------------------------------------- compile_rules / UnmatchedLeaf


def test_compile_rules_rejects_concrete_duplicate():
    with pytest.raises(ValueError, match="duplicate pattern"):
        pr.compile_rules([("a$", P()), ("a$", P("data"))])
    # The decline-chain idiom (callable first) stays legal — this is
    # exactly zero_state_rules' construction.
    pr.compile_rules([(".*", lambda n, l: None), (".*", P())])


def test_unmatched_leaf_error_lists_nearest_misses():
    tree = {"layers": {"attn": {"wq": jnp.ones((4, 4))}}}
    with pytest.raises(pr.UnmatchedLeafError) as ei:
        pr.match_partition_rules(
            [("atn/wq$", P()), ("ffn/w1$", P()), ("emb$", P())], tree)
    msg = str(ei.value)
    assert "nearest-miss" in msg
    # The typo'd pattern ranks first: its literal spine matches the
    # deepest prefix of the leaf path.
    near = msg.split("nearest-miss patterns")[1]
    assert near.index("atn/wq$") < near.index("emb$")


# ------------------------------------------- resharding attribution


def test_attribution_scopes_and_tails():
    # Declared scopes and explicit collective primitives attribute.
    assert sl.attributed("jit(f)/zero3/param_gather/concatenate")
    assert sl.attributed("jit(f)/exchange/merge/jit(shmap_body)/all_gather")
    assert sl.attributed("jit(f)/myscope/sharding_constraint")
    assert sl.attributed("jit(f)/jit(shmap_body)/psum")
    # GSPMD-inserted reshardings carry the consumer op: unattributed.
    assert not sl.attributed("jit(f)/jit(main)/dot_general")
    assert not sl.attributed("jit(f)/jit(main)/broadcast_in_dim")
    assert not sl.attributed("")


_SYNTH_HLO = """\
ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %all-gather = f32[8]{0} all-gather(f32[8]{0} %a), metadata={op_name="jit(f)/jit(main)/mul"}
  %all-gather.1 = f32[8]{0} all-gather(f32[8]{0} %a), metadata={op_name="jit(f)/zero1/all_gather/jit(shmap_body)/all_gather"}
  %cp = f32[8]{0} collective-permute(f32[8]{0} %a), metadata={op_name="jit(f)/jit(main)/pad"}
  ROOT %r = f32[8]{0} add(f32[8]{0} %all-gather, f32[8]{0} %cp)
}
"""


def test_resharding_census_parses_and_attributes():
    census = sl.resharding_census(_SYNTH_HLO)
    assert [(r["op"], r["attributed"]) for r in census] == [
        ("all-gather", False), ("all-gather", True),
        ("collective-permute", False)]
    spec = TraceSpec(name="t", fn=None, args=())
    fs = sl.reshard_findings(spec, _SYNTH_HLO)
    assert len(fs) == 2 and all(
        f.rule == "resharding-collective" and f.severity == "warn"
        and f.gating for f in fs)


def test_dropped_sharding_constraint_detected():
    """The injected regression leg: the SAME program with and without
    its with_sharding_constraint.  Constrained, the resulting
    all-gather's name stack carries `sharding_constraint` (attributed,
    no finding); dropped, GSPMD inserts the gather against the
    consumer op and the gate flags it."""
    mesh = make_mesh(MeshSpec(data=4, model=2))
    w_sh = NamedSharding(mesh, P(None, "model"))
    rep = NamedSharding(mesh, P())

    def constrained(w, x):
        w = jax.lax.with_sharding_constraint(w, rep)
        return x @ w

    def dropped(w, x):
        return x @ w

    args = (jax.ShapeDtypeStruct((16, 32), jnp.float32),
            jax.ShapeDtypeStruct((8, 16), jnp.float32))
    for fn, expect in ((constrained, 0), (dropped, 1)):
        jitted = jax.jit(fn, in_shardings=(w_sh, rep),
                         out_shardings=rep)
        spec = TraceSpec(name="synthetic/drop_wsc", fn=jitted,
                         args=args)
        art = trace_target(spec)
        fs = sl.reshard_findings(spec, art.hlo)
        gating = [f for f in fs if f.gating]
        assert len(gating) == (0 if expect == 0 else len(gating))
        if expect:
            assert gating and any("all-gather" in f.message
                                  for f in gating), [f.format()
                                                     for f in fs]
        else:
            assert not gating, [f.format() for f in fs]


# --------------------------------------------------- placement census


def test_placement_census_args_consts_and_bytes():
    mesh = make_mesh(MeshSpec(data=4, model=2))
    w = jax.device_put(jnp.ones((16, 32)),
                       NamedSharding(mesh, P(None, "model")))

    def fn(batch):
        return {"out": batch["x"] @ w}

    jitted = jax.jit(
        fn, in_shardings=({"x": NamedSharding(mesh, P("data", None))},),
        out_shardings={"out": NamedSharding(mesh, P())})
    spec = TraceSpec(
        name="t", fn=jitted,
        args=({"x": jax.ShapeDtypeStruct((8, 16), jnp.float32)},))
    art = trace_target(spec)
    census = sl.placement_census(spec, art)
    t = census["tensors"]
    assert t["args/0/x"] == ["f32[8,16]", "P('data', None)",
                             8 * 16 * 4 // 4]
    # The closed-over weight: named const/<i>, sharded bytes 1/2.
    consts = {k: v for k, v in t.items() if k.startswith("const/")}
    assert list(consts.values()) == [
        ["f32[16,32]", "P(None, 'model')", 16 * 32 * 4 // 2]]
    assert census["bytes_per_device"] == 8 * 16 * 4 // 4 + 16 * 32 * 2
    assert census["bytes_global"] == 8 * 16 * 4 + 16 * 32 * 4
    # The census also pins the attribution counts: this toy program's
    # sharded operands gather for the replicated output with no
    # declared scope, and the ledger records that.
    assert census["resharding"]["unattributed"] >= 1


def test_check_shard_budget_positive_and_negative():
    entry = {"tensors": {"args/x": ["f32[4]", "P()", 16]},
             "bytes_global": 16, "bytes_per_device": 16,
             "resharding": {"attributed": 0, "unattributed": 0}}
    assert sl.check_shard_budget("t", entry, {"t": entry}) == []
    missing = sl.check_shard_budget("other", entry, {"t": entry})
    assert [f for f in missing if f.rule == "shard-budget" and f.gating]
    import copy

    drifted = copy.deepcopy(entry)
    drifted["tensors"]["args/x"][1] = "P('data')"
    drifted["tensors"]["args/x"][2] = 2
    bad = sl.check_shard_budget("t", drifted, {"t": entry})
    assert [f for f in bad if f.rule == "shard-budget" and f.gating]
    assert "args/x" in bad[0].message


# ------------------------------------------- the shipped-plan matrix


def test_repo_plan_matrix_names_every_shipped_constructor():
    names = {name for name, *_ in sl.plan_suite()}
    assert names >= {"serving_plan", "tp_rules", "fsdp_plan+tp_rules",
                     "zero1_plan/state_rules", "zero3_plan/state_rules",
                     "exchange_codec_rules"}


def test_repo_plans_run_clean():
    """The dry-run matrix: no shipped plan constructor carries a dead,
    shadowed, duplicate, or indivisible rule against the real
    ADAG/LM/serving trees — and a future model change that strands a
    rule fails here."""
    findings = sl.lint_repo_plans()
    gating = [f.format() for f in findings if f.gating]
    assert not gating, gating


def test_repo_plan_matrix_catches_injected_regressions():
    """A stranded (dead) rule and a newly-shadowing rule in the
    serving plan are both caught by the same lint the matrix runs."""
    from distkeras_tpu.analysis.targets import _lm_cfg
    from distkeras_tpu.parallel.sharding import serving_plan

    cfg = _lm_cfg()
    tree = jax.eval_shape(
        lambda: toy_params(cfg))
    axes = {"data": 4, "model": 2}
    # Injected typo: the extra rule places nothing.
    fs = sl.lint_plan(serving_plan(extra_rules=[("atn/wq$", P())]),
                      tree, name="t", axis_sizes=axes)
    assert "dead-rule" in rules_of(fs, only_gating=True)
    # Injected shadow: a broad extra rule starves the shipped ones.
    fs = sl.lint_plan(serving_plan(
        extra_rules=[("attn/.*", P(None, None, "model", None))]),
        tree, name="t", axis_sizes=axes)
    assert "shadowed-rule" in rules_of(fs, only_gating=True)
    # Injected indivisibility: n_heads=2 cannot split 4 ways.
    fs = sl.lint_plan(serving_plan(), tree, name="t",
                      axis_sizes={"data": 1, "model": 4})
    assert "axis-divisibility" in rules_of(fs, only_gating=True)


# --------------------------------------------------- CLI mode flags


@pytest.mark.parametrize("argv,needle", [
    (["--shardings", "--source-only"], "cannot combine"),
    (["--shardings", "--ir-only"], "cannot combine"),
    (["--shardings", "--threads"], "cannot combine"),
    (["--shardings", "--update-budgets"], "both census files"),
    (["--shardings", "--update-baseline"], "full run"),
    # The symmetric pre-existing gap, closed alongside: a source-only
    # run never reaches run_ir, so a budget re-record would exit 0
    # having written nothing.
    (["--source-only", "--update-budgets"], "needs the IR pass"),
])
def test_graph_lint_cli_rejects_shardings_combos(argv, needle):
    """PR-9 gave --threads conflicting-combo rejection before the
    heavy import; --shardings gets the same parity (these subprocesses
    exit at argparse, in well under a second of work)."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "graph_lint.py")]
        + argv, capture_output=True, text=True, timeout=120, cwd=ROOT)
    assert r.returncode != 0 and needle in r.stderr, r.stderr
