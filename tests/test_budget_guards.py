"""Budget guards in tier-1: the IR lint over the REAL trainer/serving
step programs, the collective census vs scripts/comm_budget.json, the
ZeRO-1 parity proof, the shard lint's compiled-placement census vs
scripts/shard_budget.json (+ the no-unattributed-resharding
invariant), the contract census vs scripts/obs_schema.json, and the
compile-count guard — so a budget regression fails the fast gate, not
a reviewer's eyeball.
"""

import os
import subprocess
import sys

import pytest

from distkeras_tpu.analysis import ir_lint, shard_lint
from distkeras_tpu.analysis.targets import (ZERO1_PARITY_PAIRS,
                                             ZERO_PARITY_TARGETS,
                                             default_targets)
from helpers import toy_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def linted():
    """(spec, findings, census, placements) per standard target —
    traced, lowered and compiled ONCE for the whole module; the IR
    findings, the collective census, the shard lint's placement census
    and the resharding findings all read the same artifacts."""
    from distkeras_tpu.analysis.findings import (apply_baseline,
                                                 load_baseline)

    ledger = load_baseline(
        os.path.join(ROOT, "scripts", "lint_baseline.json"))
    out = {}
    for spec in default_targets():
        art = ir_lint.trace_target(spec)
        findings, census = ir_lint.lint_trace(spec, artifacts=art)
        findings += shard_lint.reshard_findings(spec, art.hlo)
        # The checked-in warn ledger applies exactly as CI applies it
        # (keys are rule:path, so per-target application is exact).
        findings = apply_baseline(findings, ledger)
        placements = shard_lint.placement_census(spec, art)
        out[spec.name] = (spec, findings, census, placements)
    return out


def test_standard_targets_cover_every_family(linted):
    names = set(linted)
    for required in ("adag_dp/accum_step", "adag_zero1/accum_step",
                     "adag_zero2/accum_step", "adag_zero3/accum_step",
                     "adag_adasum/accum_step",
                     "adag_localsgd4/accum_step",
                     "lmtrainer_dp/train_step",
                     "lmtrainer_zero1/train_step",
                     "lmtrainer_zero2/train_step",
                     "lmtrainer_zero3/train_step",
                     "lmtrainer_fsdp/train_step",
                     "lmtrainer_int8ef/train_step",
                     "lmtrainer_rulesef/train_step",
                     "lmtrainer_zero1_int8ef/train_step",
                     "continuousbatcher_per_request/decode_step",
                     "speculativebatcher_sampled/step"):
        assert required in names, names


def test_ir_lint_clean_on_real_programs(linted):
    gating = [f.format() for (_, fs, _, _) in linted.values()
              for f in fs if f.gating]
    assert not gating, gating


def test_comm_budget_matches_recorded(linted):
    budgets = ir_lint.load_budgets(
        os.path.join(ROOT, "scripts", "comm_budget.json"))
    drift = []
    for name, (_, _, census, _) in linted.items():
        drift += [f.format()
                  for f in ir_lint.check_budget(name, census, budgets)]
    assert not drift, drift


def test_shard_budget_matches_recorded(linted):
    """The placement census — every tensor's compiled sharding and the
    per-device byte ledger — matches scripts/shard_budget.json exactly
    for every standard target (re-record intentional changes with
    graph_lint.py --update-budgets; the JSON diff IS the placement
    review)."""
    budgets = shard_lint.load_shard_budgets(
        os.path.join(ROOT, "scripts", "shard_budget.json"))
    drift = []
    for name, (_, _, _, placements) in linted.items():
        drift += [f.format() for f in shard_lint.check_shard_budget(
            name, placements, budgets)]
    assert not drift, drift
    # ... and the budget has no stale targets the suite stopped tracing.
    assert set(budgets) == set(linted)


def test_no_unattributed_resharding_beyond_ledger(linted):
    """The resharding invariant: every compiled all-gather /
    collective-permute / all-to-all is either attributable to a
    declared scope or covered by the explicitly-justified
    lint_baseline.json ledger (the CPU partitioner's hierarchical
    AR+permute spelling and the fsdp/zero3 gather-on-use
    materializations — docs/graph_lint.md); anything NEW gates."""
    for name, (_, fs, _, placements) in linted.items():
        reshard = [f for f in fs if f.rule == "resharding-collective"]
        gating = [f.format() for f in reshard if f.gating]
        assert not gating, (name, gating)
        # The census pins the attribution counts too: baselined debt
        # and census must agree.
        assert placements["resharding"]["unattributed"] == len(reshard)
    # The pod-sharded serve path is fully attributed: its per-token
    # collectives are the declared psums, nothing GSPMD snuck in.
    tp2 = linted["continuousbatcher_greedy_tp2/decode_step"][3]
    assert tp2["resharding"]["unattributed"] == 0


def test_placement_census_cross_checks_live_memory_footprint(linted):
    """The per-device byte ledger is not self-referential: for the
    pod-sharded serving engine the census's per-device bytes for the
    closed-over parameters and the KV cache equal what
    engine.memory_footprint() reads off LIVE addressable shards — the
    same accounting the ~n×-per-device-bytes serving claim is asserted
    from (tests/test_serving_sharded.py), now with a static witness."""

    import distkeras_tpu as dk
    from distkeras_tpu.analysis.targets import _lm_cfg
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
    from distkeras_tpu.parallel.sharding import serving_plan

    cfg = _lm_cfg()
    params = toy_params(cfg)
    mesh = make_mesh(MeshSpec(data=4, model=2))
    eng = dk.ContinuousBatcher(params, cfg, lanes=2, prompt_buckets=(8,),
                               plan=serving_plan(), mesh=mesh)
    fp = eng.memory_footprint()
    census = linted["continuousbatcher_greedy_tp2/decode_step"][3]
    t = census["tensors"]
    const_dev = sum(v[2] for k, v in t.items() if k.startswith("const/"))
    cache_dev = sum(v[2] for k, v in t.items() if k.startswith("args/0/"))
    assert const_dev == fp["param_bytes_per_device"]
    assert cache_dev == fp["kv_bytes_per_device"]
    # The n× claim's static spelling: sharded per-device bytes strictly
    # below the replicated total.
    assert census["bytes_per_device"] < census["bytes_global"]


def test_zero_placement_ledger_static_witness(linted):
    """The ZeRO per-device-state claims, witnessed statically from the
    placement census: the zero3 step's persistent state (args) holds
    ~1/8 of the dp step's bytes per device (params + moments all
    scattered P('data', None)), zero1 sits between (moments only),
    and the batch args are identical — so the ledger, not a live-run
    measurement, pins the 8× direction."""
    def state_dev(name):
        t = linted[name][3]["tensors"]
        return sum(v[2] for k, v in t.items()
                   if k.startswith("args/0/"))

    dp = state_dev("adag_dp/accum_step")
    z1 = state_dev("adag_zero1/accum_step")
    z3 = state_dev("adag_zero3/accum_step")
    assert z3 < z1 < dp
    # All three hold the same global bytes; only placement differs.
    assert (linted["adag_zero3/accum_step"][3]["bytes_global"]
            == linted["adag_dp/accum_step"][3]["bytes_global"])
    # zero3 scatters params AND moments: > 2/3 of dp's per-device
    # state is gone (the exact figure is pinned byte-for-byte in
    # shard_budget.json; this is the direction-proof).
    assert z3 < dp / 3
    # Placement spelling: every zero3 tv leaf is P('data', None).
    t3 = linted["adag_zero3/accum_step"][3]["tensors"]
    tvs = [v for k, v in t3.items() if k.startswith("args/0/tv/")]
    assert tvs and all(v[1] == "P('data', None)" for v in tvs)


def test_adag_zero1_compiled_wire_equals_dp(linted):
    """On the MLP flagship the parity holds at the COMPILED level
    outright: total per-device wire bytes of the zero1 step (RS-
    canonicalized AR + explicit AG) == the replicated-DP step's
    all-reduces, to the byte."""
    dp = ir_lint.census_wire_total(linted["adag_dp/accum_step"][2])
    z1 = ir_lint.census_wire_total(linted["adag_zero1/accum_step"][2])
    assert dp == z1 > 0


def test_zero_parity_proof_every_stage_both_families(linted):
    """The acceptance check, extended to stages 2/3: for ADAG and
    LMTrainer at every ZeRO stage, the step's DECLARED exchange is
    pad-free (scatter == gather == parameter bytes per program
    occurrence: stage 1's RS+AG, stage 2's in-scan accumulator RS +
    update AG, stage 3's gather-on-use AG + backward grad RS), hence
    by the ring identity the per-round wire never exceeds the gradient
    all-reduce it replaces — asserted against each DP partner's
    compiled census."""
    for z_name, dp_name, _stage in ZERO_PARITY_TARGETS:
        spec = linted[z_name][0]
        findings = ir_lint.check_zero1_parity(spec, linted[dp_name][2])
        gating = [f.format() for f in findings if f.gating]
        assert not gating, (z_name, gating)


def test_declared_exchange_measures_param_bytes(linted):
    for z_name, _dp, stage in ZERO_PARITY_TARGETS:
        spec = linted[z_name][0]
        assert spec.zero_stage == stage
        decl = ir_lint.declared_zero_exchange(spec)
        assert decl["rs_bytes"] == decl["ag_bytes"] == spec.params_bytes
    # Stage-1 pairs keep their historical spelling too.
    assert ZERO1_PARITY_PAIRS == (
        ("adag_zero1/accum_step", "adag_dp/accum_step"),
        ("lmtrainer_zero1/train_step", "lmtrainer_dp/train_step"))


def test_lm_dp_tied_embedding_grads_summed_before_exchange(linted):
    """PR 3's parity machinery discovered replicated-DP LM all-reduced
    the tied embedding's two gradient contributions separately (8 KiB
    per step redundant); PR 4 sums them locally before ONE pmean per
    leaf (LMTrainer._dp_local_value_and_grad).  Pinned: the DP census
    carries exactly parameter-bytes of gradient all-reduce and the
    `comm-redundant-ar` rule — now promoted to warn, so a regression
    gates — stays silent."""
    spec = linted["lmtrainer_zero1/train_step"][0]
    findings = ir_lint.check_zero1_parity(
        spec, linted["lmtrainer_dp/train_step"][2])
    assert not any(f.rule == "comm-redundant-ar" for f in findings)
    assert not [f.format() for f in findings if f.gating]


def test_int8ef_cuts_gradient_wire_to_quarter(linted):
    """The lowcomm acceptance claim, from the COMPILED census: the
    int8-EF step's GRADIENT payload crosses the wire as s8 at exactly
    <= 1/4 the f32 baseline's gradient wire bytes (a codec that
    decompressed before the collective would show f32 payloads at full
    size — the per-dtype census field exists to catch that), and the
    f32 remnant — the per-bucket quantization scales — is declared and
    o(1): under 1% of the compressed payload, leaving the whole step
    within 1% of the quarter."""
    ef_census = linted["lmtrainer_int8ef/train_step"][2]
    dp_census = linted["lmtrainer_dp/train_step"][2]

    def grad_wire(census):  # everything but the scalar loss pmean
        return sum(c.wire_bytes for c in census if c.payload_bytes > 4)

    dp_grad = grad_wire(dp_census)
    s8 = sum(c.wire_bytes for c in ef_census if "s8" in c.dtype)
    assert 0 < s8 <= dp_grad / 4, (s8, dp_grad)
    f32_scales = grad_wire(ef_census) - s8
    assert 0 <= f32_scales <= 0.01 * s8, (f32_scales, s8)
    ef = ir_lint.census_wire_total(ef_census)
    dp = ir_lint.census_wire_total(dp_census)
    assert ef <= 1.01 * dp / 4, (ef, dp)
    # zero1 x int8 compresses the reduce-scatter leg: its s8 payload
    # must appear in the compiled program too.
    z1ef = linted["lmtrainer_zero1_int8ef/train_step"][2]
    assert any("s8" in c.dtype for c in z1ef)


def test_codec_rules_census_pins_per_bucket_wire_dtypes(linted):
    """The per-bucket codec rules claim, from the COMPILED census: the
    (emb -> topk, .* -> int8) LM exchange moves an s8 payload for the
    int8 buckets AND the top-k (values, indices) legs for the
    embedding bucket — both wire dtypes visible in one program, which
    a uniform codec can never produce."""
    census = linted["lmtrainer_rulesef/train_step"][2]
    dtypes = {c.dtype for c in census}
    assert any("s8" in d for d in dtypes), dtypes      # int8 buckets
    assert any("s32" in d for d in dtypes), dtypes     # top-k indices
    # The s8 payload must be the dominant gradient wire (dense
    # leaves), the top-k legs the small remainder.
    s8 = sum(c.wire_bytes for c in census if "s8" in c.dtype)
    assert s8 > 0


def test_zero3_census_has_no_update_gather(linted):
    """Stage 3's structural claim from the compiled census: the
    gather-on-use program all-gathers the PARAMETERS (per fusion
    bucket, gradient-sized payloads) but has no update all-gather leg
    beyond them — params stay scattered across steps — while stage 1's
    program gathers the packed update as one fused ``[n, P/n]``
    payload.  Pinned: zero3's largest all-gather payload is a bucket,
    not the whole packed update."""
    z1 = linted["adag_zero1/accum_step"][2]
    z3 = linted["adag_zero3/accum_step"][2]
    z1_ag = max(c.payload_bytes for c in z1 if c.op == "all-gather")
    z3_ag = max(c.payload_bytes for c in z3 if c.op == "all-gather")
    P = linted["adag_zero3/accum_step"][0].params_bytes
    assert z1_ag == P          # stage 1: one packed update gather
    assert z3_ag < P, (z3_ag, P)  # stage 3: bucket-granular param AGs
    ag_total = sum(c.payload_bytes * c.count for c in z3
                   if c.op == "all-gather")
    assert ag_total == P       # ...that together cover the params once


def test_localsgd_quarters_per_step_collective_count(linted):
    """The other lowcomm acceptance claim: the sync_every=4 ADAG round
    program covers FOUR optimizer steps with ONE merge's collectives,
    so the per-optimizer-step collective count is exactly its census
    count / 4 — pinned at <= 1/4 of the synchronous step's count (the
    merge itself is bucket-fused, so it is no chattier than one
    synchronous exchange)."""
    dp_count = sum(c.count
                   for c in linted["adag_dp/accum_step"][2])
    ls_count = sum(c.count
                   for c in linted["adag_localsgd4/accum_step"][2])
    # For H=4 this IS the acceptance bound (ls_count/H <= dp_count/4
    # rearranges to ls_count <= dp_count), asserted strictly: the
    # whole 4-optimizer-step round must run FEWER collectives than one
    # synchronous step (recorded: 3 vs 5).
    assert ls_count < dp_count, (ls_count, dp_count)


def test_serving_steps_have_no_collectives(linted):
    """The unsharded decode steps must stay collective-free — a
    collective appearing here means the engine started resharding
    per token."""
    for name in ("continuousbatcher_per_request/decode_step",
                 "speculativebatcher_sampled/step"):
        assert linted[name][2] == []


def test_compile_count_guard_passes():
    """The recompile guard (scripts/check_compile_counts.py) over
    every recorded session — zero1/device_data/exchange-variant
    trainers and the serving engines included — as a subprocess with
    its own deterministic mesh."""
    r = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "scripts", "check_compile_counts.py")],
        capture_output=True, text=True, timeout=540,
        cwd=ROOT)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr


def test_obs_schema_matches_recorded():
    """The contract census — every emission site's name/kind/labels,
    the dynamic-name allowlist, the scenario-event sweep, and the wire
    route census — matches scripts/obs_schema.json exactly (re-record
    intentional changes with graph_lint.py --update-budgets; the JSON
    diff IS the contract review)."""
    from distkeras_tpu.analysis import contract_lint

    built = contract_lint.build_obs_schema(ROOT)
    pinned = contract_lint.load_obs_schema(
        os.path.join(ROOT, "scripts", "obs_schema.json"))
    assert pinned is not None, (
        "scripts/obs_schema.json missing — run graph_lint.py "
        "--contracts --update-budgets")
    assert built == pinned


def test_graph_lint_cli_source_only_runs_clean():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "graph_lint.py"),
         "--source-only"],
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr


def test_adag_device_data_hook_covers_indexed_step():
    """device_data trainers hand the lint their REAL indexed-step
    program (single-process form), not the streaming one."""
    from distkeras_tpu.analysis.targets import (_mlp_dataset,
                                                 _mlp_trainer)

    t = _mlp_trainer(zero1=False)
    t.device_data = True  # _supports_device_data on ADAG
    spec = t.traced_for_analysis(_mlp_dataset())[0]
    assert spec.name == "adag_dp_device_data/accum_step"
    # Four args: state, staged X, staged Y, index block.
    assert len(spec.args) == 4
    findings, _ = ir_lint.lint_trace(spec, compile_census=False)
    assert not [f.format() for f in findings if f.gating]
