"""The ssm cell's own files — ``drivers/serve_ssm.py``,
``reference_jamba.py``, ``flops_ssm.py``, the four readers — end to end
on the CPU at a toy size: a copy of the benchmark with the rehearsal
cell of ``data/tiny_ssm`` added as new files (a tree of its own, as
``test_latent_cell.py``)."""

import importlib.util
import json
import os
import shutil
import sys
import types

import pytest

import benchmark_json
from conftest import BENCH, HERE, REPO, run_cell
from test_moe_cell import contract_order

FAULTS = ["matmul_float8", "state_bf16", "stale_state", "no_dt_norm",
          "no_b_norm", "no_c_norm", "no_d_term", "no_conv_bias",
          "conv_shift", "no_gate", "rope"]
MINE = ("ai21-jamba2-3b", "jamba2-3b.serve.chat")
BEFORE = (("brumby-14b-base_l8", "brumby.serve.longdoc"),
          ("joyai-llm-flash_l10-ep8", "joyai.serve.longctx"))
MLA_METRICS = ("step_mla_decode_roofline", "step_mla_hbm_roofline",
               "step_mla_live_positions", "step_mla_mfu",
               "step_mla_prefix_roofline")
NEW_METRICS = ("step_ssm_chunk_roofline", "step_ssm_hbm_roofline",
               "step_ssm_mfu", "step_ssm_state_roofline")
ACCEPTED = ("lanes_busy_share", "kv_used_share", "decode_step_ms",
            "prefill_ms_per_ktok")


@pytest.fixture(scope="module")
def ssm_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("ssm_tree") / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".pytest_cache"))
    shutil.copytree(os.path.join(HERE, "data", "tiny_ssm"), root,
                    dirs_exist_ok=True)
    return root


def _notes(p):
    (line,) = [json.loads(ln) for ln in p.stdout.splitlines()
               if ln.startswith('{"note": "run"')]
    return line["notes"]


def test_end_to_end_line(ssm_tree):
    p, out = run_cell(ssm_tree, "tiny.serve.ssm", trace=0, seconds=3)
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
    ref = _notes(p)["reference"]
    assert ref["longest_prompt"] > 32     # a state through four chunks
    assert ref["reused_lanes_checked"] >= 1
    assert ref["mean_gap_to_best_logit"] < 1e-4


@pytest.mark.parametrize("fault", ["stale_state", "no_c_norm"])
def test_a_planted_fault_is_not_correct(ssm_tree, fault):
    """The harness's own comparison, on the requests the window
    finished, against the reference computed WRONG: the run comes out
    not ``correct``, by the reference check and by nothing else."""
    p, out = run_cell(ssm_tree, "tiny.serve.ssm", trace=0, seconds=2,
                      env={"REFERENCE_FAULT": fault})
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is False and out["failed"] == 0 < out["attempted"]
    notes = _notes(p)
    assert notes["reference"]["ok"] is False
    assert notes["transcript_mismatches"] == 0 == notes["programs_in_window"]


def test_controls_beside_a_correct_run(ssm_tree):
    """``REFERENCE_CONTROLS=1``: the run itself is ``correct``, and the
    same sample fails every faulty reference by far."""
    p, out = run_cell(ssm_tree, "tiny.serve.ssm", trace=0, seconds=2,
                      env={"REFERENCE_CONTROLS": "1"})
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True
    ref = _notes(p)["reference"]
    assert ref["ok"] is True and sorted(ref["controls"]) == sorted(FAULTS)
    for name, verdict in ref["controls"].items():
        assert verdict["ok"] is False, name
    # the states the engine held at the end, against the reference's:
    # the first two ssm layers of the decoding lanes, float32 on both
    # sides; a state rounded to bfloat16 over a toy's few dozen
    # positions moves no logit far enough to flip a choice, and is told
    # by the states alone
    states = ref["states"]
    tol = states["ssm_state_tol"]
    assert states["ok"] is True and states["ssm_state_err"] < tol / 10
    assert {r["layer"] for r in states["by_lane"]} == {0, 1}
    errs = {name: v["ssm_state_err"] for name, v in ref["controls"].items()}
    assert errs["state_bf16"] > 10 * tol
    for name, verdict in ref["controls"].items():
        if name != "state_bf16":
            assert verdict["mean_gap_to_best_logit"] > 100 * max(
                ref["mean_gap_to_best_logit"], 1e-5), name
    # what lies after the first two ssm layers leaves their states alone
    # (a stale state is the logits' to tell: it is another request's)
    for name in ("rope", "stale_state"):
        assert errs[name] < tol, name


def test_per_layer_line_off_the_chip(ssm_tree):
    """No table of peaks and no device trace on the CPU: the readers of
    the device find nothing to read, return nothing and do not raise;
    the sampled metrics report."""
    p, out = run_cell(ssm_tree, "tiny.serve.ssm", trace=1, seconds=3)
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True
    assert set(out["metrics"]) == {"lanes_busy_share", "kv_used_share"}


def test_a_program_without_the_kind_stops_at_once(monkeypatch):
    """An older program has no ``ssm`` keys: the driver says
    so with a ``ValueError`` naming the layer kind before any weight is
    made."""
    from distkeras_tpu.models import transformer as tfm

    spec = importlib.util.spec_from_file_location(
        "serve_ssm", os.path.join(BENCH, "drivers", "serve_ssm.py"))
    drv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(drv)
    with open(os.path.join(BENCH, "configs", MINE[0] + ".json")) as f:
        conf = json.load(f)
    assert drv._program_has_ssm(conf).ssm_planes == 26

    def older(**kw):
        if "ssm_state" in kw:
            raise TypeError("unexpected keyword argument 'ssm_state'")
    monkeypatch.setattr(tfm, "TransformerConfig", older)
    with pytest.raises(ValueError, match="'ssm'"):
        drv.run(types.SimpleNamespace(conf=conf))


def test_benchmark_json_is_what_the_files_say_with_new_entries_last():
    """``build()`` sorts the cells ``end_to_end.json`` does not order
    and every metric by name; the contract wants new entries last: the
    cells added before this one in their order, then this one, and the
    four new metrics after every accepted one."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        got = json.load(f)
    want = contract_order(benchmark_json.build())
    for last in BEFORE + (MINE,):
        want = contract_order(want, last=last)
    want["per_layer"] = sorted(
        want["per_layer"], key=lambda m: (m["name"] in NEW_METRICS,
                                          m["name"] in MLA_METRICS))
    want["per_layer"] = sorted(
        want["per_layer"], key=lambda m: m["name"] in NEW_METRICS)
    assert got == want
    assert got["configs"][-1]["name"] == MINE[0]
    assert got["workloads"][-1]["name"] == MINE[1]
    assert tuple(m["name"] for m in got["per_layer"][-4:]) == NEW_METRICS
    listed = {m["name"] for m in got["per_layer"]
              if MINE[1] in m.get("workloads", ())}
    assert listed == set(NEW_METRICS) | set(ACCEPTED)
    for m in got["per_layer"] + got["end_to_end"]:
        if MINE[1] in m.get("workloads", ()):
            assert m["workloads"][-1] == MINE[1]
    for m in got["per_layer"][-4:]:
        assert m["workloads"] == [MINE[1]] and m["moves"] == "serve_tok_s"


def _module(*parts):
    name = parts[-1]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *parts) + ".py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _span(name, t0, dur, **fields):
    return {"kind": "span", "name": name, "t0": t0, "dur": dur,
            "fields": fields}


@pytest.fixture()
def record():
    """Two decode steps traced (500 lanes decode: 26 kernel calls each),
    one admission of 512 rows (26 chunk-kernel calls), then two rounds
    of 500 tokens and one admission of 512 positions after 1,024 in
    0.06 s."""
    with open(os.path.join(BENCH, "configs", MINE[0] + ".json")) as f:
        conf = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    step_call = ["%ssm_state_step.3 = (f32[500,1,5120]) custom-call(...), "
                 "custom_call_target=\"tpu_custom_call\"", 0, 500_000]
    chunk_call = ["%ssm_chunk_fwd.5 = (f32[512,5120]) custom-call(...), "
                  "custom_call_target=\"tpu_custom_call\"", 0, 1_000_000]
    rnd = dict(tokens=500, kv_live=400_000, lanes_busy=520,
               lanes_admitting=1, state_lanes=500)
    return {
        "conf": conf, "peaks": peaks, "window": (0.0, 20.0),
        "max_len": 8192, "profile_window": (1.0, 4.0),
        "trace": {"events": {"devices": {"/device:TPU:0": {
            "ops": [step_call] * 52 + [chunk_call] * 26
            + [["%fusion.3 = ...", 0, 5_000_000]],
            "modules": [["jit_step_n_p(1)", 0, 25_000_000],
                        ["jit__admit(2)", 0, 60_000_000],
                        ["jit_step_n_p(1)", 0, 25_000_000]]}}}},
        "obs_events": [
            {"kind": "event", "name": "serving.kv_layout",
             "fields": {"passes": 1, "layers": 28, "planes": 2,
                        "planes_ssm": 26,
                        "ssm_bytes_per_lane": 26 * (16 * 5120 * 4
                                                    + 3 * 5120 * 2)}},
            _span("serving.round", 2.0, 0.03, **rnd),
            _span("serving.admit_chunk", 2.5, 0.06, bucket=512,
                  positions=512, attended=1536),
            _span("serving.round", 3.0, 0.03, **rnd),
            _span("serving.round", 10.0, 0.03, **rnd),
            _span("serving.admit_chunk", 10.01, 0.001, bucket=512,
                  positions=512, attended=1536),
            _span("serving.round", 10.03, 0.03, **rnd),
        ]}


def test_flops_ssm_counts_the_models_parameters(record):
    flops = _module("flops_ssm")
    tc = record["conf"]["transformer_config"]
    assert flops.ssm_layers(tc) == 26 and flops.attn_layers(tc) == 2
    assert flops.mixer_params(tc) == pytest.approx(41.24e6, abs=0.01e6)
    assert flops.mixer_params(tc) + flops.ffn_params(tc) == pytest.approx(
        104.16e6, abs=0.01e6)
    assert flops.attn_params(tc) + flops.ffn_params(tc) == pytest.approx(
        76.68e6, abs=0.01e6)
    assert flops.total_params(tc) == pytest.approx(3.029e9, abs=0.001e9)
    assert flops.weight_bytes(tc) == pytest.approx(6.06e9, abs=0.01e9)
    # a lane: 8.52 MB of float32 state, 0.80 MB of conv tail; a cached
    # position of the two one-head attention layers 1,024 B
    assert flops.state_bytes(tc) == 26 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert flops.slot_bytes(tc) == 1024
    # the scan: 4 x 16 x 5120 a position an ssm layer, and the conv's
    assert flops.scan_flops(tc) == 26 * (4 * 16 * 5120 + 2 * 4 * 5120)


def test_the_four_new_metrics_from_a_hand_made_record(record):
    flops = _module("flops_ssm")
    tc = record["conf"]["transformer_config"]
    state = _module("readers", "ssm_state_roofline").read(record, {})
    assert state == pytest.approx(
        100 * 52 * 2 * 500 * 16 * 5120 * 4 / 819e9 / (52 * 500e-6))
    assert 0 < state < 100
    hbm = _module("readers", "ssm_hbm_roofline").read(record, {})
    need = flops.decode_step_bytes(tc, 500, 400_000)
    assert hbm == pytest.approx(100 * 2 * need / 819e9 / 0.050)
    assert 0 < hbm < 100
    chunk = _module("readers", "ssm_chunk_roofline").read(record, {})
    t_bytes, t_ops = flops.chunk_kernel_least(tc, 512, record["peaks"])
    assert chunk == pytest.approx(100 * max(t_bytes, t_ops) / 1e-3)
    assert 0 < chunk < 100
    mfu = _module("readers", "ssm_mfu").read(record, {})
    pairs = 2 * 400_000 + 512 * (1024 + 256)
    want = (1000 * flops.position_flops(tc, True)
            + 512 * flops.position_flops(tc, False)
            + flops.pair_flops(tc, pairs))
    # (the admission at 2.5 s lies inside the profile, outside both
    # stretches; the rounds before it are too few to make one)
    assert mfu == pytest.approx(100 * want / 0.06 / 197e12)
    assert 0 < mfu < 100
    # a program older than the ssm planes: nothing to read, no raise
    record["obs_events"] = [r for r in record["obs_events"]
                            if r["name"] != "serving.kv_layout"]
    record["trace"]["events"]["devices"]["/device:TPU:0"]["ops"] = [
        ["%fusion.3 = ...", 0, 5_000_000]]
    for name in ("ssm_hbm_roofline", "ssm_state_roofline",
                 "ssm_chunk_roofline", "ssm_mfu"):
        assert _module("readers", name).read(record, {}) is None
