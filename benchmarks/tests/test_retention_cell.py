"""The retention cell's own files — ``drivers/serve_retention.py``,
``reference_brumby.py``, ``flops_retention.py``, the three readers —
end to end on the CPU at a toy size: a copy of the benchmark with the
rehearsal cell of ``data/tiny_ret`` added as new files
(``conftest.py::PARTS`` does not copy a new top-level module: a tree of
its own, as ``test_moe_cell.py``)."""

import importlib.util
import json
import os
import shutil
import sys

import pytest

import benchmark_json
from conftest import BENCH, HERE, REPO, run_cell
from test_moe_cell import contract_order

FAULTS = ["degree_1", "no_gate", "no_normaliser", "state_bf16", "kv_float8",
          "matmul_float8", "no_rope", "wrong_kv_head", "stale_state"]
MINE = ("brumby-14b-base_l8", "brumby.serve.longdoc")
NEW_METRICS = ("step_ret_hbm_roofline", "step_ret_mfu",
               "step_ret_state_roofline")


@pytest.fixture(scope="module")
def ret_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("ret_tree") / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".pytest_cache"))
    shutil.copytree(os.path.join(HERE, "data", "tiny_ret"), root,
                    dirs_exist_ok=True)
    return root


def _notes(p):
    (line,) = [json.loads(ln) for ln in p.stdout.splitlines()
               if ln.startswith('{"note": "run"')]
    return line["notes"]


def test_end_to_end_line(ret_tree):
    p, out = run_cell(ret_tree, "tiny.serve.ret", trace=0, seconds=3)
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
    ref = _notes(p)["reference"]
    assert ref["longest_prompt"] > 32     # a state through four chunks
    assert ref["reused_lanes_checked"] >= 1


def test_a_planted_fault_is_not_correct(ret_tree):
    """The harness's own comparison, on the requests the window
    finished, against the reference computed WRONG: the run comes out
    not ``correct``, by the reference check and by nothing else."""
    p, out = run_cell(ret_tree, "tiny.serve.ret", trace=0, seconds=2,
                      env={"REFERENCE_FAULT": "stale_state"})
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is False and out["failed"] == 0 < out["attempted"]
    notes = _notes(p)
    assert notes["reference"]["ok"] is False
    assert notes["transcript_mismatches"] == 0 == notes["programs_in_window"]


def test_controls_beside_a_correct_run(ret_tree):
    """``REFERENCE_CONTROLS=1``: the run itself is ``correct``, and the
    same sample fails every faulty reference."""
    p, out = run_cell(ret_tree, "tiny.serve.ret", trace=0, seconds=2,
                      env={"REFERENCE_CONTROLS": "1"})
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True
    ref = _notes(p)["reference"]
    assert ref["ok"] is True and sorted(ref["controls"]) == sorted(FAULTS)
    for name, verdict in ref["controls"].items():
        if name == "state_bf16":
            # 87 tokens over 96 entries: a state rounded to bfloat16
            # moves the logits (tests/test_retention.py: by tens of
            # tolerances) and flips no choice here; what it reads at
            # the published widths is the chip's to say (PERF.md).
            continue
        assert verdict["ok"] is False, name
        assert verdict["mean_gap_to_best_logit"] \
            > 10 * ref["mean_gap_to_best_logit"], name


def test_per_layer_line_off_the_chip(ret_tree):
    """No table of peaks and no device trace on the CPU: the readers of
    the device find nothing to read, return nothing and do not raise;
    the sampled metric reports.  (The six per-layer metrics of the
    cell are read from a hand-made record below.)"""
    p, out = run_cell(ret_tree, "tiny.serve.ret", trace=1, seconds=3)
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True
    assert set(out["metrics"]) == {"lanes_busy_share"}


def test_benchmark_json_is_what_the_files_say_with_new_entries_last():
    """``build()`` sorts the cells ``end_to_end.json`` does not order
    by name (``brumby`` first of all), the contract wants new entries
    last: PR 31's cell, then this one, and the three new metrics after
    every accepted one (their names sort after ``step_moe_mfu``)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        got = json.load(f)
    assert got == contract_order(contract_order(benchmark_json.build()),
                                 last=MINE)
    assert got["configs"][-1]["name"] == MINE[0]
    assert got["workloads"][-1]["name"] == MINE[1]
    assert tuple(m["name"] for m in got["per_layer"][-3:]) == NEW_METRICS
    for m in got["per_layer"]:
        if MINE[1] in m["workloads"]:
            assert m["workloads"][-1] == MINE[1]
    assert MINE[1] not in next(m for m in got["per_layer"]
                               if m["name"] == "kv_used_share")["workloads"]


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
    """Two decode steps of 20 decoding lanes traced (8 kernel calls
    each), then two rounds of 20 tokens and one admission of 512
    positions in 0.08 s."""
    with open(os.path.join(BENCH, "configs", "brumby-14b-base_l8.json")) as f:
        conf = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    call = ["%ret_state_step.3 = (f32[22,8,8,128], f32[8,22,8,65,128,128], "
            "f32[8,22,8,65,128]) custom-call(...), "
            "custom_call_target=\"tpu_custom_call\"", 0, 2_000_000]
    rnd = dict(tokens=20, state_lanes=20, kv_live=0)
    return {
        "conf": conf, "peaks": peaks, "window": (0.0, 20.0),
        "max_len": 32768, "profile_window": (1.0, 4.0),
        "trace": {"events": {"devices": {"/device:TPU:0": {
            "ops": [call] * 16 + [["%fusion.3 = ...", 0, 5_000_000]],
            "modules": [["jit_step_n_p(1)", 0, 25_000_000],
                        ["jit__admit(2)", 0, 30_000_000],
                        ["jit_step_n_p(1)", 0, 25_000_000]]}}}},
        "obs_events": [
            {"kind": "event", "name": "serving.kv_layout",
             "fields": {"passes": 1, "layers": 8, "planes": 0,
                        "planes_state": 8, "state_dtype": "float32",
                        "state_bytes_per_lane": 274_759_680}},
            _span("serving.round", 2.0, 0.03, **rnd),
            _span("serving.round", 3.0, 0.03, **rnd),
            _span("serving.round", 10.0, 0.04, **rnd),
            _span("serving.admit_chunk", 10.02, 0.001, bucket=512,
                  positions=512, attended=0),
            _span("serving.round", 10.04, 0.04, **rnd),
        ]}


def test_flops_retention_counts_the_issues_numbers(record):
    flops = _module("flops_retention")
    tc = record["conf"]["transformer_config"]
    assert flops.phi_rows(tc) == 8256
    assert flops.layer_params(tc) == 330_342_400
    assert flops.weight_bytes(tc) == 8 * 660_684_800 + 1_555_824_640
    assert flops.state_bytes(tc) == 8 * (8 * 8256 * 128 + 8 * 8256) * 4 \
        == 272_646_144
    # the program's cyclic layout: 65 x 128 rows for 8256
    layout = record["obs_events"][0]["fields"]["state_bytes_per_lane"]
    assert layout == 8 * 8 * (8320 * 128 + 8320) * 4
    assert flops.state_flops(tc) == 8 * (2 * 8 * 8256 * 128
                                         + 2 * 40 * 8256 * 128)
    # 17.7 GB a step at 20 lanes: the state is 61 % of it
    step = flops.decode_step_bytes(tc, 20)
    assert 17.7e9 < step < 17.8e9
    assert 0.61 < 2 * 20 * flops.state_bytes(tc) / step < 0.62


def test_the_three_new_metrics_from_a_hand_made_record(record):
    flops = _module("flops_retention")
    tc = record["conf"]["transformer_config"]
    hbm = _module("readers", "ret_hbm_roofline").read(record, {})
    assert hbm == pytest.approx(
        100 * 2 * flops.decode_step_bytes(tc, 20) / 819e9 / 0.05)
    assert 85 < hbm < 90
    kern = _module("readers", "ret_state_roofline").read(record, {})
    assert kern == pytest.approx(
        100 * 16 * 2 * 20 * (8 * 8256 * 129 * 4) / 819e9 / 0.032)
    assert 80 < kern < 85
    mfu = _module("readers", "ret_mfu").read(record, {})
    need = (40 * flops.position_flops(tc, True)
            + 512 * flops.position_flops(tc, False)
            + 4 * 40 * 128 * 8 * 512 * 513 / 2)
    assert mfu == pytest.approx(100 * need / 0.08 / 197e12)
    assert 0 < mfu < 100
    # a program older than the state planes: nothing to read, no raise
    for r in record["obs_events"]:
        for k in ("planes_state", "state_dtype", "state_lanes"):
            r["fields"].pop(k, None)
    for name in ("ret_hbm_roofline", "ret_state_roofline", "ret_mfu"):
        assert _module("readers", name).read(record, {}) is None
