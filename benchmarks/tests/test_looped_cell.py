"""The looped cell's own files — ``drivers/serve_looped.py``,
``reference_ouro.py``, ``flops_looped.py``, the two readers — end to
end on the CPU at a toy size: a copy of the benchmark with the
rehearsal cell of ``data/tiny_looped`` added as new files."""

import importlib.util
import json
import os
import shutil

import pytest

from conftest import BENCH, HERE, run_cell


@pytest.fixture(scope="module")
def looped_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("looped_tree") / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".pytest_cache"))
    shutil.copytree(os.path.join(HERE, "data", "tiny_looped"), root,
                    dirs_exist_ok=True)
    return root


def test_end_to_end_line(looped_tree):
    p, out = run_cell(looped_tree, "tiny.serve.looped", trace=0, seconds=3)
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}


def _notes(p):
    (line,) = [json.loads(ln) for ln in p.stdout.splitlines()
               if ln.startswith('{"note": "run"')]
    return line["notes"]


@pytest.mark.parametrize("fault", ["one_pass_short", "previous_plane",
                                   "kv_float8", "matmul_float8"])
def test_a_planted_fault_is_not_correct(looped_tree, fault):
    """The harness's own comparison, on the requests the window
    finished, against the reference computed WRONG: the run comes out
    not ``correct``, by the reference check and by nothing else."""
    p, out = run_cell(looped_tree, "tiny.serve.looped", trace=0, seconds=2,
                      env={"REFERENCE_FAULT": fault})
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is False and out["failed"] == 0 < out["attempted"]
    notes = _notes(p)
    assert notes["reference"]["ok"] is False
    assert notes["transcript_mismatches"] == 0 == notes["programs_in_window"]


def test_controls_beside_a_correct_run(looped_tree):
    """``REFERENCE_CONTROLS=1``: the run itself is ``correct``, and the
    same sample fails every faulty reference."""
    p, out = run_cell(looped_tree, "tiny.serve.looped", trace=0, seconds=2,
                      env={"REFERENCE_CONTROLS": "1"})
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True
    ref = _notes(p)["reference"]
    assert ref["ok"] is True
    assert sorted(ref["controls"]) == ["kv_float8", "matmul_float8",
                                       "one_pass_short", "previous_plane"]
    for verdict in ref["controls"].values():
        assert verdict["ok"] is False
        assert verdict["mean_gap_to_best_logit"] \
            > 10 * ref["mean_gap_to_best_logit"]


def test_per_layer_line_off_the_chip(looped_tree):
    """No table of peaks and no device trace on the CPU: the two new
    readers find nothing to read, return nothing and do not raise; the
    sampled metrics report."""
    p, out = run_cell(looped_tree, "tiny.serve.looped", trace=1, seconds=3)
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True
    assert set(out["metrics"]) == {"lanes_busy_share", "kv_used_share"}


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(name, t0, dur, **fields):
    return {"kind": "span", "name": name, "t0": t0, "dur": dur,
            "fields": fields}


@pytest.fixture()
def record():
    """Two decode steps of 9 lanes at 2,000 live slots traced, then two
    rounds of 9 tokens and one admission of 64 positions in 0.1 s."""
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        conf = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    step = "%jit_step_n_p = ..."
    return {
        "conf": conf, "peaks": peaks, "window": (0.0, 20.0), "max_len": 512,
        "profile_window": (1.0, 4.0),
        "trace": {"events": {"devices": {"/device:TPU:0": {
            "ops": [], "modules": [["jit_step_n_p(1)", 0, 40_000_000],
                                   ["jit__admit(2)", 0, 9_000_000],
                                   ["jit_step_n_p(1)", 0, 40_000_000]]}}}},
        "obs_events": [
            {"kind": "event", "name": "serving.kv_layout",
             "fields": {"passes": 4, "layers": 48, "planes": 192,
                        "bytes_per_slot": 1572864, "slots": 4608,
                        "slab_bytes": 1572864 * 4608}},
            _span("serving.round", 2.0, 0.04, tokens=9, kv_live=1900),
            _span("serving.round", 3.0, 0.04, tokens=9, kv_live=2100),
            _span("serving.round", 3.5, 0.0, idle=True, tokens=0, kv_live=0),
            _span("serving.round", 10.0, 0.05, tokens=9, kv_live=2000),
            _span("serving.admit", 10.05, 0.001, bucket=64, positions=50,
                  attended=64),
            _span("serving.round", 10.05, 0.05, tokens=9, kv_live=2000),
        ]}


def test_step_hbm_roofline_from_a_hand_made_record(record):
    """2 x (4 x 4.934 GB of layers + 0.201 GB of head + 2,000 slots x
    1.5 MiB) = 46.2 GB / 819 GB/s = 56.4 ms of the 80 ms traced."""
    got = _reader("step_hbm_roofline").read(record, {})
    layers = 48 * (4 * 2048 * 2048 + 3 * 2048 * 5632) * 2
    need = 2 * (4 * layers + 49152 * 2048 * 2 + 2000 * 1572864)
    assert got == pytest.approx(100 * need / 819e9 / 0.08)
    assert 70 < got < 71
    record["obs_events"] = record["obs_events"][1:]     # an older program
    assert _reader("step_hbm_roofline").read(record, {}) is None


def test_serve_mfu_from_a_hand_made_record(record):
    """18 decoded tokens and 50 admitted prompt positions — the true
    number, not the bucket's 64 — in the 0.1 s from the first round
    after the profiler to the end of the last."""
    got = _reader("serve_mfu").read(record, {})
    stack = 48 * (4 * 2048 * 2048 + 3 * 2048 * 5632)
    need = (18 * 2 * (4 * stack + 49152 * 2048) + 50 * 2 * 4 * stack
            + 4 * 2048 * 48 * 4 * (2000 + 2000 + 50 * 25))
    assert got == pytest.approx(100 * need / 0.1 / 197e12)
    assert 0 < got < 100
    # A continuation chunk of 64 at slot 128 on the bounded path: its
    # positions attend the 128 before them and their own triangle.
    record["obs_events"].insert(-1, _span(
        "serving.admit_chunk", 10.06, 0.001, bucket=64, positions=64,
        attended=192))
    more = 64 * 2 * 4 * stack + 4 * 2048 * 48 * 4 * 64 * (128 + 32)
    assert _reader("serve_mfu").read(record, {}) == pytest.approx(
        100 * (need + more) / 0.1 / 197e12)
    record["obs_events"] = []
    assert _reader("serve_mfu").read(record, {}) is None
