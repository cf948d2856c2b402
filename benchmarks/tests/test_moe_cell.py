"""The typed-stack cell's own files — ``drivers/serve_moe.py``,
``reference_kexaone.py``, ``flops_moe.py``, the three readers — end to
end on the CPU at a toy size: a copy of the benchmark with the
rehearsal cell of ``data/tiny_moe`` added as new files
(``conftest.py::PARTS`` does not copy a new top-level module: a tree of
its own, as ``test_looped_cell.py``)."""

import importlib.util
import json
import os
import shutil
import sys

import pytest

import benchmark_json
from conftest import BENCH, HERE, REPO, run_cell

FAULTS = ["window_half", "top_k_less_one", "no_route_scale",
          "no_shared_expert", "no_select_bias", "rope_on_full", "kv_float8",
          "matmul_float8"]


@pytest.fixture(scope="module")
def moe_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("moe_tree") / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".pytest_cache"))
    shutil.copytree(os.path.join(HERE, "data", "tiny_moe"), root,
                    dirs_exist_ok=True)
    return root


def _notes(p):
    (line,) = [json.loads(ln) for ln in p.stdout.splitlines()
               if ln.startswith('{"note": "run"')]
    return line["notes"]


def test_end_to_end_line(moe_tree):
    p, out = run_cell(moe_tree, "tiny.serve.moe", trace=0, seconds=3)
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
    ref = _notes(p)["reference"]
    assert ref["longest_prompt"] > 16      # a ring wrapped under a chunk


def test_a_planted_fault_is_not_correct(moe_tree):
    """The harness's own comparison, on the requests the window
    finished, against the reference computed WRONG: the run comes out
    not ``correct``, by the reference check and by nothing else."""
    p, out = run_cell(moe_tree, "tiny.serve.moe", trace=0, seconds=2,
                      env={"REFERENCE_FAULT": "no_select_bias"})
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is False and out["failed"] == 0 < out["attempted"]
    notes = _notes(p)
    assert notes["reference"]["ok"] is False
    assert notes["transcript_mismatches"] == 0 == notes["programs_in_window"]


def test_controls_beside_a_correct_run(moe_tree):
    """``REFERENCE_CONTROLS=1``: the run itself is ``correct``, and the
    same sample fails every faulty reference."""
    p, out = run_cell(moe_tree, "tiny.serve.moe", trace=0, seconds=2,
                      env={"REFERENCE_CONTROLS": "1"})
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True
    ref = _notes(p)["reference"]
    assert ref["ok"] is True and sorted(ref["controls"]) == sorted(FAULTS)
    for verdict in ref["controls"].values():
        assert verdict["ok"] is False
        assert verdict["mean_gap_to_best_logit"] \
            > 10 * ref["mean_gap_to_best_logit"]


def test_per_layer_line_off_the_chip(moe_tree):
    """No table of peaks and no device trace on the CPU: the readers
    of the device find nothing to read, return nothing and do not
    raise; the sampled metrics and the program's own counter report."""
    p, out = run_cell(moe_tree, "tiny.serve.moe", trace=1, seconds=3)
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True
    assert set(out["metrics"]) == {"lanes_busy_share", "kv_used_share",
                                   "step_moe_expert_tokens"}
    # 3 lanes x top-4 of 16 experts, 4 held: under a token an expert
    assert 0 < out["metrics"]["step_moe_expert_tokens"]["value"] < 3


def contract_order(built, last=("k-exaone-236b-a23b_l5-ep8",
                                 "kexaone.serve.chat")):
    """``benchmark_json.build()`` with this cell and its configuration
    moved to the END of every list that names them: the contract reads
    an entry put into the middle of a list as a change to what was
    there, and ``build()`` sorts the cells ``end_to_end.json`` does not
    order by name (``kexaone`` before ``ouro2b6``; PERF.md §7 ask 5)."""
    end = lambda names, key: sorted(names, key=lambda n: key(n) in last)
    out = dict(built)
    for kind in ("configs", "workloads"):
        out[kind] = end(built[kind], lambda e: e["name"])
    for kind in ("end_to_end", "per_layer"):
        out[kind] = [{**m, "workloads": end(m["workloads"], str)}
                     if "workloads" in m else m for m in built[kind]]
    return out


def test_benchmark_json_is_what_the_files_say_with_new_entries_last():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert json.load(f) == contract_order(benchmark_json.build())


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
    """Two decode steps of 190 lanes traced (1.2 k live positions a
    lane), then two rounds of 190 tokens and one admission of 512
    positions after 1,024 in 0.05 s."""
    with open(os.path.join(BENCH, "configs",
                           "k-exaone-236b-a23b_l5-ep8.json")) as f:
        conf = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    live, win = 190 * 1200, 190 * 128
    moe = dict(moe_assigned=190 * 8 * 4, moe_held=190 * 4, moe_max=20)
    return {
        "conf": conf, "peaks": peaks, "window": (0.0, 20.0), "max_len": 8192,
        "profile_window": (1.0, 4.0),
        "trace": {"events": {"devices": {"/device:TPU:0": {
            "ops": [["%gmm.28 = bf16[1536,4096] custom-call(...), "
                     "custom_call_target=\"tpu_custom_call\"", 0, 1_200_000],
                    ["%gmm.29 = bf16[1536,6144] custom-call(...), "
                     "custom_call_target=\"tpu_custom_call\"", 0, 700_000],
                    ["%fusion.3 = ...", 0, 5_000_000]],
            "modules": [["jit_step_n_p(1)", 0, 12_000_000],
                                   ["jit__admit(2)", 0, 15_000_000],
                                   ["jit_step_n_p(1)", 0, 12_000_000]]}}}},
        "obs_events": [
            {"kind": "event", "name": "serving.kv_layout",
             "fields": {"passes": 1, "layers": 5, "planes": 1,
                        "planes_full": 1, "planes_window": 4, "window": 128,
                        "ring_slots": 128, "bytes_per_slot_full": 4096,
                        "bytes_per_slot_window": 16384,
                        "bytes_per_slot": 4354, "slots": 190 * 8192,
                        "slab_bytes": 4354 * 190 * 8192}},
            _span("serving.round", 2.0, 0.012, tokens=190, kv_live=live,
                  kv_live_window=win, **moe),
            _span("serving.round", 3.0, 0.012, tokens=190, kv_live=live,
                  kv_live_window=win, **moe),
            _span("serving.round", 10.0, 0.025, tokens=190, kv_live=live,
                  kv_live_window=win, **moe),
            _span("serving.admit_chunk", 10.02, 0.001, bucket=512,
                  positions=512, attended=1536),
            _span("serving.round", 10.025, 0.025, tokens=190, kv_live=live,
                  kv_live_window=win, **moe),
        ]}


def test_flops_moe_counts_the_cut_from_the_configuration_file(record):
    """The issue's bytes: attention 113.25 M, the dense feed-forward
    339.74 M, an expert 37.75 M, the router 0.79 M; 7.19 GB of weights
    a decode step reads (the embedding's rows are not read whole)."""
    flops = _module("flops_moe")
    tc = record["conf"]["transformer_config"]
    assert flops.attn_params(tc) == 113_246_208
    assert flops.expert_params(tc) == 37_748_736
    assert flops.layer_fixed_params(tc, "dense") == 113_246_208 + 339_738_624
    assert flops.layer_fixed_params(tc, "sparse") == (
        113_246_208 + 786_432 + 37_748_736)
    assert (flops.held_experts(tc), flops.sparse_layers(tc)) == (16, 4)
    total = (452_984_832 + 4 * 755_761_152 + 19200 * 6144) * 2
    assert flops.weight_bytes(tc) == total
    assert 7.18e9 < total < 7.20e9


def test_step_moe_hbm_roofline_from_a_hand_made_record(record):
    """2 x (7.19 GB of weights + 228,000 live positions x 4,096 B +
    24,320 window positions x 16,384 B) = 17.0 GB / 819 GB/s = 20.8 ms
    of the 24 ms traced."""
    _module("flops_moe")
    got = _module("readers", "moe_hbm_roofline").read(record, {})
    need = 2 * (7_187_988_480 + 190 * 1200 * 4096 + 190 * 128 * 16384)
    assert got == pytest.approx(100 * need / 819e9 / 0.024)
    assert 85 < got < 90
    record["obs_events"][0]["fields"].pop("bytes_per_slot_window")
    assert _module("readers", "moe_hbm_roofline").read(record, {}) is None


def test_step_moe_mfu_and_expert_tokens_from_a_hand_made_record(record):
    """380 decoded tokens and 512 admitted positions in the 0.05 s from
    the first round after the profiler to the end of the last; an
    eighth of the 8 assignments a position held."""
    flops = _module("flops_moe")
    tc = record["conf"]["transformer_config"]
    got = _module("readers", "moe_mfu").read(record, {})
    fixed = 452_984_832 + 4 * (113_246_208 + 786_432 + 37_748_736)
    routed = 4 * 8 * 0.125 * 37_748_736
    need = (380 * 2 * (fixed + routed + 19200 * 6144)
            + 512 * 2 * (fixed + routed)
            + 4 * 64 * 128 * (1 * (2 * 190 * 1200 + 512 * (1024 + 256))
                              + 4 * (2 * 190 * 128 + 512 * 128)))
    assert got == pytest.approx(100 * need / 0.05 / 197e12)
    assert 0 < got < 100
    assert flops.position_flops(tc, True, 0.125) == 2 * (
        fixed + routed + 19200 * 6144)
    tokens = _module("readers", "moe_expert_tokens").read(record, {})
    assert tokens == pytest.approx(190 * 4 / 16 / 4)
    for r in record["obs_events"]:         # a program older than the fields
        for k in ("moe_assigned", "moe_held", "moe_max", "kv_live_window"):
            r["fields"].pop(k, None)
    assert _module("readers", "moe_mfu").read(record, {}) is None
    assert _module("readers", "moe_expert_tokens").read(record, {}) is None


def test_step_moe_gmm_roofline_from_a_hand_made_record(record):
    """One sparse layer's application traced (two calls, 1.9 ms): its
    16 experts' 1.208 GB and the 1,520 held rows of the two decode
    rounds that began under the profiler, bound by bytes: 1.53 ms."""
    _module("flops_moe")
    got = _module("readers", "moe_gmm_roofline").read(record, {})
    rows = 2 * 190 * 4
    need = 2 * (16 * 37_748_736 + rows * (2 * 6144 + 3 * 2048))
    assert got == pytest.approx(100 * need / 819e9 / 0.0019)
    assert 75 < got < 85
    record["trace"]["events"]["devices"]["/device:TPU:0"]["ops"] = []
    assert _module("readers", "moe_gmm_roofline").read(record, {}) is None
