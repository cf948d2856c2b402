"""The latent cell's own files — ``drivers/serve_latent.py``,
``reference_joyai.py``, ``flops_mla.py``, the five readers — end to end
on the CPU at a toy size: a copy of the benchmark with the rehearsal
cell of ``data/tiny_latent`` added as new files (a tree of its own, as
``test_retention_cell.py``)."""

import importlib.util
import json
import os
import shutil
import sys

import pytest

import benchmark_json
from conftest import BENCH, HERE, REPO, run_cell
from test_moe_cell import contract_order

FAULTS = ["kv_float8", "matmul_float8", "scale_nope", "no_kv_norm",
          "no_q_norm", "rope_halves", "no_k_rope", "top_k_less_one",
          "no_select_bias", "no_route_scale", "no_shared_expert"]
MINE = ("joyai-llm-flash_l10-ep8", "joyai.serve.longctx")
BEFORE = ("brumby-14b-base_l8", "brumby.serve.longdoc")
NEW_METRICS = ("step_mla_decode_roofline", "step_mla_hbm_roofline",
               "step_mla_live_positions", "step_mla_mfu",
               "step_mla_prefix_roofline")
ACCEPTED = ("lanes_busy_share", "kv_used_share", "decode_step_ms",
            "prefill_ms_per_ktok", "step_moe_expert_tokens",
            "step_moe_gmm_roofline")


@pytest.fixture(scope="module")
def lat_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("lat_tree") / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".pytest_cache"))
    shutil.copytree(os.path.join(HERE, "data", "tiny_latent"), root,
                    dirs_exist_ok=True)
    return root


def _notes(p):
    (line,) = [json.loads(ln) for ln in p.stdout.splitlines()
               if ln.startswith('{"note": "run"')]
    return line["notes"]


def test_end_to_end_line(lat_tree):
    p, out = run_cell(lat_tree, "tiny.serve.latent", trace=0, seconds=3)
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
    ref = _notes(p)["reference"]
    assert ref["longest_prompt"] > 32     # a prefix through four chunks
    assert ref["reused_lanes_checked"] >= 1
    # the rows the engine's cache held at the end, against the
    # reference's: the lanes decoding then (two at most), layers 0 and
    # 1, float32 on both sides
    rows = ref["rows"]
    assert rows["ok"] is True and rows["latent_row_err"] < 1e-5
    assert {r["layer"] for r in rows["by_lane"]} == {0, 1}
    assert 1 <= len({r["lane"] for r in rows["by_lane"]}) <= 2
    assert all(r["rows"] >= 4 for r in rows["by_lane"])


@pytest.mark.parametrize("fault", ["no_k_rope", "kv_float8"])
def test_a_planted_fault_is_not_correct(lat_tree, fault):
    """The harness's own comparison, on the requests the window
    finished, against the reference computed WRONG: the run comes out
    not ``correct``, by the reference check and by nothing else.  A
    cache a precision apart from the reference's (``kv_float8``) is
    told by the engine's own rows."""
    p, out = run_cell(lat_tree, "tiny.serve.latent", trace=0, seconds=2,
                      env={"REFERENCE_FAULT": fault})
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is False and out["failed"] == 0 < out["attempted"]
    notes = _notes(p)
    assert notes["reference"]["ok"] is False
    assert notes["reference"]["rows"]["ok"] is False
    assert notes["transcript_mismatches"] == 0 == notes["programs_in_window"]


def test_controls_beside_a_correct_run(lat_tree):
    """``REFERENCE_CONTROLS=1``: the run itself is ``correct``, and the
    same sample fails every faulty reference."""
    p, out = run_cell(lat_tree, "tiny.serve.latent", trace=0, seconds=2,
                      env={"REFERENCE_CONTROLS": "1"})
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True
    ref = _notes(p)["reference"]
    assert ref["ok"] is True and sorted(ref["controls"]) == sorted(FAULTS)
    for name, verdict in ref["controls"].items():
        assert verdict["ok"] is False, name
        assert verdict["mean_gap_to_best_logit"] \
            > 10 * ref["mean_gap_to_best_logit"], name
    # a cache in float8 beside this one: 3 mantissa bits for 23, read
    # off the rows themselves whatever the logits make of it; every
    # fault of the attention shows there too, the router's and the
    # experts' (no sparse layer before layer 1's rows) leave them alone
    tol = ref["rows"]["latent_row_tol"]
    errs = {name: v["latent_row_err"] for name, v in ref["controls"].items()}
    assert 0.01 < errs["kv_float8"] < 0.06 and errs["kv_float8"] > 10 * tol
    for name in ("matmul_float8", "scale_nope", "no_kv_norm", "no_q_norm",
                 "rope_halves", "no_k_rope"):
        assert errs[name] > tol, name
    for name in ("top_k_less_one", "no_select_bias", "no_route_scale",
                 "no_shared_expert"):
        assert errs[name] < tol, name


def test_per_layer_line_off_the_chip(lat_tree):
    """No table of peaks and no device trace on the CPU: the readers of
    the device find nothing to read, return nothing and do not raise;
    the sampled metrics and the program's own counters report."""
    p, out = run_cell(lat_tree, "tiny.serve.latent", trace=1, seconds=3)
    assert p.returncode == 0, p.stderr[-3000:]
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "lanes_busy_share", "kv_used_share", "step_moe_expert_tokens",
        "step_mla_live_positions"}
    assert 6 < out["metrics"]["step_mla_live_positions"]["value"] < 112


def test_benchmark_json_is_what_the_files_say_with_new_entries_last():
    """``build()`` sorts the cells ``end_to_end.json`` does not order
    and every metric by name; the contract wants new entries last: PR
    31's cell, PR 33's, then this one, and the five new metrics after
    every accepted one (``step_mla_*`` sorts BEFORE ``step_moe_*``)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        got = json.load(f)
    want = contract_order(contract_order(contract_order(
        benchmark_json.build()), last=BEFORE), last=MINE)
    want["per_layer"] = sorted(
        want["per_layer"], key=lambda m: m["name"] in NEW_METRICS)
    assert got == want
    assert got["configs"][-1]["name"] == MINE[0]
    assert got["workloads"][-1]["name"] == MINE[1]
    assert tuple(m["name"] for m in got["per_layer"][-5:]) == NEW_METRICS
    listed = {m["name"] for m in got["per_layer"]
              if MINE[1] in m.get("workloads", ())}
    assert listed == set(NEW_METRICS) | set(ACCEPTED)
    for m in got["per_layer"]:
        if MINE[1] in m["workloads"]:
            assert m["workloads"][-1] == MINE[1]
    for m in got["per_layer"][-5:]:
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
    """Two decode steps traced (25 lanes decode, 1 admits: 10 kernel
    calls each over 250k live positions), then two rounds of 25 tokens
    and one admission of 512 positions after 9,728 in 0.06 s."""
    with open(os.path.join(BENCH, "configs",
                           "joyai-llm-flash_l10-ep8.json")) as f:
        conf = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    call = ["%mla_decode_fwd.3 = (f32[26,32,512], f32[26,32,128]) "
            "custom-call(...), custom_call_target=\"tpu_custom_call\"",
            0, 500_000]
    rnd = dict(tokens=25, kv_live=260_000, lanes_busy=26, lanes_admitting=1,
               moe_assigned=25 * 8 * 9, moe_held=25 * 9, moe_max=3)
    step = dict(n=1, attended=250_000 + 25 * 64)
    return {
        "conf": conf, "peaks": peaks, "window": (0.0, 20.0),
        "max_len": 32768, "profile_window": (1.0, 4.0),
        "trace": {"events": {"devices": {"/device:TPU:0": {
            "ops": [call] * 20 + [["%fusion.3 = ...", 0, 5_000_000]] + [
                ["%mla_prefix_fwd.7 = bf16[32,512,128] custom-call(...), "
                 "custom_call_target=\"tpu_custom_call\"", 0, 2_000_000]] * 10,
            "modules": [["jit_step_n_p(1)", 0, 12_000_000],
                        ["jit__admit(2)", 0, 30_000_000],
                        ["jit_step_n_p(1)", 0, 12_000_000]]}}}},
        "obs_events": [
            {"kind": "event", "name": "serving.kv_layout",
             "fields": {"passes": 1, "layers": 10, "planes": 0,
                        "planes_latent": 10, "latent_width": 640,
                        "bytes_per_slot_latent": 12_800}},
            _span("serving.round", 2.0, 0.015, **rnd),
            _span("serving.step", 2.001, 0.001, **step),
            _span("serving.round", 3.0, 0.015, **rnd),
            _span("serving.step", 3.001, 0.001, **step),
            _span("serving.round", 10.0, 0.03, **rnd),
            _span("serving.admit_chunk", 10.01, 0.001, bucket=512,
                  positions=512, attended=10_240),
            _span("serving.admit_chunk", 3.5, 0.001, bucket=512,
                  positions=512, attended=10_240),
            _span("serving.round", 10.03, 0.03, **rnd),
        ]}


def test_flops_mla_counts_the_issues_numbers(record):
    flops = _module("flops_mla")
    tc = record["conf"]["transformer_config"]
    assert flops.attn_params(tc) == 26_345_472            # 26.35 M a layer
    assert flops.layer_fixed_params(tc, "dense") == 26_345_472 + 44_040_192
    assert flops.layer_fixed_params(tc, "sparse") == (
        26_345_472 + 2048 * 256 + 4_718_592)              # 31.6 M
    assert flops.slot_bytes(tc) == 11_520 and flops.slot_bytes(tc, 1) == 1152
    # what a step reads once: all but the embedding of the 1.780 B
    # parameters held here (3.56 GB in bfloat16)
    assert flops.weight_bytes(tc) == 2 * (
        70_385_664 + 9 * (31_588_352 + 32 * 4_718_592) + 16160 * 2048) \
        == 3_493_462_016
    # attention 2 x 32 x 320 a pair a layer; the absorbed form 3.4x that
    assert flops.attention_flops(tc, 1) == 10 * 2 * 32 * 320
    t_bytes, t_ops = flops.decode_kernel_least_s(tc, 1, record["peaks"])
    assert t_bytes == pytest.approx(1.41e-9, rel=0.01)
    assert t_ops == pytest.approx(0.353e-9, rel=0.01)
    # a decode step at 250k live positions: the cache is ~45 % of it
    step = flops.decode_step_bytes(tc, 250_000)
    assert 0.44 < 250_000 * 11_520 / step < 0.46


def test_the_five_new_metrics_from_a_hand_made_record(record):
    flops = _module("flops_mla")
    tc = record["conf"]["transformer_config"]
    hbm = _module("readers", "mla_hbm_roofline").read(record, {})
    assert hbm == pytest.approx(
        100 * 2 * flops.decode_step_bytes(tc, 260_000) / 819e9 / 0.024)
    assert 60 < hbm < 70
    kern = _module("readers", "mla_decode_roofline").read(record, {})
    # the step's ``attended`` (251,600) is less than ``kv_live``
    assert kern == pytest.approx(
        100 * 20 * 251_600 * 1152 / 819e9 / 0.010)
    assert 65 < kern < 75
    chunk = _module("readers", "mla_prefix_roofline").read(record, {})
    # one admission of 512 rows after 9,728 in the profile: 10 calls;
    # the least work is ``c · wkv_b`` for the chunk's OWN rows and its
    # pairs — the 9,728 earlier positions the kernel rebuilds (43 % of
    # what it multiplies) are credited with nothing
    one = 2 * 512 * 512 * 32 * 256 + 2 * 32 * 320 * 512 * (9728 + 256)
    assert flops.prefix_kernel_flops(tc, 512, 9728) == one
    rebuilt = flops.prefix_rebuilt_flops(tc, 9728)
    assert rebuilt == 2 * 9728 * 512 * 32 * 256
    assert 0.42 < rebuilt / (one + rebuilt) < 0.44
    assert chunk == pytest.approx(100 * 10 * one / 197e12 / 0.020)
    assert 25 < chunk < 30
    live = _module("readers", "mla_live_positions").read(record, {})
    assert live == pytest.approx(260_000 / 25)
    mfu = _module("readers", "mla_mfu").read(record, {})
    need = (50 * flops.position_flops(tc, True, 1 / 8)
            + 512 * flops.position_flops(tc, False, 1 / 8)
            + flops.attention_flops(tc, 2 * 260_000 + 512 * (9728 + 256)))
    # (the admission at 3.5 s lies inside the profile, outside both stretches)
    assert mfu == pytest.approx(100 * need / 0.06 / 197e12)
    assert 0 < mfu < 100
    # a program older than the latent planes: nothing to read, no raise
    record["obs_events"] = [r for r in record["obs_events"]
                            if r["name"] != "serving.kv_layout"]
    record["trace"]["events"]["devices"]["/device:TPU:0"]["ops"] = [
        ["%fusion.3 = ...", 0, 5_000_000]]
    for name in ("mla_hbm_roofline", "mla_decode_roofline",
                 "mla_prefix_roofline", "mla_live_positions", "mla_mfu"):
        assert _module("readers", name).read(record, {}) is None
