"""The contract's limits, and the agreement of BENCHMARK.json with the
benchmark's files."""

import json
import os
import re

import benchmark_json
from conftest import BENCH, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_benchmark_json_is_what_the_files_say():
    assert bench() == benchmark_json.build(), (
        "run python3 benchmarks/tests/benchmark_json.py")


def test_every_file_is_used_by_a_cell():
    """No mix, configuration, layer metric or reader that no cell of
    BENCHMARK.json reaches."""
    cells = [load("workloads", w["name"] + ".json")
             for w in bench()["workloads"]]
    stems = lambda kind, ext: {f[:-len(ext)] for f in os.listdir(
        os.path.join(BENCH, kind)) if f.endswith(ext)}
    assert stems("workloads", ".json") == {c["name"] for c in cells}
    assert stems("configs", ".json") == {c["config"] for c in cells}
    assert stems("traffic", ".json") == {c["traffic"] for c in cells}
    metrics = {n for c in cells for n in c["per_layer"]}
    assert stems("layer_metrics", ".json") == metrics
    assert stems("readers", ".py") == {
        load("layer_metrics", n + ".json")["reader"] for n in metrics}
    assert stems("drivers", ".py") == {c["driver"] for c in cells}
    assert stems("traffic", ".py") == {
        load("traffic", c["traffic"] + ".json")["generator"] for c in cells}


def test_keys_names_units_and_lengths():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    one_line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith("benchmarks/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert one_line(m["layer"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for kind in ("configs", "workloads"):
        names = [x["name"] for x in b[kind]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(b["workloads"]) // 4)


def test_every_layer_metric_moves_a_metric_its_cells_report():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}
    reported = {c: set() for c in cells}
    for m in b["end_to_end"]:
        for c in m.get("workloads", cells):
            reported[c].add(m["name"])
    for c in cells:
        assert "setup_s" in reported[c] and len(reported[c]) >= 2
    has_layer = set()
    for m in b["per_layer"]:
        for c in m.get("workloads", cells):
            assert c in cells
            assert m["moves"] in reported[c], (m["name"], c)
            has_layer.add(c)
    assert has_layer == cells


def test_published_widths_equal_their_sources():
    # The published numbers, written out here a second time on purpose.
    sc1b = load("configs", "starcoderbase-1b_repo-block.json")
    for k, v in dict(n_embd=2048, n_head=16, n_layer=24, n_inner=8192,
                     n_positions=8192, vocab_size=49152,
                     multi_query=True).items():
        assert sc1b[k] == v, k
    tc = sc1b["transformer_config"]
    assert (tc["d_model"], tc["n_heads"], tc["n_kv_heads"], tc["n_layers"],
            tc["d_ff"], tc["max_len"], tc["vocab_size"], tc["rope"]) == (
        2048, 16, 1, 24, 8192, 8192, 49152, False)
    assert sc1b["reduced"] == []
    assert sc1b["engine"]["kwargs"]["hot_swap"] is True
    for depth in (4, 16):
        c = load("configs", f"starcoder2-3b-l{depth}_repo-block.json")
        for k, v in dict(hidden_size=3072, num_attention_heads=24,
                         num_key_value_heads=2, intermediate_size=12288,
                         vocab_size=49152, sliding_window=4096,
                         rope_theta=999999.4420358813,
                         max_position_embeddings=16384).items():
            assert c[k] == v, k
        assert c["num_hidden_layers"] == depth
        assert c["reduced"] == ["num_hidden_layers"]
        assert c["reduced_from"] == {"num_hidden_layers": 30}
        tc = c["transformer_config"]
        assert (tc["d_model"], tc["n_heads"], tc["n_kv_heads"],
                tc["n_layers"], tc["d_ff"], tc["vocab_size"], tc["rope"],
                tc["rope_theta"], tc["attention_window"]) == (
            3072, 24, 2, depth, 12288, 49152, True, 999999.4420358813, 4096)
        assert tc["d_model"] // tc["n_heads"] == 128
    for c in bench()["configs"]:
        f = load(*c["file"].split("/")[1:])
        assert f["departures"] and f["assumed"] and f["deployment"]
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]


def test_files_are_named_from_permitted_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for d, _, fs in os.walk(BENCH):
        if "__pycache__" in d or ".pytest_cache" in d:
            continue
        for f in fs:
            rel = os.path.relpath(os.path.join(d, f), REPO)
            assert ok.match(rel), rel
