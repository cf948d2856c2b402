"""The harness end to end on the CPU, at a tiny size, over rehearsal
cells that exist only here — added to a copy of the benchmark as new
files, with no edit to a file that was there."""

import pytest

from conftest import run_cell

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.serve.closed", {"serve_tok_s", "setup_s"}),
    ("tiny.serve.open", {"serve_tok_s", "ttft_p90_ms", "itl_p95_ms", "setup_s"}),
    ("tiny.train", {"train_tok_s_chip", "setup_s"}),
    ("tiny.train.fsdp4", {"train_tok_s_chip", "setup_s"}),
])
def test_end_to_end_line(tree, cell, metrics):
    # Four virtual CPU devices for the four-chip rehearsal.
    p, out = run_cell(tree, cell, trace=0, env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert p.returncode == 0, p.stderr[-3000:]
    assert set(out) == KEYS
    assert out["correct"] is True and out["failed"] == 0 < out["attempted"]
    assert set(out["metrics"]) == metrics
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert out["device"]["platform"] == "cpu"      # named for what it is
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["device"]["count"] == (4 if cell.endswith("fsdp4") else 1)


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.serve.closed", {"tiny.steps", "tiny.lanes_busy", "tiny.kv_used"}),
    # one metric file serves two cells: each cell names it
    ("tiny.serve.open", {"tiny.steps"}),
    # tiny.nothing_to_read finds no such program: left out of the line
    ("tiny.train", {"tiny.h2d"}),
])
def test_per_layer_line_finds_metrics_and_readers_added_as_files(
        tree, cell, metrics):
    p, out = run_cell(tree, cell, trace=1)
    assert p.returncode == 0, p.stderr[-3000:]
    assert set(out) == KEYS | {"breakdown"}
    assert set(out["metrics"]) == metrics
    for name, m in out["metrics"].items():
        assert m["value"] > 0 and (m["unit"] != "%" or m["value"] <= 100)
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_accelerator_no_result(tree):
    p, out = run_cell(tree, "tiny.train", trace=0, rehearse=False)
    assert p.returncode == 3 and out is None
    assert not any(ln.startswith("{\"correct\"")
                   for ln in p.stdout.splitlines())


def test_unknown_cell_is_an_error(tree):
    p, out = run_cell(tree, "no.such.cell", trace=0)
    assert p.returncode != 0 and out is None
