"""The readers over the program's ``serving.round`` spans
(``data/tiny/readers/span_gaps.py``, ``span_fields.py``) and the kernel
names: on synthetic records, on the recorded traces, and end to end
through the harness in a rehearsal cell.

They wait in the rehearsal tree, and the six metric files in
``data/wanted``: a cell names its per-layer metrics, so reporting them
in an accepted cell is an edit to that cell's file — a ``benchmark``
PR's business (PERF.md, open questions; ``wanted.py`` builds the tree
that PR would leave).
"""

import importlib.util
import json
import os

import pytest

import benchmark_json
import trace_reduce
import wanted
from conftest import BENCH, HERE, REPO, run_cell

TINY = os.path.join(HERE, "data", "tiny")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(TINY, "readers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, t0, dur, id, parent=None, **fields):
    return {"kind": "span", "name": name, "t0": t0, "dur": dur, "id": id,
            "parent": parent, "depth": 0, "fields": fields}


def one_round(t, id, gap_parts=(0.002, 0.001), chunk=True, **fields):
    """A round starting at ``t``: pump 1 ms, an optional chunk 1 ms, a
    decode step 30 ms, then an emit loop and a reap of ``gap_parts``
    seconds.  Returns its spans and its end."""
    emit, reap = gap_parts
    out = [span("serving.pump", t, 0.001, id + 1, id)]
    at = t + 0.001
    if chunk:
        out.append(span("serving.admit_chunk", at, 0.001, id + 2, id,
                        bucket=512))
        at += 0.001
    out.append(span("serving.step", at, 0.030, id + 3, id, n=1))
    at += 0.030
    out.append(span("serving.emit_loop", at, emit, id + 4, id))
    out.append(span("serving.reap", at + emit, reap, id + 5, id))
    end = at + emit + reap
    out.append(span("serving.round", t, end - t, id, lanes_busy=2,
                    lanes_admitting=1, tokens=2, **fields))
    return out, end


def record(rounds, window, profile_window=None, **extra):
    return {"obs_events": [s for r in rounds for s in r], "window": window,
            "profile_window": profile_window, "lanes": 4, "max_len": 100,
            **extra}


def test_gap_split_sums_to_the_gap_and_skips_the_profiled_rounds(capsys):
    """Four rounds, half a millisecond of the caller between them.  The
    second runs while the profiler does and is ten times slower in its
    emit loop: it is reported apart and kept out of the median."""
    gaps = reader("span_gaps")
    rounds, t = [], 10.0
    for i, emit in enumerate((0.002, 0.020, 0.002, 0.002)):
        spans, end = one_round(t, 100 * (i + 1), (emit, 0.001),
                               kv_live=100, chunks=1)
        rounds.append(spans)
        t = end + 0.0005
    second = rounds[1][-1]["t0"]
    rec = record(rounds, (9.0, 11.0), (second - 0.0001, second + 0.01))
    # After the profiler stopped: rounds 3 and 4; only round 3 has a
    # dispatch after it.  Its gap: emit 2 + reap 1 + caller 0.5 + the
    # next round's pump 1 = 4.5 ms.
    assert gaps.read(rec, {}) == pytest.approx(4.5)
    note = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert note["rounds"] == 1
    assert note["split_ms"] == pytest.approx(
        {"caller": 0.5, "emit_loop": 2.0, "pump": 1.0, "reap": 1.0})
    assert sum(note["split_ms"].values()) == pytest.approx(4.5)
    assert note["profiled"]["rounds"] == 1
    assert note["profiled"]["median_ms"] == pytest.approx(22.5)
    assert note["profiled"]["split_ms"]["emit_loop"] == pytest.approx(20.0)
    assert "anchor" not in note          # no profile to pair with
    # Without a profiler's stretch every round of the window counts.
    rec["profile_window"] = None
    assert gaps.read(rec, {}) == pytest.approx(4.5)
    assert json.loads(capsys.readouterr().out.splitlines()[-1])["rounds"] == 3


def test_the_anchor_places_device_idle_time_in_program_spans(capsys):
    """The profile's clock runs 5 s (and a microsecond of scatter)
    ahead of the program's.  The device idles from each decode step's
    end to the next round's chunk: that time lands in emit_loop, reap,
    caller and pump, as the spans say."""
    gaps = reader("span_gaps")
    rounds, t = [], 10.0
    for i in range(3):
        spans, end = one_round(t, 100 * (i + 1), kv_live=1, chunks=1)
        rounds.append(spans)
        t = end + 0.0005
    off = 5e9
    steps = [s for r in rounds for s in r if s["name"] == "serving.step"]
    host = [[f"$lanes.py:1218 _dispatch_step", int(s["t0"] * 1e9 + off) + j * 1000,
             10] for j, s in enumerate(steps)]
    ops = []
    for r in rounds:
        for s in r:
            if s["name"] in ("serving.step", "serving.admit_chunk"):
                ops.append(["%fusion.1 = f32[] fusion()",
                            int(s["t0"] * 1e9 + off), int(s["dur"] * 1e9)])
    rec = record(rounds, (9.0, 11.0), (9.5, 10.9), trace={"events": {
        "devices": {"/device:TPU:0": {"ops": ops, "modules": []}},
        "host": host}})
    assert gaps.read(rec, {}) is None      # no round after the profiler
    note = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert note["anchor"]["pairs"] == 3
    assert note["anchor"]["offset_ns"] == pytest.approx(off + 1000, abs=1)
    assert note["anchor"]["scatter_us"] == pytest.approx(1.0, abs=0.01)
    # Two idle gaps of 4.5 ms (shifted by the anchor's 1 us).
    idle = note["device_idle_s"]
    assert sum(idle.values()) == pytest.approx(0.009, abs=1e-6)
    assert idle["emit_loop"] == pytest.approx(0.004, abs=5e-6)
    assert idle["caller"] == pytest.approx(0.001, abs=5e-6)
    # One frame fewer than spans (the profiler stopped inside a step):
    # the frames still find their run of spans.
    rec["trace"]["events"]["host"].pop()
    gaps.read(rec, {})
    note = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert note["anchor"]["pairs"] == 2
    assert note["anchor"]["offset_ns"] == pytest.approx(off + 500, abs=1)
    # More frames than spans: nothing to lay them against, no anchor.
    rec["trace"]["events"]["host"] = host + host
    gaps.read(rec, {})
    assert "anchor" not in json.loads(capsys.readouterr().out.splitlines()[-1])


def test_span_fields_means_over_the_decoding_rounds_of_the_window():
    fields = reader("span_fields")
    rounds, t = [], 10.0
    for i, (kv, chunks, idle) in enumerate([
            (100, 1, False), (200, 2, False), (300, 0, False),
            (0, 7, True),          # idle: no decode step, left out
    ]):
        extra = {"idle": True} if idle else {}
        spans, end = one_round(t, 100 * (i + 1), kv_live=kv, chunks=chunks,
                               **extra)
        rounds.append(spans)
        t = end
    before, _ = one_round(1.0, 900, kv_live=10 ** 6, chunks=50)
    rec = record(rounds + [before], (9.0, 11.0))
    chunks = {"span": "serving.round", "field": "chunks"}
    assert fields.read(rec, chunks) == pytest.approx(1.0)
    assert fields.read(rec, dict(chunks, field="kv_live",
                                 per="lanes*max_len")) == pytest.approx(50.0)
    with pytest.raises(ValueError):
        fields.read(rec, dict(chunks, per="lanes"))


@pytest.mark.parametrize("name,args", [
    ("span_gaps", {}),
    ("span_fields", {"span": "serving.round", "field": "kv_live",
                     "per": "lanes*max_len"}),
])
def test_nothing_to_read_is_none(name, args, capsys):
    """The parent commit's program has ``serving.step`` and the
    admission spans but no round: the metric is left out, nothing
    raises."""
    old = [span("serving.admit_chunk", 1.0, 0.001, 1, bucket=512),
           span("serving.step", 1.001, 0.03, 2, n=1),
           span("serving.step", 1.04, 0.03, 3, n=1)]
    for rec in (record([old], (0.0, 2.0), (1.0, 1.5)), record([], (0.0, 2.0)),
                {"window": (0.0, 2.0), "lanes": 4, "max_len": 100}):
        assert reader(name).read(rec, args) is None


RECORDED = (8, 0.018717)
KERNELS = {"flash_fwd": {"fwd": 2}, "flash_bwd_dq": {"dq": 1},
           "flash_bwd_dkv": {"dkv": 1}}


def kernel_pattern(kernel):
    """The accepted ``flash_attn_roofline`` pattern narrowed to one
    kernel's own name, from the wanted metric file; its ``calls`` are
    that kernel's part of the accepted file's."""
    with open(os.path.join(HERE, "data", "wanted", "layer_metrics",
                           kernel + "_roofline.json")) as f:
        m = json.load(f)
    assert m["args"]["calls"] == KERNELS[kernel]
    assert (m["reader"], m["moves"], m["layer"]) == (
        "kernel_roofline", "train_tok_s_chip", "kernels")
    return m["args"]["pattern"]


def test_kernel_patterns_tell_the_three_kernels_apart():
    with open(os.path.join(BENCH, "layer_metrics",
                           "flash_attn_roofline.json")) as f:
        accepted = json.load(f)["args"]
    assert accepted["calls"] == {k: v for c in KERNELS.values()
                                 for k, v in c.items()}
    label = ('%{}.{} = f32[48,4096,128]{{2,1,0}} custom-call(f32[8]{{0}} '
             '%flash_fwd.9), custom_call_target="tpu_custom_call"')
    ops = [[label.format(k, i), 1000 * i, 100 * (j + 1)]
           for j, k in enumerate(KERNELS) for i in range(j + 1)]
    # An operation that only READS a kernel's result is not the kernel.
    ops.append(["%fusion.3 = f32[8]{0} fusion(f32[8]{0} %flash_fwd.9)", 0, 7])
    ev = {"devices": {"/device:TPU:0": {"ops": ops, "modules": []}}}
    match = lambda p: trace_reduce.matching(ev, p, "ops", text=True)
    assert len(match(accepted["pattern"])) == 6     # all calls together
    assert match(kernel_pattern("flash_fwd")) == pytest.approx([1e-7])
    assert match(kernel_pattern("flash_bwd_dq")) == pytest.approx([2e-7] * 2)
    assert match(kernel_pattern("flash_bwd_dkv")) == pytest.approx([3e-7] * 3)
    assert trace_reduce.op_group(ops[0][0]) == "mosaic:flash_fwd"


def test_flash_attn_roofline_reads_the_recorded_trace_as_before():
    """The accepted pattern over the recorded fsdp4 trace (PR 23, its
    kernels still named ``shard_map.N``): 8 Mosaic calls, 18.7 ms; the
    narrowed patterns find none there, so a reader on the parent commit
    leaves the three metrics out."""
    ev = trace_reduce.load_sample(os.path.join(
        HERE, "data", "trace_sample_fsdp4.json.gz"))
    with open(os.path.join(BENCH, "layer_metrics",
                           "flash_attn_roofline.json")) as f:
        pattern = json.load(f)["args"]["pattern"]
    durs = trace_reduce.matching(ev, pattern, "ops", text=True)
    assert (len(durs), round(sum(durs), 6)) == RECORDED
    for k in KERNELS:
        assert trace_reduce.matching(ev, kernel_pattern(k), "ops",
                                     text=True) == []


def test_rehearsal_cell_reports_the_round_metrics(tree):
    # Long enough for rounds after the profiler has stopped.
    p, out = run_cell(tree, "tiny.serve.rounds", trace=1, seconds=4)
    assert p.returncode == 0, p.stderr[-3000:]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"tiny.kv_used", "tiny.step_gap", "tiny.kv_live",
                      "tiny.chunks_per_round"}
    # Two counts of one thing: the driver's leaves out a prompt until
    # its first token, the program's each lane's newest token (it is
    # the next step's input) — a few per cent apart at this size.
    assert 0 < m["tiny.kv_live"] <= 100
    assert m["tiny.kv_live"] == pytest.approx(m["tiny.kv_used"], rel=0.2)
    assert 0 < m["tiny.chunks_per_round"] <= 2 and m["tiny.step_gap"] > 0
    (note,) = [json.loads(ln) for ln in p.stdout.splitlines()
               if ln.startswith('{"note": "span_gaps"')]
    assert {"emit_loop", "reap", "pump", "caller"} <= set(note["split_ms"])
    assert note["profiled"]["rounds"] > 0 < note["rounds"]
    # The Python tracer's _dispatch_step frames pair with the program's
    # serving.step spans: one clock, to microseconds.
    assert note["anchor"]["pairs"] > 0
    assert note["anchor"]["scatter_us"] < 1000


def test_wanted_tree_adds_six_metrics_and_edits_nothing_else(
        tmp_path, monkeypatch):
    """What the ``benchmark`` PR asked for in PERF.md would leave:
    ``BENCHMARK.json`` as committed plus six ``per_layer`` entries, each
    in the cells ``data/wanted/per_layer.json`` names."""
    root = wanted.build(str(tmp_path / "tree"))
    monkeypatch.setattr(benchmark_json, "ROOT", root)
    built = benchmark_json.build()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        committed = json.load(f)
    have = {m["name"] for m in committed["per_layer"]}
    new = [m for m in built["per_layer"] if m["name"] not in have]
    assert {k: v for k, v in built.items() if k != "per_layer"} \
        == {k: v for k, v in committed.items() if k != "per_layer"}
    assert [m for m in built["per_layer"] if m["name"] in have] \
        == committed["per_layer"]
    with open(os.path.join(HERE, "data", "wanted", "per_layer.json")) as f:
        cells = json.load(f)
    assert {m["name"]: m["workloads"] for m in new} == {
        n: [c for c in cells if n in cells[c]]
        for names in cells.values() for n in names}
    assert len(new) == 6
    assert {m["layer"] for m in new} == {"engine loop", "KV store",
                                         "admission", "kernels"}
