"""The reader over what the engine says it dispatched
(``data/tiny/readers/span_dispatch.py``): on a simulated engine whose
every time is known, on a committed cut of a chip run of
``sc1b.serve.batch`` (device events and the obs records of the same
stretch, no host frame in it), end to end through the harness in a
rehearsal cell, and in the tree a ``benchmark`` PR would leave
(``wanted_dispatch.py``: a cell names its per-layer metrics, so
reporting these in the accepted cells is an edit to those cells' files).
"""

import gzip
import importlib.util
import json
import os

import pytest

import benchmark_json
import wanted_dispatch
from conftest import HERE, REPO, run_cell

DECODE, ADMIT = "jit_step_n_p", "jit__admit"
OFF = 5_000_000_000          # profile clock = program clock x 1e9 + OFF
LATENCY = 50e-6              # a blocked read returns this long after the end


def reader():
    spec = importlib.util.spec_from_file_location(
        "span_dispatch", os.path.join(HERE, "data", "tiny", "readers",
                                      "span_dispatch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class NoHost(dict):
    """The trace's events; reading the Python tracer's frames fails."""

    def __getitem__(self, key):
        assert key != "host", "the reader read events['host']"
        return super().__getitem__(key)

    def get(self, key, default=None):
        assert key != "host", "the reader read events['host']"
        return super().get(key, default)


def simulate(chunks, host_pause=(), step_s=0.006, chunk_s=0.009):
    """An engine with one round in flight, every time known: round k
    pumps (0.2 ms), dispatches a 512-wide chunk where ``chunks[k]`` (1
    ms), dispatches decode step k + 1 (0.5 ms), reads step k (blocked
    until ``LATENCY`` after the device ends it), emits (0.4), reaps
    (0.1); the caller takes 0.3 ms between two rounds, and
    ``host_pause[k]`` seconds more before round k.  The device runs the
    launches in order, each as soon as it is free and was dispatched.
    Returns the record and what the sim knows."""
    spans, modules, ops, ids = [], [], [], iter(range(1, 10 ** 6))
    truth = {"lead": [], "device_idle": 0.0}
    h = free = 10.0
    ends = {}

    def span(name, t0, dur, parent=None, **fields):
        spans.append({"kind": "span", "name": name, "t0": t0, "dur": dur,
                      "id": next(ids), "parent": parent, "depth": 0,
                      "fields": fields})
        return spans[-1]

    def launch(name, at, dur):
        nonlocal free
        start = max(free, at)
        truth["device_idle"] += start - free if modules else 0.0
        for out, label in ((modules, f"{name}(123)"),
                           (ops, "%fusion.1 = f32[] fusion()")):
            out.append([label, int(round(start * 1e9)) + OFF,
                        int(round(dur * 1e9))])
        free = start + dur
        return start

    for k, chunk in enumerate(chunks):
        h += dict(host_pause).get(k, 0.0)
        rnd = span("serving.round", h, 0.0)
        span("serving.pump", h, 0.0002, rnd["id"])
        h += 0.0002
        if chunk:
            span("serving.admit_chunk", h, 0.001, rnd["id"], bucket=512,
                 program=ADMIT)
            h += 0.001
            launch(ADMIT, h, chunk_s)
        span("serving.step", h, 0.0005, rnd["id"], n=1, seq=k + 1,
             program=DECODE)
        h += 0.0005
        start = launch(DECODE, h, step_s)
        ends[k + 1] = start + step_s
        truth["lead"].append(start - h)
        waited = 0.0
        if k:
            back = max(h, ends[k] + LATENCY)
            waited = back - h
            span("serving.collect", h, waited, rnd["id"], seq=k,
                 wait_ms=waited * 1e3)
            h = back
            span("serving.emit_loop", h, 0.0004, rnd["id"])
            h += 0.0004
        span("serving.reap", h, 0.0001, rnd["id"])
        h += 0.0001
        rnd["dur"] = h - rnd["t0"]
        rnd["fields"].update(tokens=1, host_ms=(rnd["dur"] - waited) * 1e3)
        h += 0.0003
    events = NoHost(devices={"/device:TPU:0": {"ops": ops,
                                               "modules": modules}},
                    host=[["$lanes.py:1 _dispatch_step", 0, 1]])
    record = {"obs_events": spans, "window": (9.0, h + 1.0),
              "profile_window": (9.5, h + 0.5), "trace": {"events": events}}
    return record, truth


def note_of(capsys):
    (line,) = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith('{"note": "span_dispatch"')]
    return json.loads(line)


CHUNKS = [1, 1, 0, 1, 0, 1, 1, 0, 0, 1, 1, 1, 0, 1, 1, 1]


def test_the_four_values_of_a_simulated_engine(capsys):
    """The device sets the pace (a round's 15 ms of programs against
    the host's 2.5): every read is blocked, so the offset is the
    clock's less the readback latency with no scatter; the lead is what
    the sim says each launch waited; the device's only idle time is
    before its first programs reach it."""
    mod = reader()
    rec, truth = simulate(CHUNKS)
    assert mod.read(rec, {"stat": "decode_ms"}) == pytest.approx(6.0)
    n = sum(map(bool, CHUNKS))
    assert mod.read(rec, {"stat": "admit_ms_per_ktok"}) == pytest.approx(
        9.0 * n / (512 * n / 1000))
    lead = mod.read(rec, {"stat": "dispatch_lead_ms"})
    note = note_of(capsys)
    assert note["offset_ns"] == pytest.approx(OFF - LATENCY * 1e9, abs=2)
    assert note["scatter_us"] == pytest.approx(0, abs=0.01)
    # the last step is dispatched and never read: it stands at the edge
    assert note["pairs"] == note["blocked"] == len(CHUNKS) - 1
    assert note["next_us"] > 1000 and note["bound_us"] > 0
    want = sorted(truth["lead"][:-1])[(len(CHUNKS) - 1) // 2]
    assert lead == pytest.approx((want + LATENCY) * 1e3, abs=1e-3)
    assert lead > 10                     # a round and more in the queue
    assert sum(note["device_idle_s"].values()) == pytest.approx(
        truth["device_idle"], abs=1e-6)
    # the profiler covers the whole run: no round after it
    assert mod.read(rec, {"stat": "host_share"}) is None
    rec["profile_window"] = (9.0, 9.5)
    rounds = [s for s in rec["obs_events"] if s["name"] == "serving.round"]
    share = mod.read(rec, {"stat": "host_share"})
    assert 10 < share < 25
    host = sorted(r["fields"]["host_ms"] for r in rounds)
    assert share == pytest.approx(
        100 * (host[7] + host[8]) / 2 / 15.0, rel=0.2)


def test_device_idle_time_lands_in_the_span_the_host_was_in(capsys):
    """The caller pauses 40 ms before rounds 5 and 10: the device runs
    out of launches and waits — idle time that lands in ``caller``, in
    the program's names and with no Python frame — and the two reads
    after a pause find their tokens there (not blocked: they neither
    anchor the clocks nor break the join).  Where NO read blocks (a
    host that always comes late) there is nothing to anchor on, and no
    join."""
    mod = reader()
    rec, truth = simulate(CHUNKS, host_pause=[(5, 0.04), (10, 0.04)])
    assert mod.read(rec, {"stat": "dispatch_lead_ms"}) > 5
    note = note_of(capsys)
    assert note["offset_ns"] == pytest.approx(OFF - LATENCY * 1e9, abs=2)
    assert note["pairs"] == len(CHUNKS) - 1 > note["blocked"]
    idle = note["device_idle_s"]
    assert truth["device_idle"] > 0.04
    assert sum(idle.values()) == pytest.approx(truth["device_idle"],
                                               abs=1e-6)
    assert max(idle, key=idle.get) == "caller"
    assert idle["caller"] > 0.9 * truth["device_idle"]
    late, _ = simulate([1] * 12, host_pause=[(k, 0.02) for k in range(12)],
                       step_s=0.0004, chunk_s=0.0002)
    assert mod.read(late, {"stat": "dispatch_lead_ms"}) is None


@pytest.mark.parametrize("shift", [1, -1])
def test_a_seq_that_names_another_launch_is_no_join(shift, capsys):
    """Every ``serving.collect`` says one launch later (earlier) than it
    read: the executions still line up with the reads, but the
    ``serving.step`` of that number is another round's, and the
    admission programs between two executions are not the admission
    spans between those steps."""
    mod = reader()
    rec, _ = simulate(CHUNKS)
    for s in rec["obs_events"]:
        if s["name"] == "serving.collect":
            s["fields"]["seq"] += shift
    assert mod.read(rec, {"stat": "dispatch_lead_ms"}) is None
    assert "span_dispatch" not in capsys.readouterr().out
    # what needs no join still reads
    assert mod.read(rec, {"stat": "decode_ms"}) == pytest.approx(6.0)


def test_a_program_is_found_by_its_whole_name():
    mod = reader()
    rec, _ = simulate(CHUNKS)
    dev = rec["trace"]["events"]["devices"]["/device:TPU:0"]
    dev["modules"] += [["jit_step_n_p_warm(7)", OFF, 10 ** 9],
                       ["jit__admit_cont(8)", OFF, 10 ** 9]]
    assert mod.read(rec, {"stat": "decode_ms"}) == pytest.approx(6.0)
    n = sum(map(bool, CHUNKS))
    assert mod.read(rec, {"stat": "admit_ms_per_ktok"}) == pytest.approx(
        9.0 * n / (512 * n / 1000))


@pytest.mark.parametrize("stat", ["decode_ms", "admit_ms_per_ktok",
                                  "host_share", "dispatch_lead_ms"])
def test_nothing_to_read_is_none(stat, capsys):
    """The parent commit's program says neither ``program`` nor ``seq``
    nor ``host_ms``: each metric is left out, nothing raises — nor on a
    run with no trace, nor on one with no span at all."""
    mod = reader()
    rec, _ = simulate(CHUNKS)
    for s in rec["obs_events"]:
        for k in ("program", "seq", "host_ms"):
            s["fields"].pop(k, None)
    bare = {"window": (0.0, 2.0), "profile_window": None}
    for r in (rec, dict(rec, trace=None), bare, dict(bare, obs_events=[])):
        assert mod.read(r, {"stat": stat}) is None
    assert capsys.readouterr().out == ""
    with pytest.raises(ValueError):
        mod.read(rec, {"stat": "no_such_stat"})


# ------------------------------------------------ the committed chip cut

SAMPLE = os.path.join(HERE, "data", "span_dispatch_sample.json.gz")


@pytest.fixture(scope="module")
def sample():
    """``sc1b.serve.batch`` on the chip (PR 35): a
    ``trace_reduce.save_sample`` cut of the profile (its ``host`` list
    EMPTIED, its operations' labels cut to their own names) and the obs
    records of the same stretch and of a stretch after the profiler;
    ``expected``: what the reader printed there."""
    with gzip.open(SAMPLE, "rt") as f:
        data = json.load(f)
    assert data["trace"]["events"]["host"] == []
    data["trace"]["events"] = NoHost(data["trace"]["events"])
    return data


@pytest.mark.parametrize("stat", ["decode_ms", "admit_ms_per_ktok",
                                  "host_share", "dispatch_lead_ms"])
def test_the_chip_cut_reads_as_recorded(sample, stat, capsys):
    mod = reader()
    want = sample["expected"]
    assert mod.read(sample, {"stat": stat}) == pytest.approx(
        want[stat], rel=1e-9)
    if stat == "dispatch_lead_ms":
        note = note_of(capsys)
        assert note["offset_ns"] == pytest.approx(want["offset_ns"], abs=1)
        assert note["pairs"] == want["pairs"]
        assert note["scatter_us"] < 500 and note["bound_us"] > 0


def test_the_chip_cut_by_declared_program_is_the_cut_by_name(sample):
    """What ``decode_step_ms`` and ``prefill_ms_per_ktok`` find by a
    pattern over the name, the spans' ``program`` finds by the name."""
    import trace_reduce

    mod = reader()
    load = lambda n: json.load(open(os.path.join(
        REPO, "benchmarks", "layer_metrics", n + ".json")))["args"]
    spans = mod.serving_spans(sample)
    for metric, names in (("decode_step_ms", (mod.STEP,)),
                          ("prefill_ms_per_ktok", mod.ADMIT)):
        by_name = trace_reduce.matching(sample["trace"]["events"],
                                        load(metric)["pattern"], "modules")
        declared = mod.executions(sample, mod.declared(spans, names))
        assert sorted(by_name) == pytest.approx(
            sorted(d / 1e9 for _, d in declared))
        assert len(declared) > 3


@pytest.mark.parametrize("shift", [1, -1])
def test_the_chip_cut_with_a_shifted_seq_is_no_join(sample, shift):
    rec = json.loads(json.dumps({k: v for k, v in sample.items()
                                 if k != "trace"}))
    rec["trace"] = sample["trace"]
    for s in rec["obs_events"]:
        if s.get("name") == "serving.collect":
            s["fields"]["seq"] += shift
    assert reader().read(rec, {"stat": "dispatch_lead_ms"}) is None


# ------------------------------------------- through the harness, on the CPU


def test_rehearsal_cell_runs_the_reader_end_to_end(tree):
    """The CPU's profile has no device plane, so the three metrics that
    read one are left out of the line (and the run does not fail);
    ``host_ms`` against the period needs none."""
    p, out = run_cell(tree, "tiny.serve.dispatch", trace=1, seconds=4)
    assert p.returncode == 0, p.stderr[-3000:]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"tiny.kv_used", "tiny.span_host_share"}
    assert 0 < m["tiny.span_host_share"] <= 100


def test_wanted_tree_adds_the_four_metrics_last_and_edits_nothing_else(
        tmp_path, monkeypatch):
    """What the ``benchmark`` PR asked for in PERF.md §7 would leave:
    the four names appended LAST to the four serving cells'
    ``per_layer`` lists, and in ``BENCHMARK.json`` four entries after
    every accepted one (the prefix ``step_span_`` sorts there)."""
    root = wanted_dispatch.build(str(tmp_path / "tree"))
    with open(os.path.join(wanted_dispatch.DATA, "per_layer.json")) as f:
        cells = json.load(f)
    assert len(cells) == 4
    for cell, names in cells.items():
        with open(os.path.join(root, "workloads", cell + ".json")) as f:
            now = json.load(f)
        with open(os.path.join(REPO, "benchmarks", "workloads",
                               cell + ".json")) as f:
            was = json.load(f)
        assert now["per_layer"][-4:] == names
        assert now["per_layer"][:len(was["per_layer"])] == was["per_layer"]
        assert {k: v for k, v in now.items() if k != "per_layer"} \
            == {k: v for k, v in was.items() if k != "per_layer"}
    monkeypatch.setattr(benchmark_json, "ROOT", root)
    built = benchmark_json.build()["per_layer"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        accepted = [m["name"] for m in json.load(f)["per_layer"]]
    new = [m for m in built if m["name"].startswith("step_span_")]
    assert [m["name"] for m in new] == sorted(names) and len(new) == 4
    last_accepted = max(i for i, m in enumerate(built)
                        if m["name"] in accepted)
    assert all(built.index(m) > last_accepted for m in new)
    for m in new:
        assert set(m["workloads"]) == set(cells)
        assert m["moves"] == "serve_tok_s"
    assert {m["name"]: (m["layer"], m["source"]) for m in new} == {
        "step_span_decode_ms": ("model step", "device_trace"),
        "step_span_admit_ms_per_ktok": ("model step", "device_trace"),
        "step_span_host_share": ("engine loop", "program_span"),
        "step_span_dispatch_lead_ms": ("engine loop", "program_span")}
