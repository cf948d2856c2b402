"""Observability subsystem (distkeras_tpu/obs, docs/observability.md):
registry semantics, span/JSONL round-trip, the zero-overhead-when-
disabled contract (no trace file, no callbacks in jit, no extra
compiles), and end-to-end trainer + serving traces rendered by the
run-report machinery — the tier-1 obs smoke.
"""

import json
import os

import jax
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import obs
from distkeras_tpu.obs.metrics import (MetricsRegistry,
                                        percentile_from_buckets)
from distkeras_tpu.obs.report import (build_report, load_report,
                                       render_compare, render_report)
from distkeras_tpu.obs.trace import EventTrace, read_trace
from helpers import serve_cfg, spec_draft_cfg, toy_params

CFG = serve_cfg(max_len=16, rope=False)


def tokens(n=32, s=16, seed=0):
    return np.random.default_rng(seed).integers(
        0, 64, (n, s + 1)).astype(np.int32)


# ------------------------------------------------------------- registry


def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("requests", "total requests")
    c.inc()
    c.inc(2, status="ok")
    c.inc(1, status="timeout")
    assert c.value() == 1
    assert c.value(status="ok") == 2
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)

    g = reg.gauge("depth")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value() == 2
    g.set(7, lane="a")
    assert g.value(lane="a") == 7

    h = reg.histogram("lat", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()["lat"]["series"][0]
    assert snap["count"] == 4 and snap["counts"] == [1, 1, 1, 1]
    assert snap["min"] == 0.005 and snap["max"] == 5.0
    # Bucket-interpolated percentiles land inside the winning bucket.
    assert 0.1 < percentile_from_buckets(snap, 0.7) <= 1.0

    # One name, one kind.
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("requests")
    with pytest.raises(ValueError, match="edges"):
        reg.histogram("lat", buckets=(1.0, 2.0))


def test_snapshot_isolation_and_render_text():
    reg = MetricsRegistry()
    reg.counter("a").inc(5)
    snap = reg.snapshot()
    reg.counter("a").inc(100)
    assert snap["a"]["series"][0]["value"] == 5  # decoupled
    text = reg.render_text()
    assert "# TYPE a counter" in text and "a 105.0" in text
    reg.histogram("h_s", buckets=(0.1, 1.0)).observe(0.05, kind="x")
    text = reg.render_text()
    assert 'h_s_bucket{kind="x",le="0.1"} 1' in text
    assert 'h_s_count{kind="x"} 1' in text


# ------------------------------------------------------- trace roundtrip


def test_span_nesting_jsonl_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with EventTrace(path, run_id="r1") as tr:
        with tr.span("outer", phase="a"):
            tr.event("ping", x=1)
            with tr.span("inner"):
                pass
        with tr.span("outer2"):
            pass
    recs = read_trace(path)
    assert recs[0]["kind"] == "meta" and recs[0]["run"] == "r1"
    spans = {r["name"]: r for r in recs if r["kind"] == "span"}
    # inner closed first (spans are written at exit) and nests under
    # outer; outer2 is a fresh root.
    assert spans["inner"]["parent"] == spans["outer"]["id"]
    assert spans["inner"]["depth"] == 1
    assert spans["outer"]["parent"] is None and spans["outer"]["depth"] == 0
    assert spans["outer2"]["parent"] is None
    assert spans["outer"]["dur"] >= spans["inner"]["dur"] >= 0
    ev = next(r for r in recs if r["kind"] == "event")
    assert ev["name"] == "ping" and ev["fields"] == {"x": 1}
    assert ev["span"] == spans["outer"]["id"]  # emitted inside outer
    # Torn final line (crashed writer) parses to the good prefix.
    with open(path, "a") as f:
        f.write('{"kind": "ev')
    assert read_trace(path) == recs


def test_records_stay_in_memory_until_flush_close_or_the_bound(
        tmp_path, monkeypatch):
    """Recording serialises nothing: the file grows at flush(), at
    close() and when a record arrives to a buffer past its bound — a
    crash loses at most that buffer, and what is in the file is whole
    lines."""
    kinds = lambda p: [r["kind"] for r in read_trace(p)]
    path = str(tmp_path / "b.jsonl")
    tr = EventTrace(path)
    assert kinds(path) == ["meta"]            # the run is named at once
    with tr.span("s"):
        tr.event("e")
    assert kinds(path) == ["meta"]
    tr.flush()
    assert kinds(path) == ["meta", "event", "span"]
    tr.event("late")
    tr.close()
    assert kinds(path)[-1] == "event"
    tr.event("after close")                   # dropped, not an error
    tr.flush()
    assert len(kinds(path)) == 4

    monkeypatch.setattr(EventTrace, "FLUSH_RECORDS", 3)
    path = str(tmp_path / "c.jsonl")
    with EventTrace(path) as tr:
        tr.event("a")
        tr.event("b")
        assert kinds(path) == ["meta"]
        tr.event("c")                         # the third record
        assert len(kinds(path)) == 4
        with open(path) as f:
            assert f.read().endswith("\n")


def test_session_singleton_and_final_metrics_record(tmp_path):
    path = str(tmp_path / "s.jsonl")
    with obs.session(trace_path=path) as sess:
        obs.count("x")
        with pytest.raises(RuntimeError, match="already active"):
            obs.enable()
        assert obs.active() is sess
    assert obs.active() is None
    recs = read_trace(path)
    metrics = [r for r in recs if r["kind"] == "metrics"]
    assert len(metrics) == 1
    assert metrics[0]["data"]["x"]["series"][0]["value"] == 1


# --------------------------------------------------- disabled is free


def test_noop_mode_writes_nothing(tmp_path):
    assert obs.active() is None
    before = set(os.listdir(tmp_path))
    obs.count("a")
    obs.gauge("b", 1)
    obs.observe("c", 0.5)
    obs.event("d")
    with obs.span("e"):
        pass
    assert set(os.listdir(tmp_path)) == before
    # The disabled span is one shared null context: no allocation.
    assert obs.span("x") is obs.span("y")


def test_disabled_serving_round_is_free(tmp_path, monkeypatch):
    """A serving round with telemetry off: every span of the round is
    the one shared null context, the O(lanes) pass that fills the
    round's counts never runs, and no file appears."""
    from distkeras_tpu.serving.engine import _LaneEngine

    assert obs.active() is None
    null = obs.span("serving.round")
    assert all(obs.span(n) is null for n in (
        "serving.pump", "serving.step", "serving.emit_loop",
        "serving.reap", "serving.admit_chunk"))

    def never(*a, **k):
        raise AssertionError("round counts taken with telemetry off")

    monkeypatch.setattr(_LaneEngine, "_close_round", never)
    monkeypatch.chdir(tmp_path)
    params = toy_params(CFG)
    eng = dk.ContinuousBatcher(params, CFG, lanes=2, max_queue=2,
                               prompt_buckets=(8,))
    rid = eng.enqueue(np.arange(5), 3)
    # A token comes back from the call after its dispatch: the first
    # call returns none, the fourth has nothing left to dispatch.
    assert eng.step() == {}
    assert eng.step() and eng.step() and eng.step()
    assert eng.take(rid).ok and eng.step() == {}
    # The counter behind the round's ``chunks`` still follows the
    # dispatches: a session enabled later starts from a true count.
    assert eng._admit_programs == 0
    assert os.listdir(tmp_path) == []


def test_no_host_callbacks_in_jit_with_obs_enabled(tmp_path):
    """The graph lint's host-callback rule over the REAL train step,
    telemetry ENABLED: obs never reaches inside a jitted program, so
    enabling it cannot add device->host round-trips (or change
    compile/comm budgets)."""
    from distkeras_tpu.analysis import ir_lint

    with obs.session(trace_path=str(tmp_path / "lint.jsonl")):
        t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=8)
        (spec,) = t.traced_for_analysis()
        findings, _ = ir_lint.lint_trace(spec, compile_census=False)
    assert not [f.format() for f in findings
                if f.rule == "host-callback"]
    assert not [f.format() for f in findings if f.gating]


def test_obs_enabled_adds_no_compiles():
    """Enabling telemetry must not change what compiles: the same
    trainer session recompiles no MORE programs with a session active
    than without (the PR 3 compile-budget contract extends to obs)."""
    import jax.monitoring

    compiles = {"n": 0}

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)

    def run():
        t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=8)
        t.train(tokens())
        return t.history

    start = compiles["n"]
    h_plain = run()
    plain = compiles["n"] - start
    with obs.session():
        start = compiles["n"]
        h_obs = run()
        with_obs = compiles["n"] - start
    assert with_obs <= plain, (with_obs, plain)
    np.testing.assert_allclose(h_obs, h_plain, rtol=1e-6)


# ----------------------------------------------------------- end to end


def test_trainer_end_to_end_trace(tmp_path):
    path = str(tmp_path / "train.jsonl")
    with obs.session(trace_path=path) as sess:
        t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=8)
        t.train(tokens())
    recs = read_trace(path)
    span_names = {r["name"] for r in recs if r["kind"] == "span"}
    assert {"train.h2d", "train.step"} <= span_names
    snap = sess.registry.compact()
    assert snap["train.rounds{trainer=LMTrainer}"] == len(t.history)
    assert snap["train.loss{trainer=LMTrainer}"] == pytest.approx(
        t.history[-1])
    assert snap["train.attn_live_tile_share"
                "{kernel=flash_fwd,trainer=LMTrainer}"] == 1.0
    rep = load_report(path)
    assert rep["phases"]["train.step"]["count"] == len(t.history)
    text = render_report(rep)
    assert "train.step" in text and "phase breakdown" in text


def test_trainer_reports_the_live_tile_share_of_packed_rows(monkeypatch):
    """``train.attn_live_tile_share{kernel}``: the kernels' own
    predicate (``ops.attention.live_tile_share``) over the call's
    segments, at the tiles each segmented launch skips by — here set
    for the test over rows of 256 (a toy row is one tile otherwise)."""
    from distkeras_tpu.ops import attention

    tiles = {"flash_fwd": (128, 256), "flash_bwd_dq": (128, 128),
             "flash_bwd_dkv": (128, 128)}
    monkeypatch.setattr(attention, "segment_tiles_for",
                        lambda seq_len: tiles)
    cfg = serve_cfg(max_len=256, rope=False)
    rows = tokens(n=8, s=256)
    segs = np.ones_like(rows)
    segs[:, 128:] = 2       # a document boundary on a tile's edge
    with obs.session() as sess:
        dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=8).train(
            rows, segments=segs)
    snap = sess.registry.compact()
    share = {k: snap["train.attn_live_tile_share"
                     f"{{kernel={k},trainer=LMTrainer}}"] for k in tiles}
    for kernel, tile in tiles.items():
        assert share[kernel] == attention.live_tile_share(segs[:, :-1], *tile)
    # 128 x 128: the tile under the diagonal is dead, 2 of 3 computed;
    # a k tile as wide as the row holds a pair whatever the documents.
    assert share == {"flash_fwd": 1.0, "flash_bwd_dq": pytest.approx(2 / 3),
                     "flash_bwd_dkv": pytest.approx(2 / 3)}


def test_serving_end_to_end_trace_and_compare(tmp_path):
    cfg = serve_cfg(rope=False)
    params = toy_params(cfg)
    rng = np.random.default_rng(0)

    def serve(path, n_requests):
        with obs.session(trace_path=path):
            eng = dk.ContinuousBatcher(params, cfg, lanes=2,
                                       max_queue=4)
            rids = [eng.enqueue(rng.integers(0, 64, (5,)), 6)
                    for _ in range(n_requests)]
            while eng.running() or eng.queued:
                eng.step()
            res = eng.results()
            assert all(res[r].ok for r in rids)

    serve(str(tmp_path / "a.jsonl"), 3)
    serve(str(tmp_path / "b.jsonl"), 2)
    rep = load_report(str(tmp_path / "a.jsonl"))
    assert rep["scalars"]["serving.requests{status=ok}"] == 3
    lat = rep["latency"]["serving.request_s{status=ok}"]
    assert lat["count"] == 3
    assert lat["p50"] is not None and lat["p99"] >= lat["p50"] > 0
    assert "serving.step" in rep["phases"]
    rep_b = load_report(str(tmp_path / "b.jsonl"))
    out = render_compare(rep, rep_b)
    assert "serving.requests{status=ok}" in out
    assert "serving.step" in out and "->" in out
    assert rep_b["scalars"]["serving.requests{status=ok}"] == 2


def test_serving_rejects_and_deadline_metrics(tmp_path):
    cfg = serve_cfg(rope=False)
    params = toy_params(cfg)
    rng = np.random.default_rng(0)
    clock = [0.0]
    with obs.session() as sess:
        eng = dk.ContinuousBatcher(params, cfg, lanes=1, max_queue=1,
                                   clock=lambda: clock[0])
        eng.enqueue(rng.integers(0, 64, (3,)), 4)
        # Queued with a deadline that expires before a lane frees.
        rid = eng.enqueue(rng.integers(0, 64, (3,)), 4, ttl=1.0)
        with pytest.raises(dk.QueueFull):
            eng.enqueue(rng.integers(0, 64, (3,)), 4)
        clock[0] = 5.0
        res = eng.shutdown()
        assert res[rid].timed_out
    snap = sess.registry.compact()
    assert snap["serving.rejected{reason=queue_full}"] == 1
    assert snap["serving.deadline_misses"] == 1
    assert snap["serving.requests{status=timeout}"] == 1


def test_chaos_and_supervisor_events_in_trace(tmp_path):
    """Satellite: fault injections and Supervisor restarts ride the
    obs event trace — the machine-readable fault/recovery timeline."""
    import tempfile

    from helpers import make_blobs, make_mlp

    x, y = make_blobs(n=64, seed=0)
    ds = dk.Dataset.from_arrays(x, y)
    path = str(tmp_path / "chaos.jsonl")
    with obs.session(trace_path=path):
        with tempfile.TemporaryDirectory() as d:
            t = dk.SingleTrainer(
                make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="sgd", learning_rate=0.05,
                batch_size=16, num_epoch=2,
                checkpoint_dir=os.path.join(d, "c"),
                checkpoint_every=1, checkpoint_backend="pickle")
            sup = dk.Supervisor(t, max_retries=2, backoff=0.01,
                                max_backoff=0.01, jitter=0.0, seed=0)
            with dk.FaultPlan(0).fail("train.round", at=3):
                sup.run(ds)
    events = [r for r in read_trace(path) if r["kind"] == "event"]
    names = [e["name"] for e in events]
    assert "chaos.fault" in names
    fault = next(e for e in events if e["name"] == "chaos.fault")
    assert fault["fields"]["site"] == "train.round"
    attempts = [e for e in events if e["name"] == "supervisor.attempt"]
    assert [a["fields"]["outcome"] for a in attempts] == ["fault", "ok"]
    assert "supervisor.backoff" in names
    # Checkpoint persistence shows up as spans with durations.
    saves = [r for r in read_trace(path)
             if r["kind"] == "span" and r["name"] == "checkpoint.save"]
    assert saves and all(s["dur"] > 0 for s in saves)
    # The timeline renders (fault events included).
    text = render_report(build_report(read_trace(path)))
    assert "chaos.fault" in text


def test_speculative_accept_rate_counters():
    cfg = serve_cfg(rope=False)
    draft = spec_draft_cfg(rope=False)
    eng = dk.SpeculativeBatcher(
        toy_params(cfg),
        toy_params(draft, 1),
        cfg, draft, lanes=2, n_draft=2)
    prompt = np.random.default_rng(0).integers(0, 64, (4,)).astype(
        np.int32)
    with obs.session() as sess:
        lane = eng.submit(prompt, 6)
        while lane in eng.running():
            eng.step()
        eng.drain(lane)
    snap = sess.registry.compact()
    assert snap["serving.spec.proposed"] > 0
    assert 0 <= snap["serving.spec.accepted"] <= snap[
        "serving.spec.proposed"]
    assert snap["serving.requests{status=ok}"] == 1


def test_prefetch_and_devicefeed_metrics():
    from distkeras_tpu.data.prefetch import DeviceFeed, Prefetcher

    batches = [np.ones((8, 4), np.float32) for _ in range(4)]
    with obs.session() as sess:
        for _ in Prefetcher(iter(batches), depth=2):
            pass
        for item in DeviceFeed(iter(batches), depth=2):
            jax.block_until_ready(item)
    snap = sess.registry.compact()
    assert snap["data.h2d.items"] == 4
    assert snap["data.h2d.bytes"] == 4 * 8 * 4 * 4
    assert "data.prefetch.occupancy" in snap


def test_zero1_bucket_geometry_recorded():
    with obs.session() as sess:
        t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=8,
                         zero1=True)
        t.train(tokens())
    snap = sess.registry.compact()
    assert snap["zero1.buckets"] >= 1
    assert snap["zero1.pad_bytes"] == 0  # test model divides evenly
    # Exchange bytes == parameter bytes (the pad-free parity layout).
    pbytes = sum(np.prod(v.shape) * v.dtype.itemsize
                 for v in jax.tree.leaves(
                     jax.eval_shape(lambda: toy_params(CFG))))
    assert snap["zero1.exchange_bytes"] == pbytes


def test_obs_report_cli(tmp_path):
    import subprocess
    import sys

    path = str(tmp_path / "cli.jsonl")
    with obs.session(trace_path=path):
        with obs.span("train.step"):
            pass
        obs.event("marker", k=1)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "obs_report.py"),
         path], capture_output=True, text=True, timeout=120, cwd=root)
    assert r.returncode == 0, r.stderr
    assert "train.step" in r.stdout and "marker" in r.stdout
    r = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "obs_report.py"),
         path, "--compare", path, "--json"],
        capture_output=True, text=True, timeout=120, cwd=root)
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout)["new"]["phases"]["train.step"][
        "count"] == 1
