"""Live telemetry plane (round 11, docs/observability.md "Live
telemetry"): the /metrics scrape server, exposition-format
conformance under a strict mini-parser, the rolling-window SLO
engine, cluster federation, per-request trace propagation and the
request waterfall — plus the round-11 registry satellites (HELP
escaping, wire-name collision detection, compact() min/max).
"""

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import obs
from distkeras_tpu.obs.live import (HeartbeatHealth, TelemetryServer,
                                    merge_expositions)
from distkeras_tpu.obs.metrics import (MetricsRegistry, prom_name,
                                       windowed_percentiles)
from distkeras_tpu.obs.report import render_waterfall, request_waterfall
from distkeras_tpu.obs.slo import SloEngine, SloRule
from distkeras_tpu.obs.trace import EventTrace, read_trace, tail_trace
from distkeras_tpu.resilience.health import write_beat
from helpers import serve_cfg, spec_draft_cfg, toy_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = serve_cfg()


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode("utf-8")


# ------------------------------------- strict exposition mini-parser

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_SAMPLE_RE = re.compile(
    rf"^({_NAME})(?:\{{(.*)\}})? (-?(?:[0-9.]+(?:[eE][+-]?[0-9]+)?|"
    r"Inf)|\+Inf|NaN)$")


def _parse_labels(s: str) -> dict:
    """Parse a label body with full escape handling (round-trips the
    writer's backslash/quote/newline escaping)."""
    labels = {}
    i = 0
    while i < len(s):
        j = s.index("=", i)
        key = s[i:j]
        assert re.fullmatch(r"[a-zA-Z_][a-zA-Z0-9_]*", key), key
        assert s[j + 1] == '"', s
        k = j + 2
        val = []
        while True:
            c = s[k]
            if c == "\\":
                val.append({"\\": "\\", '"': '"', "n": "\n"}[s[k + 1]])
                k += 2
            elif c == '"':
                break
            else:
                val.append(c)
                k += 1
        labels[key] = "".join(val)
        k += 1
        if k < len(s):
            assert s[k] == ",", s
            k += 1
        i = k
    return labels


def parse_exposition(text: str) -> dict:
    """Strict Prometheus text-format parser: validates HELP/TYPE
    ordering, sample grammar, histogram `le` monotonicity (cumulative
    counts nondecreasing, +Inf last and == _count), _sum/_count
    presence.  Returns {family: {"type", "help", "samples":
    [(name, labels, value)]}}."""
    fams: dict = {}
    cur = None

    def family_of(name):
        if name in fams:
            return name
        for suf in ("_bucket", "_sum", "_count", "_total"):
            if name.endswith(suf) and name[: -len(suf)] in fams:
                return name[: -len(suf)]
        return name

    for line in text.splitlines():
        assert line == line.rstrip(), f"trailing whitespace: {line!r}"
        if not line:
            continue
        if line.startswith("# HELP "):
            rest = line[len("# HELP "):]
            name, _, help_text = rest.partition(" ")
            assert name not in fams, f"duplicate HELP for {name}"
            assert "\n" not in help_text
            fams[name] = {"type": None, "help": help_text, "samples": []}
            cur = name
            continue
        if line.startswith("# TYPE "):
            rest = line[len("# TYPE "):]
            name, _, kind = rest.partition(" ")
            assert kind in ("counter", "gauge", "histogram",
                            "summary", "untyped"), line
            if name in fams:
                assert fams[name]["type"] is None, \
                    f"duplicate TYPE for {name}"
                assert not fams[name]["samples"], \
                    f"TYPE after samples for {name}"
                fams[name]["type"] = kind
            else:
                fams[name] = {"type": kind, "help": None, "samples": []}
            cur = name
            continue
        assert not line.startswith("#"), f"unexpected comment: {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name, lab, value = m.group(1), m.group(2), m.group(3)
        fam = family_of(name)
        assert fam in fams and fams[fam]["type"] is not None, (
            f"sample {name} before its TYPE line")
        assert fam == cur, (
            f"sample {name} outside its family's block ({fam} != {cur})")
        labels = _parse_labels(lab) if lab else {}
        fams[fam]["samples"].append((name, labels, value))

    # Histogram invariants.
    for fam, info in fams.items():
        if info["type"] != "histogram":
            continue
        series: dict = {}
        sums, counts = set(), {}
        for name, labels, value in info["samples"]:
            rest = tuple(sorted((k, v) for k, v in labels.items()
                                if k != "le"))
            if name == fam + "_bucket":
                series.setdefault(rest, []).append(
                    (labels["le"], float(value)))
            elif name == fam + "_sum":
                sums.add(rest)
            elif name == fam + "_count":
                counts[rest] = float(value)
            else:
                raise AssertionError(f"stray sample {name} in "
                                     f"histogram {fam}")
        assert series, f"histogram {fam} has no buckets"
        for rest, buckets in series.items():
            assert rest in sums, f"{fam} missing _sum for {rest}"
            assert rest in counts, f"{fam} missing _count for {rest}"
            les = [le for le, _ in buckets]
            assert les[-1] == "+Inf", f"{fam}: +Inf bucket not last"
            edges = [float(le) for le in les[:-1]]
            assert edges == sorted(edges), f"{fam}: le not ascending"
            cums = [c for _, c in buckets]
            assert cums == sorted(cums), (
                f"{fam}: cumulative bucket counts decreased: {cums}")
            assert cums[-1] == counts[rest], (
                f"{fam}: +Inf bucket {cums[-1]} != _count "
                f"{counts[rest]}")
    return fams


# -------------------------------------------- registry satellites


def test_exposition_conformance_and_label_roundtrip():
    reg = MetricsRegistry()
    reg.counter("serving.requests", "total requests").inc(
        3, status="ok")
    reg.counter("serving.requests").inc(status='we"ird\\lab\nel')
    reg.gauge("queue.depth", "queued requests").set(2)
    reg.histogram("serving.request_s", "request latency").observe(
        0.03, status="ok")
    reg.histogram("serving.request_s").observe(7.0, status="timeout")
    fams = parse_exposition(reg.render_text())
    assert fams["serving_requests"]["type"] == "counter"
    assert fams["serving_requests"]["help"] == "total requests"
    # Label escaping round-trips through the strict parser.
    weird = [lab for _, lab, _ in fams["serving_requests"]["samples"]]
    assert {"status": 'we"ird\\lab\nel'} in weird
    assert fams["serving_request_s"]["type"] == "histogram"


def test_help_text_newline_is_escaped():
    reg = MetricsRegistry()
    reg.counter("a.b", "line one\nline two \\ slash").inc()
    text = reg.render_text()
    assert "# HELP a_b line one\\nline two \\\\ slash" in text
    # The stream still parses as one record per line.
    parse_exposition(text)


def test_wire_name_collision_raises_at_registration():
    reg = MetricsRegistry()
    reg.counter("serving.queue_depth").inc()
    with pytest.raises(ValueError, match="collides"):
        reg.counter("serving_queue_depth")
    with pytest.raises(ValueError, match="collides"):
        reg.gauge("serving-queue.depth")
    # Re-asking for the same name is still get-or-create.
    reg.counter("serving.queue_depth").inc()
    assert reg.counter("serving.queue_depth").value() == 2
    with pytest.raises(ValueError, match="legal Prometheus name"):
        reg.counter("bad name!")
    assert prom_name("a.b-c") == "a_b_c"


def test_compact_includes_exact_min_max():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s")
    for v in (0.003, 0.4, 11.0):
        h.observe(v)
    c = reg.compact()["lat_s"]
    assert c["min"] == 0.003 and c["max"] == 11.0
    assert c["count"] == 3 and c["p99"] <= 11.0


def test_windowed_percentiles_diff():
    reg = MetricsRegistry()
    h = reg.histogram("lat_s")
    for _ in range(10):
        h.observe(0.01)
    old = reg.snapshot()["lat_s"]["series"][0]
    for _ in range(10):
        h.observe(5.0)
    new = reg.snapshot()["lat_s"]["series"][0]
    cum = windowed_percentiles(new, None)
    win = windowed_percentiles(new, old)
    assert win["count"] == 10 and cum["count"] == 20
    assert win["p50"] > 1.0 > cum["p50"]  # window excludes the old obs
    assert windowed_percentiles(old, old) is None


# ------------------------------------------------------- SLO engine


def test_slo_engine_windows_breaches_and_rearms():
    t = [0.0]
    events = []
    reg = MetricsRegistry()
    hits = []
    eng = SloEngine(
        reg, [SloRule("lat_s", percentile=0.99, threshold=1.0,
                      window_s=10.0)],
        clock=lambda: t[0],
        emit=lambda name, **f: events.append((name, f)))
    # The subscriber queries the engine back — fires with the engine
    # lock RELEASED, so this must not deadlock the tick (round-11
    # review regression).
    eng.subscribe(lambda rule, value: hits.append(
        (rule.metric, value, eng.windowed(rule.metric, 0.5, 10.0))))
    h = reg.histogram("lat_s")
    for _ in range(5):
        h.observe(0.01)
    eng.tick()
    assert not events and not hits
    assert eng.windowed("lat_s", 0.5, 10.0) < 0.1
    # Latency spike -> breach (event + counter + subscriber).
    t[0] = 5.0
    for _ in range(5):
        h.observe(5.0)
    eng.tick()
    assert [n for n, _ in events] == ["slo.breach"]
    assert events[0][1]["metric"] == "lat_s"
    assert events[0][1]["value"] > 1.0
    assert hits and hits[0][0] == "lat_s"
    assert hits[0][2] is not None  # the reentrant windowed() worked
    assert reg.counter("slo.breaches").value(metric="lat_s",
                                             q="p99") == 1
    # Windowed gauges land in the registry (scrapeable).
    assert reg.gauge("slo.windowed").value(metric="lat_s",
                                           q="p99") > 1.0
    # Sustained breach: edge-triggered, no second event.
    t[0] = 6.0
    eng.tick()
    assert len(events) == 1
    # Recovery re-arms...
    t[0] = 20.0
    for _ in range(20):
        h.observe(0.01)
    eng.tick()
    assert len(events) == 1
    # ...so the next spike breaches again.
    t[0] = 21.0
    for _ in range(5):
        h.observe(5.0)
    eng.tick()
    assert len(events) == 2
    assert reg.counter("slo.breaches").value(metric="lat_s",
                                             q="p99") == 2


def test_slo_rule_validation():
    with pytest.raises(ValueError, match="percentile"):
        SloRule("m", percentile=1.5, threshold=1.0)
    with pytest.raises(ValueError, match="threshold"):
        SloRule("m", percentile=0.99, threshold=0.0)
    with pytest.raises(ValueError, match="window_s"):
        SloRule("m", percentile=0.99, threshold=1.0, window_s=-1)


# ------------------------------------------------- telemetry server


def test_server_endpoints_and_trace_tail(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with obs.session(trace_path=path, serve_port=0) as sess:
        obs.count("x.hits", 2, kind="a")
        obs.observe("x.lat_s", 0.02)
        for i in range(8):
            obs.event("marker", i=i)
        url = sess.server.url
        fams = parse_exposition(_get(url + "/metrics"))
        assert ("x_hits", {"kind": "a"}, "2.0") in \
            fams["x_hits"]["samples"]
        snap = json.loads(_get(url + "/snapshot.json"))
        assert snap["x.hits"]["series"][0]["value"] == 2
        # /trace/tail?n= — last N records, newest last.  Records sit
        # in memory until a flush: only ``meta`` has reached the file.
        tail = lambda: [json.loads(l) for l in _get(
            url + "/trace/tail?n=3").splitlines()]
        assert [r["kind"] for r in tail()] == ["meta"]
        sess.trace.flush()
        assert [r["fields"]["i"] for r in tail()] == [5, 6, 7]
        # Unknown endpoint -> 404.
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(url + "/nope")
        assert ei.value.code == 404
    # Session close stops the server.
    with pytest.raises(Exception):
        _get(url + "/metrics", timeout=2)


def test_trace_tail_lags_by_at_most_the_buffer_bound(tmp_path,
                                                     monkeypatch):
    """No flush is asked for: the tail catches up when a record
    arrives to a buffer older than FLUSH_AGE_S, or FLUSH_RECORDS
    long."""
    monkeypatch.setattr(EventTrace, "FLUSH_AGE_S", 0.05)
    path = str(tmp_path / "t.jsonl")
    with obs.session(trace_path=path, serve_port=0) as sess:
        tail = lambda: [r["fields"]["i"] for r in map(json.loads, _get(
            sess.server.url + "/trace/tail?n=100").splitlines())
            if r["kind"] == "event"]
        obs.event("marker", i=0)
        obs.event("marker", i=1)
        assert tail() == []                    # inside the bound
        time.sleep(0.06)
        obs.event("marker", i=2)               # the buffer is now old
        assert tail() == [0, 1, 2]
        monkeypatch.setattr(EventTrace, "FLUSH_AGE_S", 3600.0)
        monkeypatch.setattr(EventTrace, "FLUSH_RECORDS", 4)
        for i in range(3, 6):
            obs.event("marker", i=i)
        assert tail() == [0, 1, 2]
        obs.event("marker", i=6)               # the fourth record
        assert tail() == [0, 1, 2, 3, 4, 5, 6]


def test_healthz_flips_with_heartbeat_freshness(tmp_path):
    t = [100.0]
    hb = str(tmp_path / "hb")
    health = HeartbeatHealth(hb, host=0, window=2.0,
                             clock=lambda: t[0])
    with obs.session(serve_port=0, health=health) as sess:
        url = sess.server.url
        # No beat yet -> 503.
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(url + "/healthz")
        assert ei.value.code == 503
        write_beat(hb, 0, epoch=0, n=1, clock=lambda: t[0])
        body = json.loads(_get(url + "/healthz"))
        assert body["ok"] and body["age_s"] <= 2.0
        t[0] += 10.0            # beat goes stale -> 503
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(url + "/healthz")
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["ok"] is False
        # Terminal done beat: clean completion is healthy forever.
        write_beat(hb, 0, epoch=0, n=2, clock=lambda: t[0], done=True)
        t[0] += 100.0
        assert json.loads(_get(url + "/healthz"))["done"] is True


def test_tail_trace_tolerates_live_torn_write(tmp_path):
    path = str(tmp_path / "x.jsonl")
    with open(path, "w") as f:
        for i in range(100):
            f.write(json.dumps({"kind": "event", "name": "e",
                                "t": i, "fields": {"i": i}}) + "\n")
        f.write('{"kind": "ev')  # live writer mid-flush
    recs = tail_trace(path, 5)
    assert [r["fields"]["i"] for r in recs] == [95, 96, 97, 98, 99]
    assert tail_trace(path, 0) == []
    assert len(tail_trace(path, 1000)) == 100
    evs = tail_trace(path, 10, kinds=("span",))
    assert evs == []


def test_scrape_under_concurrent_writes_no_torn_lines():
    """The satellite stress test: trainer/serving-like threads hammer
    the registry while the server is scraped; every scrape must parse
    under the strict parser (no torn lines) and the loop must finish
    (no deadlock between the scrape snapshot and the registry lock)."""
    reg = MetricsRegistry()
    stop = threading.Event()

    def writer(k):
        while not stop.is_set():
            reg.counter("w.requests").inc(status=f"s{k}")
            reg.histogram("w.lat_s").observe(0.01 * (k + 1), kind=f"k{k}")
            reg.gauge("w.depth").set(k, lane=str(k))

    threads = [threading.Thread(target=writer, args=(k,), daemon=True)
               for k in range(3)]
    with TelemetryServer(reg) as srv:
        for th in threads:
            th.start()
        t0 = time.monotonic()
        try:
            for _ in range(30):
                parse_exposition(_get(srv.url + "/metrics"))
        finally:
            stop.set()
            for th in threads:
                th.join(timeout=5.0)
        assert time.monotonic() - t0 < 60.0, "scrape loop crawled"


# ------------------------------------------------------- federation


def test_cluster_federation_merges_hosts_and_drops_dead_peer(tmp_path):
    cdir = str(tmp_path / "coord")
    r0, r1 = MetricsRegistry(), MetricsRegistry()
    r0.counter("serving.requests").inc(3, status="ok")
    r1.counter("serving.requests").inc(5, status="ok")
    r1.gauge("only.on.one").set(7)
    with TelemetryServer(r0, cluster_dir=cdir, host_id=0) as s0, \
            TelemetryServer(r1, cluster_dir=cdir, host_id=1):
        text = _get(s0.url + "/metrics/cluster")
        fams = parse_exposition(text)
        sam = fams["serving_requests"]["samples"]
        assert ("serving_requests", {"host": "0", "status": "ok"},
                "3.0") in sam
        assert ("serving_requests", {"host": "1", "status": "ok"},
                "5.0") in sam
        up = dict(((lab["host"], v) for _, lab, v in
                   fams["cluster_scrape_up"]["samples"]))
        assert up == {"0": "1", "1": "1"}
        # A published-but-dead peer drops out instead of failing the
        # scrape.
        with open(os.path.join(cdir, "telemetry", "host7.addr"),
                  "w") as f:
            json.dump({"host": 7, "addr": "127.0.0.1:9"}, f)
        fams = parse_exposition(_get(s0.url + "/metrics/cluster"))
        up = dict(((lab["host"], v) for _, lab, v in
                   fams["cluster_scrape_up"]["samples"]))
        assert up["7"] == "0"
        assert not any(lab.get("host") == "7"
                       for _, lab, _ in
                       fams["serving_requests"]["samples"])
    # Clean stop unpublishes.
    assert not os.path.exists(os.path.join(cdir, "telemetry",
                                           "host0.addr"))


def test_merge_expositions_groups_families():
    a = ("# HELP m total\n# TYPE m counter\nm 1.0\n"
         "# TYPE h histogram\nh_bucket{le=\"+Inf\"} 1\nh_sum 0.5\n"
         "h_count 1\n")
    b = "# TYPE m counter\nm{x=\"y\"} 2.0\n"
    merged = merge_expositions({0: a, 1: b, 2: None})
    fams = parse_exposition(merged)
    assert ("m", {"host": "0"}, "1.0") in fams["m"]["samples"]
    assert ("m", {"host": "1", "x": "y"}, "2.0") in fams["m"]["samples"]
    assert ("h_bucket", {"host": "0", "le": "+Inf"}, "1") in \
        fams["h"]["samples"]


# -------------------------------------- the acceptance integration


@pytest.mark.slow
def test_live_plane_end_to_end_engine_healthz_slo_waterfall(tmp_path):
    """The round-11 acceptance test: `obs.session(serve_port=0)` over
    a real ContinuousBatcher workload — /metrics parses clean with
    serving_* series, /healthz flips 200 -> 503 when the heartbeat
    goes stale, an injected latency spike trips the SloRule
    (slo.breach event + subscriber callback), and
    `obs_report.py --request` renders the request's
    submit -> admit -> chunks -> decode waterfall from the trace."""

    path = str(tmp_path / "serve.jsonl")
    hb = str(tmp_path / "hb")
    clk = [0.0]
    hclk = [1000.0]
    params = toy_params(CFG)
    rng = np.random.default_rng(0)
    health = HeartbeatHealth(hb, host=0, window=2.0,
                             clock=lambda: hclk[0])
    rules = [SloRule("serving.request_s", percentile=0.95,
                     threshold=1.0, window_s=30.0)]
    hits = []
    with obs.session(trace_path=path, serve_port=0, health=health,
                     slo_rules=rules, slo_tick_s=30.0) as sess:
        sess.slo.subscribe(lambda rule, v: hits.append((rule.metric, v)))
        url = sess.server.url
        eng = dk.ContinuousBatcher(params, CFG, lanes=2, max_queue=4,
                                   prompt_buckets=(8,),
                                   prefill_chunk=8,
                                   clock=lambda: clk[0])
        # A long prompt (chunked admission), a short one, and a third
        # that has to QUEUE behind them (real queue wait).
        long_rid = eng.enqueue(
            rng.integers(0, 64, (20,)).astype(np.int32), 5)
        short_rid = eng.enqueue(
            rng.integers(0, 64, (4,)).astype(np.int32), 5)
        queued_rid = eng.enqueue(
            rng.integers(0, 64, (4,)).astype(np.int32), 5)
        assert eng.queued == 1
        while any(eng.poll(r) is None
                  for r in (long_rid, short_rid, queued_rid)):
            clk[0] += 2.0        # injected latency spike (engine clock)
            eng.step()
        res = {r: eng.take(r) for r in (long_rid, short_rid,
                                        queued_rid)}
        assert all(r.ok for r in res.values())

        # -- /metrics parses clean and carries serving_* series.
        write_beat(hb, 0, epoch=0, n=1, clock=lambda: hclk[0])
        fams = parse_exposition(_get(url + "/metrics"))
        assert any(f.startswith("serving_") for f in fams)
        assert fams["serving_requests"]["type"] == "counter"
        assert fams["serving_request_s"]["type"] == "histogram"
        assert "serving_ttft_s" in fams and "serving_tpot_s" in fams

        # -- /healthz: fresh 200 -> stale 503.
        assert json.loads(_get(url + "/healthz"))["ok"]
        hclk[0] += 30.0
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(url + "/healthz")
        assert ei.value.code == 503

        # -- the spike (every request took seconds of engine clock)
        # trips the rule on the next tick.
        sess.slo.tick()
        assert hits and hits[0][0] == "serving.request_s"
        assert sess.registry.counter("slo.breaches").value(
            metric="serving.request_s", q="p95") >= 1
        fams = parse_exposition(_get(url + "/metrics"))
        assert "slo_windowed" in fams and "slo_breaches" in fams

    # -- the trace carries the full per-request story.
    recs = read_trace(path)
    breach = [r for r in recs if r.get("kind") == "event"
              and r["name"] == "slo.breach"]
    assert breach and breach[0]["fields"]["metric"] == \
        "serving.request_s"

    wf = request_waterfall(recs, queued_rid)
    assert wf["found"] and wf["status"] == "ok"
    assert wf["queue_wait_s"] is not None and wf["queue_wait_s"] >= 0
    assert wf["ttft_s"] > 0 and wf["tokens"] == 5
    assert wf["gaps"] and wf["gaps"]["count"] >= 1
    text = render_waterfall(wf)
    assert "serving.emit" in text and "serving.finish" in text

    # The long prompt's waterfall shows its chunked-prefill admissions.
    wf_long = request_waterfall(recs, long_rid)
    assert wf_long["prefill_chunks"] >= 1
    assert wf_long["prompt_len"] == 20

    # -- the CLI renders the same waterfall.
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         path, "--request", str(queued_rid)],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert f"request {queued_rid}" in r.stdout
    assert "queue wait" in r.stdout and "serving.finish" in r.stdout
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "obs_report.py"),
         path, "--request", "99999"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert r.returncode == 1


def test_request_waterfall_speculative_and_unknown_id(tmp_path):
    """Per-request propagation covers the speculative engine too, and
    an unknown id reports found=False."""

    cfg = serve_cfg(rope=False)
    draft = spec_draft_cfg(rope=False)
    path = str(tmp_path / "spec.jsonl")
    with obs.session(trace_path=path):
        eng = dk.SpeculativeBatcher(
            toy_params(cfg),
            toy_params(draft, 1),
            cfg, draft, lanes=2, n_draft=2, max_queue=2)
        rid = eng.enqueue(np.arange(4, dtype=np.int32), 6)
        while eng.poll(rid) is None:
            eng.step()
        assert eng.take(rid).ok
    recs = read_trace(path)
    wf = request_waterfall(recs, rid)
    assert wf["found"] and wf["status"] == "ok" and wf["tokens"] == 6
    names = [s["name"] for s in wf["stages"]]
    assert "serving.admit" in names and "serving.finish" in names
    assert not request_waterfall(recs, 12345)["found"]
