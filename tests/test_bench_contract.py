"""The bench entry points' contract: they measure the TPU or they fail.

A backend other than TPU, or a row that raises, ends the run with a
non-zero exit code and no result — never a "skipped" line carried by
an older number; every JSON line names the device it was measured on.
Below that: the rows' own field contracts, checked at toy size.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "scripts")):
    if p not in sys.path:
        sys.path.insert(0, p)


_FAKE_DEVICE = {"platform": "tpu", "device_kind": "TPU test",
                "device_count": 1}


@pytest.fixture()
def cache_untouched(monkeypatch):
    """The mains place the compile cache first, process-wide; a test
    that calls one in-process must not re-point the suite's JAX."""
    from distkeras_tpu.utils import misc

    monkeypatch.setattr(misc, "configure_compile_cache", lambda: None)


@pytest.mark.parametrize("module", ["chip_smoke", "bench"])
def test_entry_points_fail_on_a_cpu_backend(module, capsys,
                                            cache_untouched):
    """No chip, no result: a non-zero exit whose reason names the
    backend found, and nothing on stdout."""
    with pytest.raises(SystemExit) as e:
        __import__(module).main()
    assert "TPU only" in str(e.value.code) and "'cpu'" in str(e.value.code)
    assert capsys.readouterr().out == ""


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    """chip_smoke.py proves the PROGRAM starts: copied out of the
    checkout it has nothing to drive and must not report success."""
    import shutil
    import subprocess

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("module", ["bench_suite", "bench_serving"])
def test_bench_main_exits_nonzero_off_tpu(module, capsys, cache_untouched):
    """main() reaches the device gate before any row: on the CPU
    backend it exits non-zero and prints no line."""
    mod = __import__(module)
    with pytest.raises(SystemExit) as e:
        mod.main([next(iter(mod.BENCHES))])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("module,good", [
    ("bench_suite", (10.0, 0.5, 0.0)),
    ("bench_serving", (10.0, 0.5, 0.0, {})),
])
def test_bench_main_row_that_raises_exits_nonzero(module, good,
                                                  monkeypatch, capsys,
                                                  cache_untouched):
    """A raising row is reported with its device fields, the other
    rows still run and carry theirs, and the run exits non-zero."""
    import bench_suite

    mod = __import__(module)

    def boom():
        raise RuntimeError("kernel refused")
    monkeypatch.setattr(bench_suite, "tpu_device_fields",
                        lambda: dict(_FAKE_DEVICE))
    monkeypatch.setattr(mod, "BENCHES", {"bad": (boom, "u"),
                                         "good": (lambda: good, "u")})
    with pytest.raises(SystemExit) as e:
        mod.main([])
    assert e.value.code not in (0, None)
    captured = capsys.readouterr()
    lines = [json.loads(x) for x in captured.out.strip().splitlines()]
    assert [x["metric"] for x in lines] == ["bad", "good"]
    assert "kernel refused" in lines[0]["error"]
    assert lines[1]["value"] == 10.0
    for x in lines:
        assert {k: x[k] for k in _FAKE_DEVICE} == _FAKE_DEVICE
    assert "kernel refused" in captured.err       # the traceback


def test_compile_cache_is_placed_from_outside(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: untouched, JAX reads it itself.
    Unset: the fixed in-checkout path, never a temporary one."""
    import jax

    from distkeras_tpu.utils.misc import configure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    # Importing the package (done long ago) placed no cache of its own.
    assert before == os.environ.get("JAX_COMPILATION_CACHE_DIR")
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.chdir(tmp_path)           # not the working directory
        fixed = os.path.join(ROOT, ".jax_cache")
        assert configure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_native_loader_ignores_a_binary_not_built_from_its_source(
        tmp_path, monkeypatch):
    """Only the library named by the hash of the .cc bytes is opened:
    a stale binary under the old fixed name, or under another source's
    hash, is never loaded — the loader builds the right one."""
    from distkeras_tpu import native

    if not native.available():
        pytest.skip("no C++ toolchain: the numpy path serves")
    monkeypatch.setattr(native, "_PKG_DIR", str(tmp_path))
    for stale in ("_libdkt_data.so", "_libdkt_data.0123456789abcdef.so"):
        (tmp_path / stale).write_bytes(b"not a shared library")
    want = native._artifact(native._SRC, "_libdkt_data")
    assert os.path.dirname(want) == str(tmp_path)
    handle = native._load(native._SRC, "_libdkt_data")
    assert handle is not None and handle._name == want
    # Edited source -> another name; the build above is not reused.
    edited = tmp_path / "dataloader.cc"
    edited.write_bytes(open(native._SRC, "rb").read() + b"\n// edited\n")
    other = native._artifact(str(edited), "_libdkt_data")
    assert other != want and not os.path.exists(other)
    assert native._load(str(edited), "_libdkt_data")._name == other


def test_engine_load_fields_mean_what_they_say(monkeypatch):
    """Round-4 verdict: bench_engine_load returned per-request makespan
    as the tuple element main() prints under "ms_per_token".  Contract
    now: that element is aggregate per-token wall time (1/value), the
    per-request figure lives under its own ``ms_per_request`` key, and
    the window quantization of the latency percentiles is announced as
    ``ttft_granularity_ms`` (window x median TPOT)."""
    import bench_serving as bs
    from distkeras_tpu.models import transformer as tfm

    tiny = tfm.TransformerConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_len=33, dtype="float32", rope=True)
    monkeypatch.setattr(bs, "_cfg", lambda window=None: tiny)

    run = bs.bench_engine_load(lanes=2, offered_rps=200.0)
    rate, step_s, _, extras = run(n_req=3, p_len=8, new=6, window=2)

    assert rate > 0
    # ms_per_token really is per token: the tuple element inverts the
    # achieved aggregate token rate.
    assert abs(rate * step_s - 1.0) < 1e-9
    assert extras["ms_per_request"] > 0
    # Makespan/request covers a whole 6-token request plus queueing —
    # it must dominate the per-token figure it used to masquerade as.
    assert extras["ms_per_request"] > step_s * 1e3
    assert extras["ttft_granularity_ms"] == pytest.approx(
        extras["tpot_p50_ms"] * 2, rel=0.02, abs=0.2)
    for key in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                "tpot_p99_ms", "achieved_rps"):
        assert key in extras


def _tiny_serving_cfg():
    from distkeras_tpu.models import transformer as tfm

    return tfm.TransformerConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        max_len=48, dtype="float32", rope=True)


def test_bench_longprompt_rows_report_step_gap(monkeypatch):
    """Round-10 rows: engine_longprompt_{monolithic,chunked} report
    the decoding lanes' step-gap percentiles and self-scale the chunk
    width to the config (the flagship's 128 would not even construct
    on a small cache)."""
    import bench_serving as bs

    monkeypatch.setattr(bs, "_cfg", lambda window=None:
                        _tiny_serving_cfg())
    for chunk in (None, 128):
        run = bs.bench_longprompt(chunk)
        rate, step_s, _, extras = run(p_short=6, p_long=30, new=12,
                                      long_new=4)
        assert rate > 0 and abs(rate * step_s - 1.0) < 1e-9
        for key in ("step_gap_p50_ms", "step_gap_p99_ms",
                    "step_gap_max_ms", "prefill_chunk"):
            assert key in extras
        if chunk is not None:
            # Self-scaled: 48 // 8 = 6, never the flagship 128.
            assert extras["prefill_chunk"] == 6


def test_bench_prefix_reuse_reports_speedup(monkeypatch):
    import bench_serving as bs

    monkeypatch.setattr(bs, "_cfg", lambda window=None:
                        _tiny_serving_cfg())
    run = bs.bench_prefix_reuse(2)
    rate, step_s, _, extras = run(prefix_len=8, tail_len=4, n_req=6,
                                  new=4)
    assert rate > 0
    assert extras["n_prefixes"] == 2
    assert extras["noreuse_tok_s"] > 0
    assert extras["reuse_speedup"] > 0


def test_bench_load_elastic_and_spec_rows(monkeypatch):
    """The PR-5 load-sweep follow-ups: the elastic row drives the
    enqueue/poll flow (QueueFull retried, tier trajectory reported),
    the speculative row reports TTFT/TPOT percentiles."""
    import bench_serving as bs

    monkeypatch.setattr(bs, "_cfg", lambda window=None:
                        _tiny_serving_cfg())
    rate, _, _, extras = bs.bench_engine_load_elastic(
        (1, 2), 400.0)(n_req=4, p_len=6, new=5, window=1)
    assert rate > 0 and extras["ok"] == 4
    assert extras["final_lanes"] in (1, 2)
    for key in ("request_p50_ms", "request_p99_ms", "tier_epoch"):
        assert key in extras
    rate, _, _, extras = bs.bench_engine_load_spec(
        2, 400.0)(n_req=3, p_len=6, new=5, n_draft=2)
    assert rate > 0 and not extras["degraded"]
    for key in ("ttft_p99_ms", "tpot_p50_ms", "n_draft"):
        assert key in extras


def test_bench_router_scale_row(monkeypatch):
    """Round-13 fleet row: router_scale_N drives the enqueue/poll
    load flow over N in-process replicas on per-replica step threads
    and reports achieved rps plus TTFT/TPOT percentiles off the obs
    histograms (needs the active session main() provides)."""
    import bench_serving as bs
    from distkeras_tpu import obs

    monkeypatch.setattr(bs, "_cfg", lambda window=None:
                        _tiny_serving_cfg())
    sess = obs.enable()
    try:
        rate, step_s, _, extras = bs.bench_router_scale(2)(
            n_req=4, p_len=6, new=5, lanes=1, per_replica_rps=200.0)
    finally:
        obs.disable()
    assert rate > 0 and abs(rate * step_s - 1.0) < 1e-9
    assert extras["replicas"] == 2 and extras["ok"] == 4
    for key in ("achieved_rps", "lanes_per_replica", "offered_rps",
                "ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms",
                "tpot_p99_ms"):
        assert key in extras


def test_bench_router_affinity_row(monkeypatch):
    """The affinity policy must beat (or tie, never lose to)
    round-robin on stem_hit_blocks over the SAME shuffled trace — the
    re-prefill work the cache-aware policy exists to avoid."""
    import bench_serving as bs

    monkeypatch.setattr(bs, "_cfg", lambda window=None:
                        _tiny_serving_cfg())
    rate, _, _, extras = bs.bench_router_affinity()(
        n_stems=2, reqs_per_stem=3, tail_len=4, new=4, lanes=2)
    assert rate > 0
    assert extras["affinity_hit_blocks"] > 0
    assert (extras["affinity_hit_blocks"]
            >= extras["round_robin_hit_blocks"])
    assert extras["round_robin_tok_s"] > 0
    assert _tiny_serving_cfg().max_len % extras["block"] == 0


def test_bench_router_disagg_row(monkeypatch):
    """Round-17 disaggregated-fleet row: role-split fleet vs the
    co-resident baseline on one trace — victims stream through
    Router.stream() under a storm, and the row must surface both
    fleets' streaming-TPOT percentiles plus the transfer-bytes /
    adoption-hit counters (obs session required, as main() provides),
    with the storm actually taking the ship->adopt hop."""
    import bench_serving as bs
    from distkeras_tpu import obs

    monkeypatch.setattr(bs, "_cfg", lambda window=None:
                        _tiny_serving_cfg())
    obs.enable()
    try:
        ratio, p99_s, _, extras = bs.bench_router_disagg()(
            n_storm=6, n_victims=2, storm_new=2, victim_new=6,
            lanes=2, n_stems=2, window=3)
    finally:
        obs.disable()
    assert ratio > 0 and p99_s > 0
    assert extras["storm_ok"] == 6 and extras["baseline_storm_ok"] == 6
    for key in ("tpot_p50_ms", "tpot_p99_ms", "baseline_tpot_p50_ms",
                "baseline_tpot_p99_ms", "ttft_p50_ms",
                "baseline_ttft_p50_ms", "storm_rps",
                "adoption_hit_rate", "transfer_mb", "warm_skips",
                "fallbacks"):
        assert key in extras
    # The storm must ride the disaggregated hop, not fall back: the
    # unique second block defeats the warm-skip gate on every request.
    assert extras["disagg_requests"] > 0
    assert extras["blocks_shipped"] > 0
    assert extras["transfer_mb"] > 0
    assert 0.0 <= extras["adoption_hit_rate"] <= 1.0


def test_bench_engine_sharded_row(monkeypatch):
    """Round-14 pod-sharded row: engine_sharded_tpN serves a real
    tiny-cfg workload on the 8-CPU mesh and reports per-device
    param+KV bytes (sharded AND solo — the ~tp× reduction must be
    visible in the row, not asserted in prose) plus TTFT/TPOT for
    both engines."""
    import bench_serving as bs

    monkeypatch.setattr(bs, "_cfg", lambda window=None:
                        _tiny_serving_cfg())
    rate, step_s, _, extras = bs.bench_engine_sharded(2)(
        n_req=4, p_len=6, new=5, lanes=2)
    assert rate > 0 and abs(rate * step_s - 1.0) < 1e-9
    assert extras["tp"] == 2
    # KV shards exactly 2x; params nearly (norm scales replicate).
    assert extras["solo_kv_mb_per_device"] == pytest.approx(
        extras["kv_mb_per_device"] * 2, rel=0.01)
    assert extras["bytes_reduction"] > 1.5
    for key in ("param_mb_per_device", "solo_param_mb_per_device",
                "ttft_p50_ms", "tpot_p50_ms", "solo_ttft_p50_ms",
                "solo_tpot_p50_ms", "solo_tok_s"):
        assert key in extras


def test_bench_engine_sharded_tp4_runs_when_heads_allow(monkeypatch):
    """tp4 needs n_heads % 4 == 0: a 4-head tiny cfg runs the real
    row on the 8-CPU mesh (data=2, model=4)."""
    import bench_serving as bs
    from distkeras_tpu.models import transformer as tfm

    cfg4 = tfm.TransformerConfig(
        vocab_size=64, d_model=16, n_heads=4, n_layers=1, d_ff=32,
        max_len=48, dtype="float32", rope=True)
    monkeypatch.setattr(bs, "_cfg", lambda window=None: cfg4)
    rate, _, _, extras = bs.bench_engine_sharded(4)(
        n_req=2, p_len=6, new=4, lanes=2)
    assert rate > 0 and extras["tp"] == 4
    assert extras["solo_kv_mb_per_device"] == pytest.approx(
        extras["kv_mb_per_device"] * 4, rel=0.01)


def test_bench_paged_rows(monkeypatch):
    """Round-12 paged-KV rows: the lanes-at-fixed-HBM row reports a
    >= 2x lane multiple at identical slab block counts, the shared-
    stem row reports refcounted block savings, and the CoW row
    reports fork vs whole-row-copy latency — all self-scaled to the
    config (block must divide max_len)."""
    import bench_serving as bs

    monkeypatch.setattr(bs, "_cfg", lambda window=None:
                        _tiny_serving_cfg())
    rate, step_s, _, extras = bs.bench_paged_lanes(4)(
        mono_lanes=2, p_len=6, new=4)
    assert rate > 0 and abs(rate * step_s - 1.0) < 1e-9
    assert extras["paged_lanes"] == extras["mono_lanes"] * 4
    # lanes_ratio is MEASURED peak concurrency, not the configured
    # constant — the >=2x acceptance claim must be falsifiable.
    assert extras["peak_lanes_paged"] <= extras["paged_lanes"]
    assert extras["peak_lanes_mono"] <= extras["mono_lanes"]
    assert extras["lanes_ratio"] >= 2.0
    assert extras["mono_tok_s"] > 0 and extras["slab_blocks"] > 0
    assert _tiny_serving_cfg().max_len % extras["block"] == 0

    rate, _, _, extras = bs.bench_paged_shared_stem(4)(
        stem_len=12, tail_len=4, new=4, lanes=2)
    assert rate > 0
    assert extras["blocks_saved"] > 0
    assert extras["noshare_tok_s"] > 0 and extras["share_speedup"] > 0

    ratio, fork_s, _, extras = bs.bench_paged_cow_fork()(
        p_len=8, warm_steps=2, iters=3)
    assert ratio > 0 and fork_s > 0
    assert extras["fork_ms"] > 0 and extras["cache_copy_ms"] > 0
    assert extras["bytes_ratio"] > 1


def test_bench_autoscale_row(monkeypatch):
    """Round-19 policy-vs-policy row: the SAME deterministic spike
    trace over static-min, static-max, and autoscaled fleets under
    the virtual clock.  The autoscaled leg must beat static-min on
    hot-window p99 TTFT while burning fewer replica-ticks than
    static-max, lose NOTHING, and reproduce its scaling-decision
    timeline on a repeat run (the `autoscale.decision` audit trail)."""
    import bench_serving as bs
    from distkeras_tpu import obs

    monkeypatch.setattr(bs, "_cfg", lambda window=None:
                        _tiny_serving_cfg())
    sess = obs.enable()
    try:
        value, p99_auto, _, extras = bs.bench_autoscale("spike")(
            ticks=16, min_replicas=1, max_replicas=2, lanes=2,
            steps_per_tick=3, spike_at=4, spike_len=5,
            spike_rate=7.0, base_rate=0.5)
    finally:
        obs.disable()
    assert value > 1.0, (
        f"autoscaled p99 TTFT did not beat static-min: {extras}")
    assert (extras["autoscaled_replica_ticks"]
            < extras["static_max_replica_ticks"]), (
        "elasticity burned as many replica-ticks as the static "
        f"maximum fleet: {extras}")
    assert extras["deterministic_timeline"], (
        "two same-seed runs produced different scaling decisions")
    assert extras["autoscaled_lost"] == 0
    assert extras["static_max_lost"] == 0
    assert extras["scale_ups"] >= 1
    assert extras["offered_requests"] > 0
    for key in ("static_min_ttft_p99_ticks", "scaling_changes",
                "autoscaled_ttft_p99_ticks", "shape"):
        assert key in extras
    assert p99_auto == extras["autoscaled_ttft_p99_ticks"]


def test_bench_canary_rollout_row(monkeypatch):
    """Round-20 live-push row: a canary promote lands mid-stream over
    in-flight requests.  Both legs must be per-version token-
    deterministic, the push must actually change the served tokens,
    and the victim TPOT ratio / rollout wall-clock must be finite."""
    import bench_serving as bs
    from distkeras_tpu import obs

    monkeypatch.setattr(bs, "_cfg", lambda window=None:
                        _tiny_serving_cfg())
    sess = obs.enable()
    try:
        ratio, rollout_s, _, extras = bs.bench_canary_rollout()(
            n_req=3, max_new=6, push_after=2, lanes=2)
    finally:
        obs.disable()
    assert extras["tokens_deterministic_per_version"], (
        "same-seed legs produced different token streams")
    assert extras["tokens_changed_at_push"], (
        "the mid-stream push left every token stream unchanged — the "
        "swap never landed")
    assert extras["rollout_wallclock_ms"] > 0
    # extras round to 3 decimals of a millisecond; compare in seconds
    # with the matching absolute slack.
    assert rollout_s == pytest.approx(
        extras["rollout_wallclock_ms"] / 1e3, abs=1e-6)
    assert ratio == pytest.approx(extras["tpot_p99_push_ms"]
                                  / extras["tpot_p99_baseline_ms"],
                                  rel=0.05)
    for key in ("tpot_p99_push_ms", "tpot_p99_baseline_ms", "n_req",
                "push_after_steps"):
        assert key in extras
