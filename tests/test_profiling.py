"""StepTimer + profiler trace smoke (SURVEY.md §5 observability rebuild)."""

import glob

import jax
import jax.numpy as jnp
import pytest

from distkeras_tpu import obs
from distkeras_tpu.obs import read_trace
from distkeras_tpu.utils.profiling import StepTimer, trace


def test_step_timer_rounds():
    timer = StepTimer()
    step = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((128, 128))
    for _ in range(3):
        with timer.round():
            for _ in range(4):
                x = step(x)
                timer.count()
        timer.finalize(x)
    assert timer.total_steps == 12
    assert len(timer.rounds) == 3
    assert timer.total_s > 0
    assert timer.mean_step_s > 0
    assert timer.samples_per_sec(128) > 0
    assert timer.p50_round_s > 0


def test_step_timer_named_phases():
    """Named phase counters: host wall time accumulates per phase
    (the distributed trainers record "h2d" and "step" with these)."""
    timer = StepTimer()
    step = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((64, 64))
    for _ in range(3):
        with timer.phase("h2d"):
            xd = jax.device_put(x)
        with timer.phase("step"):
            xd = step(xd)
    timer.finalize(xd)
    assert set(timer.phases) == {"h2d", "step"}
    assert timer.phase_s("h2d") > 0 and timer.phase_s("step") > 0
    assert timer.phase_s("unknown") == 0.0
    stats = timer.phase_stats()
    assert stats["step"]["calls"] == 3
    assert stats["step"]["mean_s"] == pytest.approx(
        stats["step"]["total_s"] / 3)


def test_step_timer_reset_is_explicit():
    """Regression (PR 4 satellite): stats must not silently blend
    across runs — reset() clears rounds AND phases, and abandons an
    open round instead of recording it."""
    timer = StepTimer()
    with timer.phase("h2d"):
        pass
    with timer.round(4):
        pass
    timer.finalize()
    assert timer.total_steps == 4 and timer.phases
    with timer.round(2):  # left open on purpose
        timer.reset()
    assert timer.rounds == [] and timer.phases == {}
    assert timer.total_steps == 0 and timer.total_s == 0.0
    timer.finalize()  # the abandoned round must not resurface
    assert timer.rounds == []


def test_trainer_resets_timer_per_run():
    """Two train() calls on one trainer: phase stats describe the
    SECOND run only (the trainers call timer.reset() at train())."""
    import distkeras_tpu as dk
    from helpers import make_blobs, make_mlp

    feats, labels = make_blobs(n=128)
    ds = dk.Dataset({"features": feats, "label": labels})
    t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="sgd", learning_rate=0.05, batch_size=4,
                num_epoch=1, communication_window=2)
    t.train(ds)
    rounds_per_run = len(t.history)
    first = t.step_timer.phase_stats()["step"]["calls"]
    t.train(ds)
    again = t.step_timer.phase_stats()["step"]["calls"]
    assert first == again == rounds_per_run, (first, again)


def test_trainer_populates_phase_counters():
    """A distributed trainer run leaves "h2d"/"step" populated — the
    input plane is distinguishable from compute without a profiler."""
    import numpy as np

    import distkeras_tpu as dk
    from helpers import make_blobs, make_mlp

    feats, labels = make_blobs(n=256)
    ds = dk.Dataset({"features": feats, "label": labels})
    t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="sgd", learning_rate=0.05, batch_size=4,
                num_epoch=1, communication_window=2)
    t.train(ds)
    assert t.step_timer.phase_s("h2d") > 0
    assert t.step_timer.phase_s("step") > 0
    assert t.step_timer.phase_stats()["step"]["calls"] == len(t.history)


def test_trace_writes_profile(tmp_path):
    """A profile holds the program's spans on its host plane: with a
    session active ``obs.span`` is also a TraceAnnotation of the same
    name, and the clock anchor of the trace's ``meta`` record places
    the span's ``t0`` on the profiler's clock."""
    from jax.profiler import ProfileData

    logdir = str(tmp_path / "prof")
    path = str(tmp_path / "obs.jsonl")
    step = jax.jit(lambda a: a @ a)
    jax.block_until_ready(step(jnp.ones((64, 64))))
    with obs.session(trace_path=path), trace(logdir):
        for i in range(3):
            with obs.span("matmul_region", i=i):
                jax.block_until_ready(step(jnp.ones((64, 64))))
    files = glob.glob(logdir + "/**/*", recursive=True)
    assert any("trace" in f or "xplane" in f for f in files), files

    recs = read_trace(path)
    meta, closing = recs[0], recs[-1]
    spans = [r for r in recs if r["kind"] == "span"]
    assert [s["fields"]["i"] for s in spans] == [0, 1, 2]
    # Both records carry the two clocks read back to back; the drift
    # between them over this short run is far under a millisecond.
    assert closing["kind"] == "metrics"
    to_wall = meta["time_ns"] - meta["perf_counter_ns"]
    assert abs(closing["time_ns"] - closing["perf_counter_ns"]
               - to_wall) < 1_000_000

    (pb,) = glob.glob(logdir + "/**/*.xplane.pb", recursive=True)
    planes = {p.name: p for p in ProfileData.from_file(pb).planes}
    # Event times count from the profile's start, a wall-clock stat of
    # the "Task Environment" plane.
    start = dict(planes["Task Environment"].stats)["profile_start_time"]
    found = sorted(int(e.start_ns) for line in planes["/host:CPU"].lines
                   for e in line.events if e.name == "matmul_region")
    assert len(found) == 3
    for sp, at in zip(spans, found):
        anchored = int(sp["t0"] * 1e9) + to_wall - start
        assert abs(anchored - at) < 1_000_000, (anchored, at)
