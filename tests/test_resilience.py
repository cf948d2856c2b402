"""Resilience subsystem (SURVEY.md §5: the reference dies whole-job).

Resume correctness is *bit-for-bit*: an injected kill at step N followed
by Supervisor auto-resume must reproduce the uninterrupted run's loss
trajectory exactly and land on identical parameters.  Serving deadlines
must never let an expired request occupy a decode lane, and a faulting
draft model must degrade to the plain decode path, not kill requests.
"""

import sys
import time

import jax
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.checkpoint import CheckpointManager
from helpers import generate, serve_cfg, spec_draft_cfg, toy_params
from distkeras_tpu.resilience import (EngineClosed, FaultInjected,
                                      FaultPlan, Preempted, QueueFull,
                                      Supervisor, chaos)
from distkeras_tpu.serving import ContinuousBatcher, SpeculativeBatcher

from conftest import make_blobs, make_mlp

CFG = serve_cfg(rope=False)
DRAFT = spec_draft_cfg(rope=False)

COMMON = dict(loss="sparse_categorical_crossentropy",
              worker_optimizer="sgd", learning_rate=0.05,
              batch_size=16, num_epoch=2)  # 16 rounds over 128 blobs


@pytest.fixture(scope="module")
def params():
    return toy_params(CFG)


@pytest.fixture()
def fake_clock():
    t = [0.0]

    def clock():
        return t[0]

    clock.advance = lambda dt: t.__setitem__(0, t[0] + dt)
    return clock


def _weights(model):
    return [np.asarray(w) for w in model.get_weights()]


# ------------------------------------------------------------- chaos plans


def test_fault_plan_rejects_unknown_site():
    with pytest.raises(ValueError, match="unknown chaos site"):
        FaultPlan().fail("no.such.site")


def test_fault_plan_fires_at_step_and_respects_times():
    plan = FaultPlan().fail("train.round", at=3, times=2)
    with plan:
        for rnd in range(1, 6):
            if rnd == 3:
                with pytest.raises(FaultInjected):
                    chaos.probe("train.round", step=rnd)
            else:
                chaos.probe("train.round", step=rnd)
        # `at` pins to the counter value: round 3 already passed, so the
        # second allotted firing never triggers.
    assert plan.events == [("train.round", 3, "fail")]
    # inactive outside the with-block: probes are free no-ops
    chaos.probe("train.round", step=3)


def test_fault_plan_probabilistic_rules_are_seeded():
    def firings(seed):
        plan = FaultPlan(seed).fail("serving.step", times=None, p=0.5)
        with plan:
            for _ in range(32):
                try:
                    chaos.probe("serving.step")
                except FaultInjected:
                    pass
        return [n for (_, n, _) in plan.events]

    assert firings(7) == firings(7)
    assert firings(7) != firings(8)


def test_fault_plans_do_not_nest():
    with FaultPlan():
        with pytest.raises(RuntimeError, match="already active"):
            FaultPlan().__enter__()


# -------------------------------------------------- pickle checkpoint backend


def test_pickle_backend_roundtrip(tmp_path):
    import jax.numpy as jnp

    state = {"a": jnp.arange(12.0).reshape(3, 4),
             "step": jnp.asarray(7, jnp.int32)}
    with CheckpointManager(str(tmp_path / "c"), backend="pickle") as m:
        assert m.backend == "pickle"
        assert m.latest_step() is None
        m.save(state, step=3)
        m.wait_until_finished()
        out = m.restore({"a": jnp.zeros((3, 4)),
                         "step": jnp.asarray(0, jnp.int32)})
    np.testing.assert_array_equal(out["a"], state["a"])
    assert int(out["step"]) == 7


def test_pickle_backend_orbax_parity_semantics(tmp_path):
    import jax.numpy as jnp

    with CheckpointManager(str(tmp_path / "c"), backend="pickle",
                           max_to_keep=2) as m:
        for s in (1, 2, 3):
            m.save({"v": jnp.asarray(float(s))}, step=s, force=True)
        assert m.all_steps() == [2, 3]          # GC'd like orbax
        with pytest.raises(ValueError, match="already exists"):
            m.save({"v": jnp.asarray(9.0)}, step=3, force=True)
    with CheckpointManager(str(tmp_path / "empty"),
                           backend="pickle") as m:
        with pytest.raises(FileNotFoundError):
            m.restore({"x": np.zeros(2)})


def test_missing_orbax_raises_clearly_and_auto_falls_back(
        tmp_path, monkeypatch):
    monkeypatch.setitem(sys.modules, "orbax", None)
    monkeypatch.setitem(sys.modules, "orbax.checkpoint", None)
    with pytest.raises(ImportError, match="backend='pickle'"):
        CheckpointManager(str(tmp_path / "c"), backend="orbax")
    with CheckpointManager(str(tmp_path / "c"), backend="auto") as m:
        assert m.backend == "pickle"


@pytest.mark.chaos
def test_checkpoint_save_fault_injectable(tmp_path):
    import jax.numpy as jnp

    with CheckpointManager(str(tmp_path / "c"), backend="pickle") as m:
        with FaultPlan().fail("checkpoint.save"):
            with pytest.raises(FaultInjected):
                m.save({"v": jnp.asarray(1.0)}, step=1)
        m.save({"v": jnp.asarray(1.0)}, step=1)  # plan gone: save lands
        assert m.all_steps() == [1]


# --------------------------------------------------------------- supervisor


def test_supervisor_requires_durable_trainer(tmp_path):
    with pytest.raises(ValueError, match="checkpoint_dir"):
        Supervisor(dk.SingleTrainer(make_mlp(), **COMMON))
    with pytest.raises(ValueError, match="checkpoint_every"):
        Supervisor(dk.SingleTrainer(
            make_mlp(), checkpoint_dir=str(tmp_path / "c"), **COMMON))


@pytest.mark.chaos
@pytest.mark.parametrize("kill_round,via_signal", [(7, False), (6, True)])
def test_kill_at_step_then_autoresume_bit_for_bit(tmp_path, kill_round,
                                                  via_signal):
    """The acceptance contract: injected kill at an arbitrary step ->
    Supervisor auto-resumes -> final parameters identical (allclose,
    CPU) to an uninterrupted run, resumed loss trajectory bit-for-bit.

    Exception kills die BEFORE the round commits (resume replays it);
    graceful SIGTERM forces a synchronous checkpoint of the preempted
    round first (resume continues one round later) — even at a round
    the periodic checkpoint_every cadence would have skipped.
    """
    x, y = make_blobs(n=128)
    ds = dk.Dataset.from_arrays(x, y)

    straight = dk.SingleTrainer(make_mlp(), **COMMON)
    ref = straight.train(ds)

    every = 4 if via_signal else 1  # sigterm: prove the forced sync save
    t = dk.SingleTrainer(make_mlp(), checkpoint_dir=str(tmp_path / "c"),
                         checkpoint_every=every,
                         checkpoint_backend="pickle", **COMMON)
    sup = Supervisor(t, max_retries=2, backoff=0.0, max_backoff=0.0,
                     jitter=0.0)
    plan = FaultPlan()
    if via_signal:
        plan.preempt("train.round", at=kill_round, via_signal=True)
    else:
        plan.fail("train.round", at=kill_round)
    with plan:
        out = sup.run(ds)

    for wr, wo in zip(_weights(ref), _weights(out)):
        np.testing.assert_allclose(wr, wo, rtol=1e-5, atol=1e-6)
    resume_at = kill_round if via_signal else kill_round - 1
    assert t.history == straight.history[resume_at:]
    outcomes = [a.outcome for a in sup.attempts]
    assert outcomes == (["preempted", "ok"] if via_signal
                        else ["fault", "ok"])
    if via_signal:
        # 6 is not a multiple of checkpoint_every=4: only the forced
        # preemption save can have committed it.
        assert sup.attempts[1].resumed_from == kill_round


@pytest.mark.chaos
def test_supervisor_retries_checkpoint_save_fault(tmp_path):
    x, y = make_blobs(n=128)
    ds = dk.Dataset.from_arrays(x, y)
    t = dk.SingleTrainer(make_mlp(), checkpoint_dir=str(tmp_path / "c"),
                         checkpoint_every=1, checkpoint_backend="pickle",
                         **COMMON)
    sup = Supervisor(t, max_retries=2, backoff=0.0, max_backoff=0.0,
                     jitter=0.0)
    with FaultPlan().fail("checkpoint.save", at=5):
        sup.run(ds)
    assert [a.outcome for a in sup.attempts] == ["fault", "ok"]
    assert sup.attempts[1].resumed_from == 4  # durable through round 4


@pytest.mark.chaos
def test_sigterm_interrupts_backoff_immediately(tmp_path):
    """A SIGTERM arriving during the backoff window must not ride out
    the sleep: the Supervisor's backoff waits on the preempt event, so
    the preemption cuts it short and the next attempt's first round
    boundary runs the normal forced-sync-checkpoint path.  The
    regression: a 30 s backoff used to delay the preemption checkpoint
    by the full 30 s — well past any eviction notice."""
    import threading

    x, y = make_blobs(n=128)
    ds = dk.Dataset.from_arrays(x, y)
    t = dk.SingleTrainer(make_mlp(), checkpoint_dir=str(tmp_path / "c"),
                         checkpoint_every=1, checkpoint_backend="pickle",
                         **COMMON)
    sup = Supervisor(t, max_retries=2, backoff=30.0, max_backoff=30.0,
                     jitter=0.0, handle_sigterm=False)
    # Deliver the "SIGTERM" around the fault retry's backoff window
    # (the chaos probe outranks the preempt check at a round boundary,
    # so the round-1 fault fires first in every interleaving).
    threading.Timer(0.5, sup.preempt_event.set).start()
    t0 = time.monotonic()
    with FaultPlan().fail("train.round", at=1):
        sup.run(ds)
    elapsed = time.monotonic() - t0
    assert elapsed < 15.0, (
        f"backoff was not interrupted: run took {elapsed:.1f}s against "
        "a 30s backoff")
    outcomes = [a.outcome for a in sup.attempts]
    # fault -> (interrupted backoff) -> preempted at the next round
    # boundary -> clean resumed finish.
    assert outcomes == ["fault", "preempted", "ok"], outcomes


@pytest.mark.chaos
def test_supervisor_exhausts_retries_and_reraises(tmp_path):
    x, y = make_blobs(n=128)
    ds = dk.Dataset.from_arrays(x, y)
    t = dk.SingleTrainer(make_mlp(), checkpoint_dir=str(tmp_path / "c"),
                         checkpoint_every=1, checkpoint_backend="pickle",
                         **COMMON)
    sup = Supervisor(t, max_retries=1, backoff=0.0, max_backoff=0.0,
                     jitter=0.0)
    with FaultPlan().fail("train.round", at=1, times=None):
        with pytest.raises(FaultInjected):
            sup.run(ds)
    assert [a.outcome for a in sup.attempts] == ["fault", "fault"]


@pytest.mark.chaos
@pytest.mark.slow
def test_supervisor_wraps_lm_trainer(tmp_path):
    """The supervisor is trainer-family-wide: the flagship LMTrainer
    resumes through an injected kill to the same params as straight."""
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 64, (64, 17)).astype(np.int32)
    kw = dict(optimizer="sgd", learning_rate=0.05, batch_size=8,
              num_epoch=1, seed=3)

    cfg = serve_cfg(max_len=16, rope=False)
    straight = dk.LMTrainer(cfg, **kw)
    ref_params = straight.train(rows)

    t = dk.LMTrainer(cfg, checkpoint_dir=str(tmp_path / "c"),
                     checkpoint_every=1, checkpoint_backend="pickle",
                     **kw)
    sup = Supervisor(t, max_retries=1, backoff=0.0, max_backoff=0.0,
                     jitter=0.0)
    with FaultPlan().fail("train.round", at=5):
        out = sup.run(rows)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    assert t.history == straight.history[4:]


# ------------------------------------------------------- serving deadlines


def test_expired_deadline_never_occupies_a_lane(params, rng, fake_clock):
    eng = ContinuousBatcher(params, CFG, lanes=2, max_queue=2,
                            clock=fake_clock)
    prompt = rng.integers(0, 64, (4,)).astype(np.int32)
    rid = eng.enqueue(prompt, 5, ttl=0.0)
    res = eng.take(rid)
    assert res.timed_out and res.status == "timeout"
    np.testing.assert_array_equal(res.tokens, prompt)  # nothing decoded
    assert eng.free_lanes() == [0, 1]
    # bare submit() honors the same contract: no lane, structured result
    assert eng.submit(prompt, 5, ttl=-1.0) is None
    (res,) = eng.results().values()
    assert res.timed_out and eng.free_lanes() == [0, 1]


def test_midflight_deadline_evicts_lane_with_partial_result(
        params, rng, fake_clock):
    eng = ContinuousBatcher(params, CFG, lanes=2, clock=fake_clock)
    prompt = rng.integers(0, 64, (4,)).astype(np.int32)
    lane = eng.submit(prompt, 10, ttl=5.0)
    assert lane is not None
    eng.step()
    eng.step()
    fake_clock.advance(6.0)
    # The third call reads the SECOND round (a round is read by the
    # call after its dispatch) past the deadline and keeps its token:
    # the straddling window's.  The third round, in flight at the
    # eviction, is dropped.
    eng.step()
    (res,) = eng.results().values()
    assert res.status == "timeout" and len(res.generated) == 2
    # evicted: the lane is immediately reusable
    assert eng.free_lanes() == [0, 1]
    # ... and the partial tokens match the solo run's prefix
    solo = np.asarray(generate(params, prompt[None], CFG, 10))[0]
    np.testing.assert_array_equal(res.tokens, solo[:len(res.tokens)])


def test_queued_request_expiring_before_admission_never_runs(
        params, rng, fake_clock):
    eng = ContinuousBatcher(params, CFG, lanes=1, max_queue=2,
                            clock=fake_clock)
    ra = eng.enqueue(rng.integers(0, 64, (3,)), 4)
    rb = eng.enqueue(rng.integers(0, 64, (3,)), 4, ttl=1.0)  # queued
    fake_clock.advance(2.0)
    while eng.running() or eng.queued:
        eng.step()
    res = eng.results()
    assert res[ra].ok
    assert res[rb].timed_out
    assert len(res[rb].tokens) == 3  # prompt only: never decoded


# ---------------------------------------------------- queue / backpressure


def test_bounded_queue_backpressure_and_fifo_completion(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=1, max_queue=2)
    prompts = [rng.integers(0, 64, (3,)).astype(np.int32)
               for _ in range(3)]
    rids = [eng.enqueue(p, 4) for p in prompts]
    assert eng.queued == 2
    with pytest.raises(QueueFull, match="max_queue"):
        eng.enqueue(prompts[0], 4)
    res = eng.shutdown()
    assert [res[r].ok for r in rids] == [True] * 3
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            res[rid].tokens, np.asarray(generate(params, p[None],
                                                 CFG, 4))[0])


def test_enqueue_keeps_fifo_order_over_freed_lanes(params, rng):
    """A new enqueue must not jump ahead of an already-queued request
    when a lane happens to be free at enqueue time."""
    eng = ContinuousBatcher(params, CFG, lanes=1, max_queue=4)
    p = rng.integers(0, 64, (3,)).astype(np.int32)
    ra = eng.enqueue(p, 2)
    rb = eng.enqueue(p, 2)          # queued behind ra
    while eng.poll(ra) is None:
        eng.step()                  # ra finishes; lane frees
    rc = eng.enqueue(p, 2)          # must queue BEHIND rb... or rb
    # must already hold the lane (enqueue pumps first) — either way rb
    # decodes before rc.
    while eng.poll(rc) is None:
        eng.step()
    res = eng.results()
    assert res[ra].ok and res[rb].ok and res[rc].ok
    assert res[rb].request_id < res[rc].request_id


def test_bare_submit_deadline_result_reachable_by_id(params, rng,
                                                     fake_clock):
    eng = ContinuousBatcher(params, CFG, lanes=1, clock=fake_clock)
    p = rng.integers(0, 64, (3,)).astype(np.int32)
    assert eng.submit(p, 4, ttl=-1.0) is None
    rid = eng.last_request_id
    assert eng.take(rid).timed_out
    lane = eng.submit(p, 8, ttl=1.0)
    rid = eng.last_request_id
    # engine-full decline registers nothing: last_request_id must not
    # keep pointing at the previous request
    assert eng.submit(p, 4) is None and eng.last_request_id is None
    fake_clock.advance(2.0)
    eng.step()
    assert eng.take(rid).timed_out and lane not in eng.running()


def test_queued_request_failing_deferred_validation_reports_error(
        params, rng):
    """A queued request that fails engine-specific validation when its
    lane frees (the key-iff-sampling rule can only run at admission)
    must reach a terminal structured result, not crash the loop."""
    eng = ContinuousBatcher(params, CFG, lanes=1, max_queue=2,
                            temperature=0.8)
    p = rng.integers(0, 64, (3,)).astype(np.int32)
    ra = eng.enqueue(p, 3, key=jax.random.key(1))
    rb = eng.enqueue(p, 3)          # queued; missing key: invalid
    res = eng.shutdown()
    assert res[ra].ok
    assert res[rb].status == "error" and "key iff" in res[rb].error


def test_shutdown_lifecycle(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=1, max_queue=4)
    p = rng.integers(0, 64, (3,)).astype(np.int32)
    ra = eng.enqueue(p, 4)
    rb = eng.enqueue(p, 4)          # queued behind ra
    eng.begin_shutdown()
    with pytest.raises(EngineClosed):
        eng.enqueue(p, 2)
    with pytest.raises(EngineClosed):
        eng.submit(p, 2)
    res = eng.shutdown()            # drains lane AND queue
    assert res[ra].ok and res[rb].ok
    assert not eng.running() and eng.queued == 0


def test_shutdown_max_steps_cancels_structured(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=1, max_queue=4)
    p = rng.integers(0, 64, (3,)).astype(np.int32)
    ra = eng.enqueue(p, 8)
    rb = eng.enqueue(p, 8)
    res = eng.shutdown(max_steps=2)
    assert res[ra].status == "cancelled" and len(res[ra].generated) == 2
    assert res[rb].status == "cancelled" and len(res[rb].generated) == 0


# ------------------------------------------------- speculative degradation


@pytest.mark.chaos
def test_draft_fault_falls_back_and_completes_greedy_parity(rng):
    """Acceptance: a faulting draft model must not kill requests — the
    engine degrades to the plain decode path mid-flight and greedy
    outputs still match solo generate exactly."""
    tp = toy_params(CFG)
    dp = toy_params(DRAFT, 9)
    pa = rng.integers(0, 64, (5,)).astype(np.int32)
    pb = rng.integers(0, 64, (3,)).astype(np.int32)
    eng = SpeculativeBatcher(tp, dp, CFG, DRAFT, lanes=2, n_draft=3)
    la = eng.submit(pa, 8)
    eng.step()                       # healthy speculative round first
    lb = eng.submit(pb, 6)
    plan = FaultPlan().fail("serving.draft")
    with plan:
        eng.step()                   # draft faults -> degrade, no loss
    # The plan's per-site call counter starts at ITS activation: this
    # is the first draft probe the plan sees.
    assert eng.degraded and ("serving.draft", 1, "fail") in plan.events
    assert isinstance(eng.degraded_error, FaultInjected)
    while eng.running():
        eng.step()
    np.testing.assert_array_equal(
        eng.drain(la), np.asarray(generate(tp, pa[None], CFG, 8))[0])
    np.testing.assert_array_equal(
        eng.drain(lb), np.asarray(generate(tp, pb[None], CFG, 6))[0])
    # degraded engines still admit and serve new requests
    lc = eng.submit(pa, 4)
    while lc in eng.running():
        eng.step()
    np.testing.assert_array_equal(
        eng.drain(lc), np.asarray(generate(tp, pa[None], CFG, 4))[0])


def test_enqueue_vs_shutdown_race_is_atomic(params, rng):
    """`begin_shutdown` racing in-flight `enqueue`s: the closed check
    and the queue insert are atomic under one lock, and EngineClosed
    wins — every enqueue either gets its request in (and shutdown's
    drain reaches a terminal result for it) or raises EngineClosed;
    QueueFull only ever comes from an engine that is open.  No request
    may be silently lost."""
    import threading

    prompt = rng.integers(0, 64, (3,)).astype(np.int32)
    for trial in range(4):
        # One lane, held busy by a long request, so racing enqueues
        # only ever touch the queue — the contended structure.
        eng = ContinuousBatcher(params, CFG, lanes=1, max_queue=4)
        blocker = eng.enqueue(prompt, 25)
        outcomes: list = [None] * 8
        start = threading.Barrier(9)

        def worker(i):
            start.wait()
            try:
                outcomes[i] = ("rid", eng.enqueue(prompt, 2))
            except QueueFull:
                outcomes[i] = ("queue_full", None)
            except EngineClosed:
                outcomes[i] = ("closed", None)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        start.wait()
        eng.begin_shutdown()
        for t in threads:
            t.join()
        res = eng.shutdown(max_steps=3)
        assert all(o is not None for o in outcomes)
        accepted = [rid for kind, rid in outcomes if kind == "rid"]
        # Bounded queue held under the race...
        assert len(accepted) <= eng.max_queue
        # ...and EVERY accepted request reached a terminal result.
        assert blocker in res
        for rid in accepted:
            assert rid in res, f"request {rid} lost in the race"


# --------------------------------------------------- elastic lane tiers


def test_elastic_tiers_step_up_under_backpressure_and_back_down(
        params, rng):
    """The acceptance contract: sustained overload steps the lane tier
    up (with scale_up_after=1, ZERO QueueFull is raised — the overflow
    that would have raised is absorbed by the resize), requests all
    complete with exact solo parity, and a drained idle engine steps
    back down.  Tier moves are obs-visible."""
    from distkeras_tpu import obs

    prompts = [rng.integers(0, 64, (p,)).astype(np.int32)
               for p in (3, 5, 4, 6, 3)]
    with obs.session() as sess:
        eng = ContinuousBatcher(params, CFG, lane_tiers=(1, 2, 4),
                                max_queue=1, scale_up_after=1,
                                scale_down_after=2, prompt_buckets=(8,))
        assert eng.lanes == 1
        rids = [eng.enqueue(p, 6) for p in prompts]   # never raises
        assert eng.lanes == 4, "sustained overflow did not scale up"
        while any(eng.poll(r) is None for r in rids):
            eng.step()
        for _ in range(6):
            eng.step()               # idle: tier steps down 4->2->1
        assert eng.lanes == 1, "idle engine did not scale back down"
        snap = sess.registry.snapshot()
    res = {r: eng.take(r) for r in rids}
    for rid, p in zip(rids, prompts):
        assert res[rid].ok
        np.testing.assert_array_equal(
            res[rid].tokens,
            np.asarray(generate(params, p[None], CFG, 6))[0])
    resizes = {tuple(s["labels"].items()): s["value"]
               for s in snap["serving.resizes"]["series"]}
    assert resizes[(("direction", "up"),)] == 2
    assert resizes[(("direction", "down"),)] == 2
    assert "queue_full" not in str(snap.get("serving.rejected", ""))


def test_elastic_rejects_bare_submit_and_undeclared_windows(params, rng):
    eng = ContinuousBatcher(params, CFG, lane_tiers=(1, 2), max_queue=1,
                            prompt_buckets=(8,), step_windows=(1, 4))
    p = rng.integers(0, 64, (3,)).astype(np.int32)
    with pytest.raises(ValueError, match="enqueue"):
        eng.submit(p, 4)
    rid = eng.enqueue(p, 4)
    with pytest.raises(ValueError, match="step_windows"):
        eng.step(3)
    eng.step(4)                      # declared window: fine
    while eng.poll(rid) is None:
        eng.step()
    assert eng.take(rid).ok
    with pytest.raises(ValueError, match=">= 2 distinct tiers"):
        ContinuousBatcher(params, CFG, lane_tiers=(4,), max_queue=1)
    with pytest.raises(ValueError, match="max_queue"):
        ContinuousBatcher(params, CFG, lane_tiers=(1, 2))
    with pytest.raises(ValueError, match="include 1"):
        ContinuousBatcher(params, CFG, lane_tiers=(1, 2), max_queue=1,
                          step_windows=(4,))


def test_elastic_scale_up_after_counts_strikes(params, rng):
    """scale_up_after=2: the first overflow raises QueueFull (strike
    one), the second resizes instead — backpressure must be SUSTAINED
    before the engine spends memory on a bigger tier."""
    eng = ContinuousBatcher(params, CFG, lane_tiers=(1, 2), max_queue=1,
                            scale_up_after=2, prompt_buckets=(8,))
    p = rng.integers(0, 64, (3,)).astype(np.int32)
    ra = eng.enqueue(p, 4)
    rb = eng.enqueue(p, 4)           # queued
    with pytest.raises(QueueFull):
        eng.enqueue(p, 4)            # strike 1: still tier 1
    assert eng.lanes == 1
    rc = eng.enqueue(p, 4)           # strike 2: resize absorbs it
    assert eng.lanes == 2
    res = eng.shutdown()
    assert res[ra].ok and res[rb].ok and res[rc].ok


@pytest.mark.slow
def test_elastic_resize_preserves_inflight_requests(params, rng):
    """A tier move mid-decode must not disturb running lanes: requests
    admitted before, across, and after resizes all keep exact solo
    parity (the lane compaction gathers their device rows)."""
    eng = ContinuousBatcher(params, CFG, lane_tiers=(1, 2, 4),
                            max_queue=2, scale_up_after=1,
                            scale_down_after=2, prompt_buckets=(8,))
    pa = rng.integers(0, 64, (4,)).astype(np.int32)
    pb = rng.integers(0, 64, (6,)).astype(np.int32)
    ra = eng.enqueue(pa, 12)
    eng.step(); eng.step()           # ra decodes at tier 1
    rbs = [eng.enqueue(pb, 5) for _ in range(5)]  # forces tier up
    assert eng.lanes == 4
    while any(eng.poll(r) is None for r in [ra, *rbs]):
        eng.step()
    for _ in range(6):
        eng.step()                   # drain: tier steps back down 4->2->1
    assert eng.lanes == 1
    res = eng.results()
    np.testing.assert_array_equal(
        res[ra].tokens, np.asarray(generate(params, pa[None], CFG,
                                            12))[0])
    for r in rbs:
        np.testing.assert_array_equal(
            res[r].tokens, np.asarray(generate(params, pb[None], CFG,
                                               5))[0])


def test_speculative_deadline_and_queue(rng, fake_clock):
    tp = toy_params(CFG)
    dp = toy_params(DRAFT, 9)
    eng = SpeculativeBatcher(tp, dp, CFG, DRAFT, lanes=1, n_draft=2,
                             max_queue=1, clock=fake_clock)
    p = rng.integers(0, 64, (3,)).astype(np.int32)
    rid = eng.enqueue(p, 4, ttl=0.0)
    assert eng.take(rid).timed_out and eng.free_lanes() == [0]
    ra = eng.enqueue(p, 4)
    rb = eng.enqueue(p, 4)
    with pytest.raises(QueueFull):
        eng.enqueue(p, 4)
    res = eng.shutdown()
    assert res[ra].ok and res[rb].ok
