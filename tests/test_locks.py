"""The runtime lock-order sanitizer (utils/locks.py) and the
concurrency gate built on it.

Covers: the disabled fast path (the factories return RAW stdlib
locks — zero wrapper overhead), cycle / double-acquire /
callback-under-lock detection with acquisition stacks, the obs
held-time/contention histograms, the PR-8 SLO-subscriber deadlock as
a *detected* (not timed-out) regression, thread-leak hygiene around
``obs.session``, and the multi-threaded serving stress under the
sanitizer (enqueue vs step vs /metrics scrape vs SLO tick vs
``begin_shutdown``; the elastic-resize variant is slow-gated).

Positive tests that deliberately provoke violations are marked
``expected_lock_violations`` so conftest's gate (which fails any test
recording one) stands down.
"""

import threading
import time
import urllib.request

import numpy as np
import pytest

from distkeras_tpu import obs
from distkeras_tpu.utils import locks
from distkeras_tpu.utils.locks import (LockOrderViolation, TracedLock,
                                        TracedRLock, assert_unlocked)
from helpers import serve_cfg, toy_params


@pytest.fixture(autouse=True)
def _sanitizer_on():
    """These tests need the sanitizer regardless of how the suite was
    launched (conftest enables it via DKT_LOCK_SANITIZER, but the
    driver may override)."""
    was = locks.sanitizer_enabled()
    locks.enable_sanitizer()
    yield
    if not was:
        locks.disable_sanitizer()


# ------------------------------------------------------------ fast path


def test_disabled_factories_return_raw_stdlib_locks():
    """Sanitizer-off overhead is pinned at literally zero: the
    factories hand back the raw stdlib lock type, not a wrapper."""
    was = locks.sanitizer_enabled()
    locks.disable_sanitizer()
    try:
        assert type(TracedLock()) is type(threading.Lock())
        assert type(TracedRLock()) is type(threading.RLock())
        # And the guards are no-ops.
        assert_unlocked("anywhere")
        assert locks.violations() == []
        assert locks.lock_report()["enabled"] is False
    finally:
        if was:
            locks.enable_sanitizer()


def test_enabled_locks_are_drop_in():
    lk = TracedLock("t.dropin")
    assert lk.acquire() is True
    assert lk.locked()
    lk.release()
    assert not lk.locked()
    assert lk.acquire(False) is True
    # Contended try-acquire fails without blocking (from a thread: the
    # sanitizer correctly refuses same-thread re-acquire instead).
    got = []
    t = threading.Thread(target=lambda: got.append(lk.acquire(False)))
    t.start()
    t.join()
    assert got == [False]
    lk.release()
    rl = TracedRLock("t.dropin.r")
    with rl:
        with rl:  # reentrant nesting is legal
            assert rl._inner._is_owned()


# ------------------------------------------------------------ detection


@pytest.mark.expected_lock_violations
def test_lock_order_cycle_detected_with_both_stacks():
    a, b = TracedLock("t.a"), TracedLock("t.b")
    with a:
        with b:
            pass
    before = locks.violation_count()
    with pytest.raises(LockOrderViolation) as ei:
        with b:
            with a:
                pass
    assert ei.value.kind == "cycle"
    new = locks.violations()[before:]
    assert len(new) == 1 and new[0].kind == "cycle"
    # Both acquisition stacks are in the report: the current attempt
    # AND the recorded first-observed opposite edge.
    labels = [label for label, _ in new[0].stacks]
    assert any("now" in lab for lab in labels)
    assert any("recorded" in lab for lab in labels)
    assert all(frames for _, frames in new[0].stacks)


@pytest.mark.expected_lock_violations
def test_cycle_across_threads_detected():
    """The order graph is global: thread 1 takes a->b, thread 2
    taking b->a is an inversion even though nothing ever deadlocked."""
    a, b = TracedLock("t.x1"), TracedLock("t.x2")

    def t1():
        with a:
            with b:
                pass

    th = threading.Thread(target=t1)
    th.start()
    th.join()
    with pytest.raises(LockOrderViolation):
        with b:
            with a:
                pass


@pytest.mark.expected_lock_violations
def test_double_acquire_raises_instead_of_deadlocking():
    lk = TracedLock("t.double")
    t0 = time.monotonic()
    with pytest.raises(LockOrderViolation) as ei:
        with lk:
            with lk:
                pass
    assert ei.value.kind == "double-acquire"
    assert time.monotonic() - t0 < 5.0, "sanitizer blocked instead of raising"
    assert not lk.locked(), "outer hold was not released on the raise"


def test_failed_or_bounded_tryacquire_records_no_edge():
    """The deadlock-AVOIDANCE idiom must not poison the order graph:
    holding A and try-acquiring B (failed OR successful, non-blocking
    or bounded) records no A->B edge and raises nothing — only an
    unbounded blocking acquire can deadlock, so only it
    participates."""
    a, b = TracedLock("t.try1"), TracedLock("t.try2")
    hold = threading.Event()
    release = threading.Event()

    def holder():
        with b:
            hold.set()
            release.wait(5.0)

    th = threading.Thread(target=holder, name="dkt-test-tryholder")
    th.start()
    hold.wait(5.0)
    with a:
        assert b.acquire(False) is False       # failed trylock: no edge
        assert b.acquire(True, 0.05) is False  # bounded wait: no edge
    release.set()
    th.join(5.0)
    with a:                                    # successful trylock:
        assert b.acquire(False) is True        # still no edge
        b.release()
    # The opposite blocking order is therefore NOT an inversion.
    before = locks.violation_count()
    with b:
        with a:
            pass
    assert locks.violation_count() == before


def test_rlock_reentry_and_consistent_nesting_are_clean():
    outer, inner = TracedRLock("t.outer"), TracedLock("t.inner")
    before = locks.violation_count()
    for _ in range(3):
        with outer:
            with outer:
                with inner:
                    pass
    assert locks.violation_count() == before
    rep = locks.lock_report()
    assert rep["enabled"] and rep["locks"] >= 2 and rep["edges"] >= 1


@pytest.mark.expected_lock_violations
def test_assert_unlocked_guard():
    lk = TracedLock("t.guard")
    assert_unlocked("free thread")  # nothing held: fine
    with pytest.raises(LockOrderViolation) as ei:
        with lk:
            assert_unlocked("toy fire site")
    assert ei.value.kind == "held-in-callback"
    assert "t.guard" in str(ei.value)


# --------------------------------------------------------- obs export


def test_lock_histograms_reach_obs_registry():
    lk = TracedLock("t.histo")
    evt = threading.Event()

    def holder():
        with lk:
            evt.set()
            time.sleep(0.05)

    with obs.session() as sess:
        with lk:
            pass
        th = threading.Thread(target=holder, name="dkt-test-holder")
        th.start()
        evt.wait(5.0)
        with lk:   # contended: the holder still sleeps under it
            pass
        th.join(5.0)
        snap = sess.registry.snapshot()
    held = snap.get("lock.held_s")
    assert held is not None and any(
        s["labels"].get("lock") == "t.histo" and s["count"] >= 2
        for s in held["series"])
    wait = snap.get("lock.wait_s")
    assert wait is not None and any(
        s["labels"].get("lock") == "t.histo" and s["count"] >= 1
        for s in wait["series"])


# ------------------------------------------- the PR-8 deadlock shape


class _BuggyTicker:
    """The pre-hardening PR-8 SloEngine shape, as a toy: subscribers
    fire INSIDE the engine lock, and a subscriber calls back into the
    locked query API."""

    def __init__(self):
        self._lock = TracedLock("toy.slo")
        self._subscribers = []

    def windowed(self):
        with self._lock:
            return 42

    def tick_buggy(self):
        with self._lock:
            for fn in list(self._subscribers):  # dkt: ignore[lock-callback]
                fn()


@pytest.mark.expected_lock_violations
def test_pr8_subscriber_under_lock_is_detected_not_hung():
    """The regression that motivated this gate: a subscriber calling
    ``windowed()`` from inside the tick lock used to deadlock the
    ticker until a human caught it in review.  Under the sanitizer the
    same shape is a *reported violation* at the re-acquire site — no
    timeout involved."""
    toy = _BuggyTicker()
    toy._subscribers.append(toy.windowed)
    t0 = time.monotonic()
    with pytest.raises(LockOrderViolation) as ei:
        toy.tick_buggy()
    assert ei.value.kind == "double-acquire"
    assert time.monotonic() - t0 < 5.0
    # And the guard at a fire site catches the same shape BEFORE the
    # callback even runs:
    with pytest.raises(LockOrderViolation):
        with toy._lock:
            assert_unlocked("toy subscriber fire")


def test_real_slo_engine_subscriber_calls_windowed_cleanly():
    """The FIXED production shape stays fixed: a subscriber that calls
    ``SloEngine.windowed()`` runs with the engine lock released —
    under the sanitizer (which would fail this test on any
    regression), the tick completes and the callback sees a value."""
    from distkeras_tpu.obs.metrics import MetricsRegistry
    from distkeras_tpu.obs.slo import SloEngine, SloRule

    t = [0.0]
    reg = MetricsRegistry()
    rule = SloRule("serving.request_s", percentile=0.99,
                   threshold=0.1, window_s=5.0)
    eng = SloEngine(reg, [rule], clock=lambda: t[0])
    seen = []
    eng.subscribe(lambda r, v: seen.append(
        eng.windowed(r.metric, r.percentile, r.window_s)))
    hist = reg.histogram("serving.request_s")
    eng.tick()
    t[0] = 1.0
    hist.observe(0.5)
    eng.tick()
    assert seen and seen[0] is not None and seen[0] > rule.threshold


# ------------------------------------------------- session thread hygiene


def test_obs_session_close_stops_live_plane_threads():
    """The PR-8 EADDRINUSE class: closing the session must leave no
    dkt-telemetry / dkt-slo-tick thread running (conftest asserts this
    for every test; this pins the contract explicitly)."""
    rule = obs.SloRule("serving.request_s", percentile=0.5,
                       threshold=1.0, window_s=5.0)
    with obs.session(serve_port=0, slo_rules=[rule]) as sess:
        url = sess.server.url
        urllib.request.urlopen(url + "/metrics", timeout=5).read()
        live = {t.name for t in threading.enumerate()}
        assert "dkt-telemetry" in live and "dkt-slo-tick" in live
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        left = {t.name for t in threading.enumerate()
                if t.is_alive()
                and t.name in ("dkt-telemetry", "dkt-slo-tick")}
        if not left:
            break
        time.sleep(0.02)
    assert not left, f"live-plane threads survived session close: {left}"


# ------------------------------------------------- serving stress


def _stress(eng, *, submitters: int, per_thread: int, url,
            slo, tick: bool):
    """Shared driver: N submitter threads race the stepper, a
    /metrics scraper, the SLO ticker, and finally begin_shutdown.
    Returns per-thread errors (must be empty)."""
    errors = []
    stop = threading.Event()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 64, (5,)).astype(np.int32)
               for _ in range(per_thread)]
    rids = [[] for _ in range(submitters)]

    def submit(i):
        from distkeras_tpu.serving import EngineClosed, QueueFull

        try:
            for p in prompts:
                while True:
                    try:
                        rids[i].append(eng.enqueue(p, 4))
                        break
                    except QueueFull:
                        time.sleep(0.001)
                    except EngineClosed:
                        return
        except Exception as e:  # noqa: BLE001 — reported by the test
            errors.append(("submit", repr(e)))

    def step():
        try:
            while not stop.is_set():
                eng.step()
        except Exception as e:  # noqa: BLE001
            errors.append(("step", repr(e)))

    def scrape():
        try:
            while not stop.is_set():
                urllib.request.urlopen(url + "/metrics",
                                       timeout=5).read()
                time.sleep(0.002)
        except Exception as e:  # noqa: BLE001
            errors.append(("scrape", repr(e)))

    def ticker():
        try:
            while not stop.is_set():
                slo.tick()
                time.sleep(0.002)
        except Exception as e:  # noqa: BLE001
            errors.append(("tick", repr(e)))

    threads = [threading.Thread(target=submit, args=(i,),
                                name=f"dkt-test-submit{i}")
               for i in range(submitters)]
    threads += [threading.Thread(target=step, name="dkt-test-step"),
                threading.Thread(target=scrape, name="dkt-test-scrape")]
    if tick:
        threads.append(threading.Thread(target=ticker,
                                        name="dkt-test-tick"))
    for t in threads:
        t.start()
    for t in threads[:submitters]:   # submitters drain first
        t.join(120)
    eng.begin_shutdown()             # races the live stepper on purpose
    stop.set()
    for t in threads[submitters:]:
        t.join(120)
    assert not any(t.is_alive() for t in threads)
    results = eng.shutdown(max_steps=500)
    all_rids = [r for rs in rids for r in rs]
    assert all_rids, "no request was ever admitted"
    return errors, all_rids, results


def _stress_cfg():

    return serve_cfg()


def test_concurrency_stress_bounded():
    """Fast-gate stress: 2 submitters vs the decode stepper vs a live
    /metrics scraper vs explicit SLO ticks vs ``begin_shutdown``, all
    under the sanitizer.  Every request reaches a terminal structured
    result, no thread dies, no violation is recorded (conftest's gate
    re-asserts that)."""

    from distkeras_tpu.serving import ContinuousBatcher

    cfg = _stress_cfg()
    params = toy_params(cfg)
    eng = ContinuousBatcher(params, cfg, lanes=2, max_queue=4,
                            prompt_buckets=(8,))
    rule = obs.SloRule("serving.request_s", percentile=0.99,
                       threshold=60.0, window_s=10.0)
    with obs.session(serve_port=0, slo_rules=[rule]) as sess:
        errors, rids, results = _stress(
            eng, submitters=2, per_thread=6, url=sess.server.url,
            slo=sess.slo, tick=True)
    assert not errors, errors
    for r in rids:
        res = results.get(r) or eng.poll(r)
        assert res is not None, f"request {r} has no terminal result"
        assert res.status in ("ok", "timeout", "cancelled"), res


@pytest.mark.slow
def test_concurrency_stress_elastic_resize():
    """Slow-gate stress: the elastic engine adds tier resizes to the
    race — sustained QueueFull steps lanes up mid-flight while the
    scraper, ticker, and shutdown race on.  The resize compacts the
    lane table under the admission lock; the sanitizer watches every
    acquisition."""

    from distkeras_tpu.serving import ContinuousBatcher

    cfg = _stress_cfg()
    params = toy_params(cfg)
    eng = ContinuousBatcher(params, cfg, lane_tiers=(1, 2), max_queue=2,
                            scale_up_after=1, scale_down_after=2,
                            prompt_buckets=(8,))
    rule = obs.SloRule("serving.request_s", percentile=0.99,
                       threshold=60.0, window_s=10.0)
    with obs.session(serve_port=0, slo_rules=[rule]) as sess:
        errors, rids, results = _stress(
            eng, submitters=4, per_thread=8, url=sess.server.url,
            slo=sess.slo, tick=True)
    assert not errors, errors
    assert eng.tier_epoch >= 1, "backpressure never stepped a tier"
    for r in rids:
        res = results.get(r) or eng.poll(r)
        assert res is not None and res.status in ("ok", "timeout",
                                                  "cancelled"), (r, res)
