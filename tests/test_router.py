"""Round-13 fleet router: cache-aware routing over N engine replicas.

Unit half: FAKE replicas (no jax work) pin the routing core —
membership off heartbeat staleness, affinity hit/miss decisions,
least-loaded fallback, QueueFull spillover, drain-and-reroute
idempotence by request id, and the EngineClosed-vs-enqueue race.
Integration half: two REAL in-process engines prove the routed
tokens keep solo-generate parity across a mid-request drain, the
``--request`` waterfall crosses the router, and the HTTP endpoint
serves the same contract cross-process.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

from distkeras_tpu import obs
from distkeras_tpu.obs.report import request_waterfall
from distkeras_tpu.obs.trace import read_trace
from distkeras_tpu.resilience.admission import RequestResult
from distkeras_tpu.resilience.health import write_beat, beat_age
from distkeras_tpu.serving import (EngineClosed, EngineEndpoint,
                                   HttpReplica, InProcessReplica,
                                   PagedBatcher, QueueFull, Router)
from distkeras_tpu.serving.residency import stem_hexes
from helpers import serve_cfg, toy_params


# ------------------------------------------------------- fake replicas


class FakeReplica:
    """A replica that admits into a bounded table and finishes
    requests only when the test says so — routing decisions become
    fully deterministic and jax-free."""

    remote = False

    def __init__(self, name, lanes=2, max_queue=2, block=8,
                 resident=(), prefix_ids=(), healthy=True):
        self.name = name
        self.lanes = lanes
        self.max_queue = max_queue
        self.block = block
        self.resident = list(resident)      # hex stem digests
        self.prefix_ids = list(prefix_ids)
        self.is_healthy = healthy
        self.closed = False
        self._next = 0
        self.live = {}                      # rid -> (prompt, max_new)
        self.done = {}                      # rid -> RequestResult
        self.enqueued = []                  # admission order
        self.steps = 0

    def set_rid_base(self, base):
        self._next = max(self._next, base)

    def enqueue(self, prompt, max_new_tokens, **kw):
        if self.closed:
            raise EngineClosed("fake closed")
        if len(self.live) >= self.lanes + self.max_queue:
            raise QueueFull("fake full")
        rid = self._next
        self._next += 1
        self.live[rid] = (np.asarray(prompt, np.int32),
                          int(max_new_tokens))
        self.enqueued.append(rid)
        return rid

    def complete_all(self, status="ok"):
        for rid, (prompt, n) in list(self.live.items()):
            tokens = np.concatenate(
                [prompt, np.zeros(n, np.int32)])
            self.done[rid] = RequestResult(
                request_id=rid, tokens=tokens, status=status,
                prompt_len=prompt.size)
            del self.live[rid]

    def poll(self, rid):
        return self.done.get(rid)

    def step(self):
        self.steps += 1

    def healthy(self):
        return self.is_healthy

    def residency(self):
        return {"stem_hashes": self.resident,
                "prefix_ids": self.prefix_ids, "block": self.block,
                "queue_depth": max(0, len(self.live) - self.lanes),
                "lanes_busy": min(len(self.live), self.lanes),
                "lanes": self.lanes}

    def load(self):
        return (max(0, len(self.live) - self.lanes),
                min(len(self.live), self.lanes), self.lanes)


def _prompt(rng, n=20):
    return rng.integers(0, 64, (n,)).astype(np.int32)


# ------------------------------------------------------------- routing


def test_affinity_hit_routes_to_resident_replica(rng):
    prompt = _prompt(rng)
    r0 = FakeReplica("r0")
    r1 = FakeReplica("r1", resident=stem_hexes(prompt[:-1], 8))
    router = Router([r0, r1])
    router.enqueue(prompt, 4)
    assert len(r1.enqueued) == 1 and not r0.enqueued

    # Miss: an unrelated prompt falls back to least-loaded — r0 (r1
    # now carries the routed request).
    router.enqueue(_prompt(rng), 4)
    assert len(r0.enqueued) == 1


def test_affinity_prefers_longest_resident_prefix(rng):
    prompt = _prompt(rng, 33)
    stems = stem_hexes(prompt[:-1], 8)           # 4 full blocks
    r0 = FakeReplica("r0", resident=stems[:1])
    r1 = FakeReplica("r1", resident=stems)
    router = Router([r0, r1])
    router.enqueue(prompt, 4)
    assert len(r1.enqueued) == 1 and not r0.enqueued


def test_least_loaded_fallback_spreads_by_load(rng):
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    router = Router([r0, r1])
    rids = [router.enqueue(_prompt(rng), 4) for _ in range(4)]
    assert len(r0.enqueued) == 2 and len(r1.enqueued) == 2
    for r in (r0, r1):
        r.complete_all()
    assert set(router.pump()) == set(rids)
    assert all(router.take(x).ok for x in rids)


def test_round_robin_policy_alternates(rng):
    r0, r1 = FakeReplica("r0", lanes=8), FakeReplica("r1", lanes=8)
    router = Router([r0, r1], policy="round_robin")
    for _ in range(4):
        router.enqueue(_prompt(rng), 4)
    assert len(r0.enqueued) == 2 and len(r1.enqueued) == 2


def test_queuefull_spills_to_next_candidate_then_caller(rng):
    r0 = FakeReplica("r0", lanes=1, max_queue=0)
    r1 = FakeReplica("r1", lanes=1, max_queue=0)
    router = Router([r0, r1])
    router.enqueue(_prompt(rng), 4)
    router.enqueue(_prompt(rng), 4)      # spillover to the other
    assert len(r0.enqueued) == 1 and len(r1.enqueued) == 1
    # Every live replica saturated: NOW the caller sees QueueFull,
    # and the rejected request leaves no router-side residue.
    with pytest.raises(QueueFull):
        router.enqueue(_prompt(rng), 4)
    assert router.queued == 0
    res = router.shutdown(max_steps=0)
    assert len(res) == 2                 # only the accepted two


def test_prefix_id_routes_to_advertising_replica(rng):
    r0 = FakeReplica("r0")
    r1 = FakeReplica("r1", prefix_ids=[5])
    router = Router([r0, r1])
    router.enqueue(_prompt(rng), 4, prefix_id=5)
    assert len(r1.enqueued) == 1 and not r0.enqueued
    with pytest.raises(ValueError, match="not resident"):
        router.enqueue(_prompt(rng), 4, prefix_id=9)


# ---------------------------------------------------------- membership


def test_membership_via_heartbeat_staleness(rng, tmp_path):
    t = [0.0]
    clock = lambda: t[0]
    hb = str(tmp_path)
    window = 2.0

    def health_of(host):
        def probe():
            aged = beat_age(hb, host, clock=clock)
            return aged is not None and (aged[1]
                                         or aged[0] <= window)
        return probe

    write_beat(hb, 0, 0, 1, clock=clock)
    write_beat(hb, 1, 0, 1, clock=clock)
    r0 = FakeReplica("r0", lanes=8)
    r1 = FakeReplica("r1", lanes=8)
    router = Router([InProcessReplicaLike(r0, health_of(0)),
                     InProcessReplicaLike(r1, health_of(1))],
                    clock=clock, health_interval=0.5)
    rids = [router.enqueue(_prompt(rng), 4) for _ in range(4)]
    assert len(r0.enqueued) == 2 and len(r1.enqueued) == 2
    epoch0 = router.epoch

    # Host 1's beats stop; past the window its replica leaves and its
    # two accepted requests reroute to r0 — none are lost.
    t[0] = 3.0
    write_beat(hb, 0, 0, 2, clock=clock)
    router.pump()
    assert router.replicas_up() == ["r0"]
    assert router.epoch > epoch0
    assert len(r0.enqueued) == 4
    r0.complete_all()
    router.pump()
    assert sorted(router.results()) == sorted(rids)

    # A fresh beat rejoins it under a newer epoch.
    t[0] = 3.6
    write_beat(hb, 0, 0, 3, clock=clock)
    write_beat(hb, 1, 0, 2, clock=clock)
    router.pump()
    assert router.replicas_up() == ["r0", "r1"]


class InProcessReplicaLike:
    """FakeReplica + an injected health probe (the InProcessReplica
    ``health=`` shape, without needing a real engine)."""

    remote = False

    def __init__(self, fake, health):
        self._fake = fake
        self._health = health
        self.name = fake.name

    def healthy(self):
        return bool(self._health())

    def __getattr__(self, item):
        return getattr(self._fake, item)


# ---------------------------------------------------- drain-and-reroute


def test_dead_replica_reroutes_accepted_requests(rng):
    r0 = FakeReplica("r0", lanes=8)
    r1 = FakeReplica("r1", lanes=8)
    router = Router([r0, r1], health_interval=0.0)
    rids = [router.enqueue(_prompt(rng), 4) for _ in range(4)]
    dead = r0 if len(r0.enqueued) else r1
    survivor = r1 if dead is r0 else r0
    dead.is_healthy = False
    router.pump()
    assert router.replicas_up() == [survivor.name]
    assert len(survivor.enqueued) == 4   # every accepted request moved
    survivor.complete_all()
    router.pump()
    results = router.results()
    assert sorted(results) == sorted(rids)
    assert all(results[x].ok for x in rids)


def test_result_before_death_wins_over_reroute(rng):
    """Idempotence ordering: a request its replica finished just
    before dying is RECORDED, not rerouted — one terminal result per
    request id, from the replica that actually served it."""
    r0 = FakeReplica("r0", lanes=8)
    r1 = FakeReplica("r1", lanes=8)
    router = Router([r0, r1], health_interval=0.0)
    rid = router.enqueue(_prompt(rng), 4)
    served = r0 if r0.enqueued else r1
    served.complete_all()
    served.is_healthy = False            # dies WITH the result ready
    router.pump()
    res = router.take(rid)
    assert res.ok and res.request_id == rid
    other = r1 if served is r0 else r0
    assert not other.enqueued            # never rerouted


def test_reroute_parks_when_fleet_saturated_then_recovers(rng):
    r0 = FakeReplica("r0", lanes=1, max_queue=0)
    r1 = FakeReplica("r1", lanes=1, max_queue=0)
    router = Router([r0, r1], health_interval=0.0)
    a = router.enqueue(_prompt(rng), 4)
    b = router.enqueue(_prompt(rng), 4)
    dead = r0 if r0.enqueued else r1
    survivor = r1 if dead is r0 else r0
    dead.is_healthy = False
    router.pump()
    # The survivor is full (it holds its own request): the dead
    # replica's request PARKS instead of surfacing QueueFull to a
    # caller who already holds an id.
    assert router.queued == 1
    survivor.complete_all()
    router.pump()                        # frees a slot; backlog routes
    assert router.queued == 0
    survivor.complete_all()
    router.pump()
    results = router.results()
    assert sorted(results) == sorted([a, b])
    assert all(r.ok for r in results.values())


def test_drain_replica_moves_unfinished_requests(rng):
    r0 = FakeReplica("r0", lanes=8)
    r1 = FakeReplica("r1", lanes=8)
    router = Router([r0, r1], health_interval=1e9)
    rids = [router.enqueue(_prompt(rng), 4) for _ in range(4)]
    target = r0 if r0.enqueued else r1
    other = r1 if target is r0 else r0
    n_target = len(target.enqueued)
    router.drain_replica(target.name)
    assert target.name not in router.replicas_up()
    assert len(other.enqueued) == 4      # 4 - n_target + rerouted
    other.complete_all()
    router.pump()
    assert sorted(router.results()) == sorted(rids), n_target


def test_prefix_request_dies_with_its_replica_as_structured_error(rng):
    """A prefix_id is replica-local: when its only advertising
    replica dies, the reroute cannot serve the request anywhere —
    it must become a structured ``"error"`` result, never an
    exception out of the pump round."""
    r0 = FakeReplica("r0", prefix_ids=[5])
    r1 = FakeReplica("r1")
    router = Router([r0, r1], health_interval=0.0)
    rid = router.enqueue(_prompt(rng), 4, prefix_id=5)
    assert r0.enqueued
    other = router.enqueue(_prompt(rng), 4)   # plain request, reroutable
    r0.is_healthy = False
    router.pump()                             # must not raise
    res = router.take(rid)
    assert res.status == "error" and "prefix_id" in res.error
    r1.complete_all()
    router.pump()
    assert router.take(other).ok


def test_step_thread_failure_flips_healthy():
    """InProcessReplica's driver thread dying on an engine.step()
    exception must flip healthy() so the router reroutes instead of
    hanging that replica's requests forever."""
    class BoomEngine:
        _next_id = 0
        closed = False
        queued = 1

        def running(self):
            return [0]

        def step(self):
            raise RuntimeError("boom")

    rep = InProcessReplica("boomer", BoomEngine())
    rep.start()
    deadline = time.monotonic() + 10.0
    while rep.healthy() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not rep.healthy()
    rep.stop()


# ----------------------------------------------------------- lifecycle


def test_engineclosed_wins_enqueue_race(rng):
    router = Router([FakeReplica("r0")])
    router.begin_shutdown()
    with pytest.raises(EngineClosed):
        router.enqueue(_prompt(rng), 4)
    assert router.shutdown(max_steps=0) == {}


def test_shutdown_cancels_stragglers(rng):
    r0 = FakeReplica("r0", lanes=8)
    router = Router([r0])
    rid = router.enqueue(_prompt(rng), 4)
    res = router.shutdown(max_steps=2)   # fake never completes
    assert res[rid].status == "cancelled"


def test_replica_scoped_slo_breach_demotes_automatically(rng):
    """Round-14 satellite: ``Router.slo_rules`` stamps one
    ``replica=``-labeled SloRule per attached replica, the breach
    event carries the label, and a single ``breach_demoter()``
    subscriber (no per-replica closure) demotes exactly the replica
    the breaching rule is scoped to."""
    from distkeras_tpu.obs.metrics import MetricsRegistry
    from distkeras_tpu.obs.slo import SloEngine, SloRule

    t = [0.0]
    r0, r1 = FakeReplica("r0"), FakeReplica("r1")
    router = Router([r0, r1], clock=lambda: t[0])
    rules = router.slo_rules(
        SloRule("serving.request_s", percentile=0.5, threshold=1.0,
                window_s=10.0))
    assert [r.replica for r in rules] == ["r0", "r1"]

    events = []
    reg = MetricsRegistry()
    eng = SloEngine(reg, rules, clock=lambda: t[0],
                    emit=lambda name, **f: events.append((name, f)))
    eng.subscribe(router.breach_demoter())
    h = reg.histogram("serving.request_s")
    for _ in range(5):
        h.observe(5.0)
    eng.tick()
    breaches = [f for n, f in events if n == "slo.breach"]
    # Both replicas' rules watch the same aggregated metric here, so
    # both breach — each event labeled with ITS replica.
    assert {b["replica"] for b in breaches} == {"r0", "r1"}
    assert all(m.degraded_until > t[0]
               for m in router._members.values())
    assert reg.counter("slo.breaches").value(
        metric="serving.request_s", q="p50", replica="r0") == 1

    # Degraded replicas sort behind a healthy newcomer until the
    # cooldown passes — the routing effect the label exists for.
    r2 = FakeReplica("r2")
    router.add_replica(r2)
    router.enqueue(_prompt(rng), 4)
    assert len(r2.enqueued) == 1 and not r0.enqueued \
        and not r1.enqueued


def test_expired_on_arrival_never_routes(rng):
    t = [10.0]
    r0 = FakeReplica("r0")
    router = Router([r0], clock=lambda: t[0])
    rid = router.enqueue(_prompt(rng), 4, ttl=0.0)
    assert router.take(rid).timed_out
    assert not r0.enqueued


# ------------------------------------------- integration: real engines

BLOCK = 8


@pytest.fixture(scope="module")
def engine_params():


    cfg = serve_cfg()
    return toy_params(cfg), cfg


def _paged(params, cfg, **kw):
    kw.setdefault("prompt_buckets", (8,))
    kw.setdefault("max_queue", 8)
    return PagedBatcher(params, cfg, lanes=2, block=BLOCK,
                        n_blocks=2 * (cfg.max_len // BLOCK) + 1, **kw)


def test_two_engine_affinity_and_parity(engine_params, rng):
    from helpers import generate

    params, cfg = engine_params
    engines = [_paged(params, cfg) for _ in range(2)]
    router = Router([InProcessReplica(f"r{i}", e)
                     for i, e in enumerate(engines)])
    stem = rng.integers(0, 64, (8,)).astype(np.int32)
    tails = rng.integers(0, 64, (4, 4)).astype(np.int32)
    prompts = [np.concatenate([stem, t]) for t in tails]
    rids = [router.enqueue(p, 5) for p in prompts]
    while any(router.poll(x) is None for x in rids):
        router.step()
    results = {x: router.take(x) for x in rids}
    # Affinity co-located the shared stem: 3 of 4 admissions hit.
    assert sum(e.stem_hit_blocks for e in engines) >= 3
    for x, p in zip(rids, prompts):
        solo = np.asarray(generate(params, p[None], cfg, 5))[0]
        np.testing.assert_array_equal(results[x].tokens, solo)


def test_drain_midstream_keeps_parity_and_waterfall(engine_params,
                                                    rng, tmp_path):
    from helpers import generate

    params, cfg = engine_params
    trace = str(tmp_path / "router.jsonl")
    engines = [_paged(params, cfg) for _ in range(2)]
    router = Router([InProcessReplica(f"r{i}", e)
                     for i, e in enumerate(engines)])
    prompt = rng.integers(0, 64, (6,)).astype(np.int32)
    with obs.session(trace_path=trace):
        rid = router.enqueue(prompt, 10)
        router.step()                   # partial decode on hop 0
        src = router._requests[rid].replica
        router.drain_replica(src)       # forces the re-route hop
        res = router.drain(rid)
        assert res.ok
        solo = np.asarray(generate(params, prompt[None], cfg, 10))[0]
        np.testing.assert_array_equal(res.tokens, solo)
    wf = request_waterfall(read_trace(trace), rid)
    assert wf["found"] and wf["status"] == "ok"
    assert wf["reroutes"] == 1
    names = [s["name"] for s in wf["stages"]]
    assert "router.route" in names and "router.reroute" in names
    assert "serving.emit" in names and "serving.finish" in names
    # The final hop's stages carry the serving replica's name.
    replicas = {s.get("replica") for s in wf["stages"]
                if s["name"] == "serving.emit"}
    assert replicas and None not in replicas
    assert wf["tokens"] == 10


def test_residency_digest_and_endpoint(engine_params, rng):
    params, cfg = engine_params
    eng = _paged(params, cfg)
    pid = eng.pin_prefix(rng.integers(0, 64, (8,)).astype(np.int32))
    doc = eng.residency()
    assert doc["block"] == BLOCK and doc["lanes"] == 2
    assert pid in doc["prefix_ids"]
    assert len(doc["stem_hashes"]) == 1        # one pinned full block
    with obs.session(serve_port=0, residency=eng.residency) as sess:
        url = sess.server.url + "/residency"
        got = json.loads(urllib.request.urlopen(url, timeout=5).read())
        assert got["stem_hashes"] == doc["stem_hashes"]
        assert got["block"] == BLOCK
    eng.unpin_prefix(pid)


def test_http_endpoint_serves_router(engine_params, rng):
    params, cfg = engine_params
    eng = _paged(params, cfg)
    ep = EngineEndpoint(eng, host_id=3)
    ep.start(step=True)
    try:
        replica = HttpReplica("h3", ep.addr)
        router = Router([replica], health_interval=0.0)
        prompt = rng.integers(0, 64, (6,)).astype(np.int32)
        rid = router.enqueue(prompt, 5)
        deadline = time.monotonic() + 60.0
        while router.poll(rid) is None:
            router.pump()
            assert time.monotonic() < deadline
            time.sleep(0.01)
        res = router.take(rid)
        assert res.ok and res.prompt_len == 6
        assert len(res.generated) == 5
        # The endpoint's rid base keeps fleet traces collision-free.
        assert res.request_id == rid and rid < 1_000_000
        doc = json.loads(urllib.request.urlopen(
            f"http://{ep.addr}/residency", timeout=5).read())
        assert doc["block"] == BLOCK
        assert replica.healthy()
    finally:
        ep.stop()
    assert not replica.healthy()
    router.pump()                        # health probe flips it down
    assert router.replicas_up() == []
