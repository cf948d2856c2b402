#!/usr/bin/env python
"""Chaos suite: run the full fault matrix against the resilience
subsystem on CPU and report a pass/fail table.

The deterministic, seedable end-to-end exercise of every failure mode
the subsystem claims to survive (docs/resilience.md):

- kill-at-step-N (exception and SIGTERM) under a Supervisor -> final
  parameters allclose to an uninterrupted run, resumed loss trajectory
  bit-for-bit;
- checkpoint-save faults -> retried by the Supervisor;
- serving deadlines -> expired requests never occupy a lane, running
  lanes evict with structured timeouts;
- bounded-queue backpressure -> QueueFull past capacity, queue drains
  as lanes free;
- speculative draft fault -> fallback decode completes every request
  (greedy: exact solo-generate parity);
- drain-then-shutdown -> no request is silently dropped.

The whole matrix runs under an obs telemetry session
(docs/observability.md): every injected fault, Supervisor attempt and
backoff lands in a JSONL event trace, and the suite ends with a
machine-readable **fault/recovery timeline** (one JSON object per
line) reconstructed from that trace — no log parsing.  ``--trace``
keeps the trace file for ``scripts/obs_report.py``.

``--cluster`` runs the MULTI-HOST ladder instead (PR 5): two OS
processes join one jax.distributed runtime and train under per-host
Supervisors wrapped by cluster drivers; chaos then kills one host
mid-training (``kill``), wedges its heartbeat writer (``stall``), or
partitions it (``drop``) — the survivor's collective watchdog fires
within the configured window, both hosts tear down and re-init
jax.distributed under a new cluster epoch, training resumes from the
cluster-consistent checkpoint, and the final weights must be
bit-for-bit identical to an uninterrupted two-host run.  Every
attempt's obs trace is merged (obs_report --merge machinery) into ONE
cross-host fault/recovery timeline, printed as JSON lines.

Round 13 adds the SERVING leg of ``--cluster``: ``serve_kill`` runs
two engine-replica processes (PagedBatcher behind an EngineEndpoint,
heartbeats + federation-published telemetry, lock sanitizer on) under
a cache-aware Router in the suite process, SIGKILLs one replica
mid-stream, and asserts drain-and-reroute completes every accepted
request, the dead replica's series drop out of ``/metrics/cluster``
and return after its restart, the merged timeline shows the re-route
hop, and every lock report is clean.

Round 17 adds ``serve_kill_prefill``, the DISAGGREGATED serving leg:
a role-labeled fleet (a ``prefill``-specialized and a ``decode``-
specialized replica process) serves 2-block prompts through the
prefill->ship->adopt hop, and chaos SIGKILLs the PREFILL replica
mid-transfer.  The router must fall back to plain routing (zero lost
requests), the decode replica's refcounted slab must drain to empty
once the unpins relay (shipped blocks leak nothing), the hop must
resume after the coordinated restart, and every lock ledger must be
clean.

Round 16 adds the ASYNC-TIER legs (docs/async.md): ``async_stall``
wedges a simulated host's heartbeat writer mid-training under the
bounded-staleness plane and asserts the fleet slows by less than tau
round-lengths (watchdog eviction, survivors at full quota — never a
full stall), printing the EpochStore/heartbeat membership audit
trail; ``async_kill_push`` kills a host at the ``cluster.push`` probe
and asserts the in-flight delta dropped cleanly with no torn merge
(pushes == merges == center version).

Round 20 adds the TRAIN→SERVE legs (docs/serving_guide.md):
``train_kill_push`` SIGKILLs the trainer process between a snapshot
version's bucket writes and its atomic manifest rename — the serving
fleet must keep serving the last complete version, the torn snapshot
must be refused (even when the version pointer names it), and the
canary tick must abort cleanly with zero lost requests;
``canary_bad_push`` publishes NaN weights with valid checksums — the
canary's logit-drift probe must trip, the fleet must roll back to the
promoted version (straddling requests all finish, tokens bit-identical
post-rollback), and the rejected version must be quarantined.

Usage: python scripts/chaos_suite.py [--seed N] [--kill-rounds 3,7,12]
                                     [--trace chaos.jsonl]
       python scripts/chaos_suite.py --cluster [--scenarios kill,stall]
       python scripts/chaos_suite.py --cluster --scenarios serve_kill
"""

import argparse
import os
import sys
import tempfile
import threading

os.environ.setdefault("KERAS_BACKEND", "jax")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

import distkeras_tpu as dk
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.models.generate import generate
from distkeras_tpu.resilience import (FaultPlan, QueueFull, Supervisor,
                                       chaos)
from distkeras_tpu.serving import ContinuousBatcher, SpeculativeBatcher

CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=32)
DRAFT = tfm.TransformerConfig(vocab_size=64, d_model=16, n_heads=2,
                              n_layers=1, d_ff=32, max_len=32)


def _mlp_data(seed):
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests"))
    from helpers import make_blobs, make_mlp

    x, y = make_blobs(n=128, seed=seed)
    return make_mlp, dk.Dataset.from_arrays(x, y)


COMMON = dict(loss="sparse_categorical_crossentropy",
              worker_optimizer="sgd", learning_rate=0.05,
              batch_size=16, num_epoch=2)  # 16 rounds


def check_kill_resume(seed, kill_round, via_signal):
    make_mlp, ds = _mlp_data(seed)
    straight = dk.SingleTrainer(make_mlp(), **COMMON)
    ref = straight.train(ds)
    ref_w = [np.asarray(w) for w in ref.get_weights()]
    with tempfile.TemporaryDirectory() as d:
        t = dk.SingleTrainer(make_mlp(), checkpoint_dir=os.path.join(d, "c"),
                             checkpoint_every=1, checkpoint_backend="pickle",
                             **COMMON)
        sup = Supervisor(t, max_retries=2, backoff=0.0, max_backoff=0.0,
                         jitter=0.0, seed=seed)
        plan = FaultPlan(seed)
        if via_signal:
            plan.preempt("train.round", at=kill_round, via_signal=True)
        else:
            plan.fail("train.round", at=kill_round)
        with plan:
            out = sup.run(ds)
        for a, b in zip(ref_w, [np.asarray(w) for w in out.get_weights()]):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        # Exception kill dies BEFORE round N commits -> resume replays
        # round N; graceful SIGTERM checkpoints round N synchronously
        # before raising -> resume continues at N + 1.
        resume_at = kill_round if via_signal else kill_round - 1
        assert t.history == straight.history[resume_at:], \
            "resumed loss trajectory diverged from the straight run"
        assert len(sup.attempts) == 2 and sup.attempts[-1].outcome == "ok"


def check_checkpoint_fault_retry(seed):
    make_mlp, ds = _mlp_data(seed)
    with tempfile.TemporaryDirectory() as d:
        t = dk.SingleTrainer(make_mlp(), checkpoint_dir=os.path.join(d, "c"),
                             checkpoint_every=1, checkpoint_backend="pickle",
                             **COMMON)
        sup = Supervisor(t, max_retries=2, backoff=0.0, max_backoff=0.0,
                         jitter=0.0, seed=seed)
        with FaultPlan(seed).fail("checkpoint.save", at=5):
            sup.run(ds)
        assert sup.attempts[0].outcome == "fault"
        assert sup.attempts[-1].outcome == "ok"


def check_serving_deadlines(seed):
    rng = np.random.default_rng(seed)
    params = tfm.init_params(jax.random.key(seed), CFG)
    t = [0.0]
    eng = ContinuousBatcher(params, CFG, lanes=2, max_queue=2,
                            clock=lambda: t[0])
    rid = eng.enqueue(rng.integers(0, 64, (4,)), 5, ttl=0.0)
    res = eng.take(rid)
    assert res.timed_out and eng.free_lanes() == [0, 1], \
        "expired request occupied a lane"
    lane = eng.submit(rng.integers(0, 64, (4,)).astype(np.int32), 10,
                      ttl=5.0)
    assert lane is not None
    eng.step()
    t[0] = 6.0
    eng.step()
    (res,) = eng.results().values()
    assert res.timed_out and len(res.generated) >= 1
    assert len(eng.free_lanes()) == 2, "timed-out lane was not evicted"


def check_backpressure(seed):
    rng = np.random.default_rng(seed)
    params = tfm.init_params(jax.random.key(seed), CFG)
    eng = ContinuousBatcher(params, CFG, lanes=1, max_queue=1)
    r1 = eng.enqueue(rng.integers(0, 64, (3,)), 3)
    r2 = eng.enqueue(rng.integers(0, 64, (3,)), 3)  # queued
    try:
        eng.enqueue(rng.integers(0, 64, (3,)), 3)
        raise AssertionError("queue overflow did not raise QueueFull")
    except QueueFull:
        pass
    res = eng.shutdown()
    assert res[r1].ok and res[r2].ok, "queued request lost"


def check_heartbeat_fault_kinds(seed):
    """The cluster fault kinds, single-process: a ``drop`` rule
    (partition) suppresses beats until peers see the host stale; beats
    flow again when the plan lifts."""
    import tempfile as _tf

    from distkeras_tpu.resilience.health import (HealthMonitor,
                                                  HeartbeatWriter,
                                                  read_beat)

    d = _tf.mkdtemp(prefix="chaos_hb_")
    w = HeartbeatWriter(d, host=1, interval=0.05)
    mon = HealthMonitor(d, host=0, num_hosts=2, window=60.0, grace=0.0)
    with FaultPlan(seed).drop("cluster.heartbeat", times=None):
        w.beat_once()
    assert read_beat(d, 1) is None, "partitioned beat was published"
    assert mon.stale_peers() == [1], "partitioned host not stale"
    w.beat_once()
    assert read_beat(d, 1)["host"] == 1, "beats did not resume"
    assert mon.stale_peers() == [], "fresh beat still read as stale"


def check_draft_fault_fallback(seed):
    rng = np.random.default_rng(seed)
    tp = tfm.init_params(jax.random.key(seed), CFG)
    dp = tfm.init_params(jax.random.key(seed + 9), DRAFT)
    prompt = rng.integers(0, 64, (5,)).astype(np.int32)
    eng = SpeculativeBatcher(tp, dp, CFG, DRAFT, lanes=2, n_draft=3)
    lane = eng.submit(prompt, 8)
    eng.step()
    with FaultPlan(seed).fail("serving.draft"):
        eng.step()
    assert eng.degraded, "draft fault did not degrade the engine"
    while lane in eng.running():
        eng.step()
    np.testing.assert_array_equal(
        eng.drain(lane), np.asarray(generate(tp, prompt[None], CFG, 8))[0])


# --------------------------------------------------- multi-host ladder
#
# The child below is ONE program started identically on every host of
# the cluster (deploy.py's SPMD model): join the epoch's
# jax.distributed runtime under a ClusterMember (heartbeats out,
# collective watchdog in), train the shared tiny LM under a per-host
# Supervisor with a SHARED orbax checkpoint store, and let chaos kill/
# stall/partition host 1 during epoch 0 only.  Epoch 1 must resume
# from the cluster-consistent step and finish; host 0 then writes the
# final weights for the bit-for-bit comparison.

CLUSTER_CHILD = '''
import os, sys
os.environ["KERAS_BACKEND"] = "jax"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
# Every cluster child runs under the LOCK SANITIZER (round 12): all
# engine/obs/resilience locks are instrumented, and the child emits a
# per-host locks.report event into its trace — the ladder fails on
# any recorded violation.  Must be set before distkeras imports.
os.environ.setdefault("DKT_LOCK_SANITIZER", "1")
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})

# Join the EPOCH-STAMPED runtime before anything touches a device
# (jax.distributed.initialize must precede the first computation, and
# importing the framework runs keras backend init): coordinator port =
# base + epoch, so a stale epoch's half-dead runtime cannot be
# rejoined.  Until the member starts beating below, liveness is the
# drivers' job (their launch grace covers import + join).
host = int(os.environ["DKT_CLUSTER_HOST"])
epoch = int(os.environ["DKT_CLUSTER_EPOCH"])
try:  # gloo: cross-process CPU collectives (mesh.enable_cpu_collectives)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
except Exception:
    pass
jax.distributed.initialize(
    "localhost:%d" % (int(os.environ["DKT_CLUSTER_BASE_PORT"]) + epoch),
    num_processes={nhosts}, process_id=host)

from distkeras_tpu import obs
from distkeras_tpu.resilience import FaultPlan, Supervisor, cluster

member = cluster.member_from_env()
trace = os.path.join({tracedir!r}, f"host{{host}}.e{{epoch}}.jsonl")
# Live telemetry plane (round 11): every host serves /metrics etc. on
# an ephemeral port, published into the coord dir's telemetry/ ledger
# via the DKT_CLUSTER_* env contract, so /metrics/cluster on ANY host
# federates the fleet; the rolling SLO rule makes the ladder double as
# a latency-regression canary (a breach event in any host's trace
# fails the ladder unless expected).
obs.enable(trace_path=trace, serve_port=0,
           slo_rules=[obs.SloRule("train.step_s", percentile=0.99,
                                  threshold=60.0, window_s=30.0)],
           slo_tick_s=0.25)
obs.event("cluster.child", host=host, epoch=epoch, phase="start")
member.start()
assert jax.process_count() == {nhosts}, jax.process_count()

import numpy as np
import distkeras_tpu as dk
from distkeras_tpu.models.transformer import TransformerConfig

rng = np.random.default_rng({seed})
tokens = rng.integers(0, 64, (64, 17)).astype(np.int32)
cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=17)
t = dk.LMTrainer(cfg, optimizer="sgd", learning_rate=0.05, batch_size=16,
                 num_epoch={num_epoch}, checkpoint_dir={ckdir!r},
                 checkpoint_every=1)
sup = Supervisor(t, max_retries=1, backoff=0.0, max_backoff=0.0,
                 jitter=0.0)

plan = None
spec = os.environ.get("DKT_CHAOS", "")
if spec and epoch == 0:
    kind, site, at = spec.split(":")
    plan = FaultPlan({seed})
    if kind == "kill":
        plan.kill(site, at=int(at))
    elif kind == "stall":
        plan.delay(site, seconds=3600.0, at=int(at))
    elif kind == "drop":
        plan.drop(site, at=None, times=None)
    else:
        raise ValueError(f"unknown chaos kind {{kind}}")
    plan.__enter__()

params = sup.run(tokens[host::{nhosts}])
obs.event("cluster.child", host=host, epoch=epoch, phase="trained",
          rounds=len(t.history))
from distkeras_tpu.utils import locks as _locks
_rep = _locks.lock_report()
obs.event("locks.report", host=host, epoch=epoch, **_rep)
assert not _rep["violations"], (
    "lock sanitizer violations on host %d:\\n" % host
    + "\\n".join(v.format() for v in _locks.violations()))
if host == 0:
    flat = {{"/".join(map(str, p)): np.asarray(v)
            for p, v in jax.tree_util.tree_flatten_with_path(params)[0]}}
    np.savez({out!r}, losses=np.asarray(t.history), **flat)
member.complete()
obs.disable()
print("HOST", host, "epoch", epoch, "DONE", flush=True)
'''


def _free_port():
    import socket

    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


# ------------------------------------------------ router serve ladder
#
# The round-13 serving leg of --cluster: TWO engine-replica processes
# (each: a PagedBatcher behind an EngineEndpoint, heartbeats, the
# live telemetry server federation-published, lock sanitizer on), a
# cache-aware Router in THIS process streaming requests at them, and
# a SIGKILL of replica 1 mid-stream.  Drain-and-reroute must complete
# every accepted request, the dead replica's series must drop out of
# /metrics/cluster and return after the restart, the merged timeline
# must show the re-route hop, and every lock report must be clean.

ROUTER_CHILD = '''
import os, sys, time
os.environ["KERAS_BACKEND"] = "jax"
os.environ.setdefault("DKT_LOCK_SANITIZER", "1")
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})

host = int(os.environ["DKT_CLUSTER_HOST"])
from distkeras_tpu import obs
from distkeras_tpu.resilience.health import HeartbeatWriter

trace = os.path.join({tracedir!r},
                     "replica%d.%d.jsonl" % (host, os.getpid()))
# serve_port=0: /metrics etc on an ephemeral port, published into the
# coord dir's telemetry/ ledger via the DKT_CLUSTER_* env — what the
# federation scraper proves drops and returns across the kill.
obs.enable(trace_path=trace, serve_port=0)
hb = HeartbeatWriter(os.path.join(os.environ["DKT_CLUSTER_DIR"], "hb"),
                     host, interval=0.2).start()

import numpy as np
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.serving import PagedBatcher
from distkeras_tpu.serving.router import EngineEndpoint

cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=128,
                            rope=True)
params = tfm.init_params(jax.random.key({seed}), cfg)
eng = PagedBatcher(params, cfg, lanes=2, block=8, n_blocks=33,
                   max_queue=16, prompt_buckets=(16,))
# Fixed port (parent-chosen): a restarted replica binds the SAME
# address, so the router's handle revives on the next health probe.
# DKT_SERVE_ROLE labels the endpoint for the round-17 disaggregated
# leg (prefill/decode split); unset = generalist (serve_kill).
role = os.environ.get("DKT_SERVE_ROLE") or None
ep = EngineEndpoint(eng, port=int(os.environ["DKT_SERVE_PORT"]),
                    role=role)
ep.start(step=True)
obs.event("router_child", host=host, phase="serving", port=ep.port,
          role=role or "generalist")
print("REPLICA", host, "UP", ep.port, flush=True)
stop = os.path.join(os.environ["DKT_CLUSTER_DIR"], "stop%d" % host)
while not os.path.exists(stop):
    time.sleep(0.1)
ep.stop()
# Refcounted-block leak ledger: with every request taken and every
# unpin relayed, an idle paged engine holds ZERO blocks (resident
# stem hashes are content-addressed bookkeeping, not held blocks).
_st = eng.allocator.stats()
obs.event("serving.allocator", host=host, role=role or "generalist",
          **_st)
if os.environ.get("DKT_ASSERT_IDLE_ALLOC"):
    assert _st["used"] == 0, "leaked KV blocks at exit: %r" % (_st,)
from distkeras_tpu.utils import locks as _locks
_rep = _locks.lock_report()
obs.event("locks.report", host=host, **_rep)
assert not _rep["violations"], (
    "lock sanitizer violations on replica %d:\\n" % host
    + "\\n".join(v.format() for v in _locks.violations()))
hb.mark_done()
obs.disable()
print("REPLICA", host, "DONE", flush=True)
'''


def run_router_kill_scenario(seed, workdir, n_req=12, kill_after=4):
    """The kill-a-replica-mid-stream leg.  Returns the number of
    failed assertions (0 = green), printing the same PASS/FAIL +
    timeline blocks as the training scenarios."""
    import glob
    import json
    import urllib.request

    import numpy as np

    from distkeras_tpu import obs
    from distkeras_tpu.obs.report import merge_traces
    from distkeras_tpu.serving.router import HttpReplica, Router
    from distkeras_tpu.utils import locks

    print("== cluster scenario: serve_kill (router drain-and-reroute)"
          " ==", flush=True)
    base = os.path.join(workdir, "serve_kill")
    coord = os.path.join(base, "coord")
    tracedir = os.path.join(base, "traces")
    os.makedirs(tracedir, exist_ok=True)
    os.makedirs(coord, exist_ok=True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(base, "replica.py")
    with open(script, "w", encoding="utf-8") as f:
        f.write(ROUTER_CHILD.format(repo=repo, tracedir=tracedir,
                                    seed=seed))
    ports = [_free_port(), _free_port()]

    def launch(h):
        import subprocess

        env = {**os.environ,
               "DKT_CLUSTER_DIR": coord,
               "DKT_CLUSTER_HOST": str(h),
               "DKT_CLUSTER_NHOSTS": "2",
               "DKT_CLUSTER_WINDOW": "2.0",
               "DKT_SERVE_PORT": str(ports[h])}
        return subprocess.Popen([sys.executable, script], env=env)

    def wait_port(h, deadline):
        import time as _time

        while True:
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{ports[h]}/healthz",
                    timeout=1.0).read()
                return
            except Exception:  # noqa: BLE001 — still starting
                assert _time.time() < deadline, \
                    f"replica {h} never came up on port {ports[h]}"
                _time.sleep(0.2)

    import time as _time

    locks.enable_sanitizer()
    children = [launch(0), launch(1)]
    scraper = _FederationScraper(coord)
    scraper.start()
    rng = np.random.default_rng(seed)
    router_trace = os.path.join(tracedir, "router.jsonl")
    failures = 0
    sess = None
    try:
        wait_port(0, _time.time() + 180)
        wait_port(1, _time.time() + 180)
        sess = obs.enable(trace_path=router_trace)
        router = Router(
            [HttpReplica("host0", f"127.0.0.1:{ports[0]}"),
             HttpReplica("host1", f"127.0.0.1:{ports[1]}")],
            policy="least_loaded", health_interval=0.3)
        stem = rng.integers(0, 64, (8,)).astype(np.int32)
        prompts = [np.concatenate(
            [stem, rng.integers(0, 64, (4,)).astype(np.int32)])
            for _ in range(n_req)]

        def serve_wave(wave_rids, deadline):
            done = set()
            while len(done) < len(wave_rids):
                assert _time.time() < deadline, (
                    f"serve_kill stalled: {len(done)}/"
                    f"{len(wave_rids)} done, "
                    f"up={router.replicas_up()}")
                router.pump()
                for r in wave_rids:
                    if r not in done and router.poll(r) is not None:
                        done.add(r)
                _time.sleep(0.05)

        # Wave 1: short requests, both replicas serving (also warms
        # every program outside the kill window).
        first = [router.enqueue(p, 8) for p in prompts[:kill_after]]
        serve_wave(first, _time.time() + 180)
        # Wave 2: LONG decodes, and the SIGKILL lands immediately
        # after their acceptance — the victim is guaranteed to hold
        # accepted, unfinished requests when it dies (enqueue is
        # synchronous: an id returned means the replica accepted).
        rest = [router.enqueue(p, 100) for p in prompts[kill_after:]]
        on_victim = sum(
            1 for r in rest
            if router._requests[r].replica == "host1")
        children[1].kill()
        children[1].wait(timeout=30)
        print(f"  killed replica 1 holding {on_victim} accepted "
              "request(s)", flush=True)
        assert on_victim >= 1, (
            "least-loaded spread put nothing on the victim — the "
            "kill exercised no reroute")
        serve_wave(rest, _time.time() + 300)
        rids = first + rest
        results = {r: router.take(r) for r in rids}
        lost = [r for r, v in results.items() if not v.ok]
        assert not lost, (
            f"accepted requests lost across the kill: "
            f"{[(r, results[r].status) for r in lost]}")
        snap = sess.registry.snapshot()
        n_reroutes = sum(
            s.get("value", 0) for s in
            snap.get("router.reroutes", {}).get("series", []))
        assert n_reroutes >= 1, \
            "the kill produced no drain-and-reroute"
        # Coordinated-restart half: the SAME address comes back and
        # the router's handle revives on a health probe.
        children[1] = launch(1)
        wait_port(1, _time.time() + 180)
        deadline = _time.time() + 60
        while "host1" not in router.replicas_up():
            assert _time.time() < deadline, \
                "restarted replica never rejoined the router"
            router.pump()
            _time.sleep(0.1)
        extra = router.enqueue(prompts[0], 4)
        deadline = _time.time() + 120
        while router.poll(extra) is None:
            assert _time.time() < deadline, \
                "post-restart request never finished"
            router.pump()
            _time.sleep(0.05)
        assert router.take(extra).ok
        print(f"  PASS  cluster/serve_kill: {n_req} streamed + 1 "
              f"post-restart request ok, {int(n_reroutes)} "
              "reroute(s), replica rejoined", flush=True)
    except Exception as e:  # noqa: BLE001 — report the ladder
        failures += 1
        print(f"  FAIL  cluster/serve_kill: {type(e).__name__}: {e}")
    finally:
        if sess is not None:
            obs.disable()
        for h in (0, 1):
            with open(os.path.join(coord, f"stop{h}"), "w"):
                pass
        for c in children:
            try:
                c.wait(timeout=60)
            except Exception:  # noqa: BLE001 — force it down
                c.kill()
        samples = scraper.stop()

    # Federation: both hosts seen, the killed one's series drop out,
    # then return after the restart.
    hosts_seen = [up for _, up in samples]
    try:
        both = next(i for i, up in enumerate(hosts_seen)
                    if up >= {0, 1})
        gone = next(i for i in range(both, len(hosts_seen))
                    if 0 in hosts_seen[i] and 1 not in hosts_seen[i])
        assert any(up >= {0, 1} for up in hosts_seen[gone:]), (
            "killed replica's series never returned to "
            "/metrics/cluster")
    except (StopIteration, AssertionError) as e:
        failures += 1
        print(f"  FAIL  cluster/serve_kill federation: "
              f"{type(e).__name__}: {e} (samples: {hosts_seen[:30]})")

    # Merged cross-process timeline: the re-route hop must be visible,
    # and every completing process must report a clean lock ledger.
    traces = sorted(glob.glob(os.path.join(tracedir, "*.jsonl")))
    merged = merge_traces(traces)
    print("--- cross-process serve timeline (serve_kill, JSONL) ---")
    for e in merged["timeline"]:
        if e["name"].startswith(("router", "locks", "serving.finish")):
            print(json.dumps({"t": round(e["t"], 4),
                              "host": e["host"], "event": e["name"],
                              **e["fields"]}))
    if not any(e["name"] == "router.reroute"
               for e in merged["timeline"]):
        failures += 1
        print("  FAIL  cluster/serve_kill: no router.reroute hop in "
              "the merged timeline")
    reports = [e for e in merged["timeline"]
               if e["name"] == "locks.report"]
    hosts_reported = {e["fields"].get("host") for e in reports}
    if not hosts_reported >= {0, 1}:
        failures += 1
        print(f"  FAIL  cluster/serve_kill: lock report missing for "
              f"replica(s) {sorted({0, 1} - hosts_reported)}")
    bad = [e for e in reports if e["fields"].get("violations")]
    if bad:
        failures += 1
        print("  FAIL  cluster/serve_kill: lock sanitizer "
              "violation(s) in replica report(s)")
    if locks.violation_count():
        failures += 1
        print("  FAIL  cluster/serve_kill: router-process lock "
              "sanitizer violations:")
        for v in locks.violations():
            print("  VIOLATION " + v.format())
    return failures


def run_router_prefill_kill_scenario(seed, workdir, n_wave1=4,
                                     n_wave2=6):
    """The round-17 disaggregated leg: a role-labeled fleet (host0 =
    ``decode``-specialized, host1 = ``prefill``-specialized) serving
    2-block prompts through the prefill->ship->adopt hop, and a
    SIGKILL of the PREFILL replica mid-transfer.  The router must fall
    back to plain routing (every accepted request completes on the
    decode replica — zero lost), the refcounted shipped blocks must
    leak NOTHING on the decode side (allocator drains to empty once
    the unpins relay), the hop must resume after the coordinated
    restart, and every lock ledger must be clean.  Returns the number
    of failed assertions (0 = green)."""
    import glob
    import json
    import threading
    import urllib.request

    import numpy as np

    from distkeras_tpu import obs
    from distkeras_tpu.obs.report import merge_traces
    from distkeras_tpu.serving.router import HttpReplica, Router
    from distkeras_tpu.utils import locks

    print("== cluster scenario: serve_kill_prefill (disaggregated "
          "hop under prefill death) ==", flush=True)
    base = os.path.join(workdir, "serve_kill_prefill")
    coord = os.path.join(base, "coord")
    tracedir = os.path.join(base, "traces")
    os.makedirs(tracedir, exist_ok=True)
    os.makedirs(coord, exist_ok=True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(base, "replica.py")
    with open(script, "w", encoding="utf-8") as f:
        f.write(ROUTER_CHILD.format(repo=repo, tracedir=tracedir,
                                    seed=seed))
    ports = [_free_port(), _free_port()]
    roles = ["decode", "prefill"]

    def launch(h):
        import subprocess

        env = {**os.environ,
               "DKT_CLUSTER_DIR": coord,
               "DKT_CLUSTER_HOST": str(h),
               "DKT_CLUSTER_NHOSTS": "2",
               "DKT_CLUSTER_WINDOW": "2.0",
               "DKT_SERVE_PORT": str(ports[h]),
               "DKT_SERVE_ROLE": roles[h],
               "DKT_ASSERT_IDLE_ALLOC": "1"}
        return subprocess.Popen([sys.executable, script], env=env)

    def wait_port(h, deadline):
        import time as _time

        while True:
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{ports[h]}/healthz",
                    timeout=1.0).read()
                return
            except Exception:  # noqa: BLE001 — still starting
                assert _time.time() < deadline, \
                    f"replica {h} never came up on port {ports[h]}"
                _time.sleep(0.2)

    import time as _time

    locks.enable_sanitizer()
    children = [launch(0), launch(1)]
    rng = np.random.default_rng(seed)
    router_trace = os.path.join(tracedir, "router.jsonl")
    failures = 0
    sess = None
    try:
        wait_port(0, _time.time() + 180)
        wait_port(1, _time.time() + 180)
        sess = obs.enable(trace_path=router_trace)
        dec = HttpReplica("host0", f"127.0.0.1:{ports[0]}",
                          role="decode")
        router = Router(
            [dec, HttpReplica("host1", f"127.0.0.1:{ports[1]}",
                              role="prefill")],
            policy="affinity", health_interval=0.3,
            residency_interval=0.2)
        router.pump()  # first residency refresh: the disagg planner
        # keys on the block geometry the tables now advertise.
        # 2-block prompts (the child engines run block=8, bucket 16):
        # a UNIQUE first block + a shared 1-block tail.  The planner
        # gates on the full-block stems of ``prompt[:-1]`` — one
        # block here, always fresh — so EVERY request takes the
        # ship->adopt hop (a shared first block would warm-skip all
        # but the first request per stem).
        stem = rng.integers(0, 64, (8,)).astype(np.int32)
        n_req = n_wave1 + n_wave2
        prompts = [np.concatenate(
            [rng.integers(0, 64, (8,)).astype(np.int32), stem])
            for _ in range(n_req)]

        def counter(name):
            snap = sess.registry.snapshot()
            return sum(s.get("value", 0) for s in
                       snap.get(name, {}).get("series", []))

        def serve_wave(wave_rids, deadline):
            done = set()
            while len(done) < len(wave_rids):
                assert _time.time() < deadline, (
                    f"serve_kill_prefill stalled: {len(done)}/"
                    f"{len(wave_rids)} done, "
                    f"up={router.replicas_up()}")
                router.pump()
                for r in wave_rids:
                    if r not in done and router.poll(r) is not None:
                        done.add(r)
                _time.sleep(0.05)

        # Wave 1: the healthy hop — prefill builds, ships, decode
        # adopts (also warms every program outside the kill window).
        first = [router.enqueue(p, 8) for p in prompts[:n_wave1]]
        serve_wave(first, _time.time() + 180)
        hops = counter("router.disagg_requests")
        assert hops >= 1, (
            "no request took the prefill->decode hop before the "
            "kill — the scenario exercised nothing")
        # Wave 2: LONG decodes with the SIGKILL racing the hop.  The
        # killer thread fires mid-enqueue (the hop runs synchronously
        # in the enqueue caller), and the enqueues after the kill land
        # before any health probe marks the victim down — those hops
        # fail at the prefill/transfer stage and MUST fall back to
        # plain routing, never surface to the caller.
        killer = threading.Thread(
            target=lambda: (_time.sleep(0.05), children[1].kill()),
            daemon=True)
        killer.start()
        rest = [router.enqueue(p, 100) for p in prompts[n_wave1:]]
        killer.join()
        children[1].wait(timeout=30)
        print("  killed prefill replica mid-transfer "
              f"({int(counter('router.disagg_fallbacks'))} hop "
              "fallback(s) at kill time)", flush=True)
        serve_wave(rest, _time.time() + 300)
        rids = first + rest
        results = {r: router.take(r) for r in rids}
        lost = [r for r, v in results.items() if not v.ok]
        assert not lost, (
            f"accepted requests lost across the prefill kill: "
            f"{[(r, results[r].status) for r in lost]}")
        fallbacks = counter("router.disagg_fallbacks")
        assert fallbacks >= 1, (
            "the prefill kill produced no hop fallback — nothing "
            "was mid-transfer")
        # Coordinated restart: the prefill replica returns on the
        # SAME address and the hop must RESUME (fresh stem, so the
        # warm-skip gate cannot hide a dead hop).
        children[1] = launch(1)
        wait_port(1, _time.time() + 180)
        deadline = _time.time() + 60
        while "host1" not in router.replicas_up():
            assert _time.time() < deadline, \
                "restarted prefill replica never rejoined the router"
            router.pump()
            _time.sleep(0.1)
        stem2 = rng.integers(0, 64, (8,)).astype(np.int32)
        extra = router.enqueue(np.concatenate(
            [stem2, rng.integers(0, 64, (8,)).astype(np.int32)]), 8)
        serve_wave([extra], _time.time() + 120)
        assert router.take(extra).ok
        assert counter("router.disagg_requests") > hops, (
            "the hop never resumed after the prefill restart")
        # Leak check: once every unpin has relayed, the decode
        # replica's refcounted slab must drain to empty — shipped
        # blocks pinned for adoption leak NOTHING across the chaos.
        capacity = 32          # the child's n_blocks=33 minus trash
        deadline = _time.time() + 60
        while True:
            free = dec.residency().get("kv_blocks_free")
            if free == capacity:
                break
            assert _time.time() < deadline, (
                f"decode replica still holds blocks after drain: "
                f"free={free}, expected {capacity}")
            router.pump()
            _time.sleep(0.1)
        print(f"  PASS  cluster/serve_kill_prefill: {n_req} + 1 "
              f"post-restart ok, {int(hops)} hop(s) pre-kill, "
              f"{int(fallbacks)} fallback(s), decode slab drained "
              f"to {capacity}/{capacity} free", flush=True)
    except Exception as e:  # noqa: BLE001 — report the ladder
        failures += 1
        print(f"  FAIL  cluster/serve_kill_prefill: "
              f"{type(e).__name__}: {e}")
    finally:
        if sess is not None:
            obs.disable()
        for h in (0, 1):
            with open(os.path.join(coord, f"stop{h}"), "w"):
                pass
        for c in children:
            try:
                c.wait(timeout=60)
            except Exception:  # noqa: BLE001 — force it down
                c.kill()

    # Merged cross-process timeline: the block-transfer hop and the
    # fallback must both be visible, the allocator ledgers empty, and
    # every lock report clean.
    traces = sorted(glob.glob(os.path.join(tracedir, "*.jsonl")))
    merged = merge_traces(traces)
    print("--- cross-process serve timeline (serve_kill_prefill, "
          "JSONL) ---")
    for e in merged["timeline"]:
        if e["name"].startswith(("router", "locks",
                                 "serving.allocator")):
            print(json.dumps({"t": round(e["t"], 4),
                              "host": e["host"], "event": e["name"],
                              **e["fields"]}))
    for name, what in (("router.block_transfer",
                        "no block-transfer hop"),
                       ("router.disagg_fallback",
                        "no hop fallback")):
        if not any(e["name"] == name for e in merged["timeline"]):
            failures += 1
            print(f"  FAIL  cluster/serve_kill_prefill: {what} in "
                  "the merged timeline")
    leaks = [e for e in merged["timeline"]
             if e["name"] == "serving.allocator"
             and e["fields"].get("used")]
    if leaks:
        failures += 1
        print("  FAIL  cluster/serve_kill_prefill: block leak in "
              f"exit ledger(s): {[e['fields'] for e in leaks]}")
    reports = [e for e in merged["timeline"]
               if e["name"] == "locks.report"]
    hosts_reported = {e["fields"].get("host") for e in reports}
    if not hosts_reported >= {0, 1}:
        failures += 1
        print(f"  FAIL  cluster/serve_kill_prefill: lock report "
              f"missing for replica(s) "
              f"{sorted({0, 1} - hosts_reported)}")
    bad = [e for e in reports if e["fields"].get("violations")]
    if bad:
        failures += 1
        print("  FAIL  cluster/serve_kill_prefill: lock sanitizer "
              "violation(s) in replica report(s)")
    if locks.violation_count():
        failures += 1
        print("  FAIL  cluster/serve_kill_prefill: router-process "
              "lock sanitizer violations:")
        for v in locks.violations():
            print("  VIOLATION " + v.format())
    return failures


def run_autoscale_spike_scenario(seed, workdir, ticks=14, spike_at=3,
                                 spike_len=6):
    """The round-19 autoscaling leg: one active replica (host0) plus
    two warm-pool replicas (host1, host2) behind the Autoscaler, a
    flash-spike trace driving the router hot, and a SIGKILL of the
    FIRST warm-pool replica exactly as the scale-up reaches for it.
    The join must abort cleanly (no route-table entry ever exists for
    the dead replica), the spike must be absorbed by the surviving
    warm replica, every accepted request must complete (zero lost),
    and the fleet must scale back down losslessly once the spike
    drains.  Returns the number of failed assertions (0 = green)."""
    import glob
    import json
    import urllib.request

    import numpy as np

    from distkeras_tpu import obs
    from distkeras_tpu.obs.report import merge_traces
    from distkeras_tpu.serving.autoscale import (Autoscaler,
                                                 AutoscalePolicy,
                                                 WarmPool)
    from distkeras_tpu.serving.router import HttpReplica, Router
    from distkeras_tpu.serving.traffic import TraceReplay
    from distkeras_tpu.utils import locks

    print("== cluster scenario: autoscale_spike (warm-pool scale-up "
          "under join-time death) ==", flush=True)
    base = os.path.join(workdir, "autoscale_spike")
    coord = os.path.join(base, "coord")
    tracedir = os.path.join(base, "traces")
    os.makedirs(tracedir, exist_ok=True)
    os.makedirs(coord, exist_ok=True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(base, "replica.py")
    with open(script, "w", encoding="utf-8") as f:
        f.write(ROUTER_CHILD.format(repo=repo, tracedir=tracedir,
                                    seed=seed))
    ports = [_free_port(), _free_port(), _free_port()]

    def launch(h):
        import subprocess

        env = {**os.environ,
               "DKT_CLUSTER_DIR": coord,
               "DKT_CLUSTER_HOST": str(h),
               "DKT_CLUSTER_NHOSTS": "3",
               "DKT_CLUSTER_WINDOW": "2.0",
               "DKT_SERVE_PORT": str(ports[h])}
        return subprocess.Popen([sys.executable, script], env=env)

    def wait_port(h, deadline):
        import time as _time

        while True:
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{ports[h]}/healthz",
                    timeout=1.0).read()
                return
            except Exception:  # noqa: BLE001 — still starting
                assert _time.time() < deadline, \
                    f"replica {h} never came up on port {ports[h]}"
                _time.sleep(0.2)

    import time as _time

    locks.enable_sanitizer()
    children = [launch(0), launch(1), launch(2)]
    router_trace = os.path.join(tracedir, "router.jsonl")
    failures = 0
    sess = None
    try:
        for h in range(3):
            wait_port(h, _time.time() + 180)
        sess = obs.enable(trace_path=router_trace)
        # host0 serves from the start; host1/host2 sit pre-compiled in
        # the warm pool with NO route-table entry until a scale-up
        # health-gates them in.
        # residency_interval=0.2: every pump refreshes the cached
        # queue_depth/lanes_busy the autoscaler's utilization signal
        # reads — without it the tiny engines drain each tick's
        # arrivals before the 2s default refresh ever sees them hot.
        router = Router(
            [HttpReplica("host0", f"127.0.0.1:{ports[0]}")],
            policy="least_loaded", health_interval=0.3,
            residency_interval=0.2)
        pool = WarmPool([
            HttpReplica("host1", f"127.0.0.1:{ports[1]}"),
            HttpReplica("host2", f"127.0.0.1:{ports[2]}")])
        asc = Autoscaler(router, pool, policy=AutoscalePolicy(
            min_replicas=1, max_replicas=2, up_threshold=0.9,
            down_threshold=0.2, up_after=1, down_after=3,
            cooldown_ticks=1))
        # Long decodes (max_new=16) at a rate one 2-lane replica
        # cannot drain inside a tick: the spike piles queue depth the
        # refreshed residency makes visible, driving utilization past
        # the scale-up threshold.  Pre-spike the trickle stays under
        # it, so the FIRST scale-up lands inside the spike — after
        # host1 is dead.
        trace = TraceReplay("spike", seed=seed, base_rate=0.3,
                            spike_at=spike_at, spike_len=spike_len,
                            spike_rate=20.0, max_new=(4, 8))
        # SIGKILL the FIFO head of the warm pool before the spike can
        # reach for it: the scale-up's join health gate must race the
        # death — abort cleanly, admit the survivor.
        children[1].kill()
        children[1].wait(timeout=30)
        print("  killed warm-pool replica 1 ahead of the join",
              flush=True)
        rids, retry = [], []
        for t in range(ticks):
            arrivals = (retry
                        + [trace.prompt(r, stem_len=8, tail_len=4,
                                        vocab=64) for r in
                           trace.requests_at(t)])
            retry = []
            for p in arrivals:
                try:
                    rids.append(router.enqueue(np.asarray(
                        p, np.int32), 16))
                except Exception:  # noqa: BLE001 — backpressure
                    retry.append(p)
            router.pump()
            asc.tick()
            _time.sleep(0.15)
        ups = [d for d in asc.decisions if d["action"] == "up"]
        assert ups, "the flash spike never triggered a scale-up"
        assert all(d["replica"] == "host2" for d in ups), (
            f"a dead warm-pool replica was admitted: {ups}")
        snap = router.fleet_snapshot()
        assert "host1" not in snap["replicas"], (
            "SIGKILLed warm-pool replica holds a route-table entry")
        assert "host2" in router.replicas_up(), (
            "surviving warm replica never joined the fleet")
        # Zero lost: every accepted request completes across the
        # aborted join and the scale-up.
        deadline = _time.time() + 300
        done = {}
        while len(done) < len(rids):
            assert _time.time() < deadline, (
                f"autoscale_spike stalled: {len(done)}/{len(rids)} "
                f"done, up={router.replicas_up()}")
            router.pump()
            for r in rids:
                if r not in done and router.poll(r) is not None:
                    done[r] = router.take(r)
            _time.sleep(0.05)
        lost = [r for r, v in done.items() if not v.ok]
        assert not lost, (
            f"requests lost across the spike: "
            f"{[(r, done[r].status) for r in lost]}")
        reg = sess.registry.snapshot()

        def _total(name):
            return sum(s.get("value", 0) for s in
                       reg.get(name, {}).get("series", []))

        assert _total("autoscale.join_aborts") >= 1, (
            "the killed warm-pool replica produced no join abort")
        # Spike drained: the idle fleet scales back down to the
        # envelope floor, pooling the retired still-warm handle.
        deadline = _time.time() + 60
        while len(router.replicas_up()) > 1:
            assert _time.time() < deadline, (
                "fleet never scaled back down after the spike "
                f"(up={router.replicas_up()})")
            router.pump()
            asc.tick()
            _time.sleep(0.2)
        assert len(pool) >= 1, \
            "retired replica handle was not returned to the warm pool"
        print(f"  PASS  cluster/autoscale_spike: {len(rids)} "
              f"request(s) ok across the spike, scale-up to "
              f"{ups[0]['replica']} after "
              f"{int(_total('autoscale.join_aborts'))} join "
              "abort(s), fleet back at the floor", flush=True)
    except Exception as e:  # noqa: BLE001 — report the ladder
        failures += 1
        print(f"  FAIL  cluster/autoscale_spike: "
              f"{type(e).__name__}: {e}")
    finally:
        if sess is not None:
            obs.disable()
        for h in (0, 1, 2):
            with open(os.path.join(coord, f"stop{h}"), "w"):
                pass
        for c in children:
            try:
                c.wait(timeout=60)
            except Exception:  # noqa: BLE001 — force it down
                c.kill()

    # Merged cross-process timeline: the scaling decisions and the
    # join abort must be visible, and the surviving replicas must
    # report clean lock ledgers (host1 died mid-join — no report).
    traces = sorted(glob.glob(os.path.join(tracedir, "*.jsonl")))
    merged = merge_traces(traces)
    print("--- cross-process autoscale timeline (autoscale_spike, "
          "JSONL) ---")
    for e in merged["timeline"]:
        if e["name"].startswith(("autoscale", "router.reroute",
                                 "locks")):
            print(json.dumps({"t": round(e["t"], 4),
                              "host": e["host"], "event": e["name"],
                              **e["fields"]}))
    decisions = [e for e in merged["timeline"]
                 if e["name"] == "autoscale.decision"]
    if not any(e["fields"].get("action") == "up" for e in decisions):
        failures += 1
        print("  FAIL  cluster/autoscale_spike: no scale-up decision "
              "in the merged timeline")
    if not any(e["fields"].get("action") == "abort"
               for e in decisions):
        failures += 1
        print("  FAIL  cluster/autoscale_spike: no join-abort "
              "decision in the merged timeline")
    reports = [e for e in merged["timeline"]
               if e["name"] == "locks.report"]
    hosts_reported = {e["fields"].get("host") for e in reports}
    if not hosts_reported >= {0, 2}:
        failures += 1
        print(f"  FAIL  cluster/autoscale_spike: lock report missing "
              f"for replica(s) {sorted({0, 2} - hosts_reported)}")
    bad = [e for e in reports if e["fields"].get("violations")]
    if bad:
        failures += 1
        print("  FAIL  cluster/autoscale_spike: lock sanitizer "
              "violation(s) in replica report(s)")
    if locks.violation_count():
        failures += 1
        print("  FAIL  cluster/autoscale_spike: router-process lock "
              "sanitizer violations:")
        for v in locks.violations():
            print("  VIOLATION " + v.format())
    return failures


# SLO breach classes (metric names) the cluster ladder tolerates.
# Empty on purpose: the in-child rule (train.step_s p99 < 60s over a
# 30s window) is generous enough that ANY breach means a real latency
# pathology — the ladder is a latency-regression canary, not just a
# recovery proof.
EXPECTED_BREACH_METRICS: frozenset = frozenset()


class _FederationScraper(threading.Thread):
    """Poll host 0's published telemetry address and scrape its
    ``/metrics/cluster`` while a cluster scenario runs; each sample
    records which hosts' series were present — how the ladder proves a
    killed host's series disappear and return across the coordinated
    restart."""

    def __init__(self, coord_dir: str, poll: float = 0.2):
        super().__init__(name="chaos-federation-scrape", daemon=True)
        self.coord_dir = coord_dir
        self.poll = poll
        self.samples: list = []   # (wall_t, frozenset(hosts up))
        # NOT _stop: threading.Thread owns a private _stop method.
        self._halt = threading.Event()

    def _scrape_once(self):
        import json as _json
        import urllib.request

        addr_path = os.path.join(self.coord_dir, "telemetry",
                                 "host0.addr")
        try:
            with open(addr_path, encoding="utf-8") as f:
                addr = _json.load(f)["addr"]
            with urllib.request.urlopen(
                    f"http://{addr}/metrics/cluster",
                    timeout=2.0) as resp:
                text = resp.read().decode("utf-8")
        except Exception:  # noqa: BLE001 — between epochs: no server
            return None
        up = set()
        for line in text.splitlines():
            if line.startswith("cluster_scrape_up{"):
                name, _, value = line.rpartition(" ")
                if value.strip().startswith("1"):
                    host = name.split('host="', 1)[1].split('"', 1)[0]
                    up.add(int(host))
        return frozenset(up)

    def run(self):
        import time as _time

        while not self._halt.wait(self.poll):
            up = self._scrape_once()
            if up is not None:
                self.samples.append((_time.time(), up))

    def stop(self) -> list:
        self._halt.set()
        self.join(timeout=5.0)
        return self.samples


def run_cluster_scenario(scenario, seed, workdir, window=2.0,
                         attempt_timeout=240.0, num_epoch=2,
                         kill_round=5):
    """One coordinated-restart scenario on 2 local hosts; returns
    (summaries, out_npz_path, trace_paths, federation_samples) —
    federation samples are the scraped ``/metrics/cluster`` host sets
    (round 11).  ``scenario`` None = no chaos (the uninterrupted
    reference run)."""
    import glob

    from distkeras_tpu.resilience.cluster import run_cluster_local

    name = scenario or "reference"
    base = os.path.join(workdir, name)
    coord = os.path.join(base, "coord")
    ckdir = os.path.join(base, "ckpt")
    tracedir = os.path.join(base, "traces")
    out = os.path.join(base, "host0.npz")
    os.makedirs(tracedir, exist_ok=True)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(base, "child.py")
    with open(script, "w", encoding="utf-8") as f:
        f.write(CLUSTER_CHILD.format(repo=repo, nhosts=2, seed=seed,
                                     ckdir=ckdir, out=out,
                                     tracedir=tracedir,
                                     num_epoch=num_epoch))
    per_host_env = {}
    if scenario == "kill":
        per_host_env = {1: {"DKT_CHAOS": f"kill:train.round:{kill_round}"}}
    elif scenario == "stall":
        per_host_env = {1: {"DKT_CHAOS": "stall:cluster.heartbeat:6"}}
    elif scenario == "drop":
        per_host_env = {1: {"DKT_CHAOS": "drop:cluster.heartbeat:0"}}
    elif scenario is not None:
        raise ValueError(f"unknown cluster scenario {scenario!r}")
    scraper = _FederationScraper(coord)
    scraper.start()
    try:
        summaries = run_cluster_local(
            [sys.executable, script], num_hosts=2, coord_dir=coord,
            per_host_env=per_host_env, base_port=_free_port(),
            checkpoint_dirs=[ckdir], window=window, poll=0.2,
            heartbeat_interval=0.4, grace=90.0, max_restarts=2,
            attempt_timeout=attempt_timeout)
    finally:
        samples = scraper.stop()
    return summaries, out, sorted(glob.glob(
        os.path.join(tracedir, "*.jsonl"))), samples


def run_async_scenarios(scenarios, seed, workdir):
    """The round-16 async-tier legs of ``--cluster`` (docs/async.md).
    Like ``serve_kill`` these run in-process — the hosts are simulated
    islands under a seeded virtual-time clock, so the legs are
    deterministic and fast while still exercising the real
    ``AsyncPlane`` membership/merge machinery and the real
    ``cluster.push``/``cluster.merge`` probe sites:

    * ``async_stall`` — a wedged-heartbeat straggler must slow the
      fleet by < tau round-lengths (watchdog eviction), never a full
      stall, with survivors completing their full quotas.
    * ``async_kill_push`` — a host killed mid-push must leave no torn
      merge: the in-flight delta is dropped cleanly
      (pushes == merges == center version) and the fleet drains.

    Returns the number of failed legs."""
    import json
    import shutil

    import numpy as np

    from distkeras_tpu.parallel.async_tier import AsyncSchedule
    from distkeras_tpu.resilience import chaos

    def blob_ds(n=256):
        import distkeras_tpu as dk

        rng = np.random.default_rng(seed)
        centers = rng.normal(0, 4.0, (4, 16))
        labels = rng.integers(0, 4, n)
        feats = (centers[labels]
                 + rng.normal(0, 0.5, (n, 16))).astype(np.float32)
        return dk.Dataset({"features": feats,
                           "label": labels.astype(np.int64)})

    def trainer(schedule, coord=None, tau=2):
        import keras

        import distkeras_tpu as dk

        keras.utils.set_random_seed(0)
        model = keras.Sequential([
            keras.Input((16,)),
            keras.layers.Dense(32, activation="relu"),
            keras.layers.Dense(4)])
        return dk.AsyncDP(model, hosts=3, tau=tau, schedule=schedule,
                          beat_window=1.5, coord_dir=coord,
                          loss="sparse_categorical_crossentropy",
                          worker_optimizer="sgd", learning_rate=0.05,
                          batch_size=2, num_epoch=2,
                          communication_window=2, seed=11)

    def audit_trail(coord):
        """The on-disk membership evidence the plane left behind:
        EpochStore generations + per-host heartbeat files."""
        epochs = sorted(os.listdir(os.path.join(coord, "epochs")))
        beats = {}
        for f in sorted(os.listdir(os.path.join(coord, "beats"))):
            with open(os.path.join(coord, "beats", f)) as fh:
                beats[f] = json.load(fh)
        return epochs, beats

    failures = 0
    if "async_stall" in scenarios:
        print("== cluster scenario: async_stall (bounded-staleness "
              "straggler) ==", flush=True)
        coord = os.path.join(workdir, "async_stall", "coord")
        shutil.rmtree(coord, ignore_errors=True)
        os.makedirs(coord)
        try:
            tau, ds = 2, blob_ds()
            t0 = trainer(AsyncSchedule(seed=3), tau=tau)
            t0.train(ds)
            t1 = trainer(AsyncSchedule(seed=3).stall(1, 2, 50.0),
                         coord=coord, tau=tau)
            t1.train(ds)
            m0 = t0.async_report["makespan"]
            m1 = t1.async_report["makespan"]
            assert m1 - m0 < tau * 1.0, (
                f"fleet slowed by {m1 - m0:.2f} virtual seconds — more "
                f"than tau={tau} round-lengths (full-stall behaviour)")
            assert t1.async_report["evicted"] == [1], (
                f"watchdog did not evict the wedged host: "
                f"{t1.async_report['evicted']}")
            for h in (0, 2):
                assert (t1.async_report["rounds"][h]
                        == t0.async_report["rounds"][h]), (
                    f"survivor {h} lost rounds to the straggler")
            epochs, beats = audit_trail(coord)
            assert len(epochs) >= 2, (
                f"eviction did not bump the membership epoch: {epochs}")
            print(f"  PASS  cluster/async_stall: 50s wedge cost the "
                  f"fleet {m1 - m0:.2f} virtual s (< tau={tau} "
                  f"rounds), host 1 evicted, survivors at full quota")
            print("--- membership audit trail (async_stall) ---")
            print(f"  epochs: {epochs}")
            for f, b in beats.items():
                print(f"  beat {f}: " + json.dumps(b))
        except Exception as e:  # noqa: BLE001 — report the ladder
            failures += 1
            print(f"  FAIL  cluster/async_stall: "
                  f"{type(e).__name__}: {e}")
    if "async_kill_push" in scenarios:
        print("== cluster scenario: async_kill_push (host loss "
              "mid-delta-publish) ==", flush=True)
        try:
            ds = blob_ds()
            t = trainer(AsyncSchedule(seed=3))
            with chaos.FaultPlan(seed=0).fail("cluster.push",
                                              at=5) as plan:
                t.train(ds)
            r = t.async_report
            assert plan.events == [("cluster.push", 5, "fail")], (
                f"probe never fired: {plan.events}")
            assert len(r["evicted"]) == 1, (
                f"killed host not evicted: {r['evicted']}")
            assert r["pushes"] == r["merges"] == r["version"], (
                f"torn merge: pushes={r['pushes']} merges={r['merges']} "
                f"version={r['version']}")
            assert r["members_final"] == [], (
                f"fleet did not drain: {r['members_final']}")
            print(f"  PASS  cluster/async_kill_push: push 5 died "
                  f"pre-publish, host {r['evicted'][0]} evicted, "
                  f"{r['merges']} merges == {r['pushes']} pushes "
                  f"(no torn merge), fleet drained")
        except Exception as e:  # noqa: BLE001 — report the ladder
            failures += 1
            print(f"  FAIL  cluster/async_kill_push: "
                  f"{type(e).__name__}: {e}")
    return failures


# ------------------------------------------- live weight push ladder
#
# The round-20 train→serve legs of --cluster: the trainer publishes
# versioned fusion-bucket snapshots (serving/publish.py) and a
# CanaryController pushes them across a hot_swap serving fleet.
# ``train_kill_push`` SIGKILLs the TRAINER process between a version's
# bucket writes and its atomic manifest rename (the publish.commit
# probe) and asserts the serving side never adopts the torn snapshot;
# ``canary_bad_push`` publishes a poisoned (NaN) version with VALID
# checksums — transport is healthy, the weights are not — and asserts
# the canary's logit-drift gate rolls the fleet back with zero lost
# requests.

TRAINER_PUSH_CHILD = '''
import os, sys
os.environ["KERAS_BACKEND"] = "jax"
os.environ.setdefault("DKT_LOCK_SANITIZER", "1")
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})

import numpy as np
import distkeras_tpu as dk
from distkeras_tpu.models.transformer import TransformerConfig
from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
from distkeras_tpu.resilience import FaultPlan
from distkeras_tpu.serving.publish import SnapshotPublisher

rng = np.random.default_rng({seed})
tokens = rng.integers(0, 64, (64, 17)).astype(np.int32)
cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=32)
t = dk.LMTrainer(cfg, optimizer="sgd", learning_rate=0.05, batch_size=16,
                 num_epoch=2, mesh=make_mesh(MeshSpec(data=1)),
                 seed={seed})
t.attach_publisher(SnapshotPublisher({snapdir!r}), every=1)
with FaultPlan({seed}).kill("publish.commit", at={kill_at}):
    t.train(tokens)
print("CHILD DONE (kill never fired)", flush=True)
'''


def _push_fleet(seed):
    """Two hot_swap engines behind a Router plus the canary plumbing —
    the serving half both push legs share."""
    from distkeras_tpu.serving.canary import CanaryController
    from distkeras_tpu.serving.router import InProcessReplica, Router

    params = tfm.init_params(jax.random.key(seed), CFG)
    engines = [ContinuousBatcher(params, CFG, lanes=2, hot_swap=True)
               for _ in range(2)]
    router = Router([InProcessReplica(f"r{i}", e)
                     for i, e in enumerate(engines)])
    template = jax.eval_shape(
        lambda: tfm.init_params(jax.random.key(seed), CFG))
    return engines, router, template, CanaryController


def _push_wave(router, n=4, max_new=6):
    """Serve one wave of greedy requests to completion; a request that
    fails to finish raises out of drain — completing IS the
    zero-lost-requests assertion."""
    rids = [router.enqueue([1 + i, 2, 3], max_new) for i in range(n)]
    out = []
    for r in rids:
        res = router.drain(r)
        toks = res["tokens"] if isinstance(res, dict) else res.tokens
        out.append(tuple(int(t) for t in toks))
    return out


def run_train_kill_push_scenario(seed, workdir, kill_at=2):
    """SIGKILL the trainer between bucket writes and the manifest
    rename of version ``kill_at``: the serving fleet must keep serving
    the last complete version, the torn snapshot must never be
    adopted, and the canary tick must abort cleanly."""
    from distkeras_tpu.serving.publish import (SnapshotCorrupt,
                                               SnapshotReader)
    from distkeras_tpu.utils import locks

    print("== cluster scenario: train_kill_push (trainer SIGKILL "
          "mid-publish) ==", flush=True)
    try:
        import subprocess

        snapdir = os.path.join(workdir, "push_snaps")
        os.makedirs(snapdir, exist_ok=True)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = os.path.join(workdir, "train_push_child.py")
        with open(script, "w") as f:
            f.write(TRAINER_PUSH_CHILD.format(
                repo=repo, seed=seed, snapdir=snapdir, kill_at=kill_at))
        proc = subprocess.run([sys.executable, script],
                              capture_output=True, text=True, timeout=600)
        assert proc.returncode == 137, (
            f"trainer child exited {proc.returncode}, expected 137 "
            f"(SIGKILL-equivalent)\n{proc.stdout[-400:]}"
            f"\n{proc.stderr[-800:]}")
        torn = os.path.join(snapdir, f"v{kill_at:08d}")
        assert os.path.isdir(torn), "kill fired before bucket writes"
        assert not os.path.exists(os.path.join(torn, "MANIFEST.json")), (
            "manifest present: the kill did not land mid-publish")

        engines, router, template, CanaryController = _push_fleet(seed)
        reader = SnapshotReader(snapdir)
        ctl = CanaryController(router, reader, CFG, template)
        base_viol = locks.violation_count()
        _push_wave(router)                       # serve on init params
        # LATEST never advanced past the last COMPLETE publish.
        assert reader.latest_version() == kill_at - 1, (
            reader.latest_version())
        rec = ctl.poll()
        assert rec is not None and rec["action"] == "promote", rec
        assert all(e.param_version == kill_at - 1 for e in engines)
        served = _push_wave(router)              # serve on pushed v1
        # A direct read of the torn version must refuse, loudly.
        try:
            reader.load(kill_at, template)
            raise AssertionError("torn snapshot adopted")
        except SnapshotCorrupt:
            pass
        # Worst case: the version pointer itself names the torn
        # version (simulated pointer corruption).  The canary tick
        # must abort — never a partial adoption, never a crash.
        with open(os.path.join(snapdir, "LATEST"), "w") as f:
            f.write(str(kill_at))
        rec2 = ctl.poll()
        assert rec2 is not None and rec2["action"] == "abort", rec2
        assert all(e.param_version == kill_at - 1 for e in engines)
        after = _push_wave(router)
        assert after == served, "tokens drifted across the abort"
        assert locks.violation_count() == base_viol, (
            "lock sanitizer violations during the push leg")
        print(f"  PASS  cluster/train_kill_push: trainer died at "
              f"publish.commit v{kill_at} (rc 137), torn snapshot "
              f"refused, fleet stayed on v{kill_at - 1}, canary tick "
              f"aborted cleanly, zero lost requests")
        return 0
    except Exception as e:  # noqa: BLE001 — report the ladder
        print(f"  FAIL  cluster/train_kill_push: "
              f"{type(e).__name__}: {e}")
        return 1


def run_canary_bad_push_scenario(seed, workdir):
    """Publish a poisoned (NaN) version with valid checksums: the
    drift probe must trip, the fleet must roll back to the promoted
    version with zero lost requests, and the rejected version must be
    quarantined (pushed once, never re-pushed)."""
    from distkeras_tpu.serving.publish import (SnapshotPublisher,
                                               SnapshotReader)
    from distkeras_tpu.utils import locks

    print("== cluster scenario: canary_bad_push (NaN weights, valid "
          "checksums) ==", flush=True)
    try:
        snapdir = os.path.join(workdir, "canary_snaps")
        os.makedirs(snapdir, exist_ok=True)
        engines, router, template, CanaryController = _push_fleet(seed)
        pub = SnapshotPublisher(snapdir)
        reader = SnapshotReader(snapdir)
        ctl = CanaryController(router, reader, CFG, template)
        base_viol = locks.violation_count()

        good = jax.tree.map(
            np.asarray, tfm.init_params(jax.random.key(seed + 1), CFG))
        pub.publish(good, 1)
        rec = ctl.poll()
        assert rec is not None and rec["action"] == "promote", rec
        served = _push_wave(router)
        # In-flight requests straddle the bad push: enqueue, partially
        # decode, push, then drain — every request must still finish.
        straddlers = [router.enqueue([9 + i, 8, 7], 6) for i in range(3)]
        for _ in range(2):
            router.step()
        bad = jax.tree.map(
            lambda a: np.full_like(np.asarray(a), np.nan), good)
        pub.publish(bad, 2)                  # checksums are VALID
        rec2 = ctl.poll()
        assert rec2 is not None and rec2["action"] == "rollback", rec2
        assert rec2["reason"] == "drift" and rec2["drift"] == float(
            "inf"), rec2
        assert all(e.param_version == 1 for e in engines), (
            [e.param_version for e in engines])
        for r in straddlers:                 # zero lost requests
            router.drain(r)
        after = _push_wave(router)
        assert after == served, (
            "rollback did not restore bit-identical serving")
        assert ctl.poll() is None, "rejected version re-pushed"
        assert locks.violation_count() == base_viol, (
            "lock sanitizer violations during the canary leg")
        print("  PASS  cluster/canary_bad_push: drift probe tripped "
              "(inf), fleet rolled back to v1, straddling requests "
              "all finished, tokens bit-identical post-rollback, "
              "rejected v2 quarantined")
        return 0
    except Exception as e:  # noqa: BLE001 — report the ladder
        print(f"  FAIL  cluster/canary_bad_push: "
              f"{type(e).__name__}: {e}")
        return 1


def run_cluster_ladder(scenarios, seed, workdir):
    """The --cluster entry: reference run + one chaos run per
    training scenario (bit-for-bit weight comparison, merged
    cross-host timeline), plus the round-13 ``serve_kill`` router leg
    (kill-a-replica-mid-stream).  Returns the number of failures."""
    import json

    import numpy as np

    from distkeras_tpu.obs.report import merge_traces

    failures = 0
    scenarios = list(scenarios)
    async_legs = [s for s in scenarios
                  if s in ("async_stall", "async_kill_push")]
    if async_legs:
        scenarios = [s for s in scenarios if s not in async_legs]
        failures += run_async_scenarios(async_legs, seed, workdir)
    if "serve_kill" in scenarios:
        scenarios.remove("serve_kill")
        failures += run_router_kill_scenario(seed, workdir)
    if "serve_kill_prefill" in scenarios:
        scenarios.remove("serve_kill_prefill")
        failures += run_router_prefill_kill_scenario(seed, workdir)
    if "autoscale_spike" in scenarios:
        scenarios.remove("autoscale_spike")
        failures += run_autoscale_spike_scenario(seed, workdir)
    if "train_kill_push" in scenarios:
        scenarios.remove("train_kill_push")
        failures += run_train_kill_push_scenario(seed, workdir)
    if "canary_bad_push" in scenarios:
        scenarios.remove("canary_bad_push")
        failures += run_canary_bad_push_scenario(seed, workdir)
    if not scenarios:
        return failures

    print("== cluster ladder: uninterrupted 2-host reference ==",
          flush=True)
    _, ref_out, _, _ = run_cluster_scenario(None, seed, workdir)
    ref = np.load(ref_out)

    for scenario in scenarios:
        print(f"== cluster scenario: {scenario} ==", flush=True)
        try:
            summaries, out, traces, samples = run_cluster_scenario(
                scenario, seed, workdir)
            assert all(s["epochs"] >= 2 for s in summaries), (
                f"no coordinated restart happened: {summaries}")
            got = np.load(out)
            mismatch = [k for k in ref.files if k != "losses"
                        and not np.array_equal(got[k], ref[k])]
            assert not mismatch, (
                f"resumed weights differ from the uninterrupted run: "
                f"{mismatch}")
            # Federation (round 11): /metrics/cluster must have served
            # BOTH hosts' series host=-labeled at some point, and on a
            # host kill the dead host's series must visibly drop out
            # and return across the coordinated restart.
            hosts_seen = [up for _, up in samples]
            assert any(up >= {0, 1} for up in hosts_seen), (
                f"/metrics/cluster never federated both hosts "
                f"(samples: {hosts_seen[:20]})")
            if scenario == "kill":
                both = next(i for i, up in enumerate(hosts_seen)
                            if up >= {0, 1})
                gone = next((i for i in range(both, len(hosts_seen))
                             if 0 in hosts_seen[i]
                             and 1 not in hosts_seen[i]), None)
                assert gone is not None, (
                    "killed host's series never dropped out of "
                    "/metrics/cluster")
                assert any(up >= {0, 1}
                           for up in hosts_seen[gone:]), (
                    "killed host's series never returned after the "
                    "coordinated restart")
            print(f"  PASS  cluster/{scenario}: restart under epoch "
                  f"{summaries[0]['epochs'] - 1}, weights bit-for-bit, "
                  f"{len(samples)} federation scrape(s)")
        except Exception as e:  # noqa: BLE001 — report the ladder
            failures += 1
            print(f"  FAIL  cluster/{scenario}: "
                  f"{type(e).__name__}: {e}")
            continue
        # Machine-readable cross-host fault/recovery timeline,
        # assembled by the obs_report --merge machinery.
        merged = merge_traces(traces)
        print(f"--- cross-host fault/recovery timeline "
              f"({scenario}, JSONL) ---")
        for e in merged["timeline"]:
            print(json.dumps({"t": round(e["t"], 4), "host": e["host"],
                              "run": e["run"], "event": e["name"],
                              **e["fields"]}))
        # Per-host SLO/breach timeline (round 11): the in-child
        # rolling SLO rule turns the ladder into a latency-regression
        # canary — a breach class outside EXPECTED_BREACH_METRICS
        # fails the scenario.
        breaches = [e for e in merged["timeline"]
                    if e["name"] == "slo.breach"]
        print(f"--- per-host SLO/breach timeline ({scenario}) ---")
        if not breaches:
            print("  (no SLO breaches)")
        for e in breaches:
            print(f"  +{e['t']:.3f}s host {e['host']} BREACH "
                  + json.dumps(e["fields"]))
        unexpected = [e for e in breaches
                      if e["fields"].get("metric")
                      not in EXPECTED_BREACH_METRICS]
        if unexpected:
            failures += 1
            print(f"  FAIL  cluster/{scenario}: {len(unexpected)} "
                  f"unexpected SLO breach(es) — latency regressed "
                  f"under chaos (classes: "
                  f"{sorted({e['fields'].get('metric') for e in unexpected})})")
        # Per-host lock-sanitizer report (round 12): every completing
        # child emits one; a recorded violation anywhere in the
        # ladder — any host, any epoch — fails the scenario.  (A
        # chaos-killed epoch-0 child dies before reporting; the
        # coordinated restart's completing attempt must still report
        # for BOTH hosts.)
        reports = [e for e in merged["timeline"]
                   if e["name"] == "locks.report"]
        print(f"--- per-host lock sanitizer report ({scenario}) ---")
        for e in reports:
            print(f"  host {e['host']}: " + json.dumps(e["fields"]))
        hosts_reported = {e["fields"].get("host") for e in reports}
        if not hosts_reported >= {0, 1}:
            failures += 1
            print(f"  FAIL  cluster/{scenario}: lock report missing "
                  f"for host(s) {sorted({0, 1} - hosts_reported)}")
        bad = [e for e in reports if e["fields"].get("violations")]
        if bad:
            failures += 1
            print(f"  FAIL  cluster/{scenario}: lock sanitizer "
                  f"violation(s) recorded on host(s) "
                  f"{sorted({e['fields'].get('host') for e in bad})}")
    return failures


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kill-rounds", default="3,7,12",
                    help="comma-separated rounds for the kill matrix")
    ap.add_argument("--trace", default=None,
                    help="write the obs event trace here (default: a "
                         "temp file, deleted after the timeline prints)")
    ap.add_argument("--cluster", action="store_true",
                    help="run the multi-host coordinated-restart "
                         "ladder instead of the single-host matrix")
    ap.add_argument("--scenarios",
                    default="kill,stall,drop,serve_kill,"
                            "serve_kill_prefill,autoscale_spike,"
                            "async_stall,async_kill_push,"
                            "train_kill_push,canary_bad_push",
                    help="--cluster fault kinds to run "
                         "(kill = host loss, stall = wedged heartbeat "
                         "writer, drop = partition, serve_kill = "
                         "kill-a-serving-replica-mid-stream under the "
                         "router, autoscale_spike = flash-spike "
                         "scale-up with a warm-pool replica SIGKILLed "
                         "mid-join, async_stall = bounded-staleness "
                         "straggler in the async tier, async_kill_push "
                         "= host loss mid-delta-publish, "
                         "train_kill_push = trainer SIGKILL mid-weight-"
                         "publish, canary_bad_push = poisoned weight "
                         "push rolled back by the canary gate)")
    ap.add_argument("--workdir", default=None,
                    help="--cluster scratch dir (default: a temp dir, "
                         "kept on failure)")
    args = ap.parse_args()

    if args.cluster:
        workdir = args.workdir or tempfile.mkdtemp(prefix="chaos_cluster_")
        failures = run_cluster_ladder(
            [s for s in args.scenarios.split(",") if s], args.seed,
            workdir)
        if failures:
            print(f"cluster ladder: {failures} scenario(s) FAILED "
                  f"(artifacts kept at {workdir})")
            return 1
        print("cluster ladder: all scenarios passed")
        if not args.workdir:
            import shutil

            shutil.rmtree(workdir, ignore_errors=True)
        return 0
    kills = [int(r) for r in args.kill_rounds.split(",")]

    matrix = []
    for r in kills:
        matrix.append((f"kill@round{r}/exception",
                       lambda r=r: check_kill_resume(args.seed, r, False)))
    matrix.append((f"kill@round{kills[0]}/sigterm",
                   lambda: check_kill_resume(args.seed, kills[0], True)))
    matrix += [
        ("checkpoint-save-fault", lambda: check_checkpoint_fault_retry(
            args.seed)),
        ("cluster-heartbeat-partition",
         lambda: check_heartbeat_fault_kinds(args.seed)),
        ("serving-deadlines", lambda: check_serving_deadlines(args.seed)),
        ("queue-backpressure", lambda: check_backpressure(args.seed)),
        ("draft-fault-fallback", lambda: check_draft_fault_fallback(
            args.seed)),
    ]

    import json

    from distkeras_tpu import obs
    from distkeras_tpu.obs.trace import read_trace
    from distkeras_tpu.utils import locks

    # The single-host matrix runs under the lock sanitizer too: every
    # engine/obs lock the checks construct from here on is
    # instrumented, and a recorded violation fails the suite.
    locks.enable_sanitizer()
    trace_path = args.trace or os.path.join(
        tempfile.mkdtemp(prefix="chaos_obs_"), "chaos.jsonl")
    failures = 0
    with obs.session(trace_path=trace_path):
        for name, fn in matrix:
            obs.event("chaos_suite.check", check=name, status="start")
            try:
                fn()
                print(f"  PASS  {name}")
                obs.event("chaos_suite.check", check=name, status="pass")
            except Exception as e:  # noqa: BLE001 — report the matrix
                failures += 1
                print(f"  FAIL  {name}: {type(e).__name__}: {e}")
                obs.event("chaos_suite.check", check=name,
                          status="fail", error=repr(e)[:200])
            assert chaos.active_plan() is None, "a FaultPlan leaked"
        obs.event("locks.report", **locks.lock_report())
    print(f"{len(matrix) - failures}/{len(matrix)} chaos checks passed")
    print("--- lock sanitizer report ---")
    print(f"  {json.dumps(locks.lock_report())}")
    if locks.violation_count():
        failures += 1
        for v in locks.violations():
            print("  VIOLATION " + v.format())

    # Machine-readable fault/recovery timeline, straight off the obs
    # event trace: injected faults (chaos.fault), Supervisor attempts/
    # backoffs (supervisor.*), preemption checkpoints and engine
    # degradation — one JSON object per line, time-ordered.
    records = [r for r in read_trace(trace_path)
               if r.get("kind") == "event"]
    t0 = min((r["t"] for r in records), default=0.0)
    print("--- fault/recovery timeline (JSONL) ---")
    for r in sorted(records, key=lambda r: r["t"]):
        print(json.dumps({"t": round(r["t"] - t0, 4),
                          "event": r["name"], **r.get("fields", {})}))
    if args.trace:
        print(f"--- obs trace kept at {args.trace} "
              "(render: scripts/obs_report.py) ---")
    else:
        import shutil

        shutil.rmtree(os.path.dirname(trace_path), ignore_errors=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
