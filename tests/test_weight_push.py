"""Live train→serve weight push (round 20): atomic versioned publish,
zero-recompile hot swap, and the SLO-gated canary rollout.

The contracts pinned here:
- a reader NEVER adopts a torn/corrupt/stale snapshot;
- ``swap_params`` replaces the served weights between steps, version-
  monotone, atomically under the admission lock — per-version tokens
  are bit-identical to a solo ``generate`` run under those params;
- the canary controller promotes a good push fleet-wide and rolls a
  bad one back (NaN drift, chaos fault at the promote probe), always
  under a bumped router epoch;
- the autoscaler's decision timeline is blind to ``param_version``.
"""

import json
import os
import threading

import jax
import numpy as np
import pytest

from helpers import generate, serve_cfg, toy_params
from distkeras_tpu.resilience import chaos
from distkeras_tpu.serving import (CanaryController, ContinuousBatcher,
                                   InProcessReplica, Router,
                                   SnapshotCorrupt, SnapshotPublisher,
                                   SnapshotReader, StaleSnapshot)
from distkeras_tpu.utils import locks


CFG = serve_cfg()


@pytest.fixture(scope="module")
def params():
    return toy_params(CFG)


@pytest.fixture(scope="module")
def params_v1():
    return toy_params(CFG, 1)


@pytest.fixture(scope="module")
def template():
    return jax.eval_shape(lambda: toy_params(CFG))


def np_tree(tree):
    return jax.tree.map(np.asarray, tree)


def solo(params, prompt, n):
    return np.asarray(generate(params, np.asarray(prompt)[None], CFG,
                               n))[0]


# ------------------------------------------------------------- publish


def test_publish_roundtrip_raw_and_int8(tmp_path, params, template):
    tree = np_tree(params)
    for coding in (None, "int8"):
        root = tmp_path / (coding or "raw")
        SnapshotPublisher(str(root), coding=coding).publish(tree, 3)
        reader = SnapshotReader(str(root))
        assert reader.latest_version() == 3
        version, got = reader.poll(template)
        assert version == 3
        assert (jax.tree_util.tree_structure(got)
                == jax.tree_util.tree_structure(tree))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
            assert a.shape == b.shape and a.dtype == b.dtype
            if coding is None:
                np.testing.assert_array_equal(a, b)
            else:
                assert float(np.max(np.abs(
                    np.asarray(a, np.float32)
                    - np.asarray(b, np.float32)))) < 0.1


def test_reader_declines_torn_manifest(tmp_path, params, template):
    root = str(tmp_path)
    SnapshotPublisher(root).publish(np_tree(params), 1)
    # A publish killed between bucket writes and the manifest rename:
    # bucket files exist, MANIFEST.json does not, LATEST still says 1.
    os.makedirs(os.path.join(root, "v00000002"))
    with open(os.path.join(root, "v00000002", "bucket_0000.npz"),
              "wb") as f:
        f.write(b"partial")
    reader = SnapshotReader(root)
    assert reader.latest_version() == 1
    with pytest.raises(SnapshotCorrupt):
        reader.load(2, template)
    # The good version is untouched by the torn sibling.
    assert reader.poll(template)[0] == 1


def test_reader_declines_checksum_mismatch(tmp_path, params, template):
    root = str(tmp_path)
    SnapshotPublisher(root).publish(np_tree(params), 1)
    manifest = os.path.join(root, "v00000001", "MANIFEST.json")
    with open(manifest) as f:
        body = json.load(f)
    # A VALID npz whose payload does not match the manifest checksum
    # (silent disk corruption, not a torn write).
    bucket = os.path.join(root, "v00000001", body["buckets"][0]["file"])
    data = np.load(bucket)["raw"].copy()
    data[0] ^= 0xFF
    np.savez(bucket[:-4], raw=data)
    with pytest.raises(SnapshotCorrupt):
        SnapshotReader(root).load(1, template)


def test_reader_declines_stale_version(tmp_path, params, template):
    root = str(tmp_path)
    pub = SnapshotPublisher(root)
    pub.publish(np_tree(params), 1)
    pub.publish(np_tree(params), 2)
    reader = SnapshotReader(root)
    reader.adopt(2)
    with pytest.raises(StaleSnapshot):
        reader.load(1, template)
    with pytest.raises(StaleSnapshot):
        reader.load(2, template)
    assert reader.poll(template) is None


# ------------------------------------------------------------ hot swap


def test_hot_swap_per_version_parity(params, params_v1, rng):
    """Each param version's tokens are bit-identical to a solo
    generate() run under those params — across swap and rollback."""
    eng = ContinuousBatcher(params, CFG, lanes=2, hot_swap=True)
    prompt = rng.integers(0, 64, (5,)).astype(np.int32)

    def serve():
        lane = eng.submit(prompt, 6)
        while lane in eng.running():
            eng.step()
        return eng.drain(lane)

    np.testing.assert_array_equal(serve(), solo(params, prompt, 6))
    assert eng.param_version == 0
    eng.swap_params(np_tree(params_v1), 1)
    assert eng.param_version == 1
    np.testing.assert_array_equal(serve(), solo(params_v1, prompt, 6))
    # Rollback path: downgrade restores version 0's exact tokens.
    eng.swap_params(np_tree(params), 0, allow_downgrade=True)
    np.testing.assert_array_equal(serve(), solo(params, prompt, 6))


def test_swap_validation(params, params_v1):
    eng = ContinuousBatcher(params, CFG, lanes=2, hot_swap=True)
    eng.swap_params(np_tree(params_v1), 2)
    with pytest.raises(ValueError, match="monotone|<="):
        eng.swap_params(np_tree(params), 2)
    with pytest.raises(ValueError, match="monotone|<="):
        eng.swap_params(np_tree(params), 1)
    bad = {k: v for k, v in np_tree(params).items() if k != "tok_emb"}
    with pytest.raises(ValueError):
        eng.swap_params(bad, 3)
    plain = ContinuousBatcher(params, CFG, lanes=2)
    with pytest.raises(ValueError, match="hot_swap"):
        plain.swap_params(np_tree(params_v1), 1)


def test_hot_swap_rejects_baked_prefix_state(params):
    from distkeras_tpu.serving import PrefixPool

    with pytest.raises(ValueError, match="hot_swap"):
        ContinuousBatcher(params, CFG, lanes=2, hot_swap=True,
                          prefix_pool=PrefixPool(CFG, slots=1))


def test_concurrent_publish_while_swap_atomic(tmp_path, params,
                                              params_v1, template,
                                              rng):
    """A publisher thread and a swap+serve loop race: every serve
    wave's tokens must match exactly one version (never a mix), and
    the lock ledger stays clean."""
    root = str(tmp_path)
    pub = SnapshotPublisher(root)
    reader = SnapshotReader(root)
    eng = ContinuousBatcher(params, CFG, lanes=2, hot_swap=True)
    prompt = rng.integers(0, 64, (5,)).astype(np.int32)
    refs = {0: solo(params, prompt, 6), 1: solo(params_v1, prompt, 6)}
    trees = {1: np_tree(params_v1), 2: np_tree(params)}
    base_viol = locks.violation_count()
    errs = []
    adopted_v1 = threading.Event()

    def publish_loop():
        try:
            pub.publish(trees[1], 1)
            # Hold v2 until the serving side has actually swapped v1
            # in, so the race covers BOTH transitions.
            adopted_v1.wait(timeout=30)
            pub.publish(trees[2], 2)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    t = threading.Thread(target=publish_loop)
    t.start()
    seen = []
    for _ in range(50):
        nxt = reader.poll(template)
        if nxt is not None:
            version, tree = nxt
            eng.swap_params(tree, version)
            reader.adopt(version)
            if version >= 1:
                adopted_v1.set()
        lane = eng.submit(prompt, 6)
        while lane in eng.running():
            eng.step()
        out = np.asarray(eng.drain(lane))
        matched = [v for v, ref in refs.items()
                   if np.array_equal(out, ref)]
        assert matched, "serve wave matched NO whole version (torn mix)"
        seen.append(matched[0])
        if eng.param_version == 2:
            break
    t.join()
    assert not errs, errs
    assert eng.param_version == 2
    # v2 re-publishes version-0's weights: both references must have
    # been served across the race.
    assert {0, 1} <= set(seen), seen
    assert locks.violation_count() == base_viol


# -------------------------------------------------------------- canary


def fleet(params, n=2):
    engines = [ContinuousBatcher(params, CFG, lanes=2, hot_swap=True)
               for _ in range(n)]
    router = Router([InProcessReplica(f"r{i}", e)
                     for i, e in enumerate(engines)])
    return engines, router


def wave(router, n=3, max_new=5):
    rids = [router.enqueue([1 + i, 2, 3], max_new) for i in range(n)]
    out = []
    for r in rids:
        res = router.drain(r)
        toks = res["tokens"] if isinstance(res, dict) else res.tokens
        out.append(tuple(int(t) for t in toks))
    return out


def test_canary_lifecycle(params, params_v1, template):
    """Promote → NaN rollback → chaos fault at the promote probe →
    quarantine, with per-replica ``param_version`` in the fleet
    snapshot and a clean lock ledger throughout."""
    engines, router = fleet(params)
    ctl = CanaryController(router, None, CFG, template)
    base_viol = locks.violation_count()
    v1 = np_tree(params_v1)

    snap = router.fleet_snapshot()
    assert all(r["param_version"] == 0
               for r in snap["replicas"].values())
    epoch0 = snap["epoch"]

    rec = ctl.rollout(1, v1)
    assert rec["action"] == "promote" and rec["promoted"] == 2
    assert all(e.param_version == 1 for e in engines)
    snap = router.fleet_snapshot()
    assert all(r["param_version"] == 1
               for r in snap["replicas"].values())
    assert snap["epoch"] > epoch0
    served = wave(router)

    bad = jax.tree.map(lambda a: np.full_like(a, np.nan), v1)
    rec = ctl.rollout(2, bad)
    assert rec["action"] == "rollback" and rec["reason"] == "drift"
    assert rec["drift"] == float("inf")
    assert all(e.param_version == 1 for e in engines)
    assert wave(router) == served

    plan = chaos.FaultPlan().fail("canary.promote", at=3)
    with plan:
        with pytest.raises(chaos.FaultInjected):
            ctl.rollout(3, v1)
    assert ("canary.promote", 3, "fail") in plan.events
    assert all(e.param_version == 1 for e in engines)
    assert wave(router) == served
    assert locks.violation_count() == base_viol


def test_canary_poll_quarantines_rejected_version(tmp_path, params,
                                                  params_v1, template):
    root = str(tmp_path)
    pub = SnapshotPublisher(root)
    engines, router = fleet(params)
    ctl = CanaryController(router, SnapshotReader(root), CFG, template)
    pub.publish(np_tree(params_v1), 1)
    assert ctl.poll()["action"] == "promote"
    bad = jax.tree.map(lambda a: np.full_like(a, np.nan),
                       np_tree(params_v1))
    pub.publish(bad, 2)
    assert ctl.poll()["action"] == "rollback"
    assert all(e.param_version == 1 for e in engines)
    # The rejected version is pushed ONCE — the next tick skips it.
    assert ctl.poll() is None


def test_autoscaler_ignores_param_version(params, params_v1):
    """Small fix regression: ``param_version`` rides the fleet
    snapshot, and the scaling-decision timeline is identical whether
    or not a swap lands between ticks."""
    from distkeras_tpu.serving import (AutoscalePolicy, Autoscaler,
                                       WarmPool)

    def run(swap):
        engines, router = fleet(params)
        spare = ContinuousBatcher(params, CFG, lanes=2, hot_swap=True)
        asc = Autoscaler(router, WarmPool([InProcessReplica("w0",
                                                            spare)]),
                         policy=AutoscalePolicy(
                             min_replicas=1, max_replicas=3,
                             up_after=1, down_after=10,
                             cooldown_ticks=0))
        timeline = []
        for tick in range(4):
            if swap and tick == 2:
                for e in engines:
                    e.swap_params(np_tree(params_v1), 1)
            rec = asc.tick()
            timeline.append((tick, rec["action"]))
        return timeline

    assert run(swap=False) == run(swap=True)


# ------------------------------------------------------ trainer hook


def test_trainer_publishes_and_fleet_adopts(tmp_path, params,
                                            template, devices):
    """The closed loop: an LMTrainer publishes every round while a
    hot_swap fleet polls — versions advance mid-session and the final
    served weights are the final trained weights."""
    import distkeras_tpu as dk
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh

    root = str(tmp_path)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 64, (32, 17)).astype(np.int32)
    mesh = make_mesh(MeshSpec(data=2), devices=devices[:2])
    t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16,
                     num_epoch=2, mesh=mesh)
    t.attach_publisher(SnapshotPublisher(root), every=1)

    engines, router = fleet(params)
    ctl = CanaryController(router, SnapshotReader(root), CFG, template)
    versions = []
    done = threading.Event()

    def poll_loop():
        while not done.is_set():
            rec = ctl.poll()
            if rec is not None and rec["action"] == "promote":
                versions.append(rec["version"])
            done.wait(0.01)

    poller = threading.Thread(target=poll_loop)
    poller.start()
    try:
        trained = t.train(dk.Dataset({"tokens": toks}))
    finally:
        done.set()
        poller.join()
    # Drain any publish the poller missed after training finished.
    rec = ctl.poll()
    if rec is not None and rec["action"] == "promote":
        versions.append(rec["version"])
    rounds = len(t.history)
    assert versions and versions[-1] == rounds, (versions, rounds)
    assert all(e.param_version == rounds for e in engines)
    # The fleet serves the trainer's final weights, bit-exactly.
    prompt = np.asarray([1, 2, 3], np.int32)
    rid = router.enqueue(prompt, 5)
    res = router.drain(rid)
    toks_served = res["tokens"] if isinstance(res, dict) else res.tokens
    np.testing.assert_array_equal(np.asarray(toks_served),
                                  solo(trained, prompt, 5))
