"""Round-14 pod-sharded serving: ``plan=``/``mesh=`` on both engine
families (ROADMAP item 1's second half — one router replica is a whole
mesh).

The acceptance contract: a sharded engine on the 8-CPU mesh emits
BIT-EXACT greedy and seeded-sampled tokens vs the solo engine, holds
~n× fewer param+KV bytes per device (asserted from addressable
shards, the ``zero=3`` accounting), publishes the SAME residency
digests (host-side content hashes — the router never sees the mesh),
and serves behind the Router like any other replica.  Invalid plans
are rejected at construction naming the offending rule.
"""

import jax
import numpy as np
import pytest

from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
from distkeras_tpu.parallel.rules import kv_slab_specs, serving_kv_axis
from distkeras_tpu.parallel.sharding import fsdp_plan, serving_plan
from distkeras_tpu.serving import (ContinuousBatcher, InProcessReplica,
                                   PagedBatcher, PrefixPool, Router,
                                   SpeculativeBatcher)
from jax.sharding import PartitionSpec as P
from helpers import generate, jgen, serve_cfg, spec_draft_cfg, toy_params

CFG = serve_cfg()
BLOCK = 8


@pytest.fixture(scope="module")
def params():
    return toy_params(CFG)


@pytest.fixture(scope="module")
def tp2(devices):
    """(mesh, plan) for the standard data=4 x model=2 serving layout."""
    return make_mesh(MeshSpec(data=4, model=2), devices=devices), \
        serving_plan()


def _prompts(rng, lens=(5, 9)):
    return [rng.integers(0, 64, (n,)).astype(np.int32) for n in lens]


def _serve(eng, prompts, new, keys=None):
    lanes = [eng.submit(p, new, key=None if keys is None else keys[i])
             for i, p in enumerate(prompts)]
    while eng.running():
        eng.step()
    return [eng.drain(lane) for lane in lanes]


# ------------------------------------------------------------- parity


def test_sharded_cb_greedy_bit_exact(params, tp2, rng):
    mesh, plan = tp2
    prompts = _prompts(rng)
    refs = [np.asarray(generate(params, p[None], CFG, 6))[0]
            for p in prompts]
    eng = ContinuousBatcher(params, CFG, lanes=2, prompt_buckets=(8,),
                            plan=plan, mesh=mesh)
    assert eng._kv_axis == "model"
    for out, ref in zip(_serve(eng, prompts, 6), refs):
        np.testing.assert_array_equal(out, ref)


def test_sharded_cb_sampled_bit_exact(params, tp2, rng):
    mesh, plan = tp2
    prompts = _prompts(rng)
    keys = [jax.random.key(3), jax.random.key(4)]
    kw = dict(temperature=0.8, top_k=20)
    refs = [np.asarray(generate(params, p[None], CFG, 6, key=k, **kw))[0]
            for p, k in zip(prompts, keys)]
    eng = ContinuousBatcher(params, CFG, lanes=2, prompt_buckets=(8,),
                            plan=plan, mesh=mesh, **kw)
    for out, ref in zip(_serve(eng, prompts, 6, keys=keys), refs):
        np.testing.assert_array_equal(out, ref)


def _paged(params, plan=None, mesh=None, **kw):
    kw.setdefault("prompt_buckets", (8,))
    return PagedBatcher(params, CFG, lanes=2, block=BLOCK,
                        n_blocks=2 * (CFG.max_len // BLOCK) + 1,
                        plan=plan, mesh=mesh, **kw)


def test_sharded_paged_greedy_and_sampled_bit_exact(params, tp2, rng):
    mesh, plan = tp2
    prompts = _prompts(rng, lens=(6, 11))
    grefs = [np.asarray(generate(params, p[None], CFG, 6))[0]
             for p in prompts]
    eng = _paged(params, plan=plan, mesh=mesh)
    for out, ref in zip(_serve(eng, prompts, 6), grefs):
        np.testing.assert_array_equal(out, ref)

    keys = [jax.random.key(7), jax.random.key(8)]
    kw = dict(temperature=0.7, top_k=16)
    srefs = [np.asarray(generate(params, p[None], CFG, 5, key=k,
                                 **kw))[0]
             for p, k in zip(prompts, keys)]
    se = _paged(params, plan=plan, mesh=mesh, **kw)
    for out, ref in zip(_serve(se, prompts, 5, keys=keys), srefs):
        np.testing.assert_array_equal(out, ref)


@pytest.mark.slow
def test_sharded_chunked_prefill_bit_exact(params, tp2, rng):
    """Chunked admission under the sharded layout: the continuation
    programs land sharded chunks, the parked lane un-parks, tokens
    identical to monolithic admission AND to solo generate."""
    mesh, plan = tp2
    long_p = rng.integers(0, 64, (21,)).astype(np.int32)
    short = rng.integers(0, 64, (4,)).astype(np.int32)
    ref_long = np.asarray(generate(params, long_p[None], CFG, 4))[0]
    ref_short = np.asarray(generate(params, short[None], CFG, 8))[0]
    eng = ContinuousBatcher(params, CFG, lanes=2, prefill_chunk=8,
                            prompt_buckets=(8,), plan=plan, mesh=mesh)
    ls = eng.submit(short, 8)
    eng.step()
    ll = eng.submit(long_p, 4)
    while eng.running():
        eng.step()
    np.testing.assert_array_equal(eng.drain(ll), ref_long)
    np.testing.assert_array_equal(eng.drain(ls), ref_short)


def test_sharded_prefix_pool_bit_exact(params, tp2, rng):
    """Pool slab placed with the engine's KV sharding: the pooled
    gather is a sharded device gather, parity vs
    generate(prompt_cache=...) exact."""
    mesh, plan = tp2
    pool = PrefixPool(CFG, slots=2, mesh=mesh, kv_axis="model")
    pref = rng.integers(0, 64, (1, 6)).astype(np.int32)
    cache, _ = jgen.prefill(params, pref, CFG, last_logits=False)
    pid = pool.put(cache, 6)
    tail = rng.integers(0, 64, (4,)).astype(np.int32)
    ref = np.asarray(generate(params, tail[None], CFG, 4,
                              prompt_cache=(cache, 6)))[0]
    eng = ContinuousBatcher(params, CFG, lanes=2, prefix_pool=pool,
                            prompt_buckets=(8,), plan=plan, mesh=mesh)
    lane = eng.submit(tail, 4, prefix_id=pid)
    while eng.running():
        eng.step()
    np.testing.assert_array_equal(eng.drain(lane), ref)


def test_fsdp_plan_serves_with_replicated_cache(params, devices, rng):
    """A pure-FSDP plan (no attention-head rule) derives NO KV axis:
    params scatter gather-on-use, the cache replicates, and tokens
    stay bit-exact — the plan spelling training's fsdp=True uses."""
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    plan = fsdp_plan()
    assert serving_kv_axis(plan, mesh, CFG) is None
    prompts = _prompts(rng)
    refs = [np.asarray(generate(params, p[None], CFG, 5))[0]
            for p in prompts]
    eng = ContinuousBatcher(params, CFG, lanes=2, prompt_buckets=(8,),
                            plan=plan, mesh=mesh)
    for out, ref in zip(_serve(eng, prompts, 5), refs):
        np.testing.assert_array_equal(out, ref)


# ----------------------------------------------------- bytes + digest


def test_per_device_bytes_drop_with_axis(params, tp2):
    mesh, plan = tp2
    sharded = ContinuousBatcher(params, CFG, lanes=2,
                                prompt_buckets=(8,), plan=plan,
                                mesh=mesh)
    solo = ContinuousBatcher(params, CFG, lanes=2, prompt_buckets=(8,))
    fs, fo = sharded.memory_footprint(), solo.memory_footprint()
    # Totals agree; the per-device split is the claim.
    assert fs["param_bytes"] == fo["param_bytes"]
    assert fs["kv_bytes"] == fo["kv_bytes"]
    # KV heads shard exactly 2x; params ~2x (norm scales replicate).
    assert fs["kv_bytes_per_device"] * 2 == fo["kv_bytes_per_device"]
    assert fs["param_bytes_per_device"] < 0.6 * fo["param_bytes"]
    # Solo engine: one device holds everything.
    assert fo["param_bytes_per_device"] == fo["param_bytes"]


def test_paged_residency_digest_equal_sharded_vs_solo(params, tp2,
                                                      rng):
    """Residency is host-side content hashing: the sharded paged
    engine publishes exactly the digests its solo twin does for the
    same served prompts — to the router, a pod-sharded engine is ONE
    mesh-agnostic replica handle."""
    mesh, plan = tp2
    prompts = [np.concatenate([rng.integers(0, 64, (8,)),
                               rng.integers(0, 64, (4,))]).astype(
                                   np.int32)
               for _ in range(2)]
    sharded = _paged(params, plan=plan, mesh=mesh)
    solo = _paged(params)
    for eng in (sharded, solo):
        _serve(eng, prompts, 4)
    r_sh, r_solo = sharded.residency(), solo.residency()
    assert sorted(r_sh["stem_hashes"]) == sorted(r_solo["stem_hashes"])
    assert r_sh["block"] == r_solo["block"] == BLOCK
    assert r_sh["model_shards"] == 2 and r_solo["model_shards"] == 1


def test_router_over_one_sharded_replica(params, tp2, rng):
    """A pod-sharded engine behind the Router: enqueue/poll/drain
    through the fleet surface, results keyed to fleet-wide ids,
    tokens bit-exact vs solo generate."""
    mesh, plan = tp2
    eng = _paged(params, plan=plan, mesh=mesh, max_queue=8)
    router = Router([InProcessReplica("pod0", eng)])
    prompts = _prompts(rng, lens=(6, 10, 7))
    rids = [router.enqueue(p, 5) for p in prompts]
    while any(router.poll(r) is None for r in rids):
        router.step()
    for rid, p in zip(rids, prompts):
        res = router.take(rid)
        assert res.ok and res.request_id == rid
        solo = np.asarray(generate(params, p[None], CFG, 5))[0]
        np.testing.assert_array_equal(res.tokens, solo)
    assert router.replicas_up() == ["pod0"]


# ------------------------------------- elastic x plan (round 17)


def test_sharded_elastic_cb_scales_with_parity(params, tp2, rng):
    """lane_tiers= composes with plan= (round 17): sustained overflow
    steps a pod-sharded engine's tier up through the pre-compiled
    sharded resize gather, every request keeps exact solo parity, and
    the drained engine steps back down."""
    mesh, plan = tp2
    eng = ContinuousBatcher(params, CFG, lane_tiers=(1, 2), max_queue=1,
                            scale_up_after=1, scale_down_after=2,
                            prompt_buckets=(8,), plan=plan, mesh=mesh)
    assert eng.lanes == 1
    prompts = _prompts(rng, lens=(5, 9, 7))
    rids = [eng.enqueue(p, 5) for p in prompts]
    assert eng.lanes == 2, "sharded elastic engine did not scale up"
    while any(eng.poll(r) is None for r in rids):
        eng.step()
    for _ in range(4):
        eng.step()
    assert eng.lanes == 1, "idle sharded engine did not scale down"
    res = eng.shutdown()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            res[rid].tokens,
            np.asarray(generate(params, p[None], CFG, 5))[0])


def test_sharded_elastic_paged_rows_only_resize(params, tp2, rng):
    """Elastic paged x plan: a tier move gathers only row metadata —
    the sharded slab stays put and the page tables remap host-side,
    so requests decoding ACROSS the move keep exact parity and the
    allocator drains clean.  fork() is rejected (lane ids are not
    stable across a resize)."""
    mesh, plan = tp2
    eng = PagedBatcher(params, CFG, block=BLOCK, lane_tiers=(1, 2),
                       max_queue=1, scale_up_after=1,
                       scale_down_after=2, prompt_buckets=(8,),
                       plan=plan, mesh=mesh)
    with pytest.raises(ValueError, match="elastic"):
        eng.fork(0, 1)
    prompts = _prompts(rng, lens=(6, 10, 7))
    ra = eng.enqueue(prompts[0], 6)
    eng.step()                        # ra decodes at tier 1...
    rbs = [eng.enqueue(p, 6) for p in prompts[1:]]   # ...resize here
    assert eng.lanes == 2
    rids = [ra, *rbs]
    while any(eng.poll(r) is None for r in rids):
        eng.step()
    res = eng.shutdown()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(
            res[rid].tokens,
            np.asarray(generate(params, p[None], CFG, 6))[0])
    assert eng.allocator.stats()["used"] == 0


# ------------------------------------ speculative x plan (round 17)

SPEC_DRAFT = spec_draft_cfg()


def test_sharded_speculative_greedy_parity(params, tp2, rng):
    """plan= on the speculative engine (round 17): target sharded,
    draft replicated — greedy output stays bit-exact vs the solo
    pinned contract (greedy speculative IS greedy generate)."""
    mesh, plan = tp2
    draft = toy_params(SPEC_DRAFT, 8)
    eng = SpeculativeBatcher(params, draft, CFG, SPEC_DRAFT, lanes=2,
                             n_draft=3, prompt_buckets=(8,),
                             plan=plan, mesh=mesh)
    prompts = _prompts(rng, lens=(5, 9))
    lanes = [eng.submit(p, 8) for p in prompts]
    while eng.running():
        eng.step()
    for lane, p in zip(lanes, prompts):
        np.testing.assert_array_equal(
            eng.drain(lane),
            np.asarray(generate(params, p[None], CFG, 8))[0])


def test_sharded_speculative_rejections(params, tp2):
    mesh, plan = tp2
    draft = toy_params(SPEC_DRAFT, 8)
    with pytest.raises(ValueError, match="plan= and mesh= together"):
        SpeculativeBatcher(params, draft, CFG, SPEC_DRAFT, plan=plan)
    pool = PrefixPool(CFG, slots=1, draft_cfg=SPEC_DRAFT)
    with pytest.raises(ValueError, match="prefix_pool"):
        SpeculativeBatcher(params, draft, CFG, SPEC_DRAFT,
                           prefix_pool=pool, plan=plan, mesh=mesh)


# --------------------------------------------------- rejection matrix


def test_rejection_matrix(params, tp2, devices):
    mesh, plan = tp2
    with pytest.raises(ValueError, match="plan= and mesh= together"):
        ContinuousBatcher(params, CFG, plan=plan)
    with pytest.raises(ValueError, match="plan= and mesh= together"):
        ContinuousBatcher(params, CFG, mesh=mesh)

    # Head count not divisible by the model axis: the error names the
    # offending RULE, not just the numbers (2 heads, model=4).
    mesh4 = make_mesh(MeshSpec(data=2, model=4), devices=devices)
    with pytest.raises(ValueError, match=r"attn/w\[qkv\]") as e:
        ContinuousBatcher(params, CFG, plan=plan, mesh=mesh4)
    assert "not divisible" in str(e.value)
    with pytest.raises(ValueError, match="not divisible"):
        PagedBatcher(params, CFG, block=BLOCK, plan=plan, mesh=mesh4)

    with pytest.raises(ValueError, match="prompt_cache"):
        ContinuousBatcher(params, CFG, plan=plan, mesh=mesh,
                          prompt_cache=(jax.tree.map(
                              lambda a: a, jgen.prefill(
                                  params, np.zeros((1, 4), np.int32),
                                  CFG, last_logits=False)[0]), 4))
    wcfg = serve_cfg(attention_window=16)
    with pytest.raises(ValueError, match="full-cache"):
        ContinuousBatcher(params, wcfg, plan=plan, mesh=mesh)
    # Pool placement must match the engine's.
    with pytest.raises(ValueError, match="prefix_pool placement"):
        ContinuousBatcher(params, CFG, prefix_pool=PrefixPool(
            CFG, slots=1), plan=plan, mesh=mesh)

    # A callable rule claiming an attention path cannot drive the KV
    # derivation — rejected loudly, not silently skipped (review fix).
    from distkeras_tpu.parallel.sharding import ShardingPlan
    cplan = ShardingPlan(rules=[(r"attn/w[qkv]$",
                                 lambda name, leaf: None)])
    with pytest.raises(ValueError, match="concrete PartitionSpecs"):
        serving_kv_axis(cplan, mesh, CFG)


def test_equal_mesh_from_separate_make_mesh_accepted(params, devices,
                                                     rng):
    """Pool/engine mesh matching is by EQUALITY, not identity: a pool
    built against its own (equal) make_mesh call serves fine.  (jax
    interns Mesh objects, so equal constructions may also be
    identical — the engine check uses `!=` so the contract holds
    either way.)"""
    mesh_a = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    mesh_b = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    assert mesh_a == mesh_b
    pool = PrefixPool(CFG, slots=1, mesh=mesh_a, kv_axis="model")
    pref = rng.integers(0, 64, (1, 6)).astype(np.int32)
    cache, _ = jgen.prefill(params, pref, CFG, last_logits=False)
    pid = pool.put(cache, 6)
    eng = ContinuousBatcher(params, CFG, lanes=2, prefix_pool=pool,
                            prompt_buckets=(8,), plan=serving_plan(),
                            mesh=mesh_b)
    tail = rng.integers(0, 64, (4,)).astype(np.int32)
    ref = np.asarray(generate(params, tail[None], CFG, 4,
                              prompt_cache=(cache, 6)))[0]
    lane = eng.submit(tail, 4, prefix_id=pid)
    while eng.running():
        eng.step()
    np.testing.assert_array_equal(eng.drain(lane), ref)


def test_kv_slab_specs_layouts():
    """The shared KV-spec rule covers every slab layout in the repo:
    monolithic cache, paged block slab, pool slab (leading slots
    axis), int8 scale leaves included — heads dim sharded, everything
    else replicated."""
    cache = {"k": np.zeros((2, 3, 8, 2, 4)),
             "k_scale": np.zeros((2, 3, 8, 2))}
    specs = kv_slab_specs(cache, "model")
    assert specs["k"] == P(None, None, None, "model")
    assert specs["k_scale"] == P(None, None, None, "model")
    pool = {"v": np.zeros((4, 2, 1, 8, 2, 4))}
    assert kv_slab_specs(pool, "model")["v"] == P(
        None, None, None, None, "model")
    assert kv_slab_specs(cache, None)["k"] == P()
