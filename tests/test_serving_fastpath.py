"""Round-10 decode fast path: the serving/ package split (import
compatibility), chunked prefill interleaved with decode, and the
multi-prefix KV PrefixPool on both engines.

The exact-parity contract is the same as tests/test_serving.py's:
every request matches its solo generate()/prompt_cache run bit for
bit; the new machinery (chunk scheduling, pool gathers) must be
invisible in the emitted tokens.
"""

import importlib
import os

import jax
import numpy as np
import pytest

from distkeras_tpu import obs
from distkeras_tpu.serving import (ContinuousBatcher, PrefixPool,
                                   SpeculativeBatcher)
from helpers import generate, jgen, serve_cfg, spec_draft_cfg, toy_params

CFG = serve_cfg(max_len=64)


@pytest.fixture(scope="module")
def params():
    return toy_params(CFG)


def run_to_done(eng, lane):
    while lane in eng.running():
        eng.step()
    return eng.drain(lane)


def solo(params, prompt, n, **kw):
    return np.asarray(generate(params, np.asarray(prompt)[None], CFG,
                               n, **kw))[0]


# ------------------------------------------------------- package split


def test_package_split_import_compat():
    """serving.py is gone; the serving/ package re-exports the exact
    public API at the old import path, and each split module imports
    on its own."""
    import distkeras_tpu
    import distkeras_tpu.serving as serving

    root = os.path.dirname(distkeras_tpu.__file__)
    assert not os.path.exists(os.path.join(root, "serving.py"))
    assert os.path.isdir(os.path.join(root, "serving"))
    for name in ("ContinuousBatcher", "SpeculativeBatcher",
                 "RequestResult", "QueueFull", "EngineClosed",
                 "PrefixPool"):
        assert name in serving.__all__, name
        assert getattr(serving, name) is not None
    for mod in ("engine", "lanes", "admission", "speculative",
                "elastic", "prefix"):
        m = importlib.import_module(f"distkeras_tpu.serving.{mod}")
        assert m is not None
    # The resilience-owned types are the SAME objects on every path.
    from distkeras_tpu.resilience.admission import QueueFull as RQ
    assert serving.QueueFull is RQ is distkeras_tpu.QueueFull
    assert distkeras_tpu.ContinuousBatcher is serving.ContinuousBatcher
    assert distkeras_tpu.PrefixPool is serving.PrefixPool


# ------------------------------------------------------ chunked prefill


def test_chunked_prefill_parity_and_interleave(params, rng):
    """A prompt longer than prefill_chunk admits in chunks between
    decode steps: the OTHER lane keeps emitting one token on EVERY
    step while the long prompt admits (the inter-token gap is bounded
    by one chunk), and both outputs match their solo runs exactly."""
    eng = ContinuousBatcher(params, CFG, lanes=2, prefill_chunk=8,
                            prompt_buckets=(8, 16))
    ps = rng.integers(0, 64, (4,)).astype(np.int32)
    pl = rng.integers(0, 64, (30,)).astype(np.int32)  # warm 29: 3+tail
    ls = eng.submit(ps, 24)
    for _ in range(2):
        eng.step()
    ll = eng.submit(pl, 8)               # parked, admits over steps
    assert ll in eng.running()           # running() covers admitting
    with pytest.raises(ValueError, match="still decoding"):
        eng.drain(ll)
    short_emissions = []
    while ll in eng.running():
        out = eng.step()
        short_emissions.append(len(out.get(ls, [])))
    # The short lane emitted on every step of the long admission.
    assert short_emissions and all(n == 1 for n in short_emissions)
    np.testing.assert_array_equal(eng.drain(ll), solo(params, pl, 8))
    np.testing.assert_array_equal(run_to_done(eng, ls),
                                  solo(params, ps, 24))


def test_chunked_prefill_1k_prompt_bounded_gap(rng):
    """The acceptance shape: a >= 1k-token prompt admitted mid-flight
    never blocks the other lane for more than one chunk step — the
    decoding lane emits exactly one token per step() through the whole
    8-chunk admission, and the long request's output still matches its
    solo run."""
    big = serve_cfg(max_len=1056)
    bparams = toy_params(big, 2)
    eng = ContinuousBatcher(bparams, big, lanes=2, prefill_chunk=128,
                            prompt_buckets=(8, 128))
    ps = rng.integers(0, 64, (4,)).astype(np.int32)
    pl = rng.integers(0, 64, (1025,)).astype(np.int32)  # warm = 1024
    ls = eng.submit(ps, 24)
    eng.step()
    ll = eng.submit(pl, 4)          # chunk 0 at submit, 7 interleaved
    assert len(eng._lane_state[ll].chunks) == 7
    gaps = []
    while ll in eng.running():
        out = eng.step()
        gaps.append(len(out.get(ls, [])))
    assert all(n == 1 for n in gaps[:7])   # one token per chunk step
    out_l = eng.drain(ll)
    np.testing.assert_array_equal(
        out_l, np.asarray(generate(bparams, pl[None], big, 4))[0])
    np.testing.assert_array_equal(
        run_to_done(eng, ls),
        np.asarray(generate(bparams, ps[None], big, 24))[0])


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_sampled_and_tail_overlap(params, rng, chunk):
    """Chunked admission writes exactly the monolithic K/V: sampled
    requests replay their solo streams through awkward tail sizes
    (warm % chunk != 0 exercises the backed-up overlap tail)."""
    eng = ContinuousBatcher(params, CFG, lanes=1, prefill_chunk=chunk,
                            temperature=0.8, top_k=8,
                            prompt_buckets=(8,))
    for plen in (chunk + 2, 3 * chunk - 1):
        p = rng.integers(0, 64, (plen,)).astype(np.int32)
        k = jax.random.key(plen)
        lane = eng.submit(p, 6, key=k)
        np.testing.assert_array_equal(
            run_to_done(eng, lane),
            solo(params, p, 6, temperature=0.8, top_k=8, key=k))


def test_chunked_prefill_validation(params):
    with pytest.raises(ValueError, match="full-cache"):
        roll = serve_cfg(max_len=12, attention_window=5)
        ContinuousBatcher(toy_params(roll, 1),
                          roll, lanes=1, prefill_chunk=4)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ContinuousBatcher(params, CFG, lanes=1, prefill_chunk=0)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ContinuousBatcher(params, CFG, lanes=1, prefill_chunk=100)


def test_chunked_lane_evicted_mid_admission(params, rng):
    """A deadline that expires while a lane is still admitting evicts
    it cleanly: structured timeout, chunk queue drained, and the lane
    is immediately reusable with exact parity."""
    t = {"now": 0.0}
    eng = ContinuousBatcher(params, CFG, lanes=1, prefill_chunk=8,
                            prompt_buckets=(8,),
                            clock=lambda: t["now"])
    pl = rng.integers(0, 64, (30,)).astype(np.int32)
    lane = eng.submit(pl, 8, ttl=5.0)
    rid = eng.last_request_id
    assert eng._admitting            # parked mid-admission
    t["now"] = 10.0
    eng.step()                       # reap evicts the parked lane
    res = eng.take(rid)
    assert res.timed_out and not eng._admitting
    p2 = rng.integers(0, 64, (5,)).astype(np.int32)
    lane2 = eng.submit(p2, 6)
    np.testing.assert_array_equal(run_to_done(eng, lane2),
                                  solo(params, p2, 6))


# ---------------------------------------------------------- PrefixPool


def test_prefix_pool_refcount_lru_and_errors(params, rng):
    pool = PrefixPool(CFG, slots=2)
    segs = {}
    for name, n in (("a", 4), ("b", 6), ("c", 5)):
        pref = rng.integers(0, 64, (n,)).astype(np.int32)
        cache, _ = jgen.prefill(params, pref[None], CFG, last_logits=False)
        segs[name] = (pref, cache)
    ida = pool.put(segs["a"][1], 4)
    idb = pool.put(segs["b"][1], 6)
    assert len(pool) == 2 and pool.length_of(ida) == 4
    # LRU: touch a, insert c -> b (least recent, unreferenced) evicted.
    pool.acquire(ida)
    pool.release(ida)
    idc = pool.put(segs["c"][1], 5)
    assert idb not in pool and ida in pool and idc in pool
    with pytest.raises(KeyError, match="prefix_id"):
        pool.length_of(idb)          # stale id fails loudly
    # Pinned entries are never evicted: pin both, put must raise.
    pool.acquire(ida)
    pool.acquire(idc)
    with pytest.raises(RuntimeError, match="referenced"):
        pool.put(segs["b"][1], 6)
    pool.release(ida)
    pool.put(segs["b"][1], 6)        # unpinned LRU slot frees up
    assert ida not in pool and idc in pool
    # Validation: segment shape/quantization must match the pool spec.
    with pytest.raises(ValueError, match="spec"):
        qcache, _ = jgen.prefill(params, segs["a"][0][None], CFG,
                            last_logits=False, kv_int8=True)
        pool.put(qcache, 4)
    with pytest.raises(ValueError, match="length"):
        pool.put(segs["a"][1], 0)


def test_prefix_pool_engine_parity_and_zero_prefix_work(params, rng,
                                                        tmp_path):
    """Two distinct pooled prefixes on one engine: each request
    matches generate(tail, prompt_cache=(segment, P)) exactly, a
    plain request still works, and the admission span proves the
    prefix tokens ran NO prefill work (the admitted bucket covers only
    the tail, not prefix + tail)."""
    from distkeras_tpu.obs.trace import read_trace

    pool = PrefixPool(CFG, slots=2)
    pref_a = rng.integers(0, 64, (20,)).astype(np.int32)
    pref_b = rng.integers(0, 64, (6,)).astype(np.int32)
    ca, _ = jgen.prefill(params, pref_a[None], CFG, last_logits=False)
    cb, _ = jgen.prefill(params, pref_b[None], CFG, last_logits=False)
    ida, idb = pool.put(ca, 20), pool.put(cb, 6)
    eng = ContinuousBatcher(params, CFG, lanes=2, prefix_pool=pool,
                            prompt_buckets=(8,))
    tail = rng.integers(0, 64, (4,)).astype(np.int32)
    path = str(tmp_path / "admit.jsonl")
    with obs.session(trace_path=path):
        la = eng.submit(tail, 6, prefix_id=ida)
        lb = eng.submit(tail, 6, prefix_id=idb)
        assert pool.refs_of(ida) == pool.refs_of(idb) == 1
        oa, ob = run_to_done(eng, la), run_to_done(eng, lb)
    np.testing.assert_array_equal(
        oa, np.asarray(generate(params, tail[None], CFG, 6,
                                prompt_cache=(ca, 20)))[0])
    np.testing.assert_array_equal(
        ob, np.asarray(generate(params, tail[None], CFG, 6,
                                prompt_cache=(cb, 6)))[0])
    assert pool.refs_of(ida) == 0    # drain released the pin
    # Step accounting for "no prefill work for the prefix": the
    # 20-token prefix + 3 warm tokens admitted through the 8-wide
    # bucket.  Re-prefilling prefix+tail would need a >= 23-wide
    # program (the 64 bucket); bucket == 8 proves only the tail ran.
    admits = [r for r in read_trace(path)
              if r.get("name") == "serving.admit"]
    assert len(admits) == 2
    assert all(r["fields"]["bucket"] == 8 for r in admits)
    # Plain request on the pooled engine (slot -1 = zero seed).
    lp = eng.submit(tail, 6)
    np.testing.assert_array_equal(run_to_done(eng, lp),
                                  solo(params, tail, 6))
    # Stale prefix id at submit fails loudly.
    with pytest.raises(ValueError, match="needs"):
        ContinuousBatcher(params, CFG, lanes=1).submit(
            tail, 4, prefix_id=ida)


def test_prefix_pool_sampled_kv_int8_and_lane_reuse(params, rng):
    """kv_int8 engines pool kv_int8 segments (quantization-matched
    gather, scale slabs included): greedy AND sampled pooled requests
    match generate(prompt_cache=..., kv_int8=True), through lane
    reuse and the 1-token-prompt reseed path."""
    pool = PrefixPool(CFG, slots=2, kv_int8=True)
    pref = rng.integers(0, 64, (6,)).astype(np.int32)
    cache, _ = jgen.prefill(params, pref[None], CFG, last_logits=False,
                       kv_int8=True)
    pid = pool.put(cache, 6)
    with pytest.warns(RuntimeWarning, match="kv_int8"):
        eng = ContinuousBatcher(params, CFG, lanes=1, kv_int8=True,
                                prefix_pool=pool, prompt_buckets=(8,),
                                temperature=0.8,
                                per_request_sampling=True)
    for tail_len in (3, 1):          # 1: the pooled reseed path
        tail = rng.integers(0, 64, (tail_len,)).astype(np.int32)
        lane = eng.submit(tail, 5, temperature=0.0, prefix_id=pid)
        out = run_to_done(eng, lane)
        np.testing.assert_array_equal(
            out, np.asarray(generate(params, tail[None], CFG, 5,
                                     prompt_cache=(cache, 6),
                                     kv_int8=True))[0])
    tail = rng.integers(0, 64, (3,)).astype(np.int32)
    k = jax.random.key(17)
    lane = eng.submit(tail, 5, key=k, prefix_id=pid)
    np.testing.assert_array_equal(
        run_to_done(eng, lane),
        np.asarray(generate(params, tail[None], CFG, 5,
                            prompt_cache=(cache, 6), kv_int8=True,
                            temperature=0.8, key=k))[0])
    # Quantization mismatch between pool and engine rejects (before
    # the small-lane advisory is even reached).
    with pytest.raises(ValueError, match="kv_int8"):
        ContinuousBatcher(params, CFG, lanes=1, kv_int8=True,
                          prefix_pool=PrefixPool(CFG, slots=1))


def test_prefix_pin_taken_first_and_released_on_decline(params, rng):
    """The eviction race is closed by pinning BEFORE any slab access:
    while a pooled request occupies a lane its entry cannot be evicted
    by put() (pinned entries are never victims), and every declined
    or failed submit releases the pin it took."""
    pool = PrefixPool(CFG, slots=1)
    pref = rng.integers(0, 64, (6,)).astype(np.int32)
    cache, _ = jgen.prefill(params, pref[None], CFG, last_logits=False)
    pid = pool.put(cache, 6)
    eng = ContinuousBatcher(params, CFG, lanes=1, prefix_pool=pool,
                            prompt_buckets=(8,))
    tail = rng.integers(0, 64, (4,)).astype(np.int32)
    # Validation failure AFTER the pin releases it.
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(tail, 100, prefix_id=pid)
    assert pool.refs_of(pid) == 0
    lane = eng.submit(tail, 5, prefix_id=pid)
    assert pool.refs_of(pid) == 1
    # Engine-full decline releases its own pin, not the lane's.
    assert eng.submit(tail, 5, prefix_id=pid) is None
    assert pool.refs_of(pid) == 1
    # While the lane decodes, the pinned entry can NEVER be evicted.
    with pytest.raises(RuntimeError, match="referenced"):
        pool.put(cache, 6)
    run_to_done(eng, lane)
    assert pool.refs_of(pid) == 0
    pool.put(cache, 6)               # now evictable again


def test_prefix_pool_chunked_compose(params, rng):
    """prefill_chunk and prefix_pool compose: a long tail past a
    pooled prefix admits in chunks and still matches
    generate(prompt_cache=...)."""
    pool = PrefixPool(CFG, slots=1)
    pref = rng.integers(0, 64, (6,)).astype(np.int32)
    cache, _ = jgen.prefill(params, pref[None], CFG, last_logits=False)
    pid = pool.put(cache, 6)
    eng = ContinuousBatcher(params, CFG, lanes=1, prefix_pool=pool,
                            prefill_chunk=8, prompt_buckets=(8,))
    tail = rng.integers(0, 64, (25,)).astype(np.int32)  # warm 24: 3 ch
    lane = eng.submit(tail, 6, prefix_id=pid)
    np.testing.assert_array_equal(
        run_to_done(eng, lane),
        np.asarray(generate(params, tail[None], CFG, 6,
                            prompt_cache=(cache, 6)))[0])


# ------------------------------------------- SpeculativeBatcher prefix


def test_speculative_prefix_pool_greedy_parity(params, rng):
    """The v1 'no shared prefix' exclusion is lifted: pooled
    (target, draft) prefix pairs serve speculative lanes with exact
    greedy parity vs generate(prompt_cache=...) — including the
    1-token-prompt reseed (which needs the recorded last_token) — and
    refcounts release at drain."""
    draft_cfg = spec_draft_cfg(max_len=64)
    draft = toy_params(draft_cfg, 9)
    pref = rng.integers(0, 64, (10,)).astype(np.int32)
    tca, _ = jgen.prefill(params, pref[None], CFG, last_logits=False)
    dca, _ = jgen.prefill(draft, pref[None], draft_cfg, last_logits=False)
    pool = PrefixPool(CFG, slots=2, draft_cfg=draft_cfg)
    pid = pool.put((tca, dca), 10, last_token=int(pref[-1]))
    pid_bare = pool.put((tca, dca), 10)      # no last_token recorded
    eng = SpeculativeBatcher(params, draft, CFG, draft_cfg, lanes=2,
                             n_draft=3, prefix_pool=pool,
                             prompt_buckets=(8,))
    tail = rng.integers(0, 64, (4,)).astype(np.int32)
    one = np.asarray([5], np.int32)
    la = eng.submit(tail, 6, prefix_id=pid)
    lb = eng.submit(one, 5, prefix_id=pid)
    assert pool.refs_of(pid) == 2
    oa, ob = run_to_done(eng, la), run_to_done(eng, lb)
    np.testing.assert_array_equal(
        oa, np.asarray(generate(params, tail[None], CFG, 6,
                                prompt_cache=(tca, 10)))[0])
    np.testing.assert_array_equal(
        ob, np.asarray(generate(params, one[None], CFG, 5,
                                prompt_cache=(tca, 10)))[0])
    assert pool.refs_of(pid) == 0
    # Budget counts the prefix: 10 + 4 + 50 - 1 > cap(60) rejects.
    with pytest.raises(ValueError, match="prefix"):
        eng.submit(tail, 50, prefix_id=pid)
    # 1-token prompt without a recorded last_token fails loudly.
    with pytest.raises(ValueError, match="last token"):
        eng.submit(one, 5, prefix_id=pid_bare)
    # A plain (no-prefix) request on the pooled engine still matches.
    lc = eng.submit(tail, 6)
    np.testing.assert_array_equal(run_to_done(eng, lc),
                                  solo(params, tail, 6))


def test_speculative_pool_validation(params, rng):
    draft_cfg = spec_draft_cfg(max_len=64)
    draft = toy_params(draft_cfg, 9)
    with pytest.raises(ValueError, match="speculative pool"):
        SpeculativeBatcher(params, draft, CFG, draft_cfg,
                           prefix_pool=PrefixPool(CFG, slots=1))
    with pytest.raises(ValueError, match="plain PrefixPool"):
        ContinuousBatcher(params, CFG, prefix_pool=PrefixPool(
            CFG, slots=1, draft_cfg=draft_cfg))
    with pytest.raises(ValueError, match="full-cache"):
        PrefixPool(serve_cfg(max_len=12, attention_window=5), slots=1)


# ------------------------------------------------------ kv_int8 advice


def test_kv_int8_small_lane_advisory(params, tmp_path):
    """Construction-time advisory: kv_int8 below the documented
    cache-bound regime (−15% at b8, serving_guide byte-lever table)
    warns and records an obs event; at/above the threshold it is
    silent."""
    from distkeras_tpu.obs.trace import read_trace
    from distkeras_tpu.serving.lanes import KV_INT8_LANE_ADVISORY

    path = str(tmp_path / "adv.jsonl")
    with obs.session(trace_path=path):
        with pytest.warns(RuntimeWarning, match="kv_int8"):
            ContinuousBatcher(params, CFG, lanes=2, kv_int8=True)
    evs = [r for r in read_trace(path)
           if r.get("name") == "serving.advisory"]
    assert len(evs) == 1
    assert evs[0]["fields"]["kind"] == "kv_int8_small_lanes"
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        ContinuousBatcher(params, CFG, lanes=KV_INT8_LANE_ADVISORY,
                          kv_int8=True, prompt_buckets=(8,))


# --------------------------------------------------- elastic composure


def test_elastic_chunked_pool_enqueue(params, rng):
    """Elastic tiers compose with chunked prefill + the pool: a long
    pooled request enqueued under load admits in chunks across a tier
    step-up and finishes with exact parity."""
    pool = PrefixPool(CFG, slots=1)
    pref = rng.integers(0, 64, (6,)).astype(np.int32)
    cache, _ = jgen.prefill(params, pref[None], CFG, last_logits=False)
    pid = pool.put(cache, 6)
    eng = ContinuousBatcher(params, CFG, lane_tiers=(1, 2),
                            max_queue=1, scale_up_after=1,
                            scale_down_after=4, prompt_buckets=(8,),
                            prefill_chunk=8, prefix_pool=pool)
    long_tail = rng.integers(0, 64, (20,)).astype(np.int32)
    short = rng.integers(0, 64, (3,)).astype(np.int32)
    rids = [eng.enqueue(long_tail, 5, prefix_id=pid),
            eng.enqueue(short, 5),
            eng.enqueue(short, 5)]
    while any(eng.poll(r) is None for r in rids):
        eng.step()
    res = [eng.take(r) for r in rids]
    assert all(r.ok for r in res)
    np.testing.assert_array_equal(
        res[0].tokens,
        np.asarray(generate(params, long_tail[None], CFG, 5,
                            prompt_cache=(cache, 6)))[0])
    np.testing.assert_array_equal(res[1].tokens,
                                  solo(params, short, 5))
