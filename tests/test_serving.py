"""Continuous batching: every request matches its solo generate() run,
under staggered admission and lane reuse."""

import jax
import numpy as np
import pytest

from helpers import generate, jgen, serve_cfg, spec_draft_cfg, toy_params
from distkeras_tpu.serving import ContinuousBatcher


CFG = serve_cfg()


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


def test_single_request_matches_generate(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=4)
    prompt = rng.integers(0, 64, (5,)).astype(np.int32)
    lane = eng.submit(prompt, 8)
    out = run_to_done(eng, lane)
    np.testing.assert_array_equal(out, solo(params, prompt, 8))


def test_sampled_request_matches_generate(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=2, temperature=0.8,
                            top_k=8)
    prompt = rng.integers(0, 64, (4,)).astype(np.int32)
    k = jax.random.key(11)
    lane = eng.submit(prompt, 6, key=k)
    out = run_to_done(eng, lane)
    np.testing.assert_array_equal(
        out, solo(params, prompt, 6, temperature=0.8, top_k=8, key=k))


def test_staggered_admission_and_lane_reuse(params, rng):
    """Requests admitted mid-flight (and into a reused lane) still
    match their solo runs — lanes are independent."""
    eng = ContinuousBatcher(params, CFG, lanes=2)
    pa = rng.integers(0, 64, (6,)).astype(np.int32)
    pb = rng.integers(0, 64, (3,)).astype(np.int32)
    pc = rng.integers(0, 64, (9,)).astype(np.int32)

    la = eng.submit(pa, 10)
    for _ in range(3):
        eng.step()                       # A decodes alone for 3 steps
    lb = eng.submit(pb, 5)               # B admitted mid-flight
    out_a = run_to_done(eng, la)
    out_b = run_to_done(eng, lb)
    lc = eng.submit(pc, 4)               # reuses a freed lane
    out_c = run_to_done(eng, lc)

    np.testing.assert_array_equal(out_a, solo(params, pa, 10))
    np.testing.assert_array_equal(out_b, solo(params, pb, 5))
    np.testing.assert_array_equal(out_c, solo(params, pc, 4))
    assert lc in (la, lb)                # a lane was actually reused


def test_eos_and_one_token_prompt(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=2, eos_token=7)
    p1 = rng.integers(0, 64, (1,)).astype(np.int32)
    lane = eng.submit(p1, 12)
    out = run_to_done(eng, lane)
    ref = solo(params, p1, 12, eos_token=7)
    # The engine stops at eos; generate() sticky-fills to full length.
    np.testing.assert_array_equal(out, ref[:len(out)])
    if len(out) < len(ref):
        assert out[-1] == 7 and (ref[len(out):] == 7).all()


def test_capacity_and_validation(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=1)
    p = rng.integers(0, 64, (4,)).astype(np.int32)
    assert eng.submit(p, 4) == 0
    assert eng.submit(p, 4) is None      # full
    with pytest.raises(ValueError, match="still decoding"):
        eng.drain(0)
    run_to_done(eng, 0)
    assert eng.submit(p, 4) == 0              # drained lane is reusable
    with pytest.raises(ValueError, match="max_len"):
        ContinuousBatcher(params, CFG, lanes=1).submit(p, 40)
    with pytest.raises(ValueError, match="key iff"):
        eng.submit(p, 4, key=jax.random.key(0))
    with pytest.raises(ValueError, match="temperature > 0"):
        ContinuousBatcher(params, CFG, top_k=5)


def test_quantized_weights_match_quantized_generate(params, rng):
    """int8 weight trees serve through the engine (the chunk path
    dequantizes per read) and match their solo quantized run."""
    from distkeras_tpu.models.quant import quantize_params

    qp = quantize_params(params)
    eng = ContinuousBatcher(qp, CFG, lanes=2)
    prompt = rng.integers(0, 64, (5,)).astype(np.int32)
    lane = eng.submit(prompt, 6)
    out = run_to_done(eng, lane)
    np.testing.assert_array_equal(out, solo(qp, prompt, 6))


def test_engine_shared_prefix_matches_generate_prompt_cache(params, rng):
    """An engine built over a shared prefilled prefix emits exactly
    what generate(prompt_cache=...) emits per request — including for a
    lane's SECOND occupant (the admission reseed from the prefix)."""
    prefix = rng.integers(0, 64, (6,)).astype(np.int32)
    cache, _ = jgen.prefill(params, prefix[None], CFG, last_logits=False)
    eng = ContinuousBatcher(params, CFG, lanes=1,
                            prompt_cache=(cache, 6))
    for tail_len in (3, 1):    # second pass reuses lane 0; tail_len 1
        #                          pins the no-admission reseed path
        tail = rng.integers(0, 64, (tail_len,)).astype(np.int32)
        lane = eng.submit(tail, 5)
        out = run_to_done(eng, lane)
        ref = np.asarray(generate(params, tail[None], CFG, 5,
                                  prompt_cache=(cache, 6)))[0]
        np.testing.assert_array_equal(out, ref)
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(rng.integers(0, 64, (20,)).astype(np.int32), 10)
    with pytest.raises(ValueError, match="batch 1"):
        big = {k: np.repeat(np.asarray(v), 2, axis=1)
               for k, v in cache.items()}
        ContinuousBatcher(params, CFG, prompt_cache=(big, 6))


def test_multi_token_step_matches_single_steps(params, rng):
    """step(n) emits exactly the tokens of n step(1) calls — greedy and
    sampled — including mid-window retirement truncation."""
    for kw in [{}, dict(temperature=0.8, top_k=8)]:
        key = jax.random.key(5) if kw else None
        prompts = [rng.integers(0, 64, (4,)).astype(np.int32)
                   for _ in range(2)]
        outs = {}
        for n in (1, 4):
            eng = ContinuousBatcher(params, CFG, lanes=2,
                                    eos_token=3, **kw)
            lanes = [eng.submit(p, 9, key=key) if kw else
                     eng.submit(p, 9) for p in prompts]
            while eng.running():
                eng.step(n)
            outs[n] = [eng.drain(l) for l in lanes]
        for a, b in zip(outs[1], outs[4]):
            np.testing.assert_array_equal(a, b)


def test_engine_fuzz_schedule_matches_solo(params, rng):
    """Property test: a randomized arrival/length/window schedule over
    few lanes still gives every request exactly its solo generate()
    output (with sticky-eos truncation)."""
    eng = ContinuousBatcher(params, CFG, lanes=3, eos_token=9)
    reqs = []            # (prompt, max_new)
    for _ in range(8):
        p = rng.integers(1, 12)
        reqs.append((rng.integers(0, 64, (p,)).astype(np.int32),
                     int(rng.integers(1, 32 - p))))
    pending = list(range(len(reqs)))
    lane_of, outs = {}, {}
    while len(outs) < len(reqs):
        while pending and eng.free_lanes():
            rid = pending.pop(0)
            lane_of[eng.submit(*reqs[rid])] = rid
        eng.step(int(rng.integers(1, 5)))
        for lane in list(lane_of):
            if lane not in eng.running():
                outs[lane_of.pop(lane)] = eng.drain(lane)
    for rid, (prompt, n) in enumerate(reqs):
        ref = solo(params, prompt, n, eos_token=9)
        out = outs[rid]
        np.testing.assert_array_equal(out, ref[:len(out)])
        # Truncation only ever drops sticky-eos fill.
        if len(out) < len(ref):
            assert out[-1] == 9 and (ref[len(out):] == 9).all()


def test_lane_pos_clamped_and_idle_engine_skips_device(params, rng):
    """Device-side invariants (advisor round-3): (a) per-lane positions
    never advance past max_len - 1 — free/done lanes keep decoding but
    their pos pins at the last slot instead of relying on
    dynamic_update_slice start-clamping; (b) an engine whose lanes are
    all empty/finished returns {} without a device round-trip; (c) a
    lane reused after a long over-decode run still matches solo."""
    eng = ContinuousBatcher(params, CFG, lanes=2)
    pa = rng.integers(0, 64, (4,)).astype(np.int32)
    la = eng.submit(pa, 3)
    # Over-step far past every budget: lane A finishes (done, undrained)
    # while lane B is free; both keep decoding until A retires.
    out = []
    while la in eng.running():
        out.extend(eng.step().get(la, []))
    np.testing.assert_array_equal(
        eng.drain(la), solo(params, pa, 3))
    # Idle engine: no lane can emit -> no device work, state untouched.
    pos_before = np.asarray(eng.pos)
    assert eng.step(4) == {}
    np.testing.assert_array_equal(np.asarray(eng.pos), pos_before)
    # Force many windows with one live lane so the OTHER (free) lane
    # over-decodes; its pos must pin at max_len - 1.
    lb = eng.submit(rng.integers(0, 64, (2,)).astype(np.int32),
                    CFG.max_len - 3)
    while lb in eng.running():
        eng.step(4)
    assert int(np.asarray(eng.pos).max()) <= CFG.max_len - 1
    # Lane 1 was never admitted and over-decoded the whole test: it
    # sits AT the clamp.  Readmit THAT lane (occupy lane 0 first —
    # submit picks the lowest free lane) and require solo parity.
    assert int(np.asarray(eng.pos)[1]) == CFG.max_len - 1
    eng.drain(lb)
    assert eng.submit(rng.integers(0, 64, (2,)).astype(np.int32),
                      2) == 0
    pc = rng.integers(0, 64, (5,)).astype(np.int32)
    lc = eng.submit(pc, 6)
    assert lc == 1
    np.testing.assert_array_equal(run_to_done(eng, lc),
                                  solo(params, pc, 6))


ROLL_CFG = serve_cfg(max_len=12, attention_window=5)


def test_rolling_engine_matches_rolling_generate(params, rng):
    """Windowed (rope + attention_window) engines run ROLLING lanes:
    each request decodes past max_len on the ring cache and must match
    its solo rolling generate() run exactly — staggered admission,
    lane reuse, and lanes mid-wrap while a fresh lane is admitted."""
    rparams = toy_params(ROLL_CFG, 3)

    def rsolo(prompt, n, **kw):
        return np.asarray(generate(rparams, np.asarray(prompt)[None],
                                   ROLL_CFG, n, **kw))[0]

    eng = ContinuousBatcher(rparams, ROLL_CFG, lanes=2)
    pa = rng.integers(0, 64, (4,)).astype(np.int32)
    pb = rng.integers(0, 64, (6,)).astype(np.int32)
    pc = rng.integers(0, 64, (3,)).astype(np.int32)
    la = eng.submit(pa, 30)              # 4 + 30 = 34 >> 12: wraps
    for _ in range(10):                  # A rolls past the ring alone
        eng.step()
    lb = eng.submit(pb, 20)              # admitted mid-wrap of A
    out_a = run_to_done(eng, la)
    out_b = run_to_done(eng, lb)
    lc = eng.submit(pc, 25)              # reuses a freed, wrapped lane
    out_c = run_to_done(eng, lc)
    np.testing.assert_array_equal(out_a, rsolo(pa, 30))
    np.testing.assert_array_equal(out_b, rsolo(pb, 20))
    np.testing.assert_array_equal(out_c, rsolo(pc, 25))
    assert lc in (la, lb)


def test_rolling_engine_sampled_and_validation(params, rng):
    """Sampled rolling lanes match solo rolling generate with the same
    per-request key; windowed engines without rope are rejected, and
    rolling submit has no total-length cap while the prompt still must
    fit the admission buckets."""
    import dataclasses

    rparams = toy_params(ROLL_CFG, 4)
    eng = ContinuousBatcher(rparams, ROLL_CFG, lanes=2,
                            temperature=0.8, top_k=8)
    p = rng.integers(0, 64, (4,)).astype(np.int32)
    k = jax.random.key(21)
    lane = eng.submit(p, 24, key=k)      # 4 + 24 = 28 > 12
    out = run_to_done(eng, lane)
    ref = np.asarray(generate(rparams, p[None], ROLL_CFG, 24,
                              temperature=0.8, top_k=8, key=k))[0]
    np.testing.assert_array_equal(out, ref)

    norope = dataclasses.replace(ROLL_CFG, rope=False)
    with pytest.raises(ValueError, match="rolling lanes"):
        ContinuousBatcher(toy_params(norope),
                          norope, lanes=1)
    # Prompt must fit the ring (admission chunk cannot wrap).
    with pytest.raises(ValueError, match="admission"):
        eng.submit(rng.integers(0, 64, (20,)).astype(np.int32), 4,
                   key=jax.random.key(1))


def test_kv_int8_engine_matches_sequential_generate(params, rng):
    """kv_int8 engines: every request matches its solo
    generate(kv_int8=True, use_prefill=False) run EXACTLY — both paths
    attend the already-quantized cache position by position (the
    admission chunk writes quantized K/V and its in-chunk attention
    reads them, same as the sequential step loop; prefill() would
    differ by quantization noise).  Staggered admission + lane reuse +
    a sampled request, plus the windowed/prefix validation edges."""
    eng = ContinuousBatcher(params, CFG, lanes=2, kv_int8=True)
    pa = rng.integers(0, 64, (6,)).astype(np.int32)
    pb = rng.integers(0, 64, (3,)).astype(np.int32)
    la = eng.submit(pa, 8)
    for _ in range(3):
        eng.step()
    lb = eng.submit(pb, 6)                  # admitted mid-flight
    out_a = run_to_done(eng, la)
    out_b = run_to_done(eng, lb)
    lc = eng.submit(pb, 4)                  # reused (quantized) lane
    out_c = run_to_done(eng, lc)
    for out, p, n in [(out_a, pa, 8), (out_b, pb, 6), (out_c, pb, 4)]:
        np.testing.assert_array_equal(
            out, solo(params, p, n, kv_int8=True, use_prefill=False))

    seng = ContinuousBatcher(params, CFG, lanes=1, kv_int8=True,
                             temperature=0.8, top_k=8)
    k = jax.random.key(31)
    lane = seng.submit(pa, 6, key=k)
    np.testing.assert_array_equal(
        run_to_done(seng, lane),
        solo(params, pa, 6, kv_int8=True, use_prefill=False,
             temperature=0.8, top_k=8, key=k))

    # Windowed engines take kv_int8 too since round 5 — positive
    # coverage in test_kv_int8_rolling_engine_matches_rolling_generate.
    # Prefix quantization must match the engine cache.
    fp_cache, _ = jgen.prefill(params, pa[None], CFG, last_logits=False)
    with pytest.raises(ValueError, match="quantization must match"):
        ContinuousBatcher(params, CFG, lanes=1, kv_int8=True,
                          prompt_cache=(fp_cache, 6))


def test_kv_int8_engine_shared_prefix(params, rng):
    """A kv_int8 engine over a kv_int8-prefilled shared prefix matches
    generate(prompt_cache=..., kv_int8=True) per request, including
    the lane-reuse reseed."""
    prefix = rng.integers(0, 64, (6,)).astype(np.int32)
    cache, _ = jgen.prefill(params, prefix[None], CFG, last_logits=False,
                       kv_int8=True)
    eng = ContinuousBatcher(params, CFG, lanes=1, kv_int8=True,
                            prompt_cache=(cache, 6))
    for tail_len in (3, 1):
        tail = rng.integers(0, 64, (tail_len,)).astype(np.int32)
        lane = eng.submit(tail, 5)
        out = run_to_done(eng, lane)
        ref = np.asarray(generate(params, tail[None], CFG, 5,
                                  prompt_cache=(cache, 6),
                                  kv_int8=True))[0]
        np.testing.assert_array_equal(out, ref)


def test_kv_int8_rolling_engine_matches_rolling_generate(rng):
    """kv_int8 on ROLLING ring lanes (round-5: serving.py's windowed x
    kv_int8 rejection deleted): every request decodes past max_len on
    the int8 ring cache and matches its solo sequential
    generate(kv_int8=True, use_prefill=False) run EXACTLY — admission
    chunk and decode loop both attend the already-quantized cache."""
    rparams = toy_params(ROLL_CFG, 5)
    eng = ContinuousBatcher(rparams, ROLL_CFG, lanes=2, kv_int8=True)
    assert eng.kv_int8 and "k_scale" in eng.cache

    def rsolo(prompt, n):
        return np.asarray(generate(rparams, np.asarray(prompt)[None],
                                   ROLL_CFG, n, kv_int8=True,
                                   use_prefill=False))[0]

    pa = rng.integers(0, 64, (4,)).astype(np.int32)
    pb = rng.integers(0, 64, (6,)).astype(np.int32)
    la = eng.submit(pa, 30)              # 4 + 30 = 34 >> 12: wraps
    for _ in range(8):                   # A rolls ahead alone
        eng.step()
    lb = eng.submit(pb, 20)              # admitted mid-wrap of A
    out_a = run_to_done(eng, la)
    out_b = run_to_done(eng, lb)
    np.testing.assert_array_equal(out_a, rsolo(pa, 30))
    np.testing.assert_array_equal(out_b, rsolo(pb, 20))


def test_per_request_sampling_mixed_lanes(params, rng):
    """per_request_sampling=True: greedy and differently-parameterized
    sampled requests decode in ONE batch, each matching its solo
    generate() run exactly (the vectorized per-lane params select per
    row; no-op rows are bit-exact with the scalar path)."""
    eng = ContinuousBatcher(params, CFG, lanes=4,
                            per_request_sampling=True)
    pa, pb, pc, pd = (rng.integers(0, 64, (5,)).astype(np.int32)
                      for _ in range(4))
    ka, kc, kd = (jax.random.key(i) for i in (41, 42, 43))
    la = eng.submit(pa, 8, key=ka, temperature=0.8)
    lb = eng.submit(pb, 8)                        # greedy default
    lc = eng.submit(pc, 8, key=kc, temperature=1.0, top_p=0.9)
    ld = eng.submit(pd, 8, key=kd, temperature=0.7, min_p=0.2)
    outs = {ln: run_to_done(eng, ln) for ln in (la, lb, lc, ld)}
    np.testing.assert_array_equal(
        outs[la], solo(params, pa, 8, temperature=0.8, key=ka))
    np.testing.assert_array_equal(outs[lb], solo(params, pb, 8))
    np.testing.assert_array_equal(
        outs[lc], solo(params, pc, 8, temperature=1.0, top_p=0.9,
                       key=kc))
    np.testing.assert_array_equal(
        outs[ld], solo(params, pd, 8, temperature=0.7, min_p=0.2,
                       key=kd))
    # Lane reuse flips a sampled lane back to greedy cleanly.
    le = eng.submit(pa, 6)
    np.testing.assert_array_equal(run_to_done(eng, le),
                                  solo(params, pa, 6))


def test_per_request_eos_and_validation(params, rng):
    """Per-request eos_token works on ANY engine (host-side
    bookkeeping); param overrides need per_request_sampling=True and
    keep generate()'s key/filter contracts per request."""
    eng = ContinuousBatcher(params, CFG, lanes=2)
    p = rng.integers(0, 64, (4,)).astype(np.int32)
    base = solo(params, p, 10)
    tok = int(base[len(p) + 2])           # emitted at the 3rd new slot
    lane = eng.submit(p, 10, eos_token=tok)
    out = run_to_done(eng, lane)
    assert out[-1] == tok and len(out) <= len(base)
    np.testing.assert_array_equal(out, base[:len(out)])

    with pytest.raises(ValueError, match="per_request_sampling"):
        eng.submit(p, 4, key=jax.random.key(0), temperature=0.5)
    pr = ContinuousBatcher(params, CFG, lanes=2,
                           per_request_sampling=True)
    with pytest.raises(ValueError, match="iff this request samples"):
        pr.submit(p, 4, temperature=0.5)  # samples but no key
    with pytest.raises(ValueError, match="iff this request samples"):
        pr.submit(p, 4, key=jax.random.key(0))  # greedy with key
    with pytest.raises(ValueError, match="top_p/min_p need"):
        pr.submit(p, 4, top_p=0.9)        # filter on a greedy request
    with pytest.raises(ValueError, match="top_p must be"):
        pr.submit(p, 4, key=jax.random.key(0), temperature=0.5,
                  top_p=1.5)
    # Sampling-default engine: a request can drop to greedy (no key).
    sd = ContinuousBatcher(params, CFG, lanes=2, temperature=0.8,
                           top_k=8, per_request_sampling=True)
    ln = sd.submit(p, 6, temperature=0.0)
    np.testing.assert_array_equal(run_to_done(sd, ln),
                                  solo(params, p, 6))
    # min_p=0.0 is the explicit OFF override for a filtering default.
    fd = ContinuousBatcher(params, CFG, lanes=2, temperature=0.8,
                           min_p=0.3, per_request_sampling=True)
    k2 = jax.random.key(77)
    ln2 = fd.submit(p, 6, key=k2, min_p=0.0)
    np.testing.assert_array_equal(
        run_to_done(fd, ln2),
        solo(params, p, 6, temperature=0.8, key=k2))
    # Bad constructor defaults fail eagerly (the per-request arrays
    # would otherwise sample silent garbage).
    with pytest.raises(ValueError, match="min_p must be"):
        ContinuousBatcher(params, CFG, temperature=0.8, min_p=-0.5,
                          per_request_sampling=True)


def test_per_request_fuzz_schedule_matches_solo(params, rng):
    """Property test: randomized arrivals x random per-request params
    (greedy/temperature/top_p/min_p/eos mixes) on a
    per_request_sampling engine — every request still equals its solo
    generate() run."""
    eng = ContinuousBatcher(params, CFG, lanes=3,
                            per_request_sampling=True)
    reqs = []           # (prompt, n, submit_kw, solo_kw)
    for i in range(8):
        p = rng.integers(1, 10)
        prompt = rng.integers(0, 64, (p,)).astype(np.int32)
        n = int(rng.integers(1, 32 - p))
        kind = i % 4
        if kind == 0:
            sub, sol = {}, {}
        elif kind == 1:
            k = jax.random.key(100 + i)
            sub = dict(key=k, temperature=0.8)
            sol = dict(key=k, temperature=0.8)
        elif kind == 2:
            k = jax.random.key(100 + i)
            sub = dict(key=k, temperature=1.1, top_p=0.85, eos_token=9)
            sol = dict(key=k, temperature=1.1, top_p=0.85, eos_token=9)
        else:
            k = jax.random.key(100 + i)
            sub = dict(key=k, temperature=0.6, min_p=0.25)
            sol = dict(key=k, temperature=0.6, min_p=0.25)
        reqs.append((prompt, n, sub, sol))
    pending = list(range(len(reqs)))
    lane_of, outs = {}, {}
    while len(outs) < len(reqs):
        while pending and eng.free_lanes():
            rid = pending.pop(0)
            prompt, n, sub, _ = reqs[rid]
            lane_of[eng.submit(prompt, n, **sub)] = rid
        eng.step(int(rng.integers(1, 4)))
        for lane in list(lane_of):
            if lane not in eng.running():
                outs[lane_of.pop(lane)] = eng.drain(lane)
    for rid, (prompt, n, _, sol) in enumerate(reqs):
        ref = solo(params, prompt, n, **sol)
        out = outs[rid]
        np.testing.assert_array_equal(out, ref[:len(out)],
                                      err_msg=f"request {rid}")
        if len(out) < len(ref):   # eos truncation: tail is sticky fill
            eos = sol["eos_token"]
            assert out[-1] == eos and (ref[len(out):] == eos).all()


def test_per_request_sampling_on_rolling_lanes(rng):
    """per_request_sampling composes with rolling ring lanes: a greedy
    and a sampled request decode past max_len side by side, each
    matching its solo rolling generate() run."""
    rparams = toy_params(ROLL_CFG, 6)
    eng = ContinuousBatcher(rparams, ROLL_CFG, lanes=2,
                            per_request_sampling=True)
    pa = rng.integers(0, 64, (4,)).astype(np.int32)
    pb = rng.integers(0, 64, (5,)).astype(np.int32)
    kb = jax.random.key(33)
    la = eng.submit(pa, 20)                        # greedy, wraps
    lb = eng.submit(pb, 18, key=kb, temperature=0.9, top_p=0.9)
    out_a = run_to_done(eng, la)
    out_b = run_to_done(eng, lb)
    np.testing.assert_array_equal(
        out_a, np.asarray(generate(rparams, pa[None], ROLL_CFG, 20))[0])
    np.testing.assert_array_equal(
        out_b, np.asarray(generate(rparams, pb[None], ROLL_CFG, 18,
                                   temperature=0.9, top_p=0.9,
                                   key=kb))[0])


# ------------------------------------------------------ SpeculativeBatcher

def test_speculative_batcher_matches_solo(params, rng):
    """Draft-assisted lanes: each request's output is exactly its solo
    greedy speculative_generate run (== generate's greedy rollout),
    under staggered admission and lane reuse, with per-request eos."""
    from distkeras_tpu.models.speculative import speculative_generate
    from distkeras_tpu.serving import SpeculativeBatcher

    draft_cfg = spec_draft_cfg()
    draft = toy_params(draft_cfg, 9)
    eng = SpeculativeBatcher(params, draft, CFG, draft_cfg, lanes=2,
                             n_draft=3)
    pa = rng.integers(0, 64, (5,)).astype(np.int32)
    pb = rng.integers(0, 64, (1,)).astype(np.int32)   # 1-token prompt
    pc = rng.integers(0, 64, (7,)).astype(np.int32)

    la = eng.submit(pa, 10)
    eng.step()                            # A advances alone first
    lb = eng.submit(pb, 8)                # admitted mid-flight
    out_a = run_to_done(eng, la)
    out_b = run_to_done(eng, lb)
    lc = eng.submit(pc, 6, eos_token=9)   # reuses a freed lane
    out_c = run_to_done(eng, lc)

    def solo_spec(p, n, **kw):
        out, _ = speculative_generate(params, draft, p[None], CFG,
                                      draft_cfg, n, n_draft=3, **kw)
        return np.asarray(out)[0]

    np.testing.assert_array_equal(out_a, solo_spec(pa, 10))
    np.testing.assert_array_equal(out_b, solo_spec(pb, 8))
    ref_c = solo_spec(pc, 6, eos_token=9)
    np.testing.assert_array_equal(out_c, ref_c[:len(out_c)])
    if len(out_c) < len(ref_c):
        assert out_c[-1] == 9 and (ref_c[len(out_c):] == 9).all()
    assert lc in (la, lb)


def test_speculative_batcher_validation(params, rng):
    import dataclasses

    from distkeras_tpu.serving import SpeculativeBatcher

    draft_cfg = spec_draft_cfg()
    draft = toy_params(draft_cfg, 9)
    p = rng.integers(0, 64, (4,)).astype(np.int32)
    with pytest.raises(ValueError, match="full-cache"):
        SpeculativeBatcher(params, draft,
                           dataclasses.replace(CFG, attention_window=8),
                           draft_cfg)
    with pytest.raises(ValueError, match="vocab"):
        SpeculativeBatcher(params, draft, CFG,
                           dataclasses.replace(draft_cfg, vocab_size=32))
    eng = SpeculativeBatcher(params, draft, CFG, draft_cfg, lanes=1,
                             n_draft=3)
    with pytest.raises(ValueError, match="slack"):
        eng.submit(p, 26)                  # 4 + 26 + 3 > 32
    assert eng.submit(p, 8) == 0
    assert eng.submit(p, 8) is None        # full
    with pytest.raises(ValueError, match="still decoding"):
        eng.drain(0)


def test_speculative_batcher_sampled_matches_solo(params, rng):
    """Sampled speculative lanes: per-lane iteration-keyed draws
    replay each request's solo b=1 sampled speculative_generate run
    exactly, regardless of when the lane was admitted."""
    from distkeras_tpu.models.speculative import speculative_generate
    from distkeras_tpu.serving import SpeculativeBatcher

    draft_cfg = spec_draft_cfg()
    draft = toy_params(draft_cfg, 9)
    eng = SpeculativeBatcher(params, draft, CFG, draft_cfg, lanes=2,
                             n_draft=3, temperature=0.8)
    pa = rng.integers(0, 64, (5,)).astype(np.int32)
    pb = rng.integers(0, 64, (3,)).astype(np.int32)
    ka, kb = jax.random.key(51), jax.random.key(52)
    la = eng.submit(pa, 10, key=ka)
    eng.step()                            # A ahead by one round
    lb = eng.submit(pb, 8, key=kb)        # admitted mid-flight
    out_a = run_to_done(eng, la)
    out_b = run_to_done(eng, lb)

    def solo(p, n, key):
        out, _ = speculative_generate(params, draft, p[None], CFG,
                                      draft_cfg, n, n_draft=3,
                                      temperature=0.8, key=key)
        return np.asarray(out)[0]

    np.testing.assert_array_equal(out_a, solo(pa, 10, ka))
    np.testing.assert_array_equal(out_b, solo(pb, 8, kb))

    with pytest.raises(ValueError, match="key iff"):
        eng.submit(pa, 4)                 # sampling engine, no key
    greedy = SpeculativeBatcher(params, draft, CFG, draft_cfg,
                                lanes=1, n_draft=2)
    with pytest.raises(ValueError, match="key iff"):
        greedy.submit(pa, 4, key=ka)      # greedy engine with key


def test_speculative_impossible_config_rejected_eagerly(params):
    """Round-6 fix: a n_draft/max_len combination that can never admit
    any request fails at CONSTRUCTION, naming n_draft and max_len —
    not at every submit() with an error blaming the prompt."""
    import dataclasses

    from distkeras_tpu.serving import SpeculativeBatcher

    draft_cfg = spec_draft_cfg(max_len=4)
    draft = toy_params(draft_cfg, 9)
    # min(max_len) = 4 <= n_draft + 1 = 5: no request can ever fit.
    with pytest.raises(ValueError, match=r"n_draft=4.*max_len"):
        SpeculativeBatcher(params, draft, CFG, draft_cfg, n_draft=4)
    # The boundary case (cap == 1) constructs and admits a 1-token
    # prompt with one new token.
    ok_draft_cfg = dataclasses.replace(draft_cfg, max_len=6)
    ok_draft = toy_params(ok_draft_cfg, 9)
    eng = SpeculativeBatcher(params, ok_draft, CFG, ok_draft_cfg,
                             n_draft=4, lanes=1)
    assert eng.submit(np.asarray([3], np.int32), 1) == 0


def test_engine_top_p_one_matches_unfiltered_solo(params, rng):
    """Round-6 parity fix: a scalar-path engine built with top_p=1.0
    decodes exactly like solo generate with NO nucleus filter (and
    like generate(top_p=1.0), which now bypasses the mask too)."""
    # The no-op values are legal on every engine mode — scalar sampled,
    # scalar greedy (they turn nothing ON), and per-request (already).
    ContinuousBatcher(params, CFG, lanes=1, temperature=0.9, min_p=0.0)
    ContinuousBatcher(params, CFG, lanes=1, top_p=1.0, min_p=0.0)
    eng = ContinuousBatcher(params, CFG, lanes=1, temperature=0.9,
                            top_p=1.0, prompt_buckets=(8,))
    prompt = rng.integers(0, 64, (5,)).astype(np.int32)
    k = jax.random.key(3)
    lane = eng.submit(prompt, 6, key=k)
    out = run_to_done(eng, lane)
    unfiltered = solo(params, prompt, 6, temperature=0.9, key=k)
    explicit = solo(params, prompt, 6, temperature=0.9, top_p=1.0,
                    key=k)
    np.testing.assert_array_equal(out, unfiltered)
    np.testing.assert_array_equal(out, explicit)
