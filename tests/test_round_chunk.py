"""A decode round that holds a continuation chunk is ONE program
(``ContinuousBatcher._make_round_chunk`` ->
``generate._chunk_in_place``'s ``chunk=``): the chunk's rows and the
lanes' tokens go through every layer's products together.  Held here
to the two programs it replaces — the admission, then the decode step —
on the same state; to solo ``generate`` through an engine; and to the
gate: which rounds fuse, and which engines never do.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import obs
from distkeras_tpu.models import generate as gen
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.obs import read_trace
from helpers import generate, serve_cfg, spec_draft_cfg, toy_params

TOL = 2e-4          # float32 against float32: rounding order only
PLAIN = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                              n_layers=2, d_ff=64, max_len=256)
# The extended block: rope, grouped K/V heads, one projection matrix,
# a gated feed-forward, an untied head.
EXTENDED = dataclasses.replace(PLAIN, n_kv_heads=2, rope=True,
                               fused_qkv=True, ffn_gated=True,
                               tie_head=False, qk_norm=True)
PARKED = 1          # the admitting lane


def _state(cfg, lanes, chunk, seed=0):
    """A slab of noise (a previous occupant's K/V everywhere), lane 0
    at position 0, the admitting lane parked at ``max_len - 1``, the
    others somewhere inside; a chunk for the admitting lane at ``chunk``
    (its second)."""
    rng = np.random.default_rng(seed)
    cache = jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        gen.init_cache(cfg, lanes))
    pos = rng.integers(1, 100, size=lanes).astype(np.int32)
    pos[0], pos[PARKED] = 0, cfg.max_len - 1
    cur = rng.integers(0, cfg.vocab_size, size=lanes).astype(np.int32)
    rows = rng.integers(0, cfg.vocab_size, size=(1, chunk)).astype(np.int32)
    return cache, jnp.asarray(cur), jnp.asarray(pos), jnp.asarray(rows)


def _but_the_parked_slot(cache, cfg):
    """The slab without the parked lane's last slot: its decode row is
    burnt compute in either form (it attends a row the other form has
    or has not yet written the chunk into) and writes there."""
    out = {}
    for name, a in cache.items():
        a = np.array(a)
        a[:, PARKED, cfg.max_len - 1] = 0
        out[name] = a
    return out


@pytest.mark.parametrize("cfg", [PLAIN, EXTENDED], ids=["plain", "extended"])
@pytest.mark.parametrize("lanes,chunk", [(3, 16), (8, 64)])
def test_the_round_is_the_admission_then_the_step(cfg, lanes, chunk):
    """The fused program against ``_admit`` then ``step_n_p`` from one
    state: the decoding lanes' tokens equal, their logits and the slab
    within rounding — the chunk in its lane at its offset, every lane's
    token at its own position — with one lane parked and one at
    position 0."""
    params = toy_params(cfg)
    eng = dk.ContinuousBatcher(params, cfg, lanes=lanes, hot_swap=True,
                               prefill_chunk=chunk, prompt_buckets=(chunk,))
    cache, cur, pos, rows = _state(cfg, lanes, chunk)
    lane, off = jnp.int32(PARKED), jnp.int32(chunk)
    rest = (eng.keys, eng.temps, eng.tps, eng.mps)
    copy = lambda: jax.tree.map(jnp.copy, cache)

    two = eng._admit(params, copy(), rows, lane, off)
    two, cur2, pos2, toks2 = eng._make_step(1)(params, two, cur, pos, *rest)
    one, cur1, pos1, toks1 = eng._round_chunk(params, copy(), cur, pos, *rest,
                                              rows, lane, off)
    live = np.arange(lanes) != PARKED
    assert toks1.shape == toks2.shape == (lanes, 1)
    np.testing.assert_array_equal(np.asarray(toks1)[live],
                                  np.asarray(toks2)[live])
    np.testing.assert_array_equal(np.asarray(cur1)[live],
                                  np.asarray(cur2)[live])
    np.testing.assert_array_equal(pos1, pos2)
    want, got = (_but_the_parked_slot(c, cfg) for c in (two, one))
    for name in want:
        np.testing.assert_allclose(got[name], want[name], atol=TOL, rtol=0)
    # The chunk is where the admission puts it, and nowhere else.
    k0 = np.asarray(cache["k"])
    moved = np.abs(got["k"] - _but_the_parked_slot(cache, cfg)["k"]
                   ).reshape(k0.shape[:3] + (-1,)).max(axis=(0, 3)) > 0
    expect = np.zeros_like(moved)
    expect[PARKED, chunk:2 * chunk] = True
    expect[live, np.asarray(pos)[live]] = True
    np.testing.assert_array_equal(moved, expect)

    # The logits, from the model's function itself.
    _, after = gen._decode_chunk(params, copy(), rows, off[None], cfg,
                                 uniform_pos=True, lane=lane)
    want_l, _ = gen._decode_chunk(params, after, cur[:, None], pos, cfg)
    got_l, _ = gen._decode_chunk(params, copy(), cur[:, None], pos, cfg,
                                 chunk=(rows, lane, off))
    assert got_l.shape == (lanes, 1, cfg.vocab_size)
    np.testing.assert_allclose(np.asarray(got_l)[live],
                               np.asarray(want_l)[live], atol=TOL, rtol=0)


def test_chunk_rides_a_plain_decode_step_only():
    """``chunk=`` is for one token a row of an untyped one-pass stack;
    anything else is refused by name.  An int8 cache takes it too: the
    scale planes beside the K/V planes, the same logits and slab as the
    admission then the step."""
    params = toy_params(PLAIN)
    cache, cur, pos, rows = _state(PLAIN, 2, 8)
    chunk = (rows, jnp.int32(1), jnp.int32(8))
    looped = dataclasses.replace(EXTENDED, n_passes=2, post_norms=True)
    with pytest.raises(ValueError, match="one-pass"):
        gen._decode_chunk(toy_params(looped), gen.init_cache(looped, 2),
                          cur[:, None], pos, looped, chunk=chunk)
    with pytest.raises(ValueError, match="one token a row"):
        gen._decode_chunk(params, cache, jnp.stack([cur, cur], 1), pos,
                          PLAIN, chunk=chunk)
    rng = np.random.default_rng(1)
    cache = {name: jnp.asarray(
        rng.integers(-127, 128, a.shape) if a.dtype == jnp.int8
        else rng.uniform(0.005, 0.02, a.shape), a.dtype)
        for name, a in gen.init_cache(PLAIN, 2, kv_int8=True).items()}
    _, admitted = gen._decode_chunk(params, cache, rows, chunk[2][None],
                                    PLAIN, uniform_pos=True, lane=chunk[1])
    want, want_cache = gen._decode_chunk(params, admitted, cur[:, None], pos,
                                         PLAIN)
    got, got_cache = gen._decode_chunk(params, cache, cur[:, None], pos,
                                       PLAIN, chunk=chunk)
    np.testing.assert_allclose(got[0], want[0], atol=TOL, rtol=0)
    want_cache, got_cache = (_but_the_parked_slot(c, PLAIN)
                             for c in (want_cache, got_cache))
    for name in want_cache:
        np.testing.assert_allclose(got_cache[name], want_cache[name],
                                   atol=TOL, rtol=0)


# ------------------------------------------------- through an engine

CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=64)
CHUNK = 8


def _prompt(rng, n):
    return rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)


@pytest.fixture(scope="module", params=[False, True],
                ids=["closure", "hot_swap"])
def served(request, tmp_path_factory):
    """Prompts of 1, 3 and 5 chunks (and one with no tail) arriving
    while other lanes decode, through a chunked engine under a session:
    ``(prompts, budgets, transcripts, spans)``."""
    path = str(tmp_path_factory.mktemp("served") / "t.jsonl")
    params = toy_params(CFG)
    rng = np.random.default_rng(3)
    # warm lengths + 1: one chunk; 2 chunks + a tail; 4 + a tail; 3 whole
    lengths = (6, 1 + 2 * CHUNK + 3, 1 + 4 * CHUNK + 5, 1 + 3 * CHUNK)
    prompts = [_prompt(rng, 4)] + [_prompt(rng, n) for n in lengths]
    budgets = [24, 5, 5, 5, 5]
    with obs.session(trace_path=path):
        eng = dk.ContinuousBatcher(params, CFG, lanes=3, max_queue=8,
                                   hot_swap=request.param,
                                   prefill_chunk=CHUNK,
                                   prompt_buckets=(4, CHUNK))
        rids = [eng.enqueue(prompts[0], budgets[0])]
        eng.step()
        for p, n in zip(prompts[1:], budgets[1:]):
            rids.append(eng.enqueue(p, n))
            eng.step()
        for _ in range(200):
            if not (eng.running() or eng.queued
                    or eng._inflight is not None):
                break
            eng.step()
        outs = [eng.take(r).tokens for r in rids]
    spans = [r for r in read_trace(path) if r["kind"] == "span"]
    return params, prompts, budgets, outs, spans, eng


def test_every_transcript_equals_solo_generate(served):
    params, prompts, budgets, outs, _, _ = served
    for prompt, n, out in zip(prompts, budgets, outs):
        np.testing.assert_array_equal(
            out, np.asarray(generate(params, prompt[None], CFG, n))[0])


def test_the_fused_rounds_are_the_middle_chunks(served):
    """A plan's chunks by request, in order: the first is
    ``serving.admit``, the last ``serving.admit_chunk`` (a bucket-padded
    tail, or a whole chunk that un-parks its lane), and exactly the
    full-width ones between them ride a ``serving.step`` — every lane
    was decoding beside them."""
    _, prompts, _, _, spans, eng = served
    by_rid = {}
    for s in sorted(spans, key=lambda s: s["t0"]):
        f = s["fields"]
        if s["name"] in ("serving.admit", "serving.admit_chunk"):
            by_rid.setdefault(f["request_id"], []).append(
                (s["name"], f["bucket"], f["positions"]))
        elif s["name"] == "serving.step" and "bucket" in f:
            by_rid.setdefault(f["request_id"], []).append(
                ("fused", f["bucket"], f["positions"]))
    whole = lambda kind: (kind, CHUNK, CHUNK)
    assert [by_rid[i] for i in range(5)] == [
        [("serving.admit", 4, 3)],
        [("serving.admit", 8, 5)],
        [whole("serving.admit"), whole("fused"),
         ("serving.admit_chunk", 4, 3)],
        [whole("serving.admit"), whole("fused"), whole("fused"),
         whole("fused"), ("serving.admit_chunk", 8, 5)],
        [whole("serving.admit"), whole("fused"),
         whole("serving.admit_chunk")],
    ]
    # Every prompt position was written once.
    for rid, plan in by_rid.items():
        assert sum(p for _, _, p in plan) == len(prompts[rid]) - 1
    # The round says so, and counts the chunk.
    rounds = {s["id"]: s["fields"] for s in spans
              if s["name"] == "serving.round"}
    steps = [s for s in spans if s["name"] == "serving.step"]
    fused = [s for s in steps if "bucket" in s["fields"]]
    assert len(fused) == 5
    name = "jit_" + eng._round_chunk.__name__
    for s in steps:
        rnd = rounds[s["parent"]]
        assert rnd["fused"] == int(s in fused)
        assert (s["fields"]["program"] == name) == (s in fused)
        assert rnd["chunks"] >= rnd["fused"]
    assert sum(r["fused"] for r in rounds.values()) == 5
    assert all(r["fused"] in (0, 1) for r in rounds.values())


def test_a_chunk_with_no_lane_decoding_runs_alone(tmp_path):
    """Nothing decodes beside the admitting lane: its chunks go out as
    admission programs, as they did, and the transcript is solo's."""
    params = toy_params(CFG)
    prompt = _prompt(np.random.default_rng(5), 1 + 3 * CHUNK + 2)
    path = str(tmp_path / "t.jsonl")
    with obs.session(trace_path=path):
        eng = dk.ContinuousBatcher(params, CFG, lanes=2, max_queue=2,
                                   prefill_chunk=CHUNK,
                                   prompt_buckets=(4, CHUNK))
        assert eng._round_chunk is not None
        rid = eng.enqueue(prompt, 4)
        while eng.running() or eng._inflight is not None:
            eng.step()
    np.testing.assert_array_equal(
        eng.take(rid).tokens,
        np.asarray(generate(params, prompt[None], CFG, 4))[0])
    spans = [r for r in read_trace(path) if r["kind"] == "span"]
    assert [s["name"] for s in spans
            if s["name"].startswith("serving.admit")] == [
        "serving.admit"] + ["serving.admit_chunk"] * 3
    assert not any(r["fields"]["fused"] for r in spans
                   if r["name"] == "serving.round")


def test_an_int8_kv_engine_fuses_and_matches_solo(tmp_path):
    """An int8 K/V engine fuses a prompt's middle chunks into the
    decode rounds as the plain one does (the scale planes take the
    chunk's writes beside the K/V planes), and every transcript is its
    solo ``generate(kv_int8=True)`` run's."""
    params = toy_params(CFG)
    rng = np.random.default_rng(11)
    prompts = [_prompt(rng, 5), _prompt(rng, 1 + 3 * CHUNK + 3)]
    path = str(tmp_path / "t.jsonl")
    with obs.session(trace_path=path):
        with pytest.warns(RuntimeWarning, match="kv_int8"):
            eng = dk.ContinuousBatcher(params, CFG, kv_int8=True, lanes=2,
                                       max_queue=4, prefill_chunk=CHUNK,
                                       prompt_buckets=(4, CHUNK))
        assert eng._round_chunk is not None
        rids = [eng.enqueue(prompts[0], 12)]
        eng.step()
        rids.append(eng.enqueue(prompts[1], 3))
        for _ in range(200):
            if all(eng.poll(r) is not None for r in rids):
                break
            eng.step()
    for rid, prompt, n in zip(rids, prompts, (12, 3)):
        np.testing.assert_array_equal(
            eng.take(rid).tokens, np.asarray(generate(
                params, prompt[None], CFG, n, kv_int8=True,
                use_prefill=False))[0])
    assert any(r["fields"].get("fused") for r in read_trace(path)
               if r["kind"] == "span" and r["name"] == "serving.round")


# ------------------------------------------------- the engines that never fuse

TYPED = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=64,
    rope=True, ffn_gated=True, tie_head=False, fused_qkv=True,
    layer_types=("window", "full"), sliding_window=8)
LOOPED = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=64,
    rope=True, ffn_gated=True, tie_head=False, post_norms=True,
    fused_qkv=True, n_passes=3)


def _unfused(kind):
    """An engine of ``kind`` and the ``step(n)`` it is driven with."""
    chunked = dict(lanes=2, max_queue=4, prefill_chunk=CHUNK,
                   prompt_buckets=(4, CHUNK))
    if kind == "typed":
        return dk.ContinuousBatcher(toy_params(TYPED), TYPED, **chunked), 1
    if kind == "looped":
        return dk.ContinuousBatcher(toy_params(LOOPED), LOOPED, **chunked), 1
    if kind == "rolling":       # a ring admits no chunked prefill at all
        roll = serve_cfg(max_len=48, attention_window=16)
        return dk.ContinuousBatcher(toy_params(roll), roll, lanes=2,
                                    max_queue=4, prompt_buckets=(8, 32)), 1
    if kind == "paged":
        return dk.PagedBatcher(toy_params(CFG), CFG, lanes=2, block=8,
                               max_queue=4, prefill_chunk=CHUNK), 1
    if kind == "speculative":
        cfg, draft = serve_cfg(max_len=64), spec_draft_cfg(max_len=64)
        return dk.SpeculativeBatcher(
            toy_params(cfg), toy_params(draft, 1), cfg, draft, lanes=2,
            n_draft=2, max_queue=4, prompt_buckets=(8, 32)), None
    if kind == "tiered":
        return dk.ContinuousBatcher(
            toy_params(CFG), CFG, lane_tiers=(1, 2), max_queue=4,
            scale_up_after=1, prefill_chunk=CHUNK,
            prompt_buckets=(4, CHUNK)), 1
    assert kind == "window_of_2"
    return dk.ContinuousBatcher(toy_params(CFG), CFG, step_windows=(1, 2),
                                **chunked), 2


@pytest.mark.parametrize("kind", [
    "typed", "looped", "rolling", "paged", "speculative", "tiered",
    "window_of_2"])
def test_no_fused_program_is_built_or_launched(kind, tmp_path):
    """Everything but the plain one-pass stack on one untiered device,
    a step at a time, takes the two programs it took: the engine holds
    no fused program (``step(n > 1)``: holds it, never launches it),
    no round says ``fused`` and no span names another program than the
    engine's own — through a four-chunk prompt admitted beside a
    decoding lane."""
    path = str(tmp_path / "t.jsonl")
    rng = np.random.default_rng(11)
    with obs.session(trace_path=path):
        eng, n = _unfused(kind)
        built = getattr(eng, "_round_chunk", None)
        assert (built is None) == (kind != "window_of_2")
        step = eng.step if n is None else (lambda: eng.step(n))
        rids = [eng.enqueue(_prompt(rng, 5), 12)]
        step()
        rids.append(eng.enqueue(_prompt(rng, 1 + 3 * CHUNK + 3), 3))
        for _ in range(200):
            if all(eng.poll(r) is not None for r in rids):
                break
            step()
        assert all(eng.take(r).ok for r in rids)
    spans = [r for r in read_trace(path) if r["kind"] == "span"]
    rounds = [s for s in spans if s["name"] == "serving.round"]
    steps = [s for s in spans if s["name"] == "serving.step"]
    assert steps and not any(r["fields"].get("fused") for r in rounds)
    assert not any("bucket" in s["fields"] for s in steps)
    assert not any("round_chunk" in s["fields"]["program"] for s in steps)
    if kind not in ("rolling", "speculative"):     # chunked: four programs
        assert len([s for s in spans
                    if s["name"] == "serving.admit_chunk"]) == 3
