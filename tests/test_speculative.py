"""Speculative decoding: exactness vs generate(), chunk machinery,
acceptance statistics, and the sampled-mode distribution guarantee."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.models import speculative
from helpers import (generate, jgen, jitted, jtfm, no_compile_cache,
                     toy_params)

_decode_chunk, _decode_step, init_cache = (
    jgen._decode_chunk, jgen._decode_step, jgen.init_cache)
speculative_generate = jitted(speculative.speculative_generate)


# max_len carries the n_draft slack past prompt + new (validated).
CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=32)
DRAFT = tfm.TransformerConfig(vocab_size=64, d_model=16, n_heads=2,
                              n_layers=1, d_ff=32, max_len=32)


def _models(cfg=CFG, draft=DRAFT):
    return (toy_params(cfg),
            toy_params(draft, 9))


def test_decode_chunk_matches_decode_step(rng):
    """The chunked step is the T-token generalization of _decode_step:
    teacher-forcing T tokens through one chunk must give the same
    logits as T sequential steps."""
    params, _ = _models()
    toks = jnp.asarray(rng.integers(0, 64, (3, 9)), jnp.int32)
    cache = init_cache(CFG, 3)
    seq_logits = []
    for pos in range(9):
        lg, cache = _decode_step(params, cache, toks[:, pos], pos, CFG)
        seq_logits.append(lg)
    seq_logits = np.stack(seq_logits, axis=1)

    chunk_logits, _ = _decode_chunk(params, init_cache(CFG, 3), toks,
                                    jnp.zeros((3,), jnp.int32), CFG)
    np.testing.assert_allclose(np.asarray(chunk_logits), seq_logits,
                               atol=2e-4, rtol=2e-4)


def test_decode_chunk_per_row_offsets(rng):
    """Rows at different positions share one chunk call: each row's
    logits equal the same row processed alone at its own offset."""
    params, _ = _models()
    warm = jnp.asarray(rng.integers(0, 64, (2, 6)), jnp.int32)
    cache = init_cache(CFG, 2)
    for pos in range(6):
        _, cache = _decode_step(params, cache, warm[:, pos], pos, CFG)
    toks = jnp.asarray(rng.integers(0, 64, (2, 3)), jnp.int32)
    # Row 0 continues at position 6, row 1 pretends it only consumed 4.
    pos0 = jnp.asarray([6, 4], jnp.int32)
    out, _ = _decode_chunk(params, cache, toks, pos0, CFG)
    for r, start in enumerate(pos0.tolist()):
        solo_cache = init_cache(CFG, 1)
        for pos in range(start):
            _, solo_cache = _decode_step(params, solo_cache,
                                         warm[r:r + 1, pos], pos, CFG)
        solo, _ = _decode_chunk(params, solo_cache, toks[r:r + 1],
                                jnp.asarray([start], jnp.int32), CFG)
        np.testing.assert_allclose(np.asarray(out[r]),
                                   np.asarray(solo[0]),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("n_draft", [1, 3, 4])
def test_greedy_matches_generate(rng, n_draft):
    """The exactness guarantee: greedy speculative output == generate's
    greedy rollout, token for token, at any draft quality/width."""
    params, draft = _models()
    prompt = jnp.asarray(rng.integers(1, 64, (4, 5)), jnp.int32)
    ref = np.asarray(generate(params, prompt, CFG, 10))
    out, stats = speculative_generate(params, draft, prompt, CFG, DRAFT,
                                      10, n_draft=n_draft)
    np.testing.assert_array_equal(np.asarray(out), ref)
    assert int(stats["iterations"]) >= 1


def test_greedy_rope_gqa_matches_generate(rng):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_kv_heads=2, n_layers=2, d_ff=64,
                                max_len=32, rope=True)
    draft_cfg = dataclasses.replace(cfg, n_layers=1)
    params = toy_params(cfg, 1)
    draft = toy_params(draft_cfg, 8)
    prompt = jnp.asarray(rng.integers(1, 64, (3, 4)), jnp.int32)
    ref = np.asarray(generate(params, prompt, cfg, 9))
    out, _ = speculative_generate(params, draft, prompt, cfg, draft_cfg,
                                  9, n_draft=3)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_greedy_moe_matches_generate(rng):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_len=32,
                                num_experts=4, moe_top_k=2,
                                capacity_factor=1.25)
    params = toy_params(cfg, 2)
    _, draft = _models()
    prompt = jnp.asarray(rng.integers(1, 64, (2, 4)), jnp.int32)
    ref = np.asarray(generate(params, prompt, cfg, 8))
    out, _ = speculative_generate(params, draft, prompt, cfg, DRAFT, 8,
                                  n_draft=2)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_perfect_draft_accepts_everything(rng):
    """Draft == target: every proposal is the target argmax, so the
    acceptance rate is 1 and each target pass advances n_draft + 1
    positions (the best-case iteration count)."""
    params, _ = _models()
    prompt = jnp.asarray(rng.integers(1, 64, (2, 4)), jnp.int32)
    n_new, k = 12, 3
    out, stats = speculative_generate(params, params, prompt, CFG, CFG,
                                      n_new, n_draft=k)
    ref = np.asarray(generate(params, prompt, CFG, n_new))
    np.testing.assert_array_equal(np.asarray(out), ref)
    assert float(stats["acceptance_rate"]) == 1.0
    assert int(stats["iterations"]) == -(-n_new // (k + 1))  # ceil


def test_nonuniform_acceptance_rows_finish_cleanly(rng):
    """Rows finishing at DIFFERENT iterations must keep their final
    token: a done row still executes the loop body and writes its
    window into the scratch region — one scratch column too few and
    dynamic_update_slice clamps the write back onto buf[total-1]
    (regression: int8 self-draft gives ~0.8 acceptance with real
    per-row variance, unlike the perfect/random drafts elsewhere)."""
    from distkeras_tpu.models.quant import quantize_params

    cfg = dataclasses.replace(CFG, max_len=40)
    params = toy_params(cfg, 6)
    draft = quantize_params(params)
    prompt = jnp.asarray(rng.integers(1, 64, (8, 4)), jnp.int32)
    ref = np.asarray(generate(params, prompt, cfg, 20))
    out, stats = speculative_generate(params, draft, prompt, cfg, cfg,
                                      20, n_draft=3)
    np.testing.assert_array_equal(np.asarray(out), ref)
    # The regression needs per-row variance to bite; make sure this
    # config still provides it (acceptance strictly between the
    # uniform extremes).
    assert 0.0 < float(stats["acceptance_rate"]) < 1.0


def test_quantized_target_matches_quantized_generate(rng):
    from distkeras_tpu.models.quant import quantize_params

    params, draft = _models()
    qp = quantize_params(params)
    prompt = jnp.asarray(rng.integers(1, 64, (2, 4)), jnp.int32)
    ref = np.asarray(generate(qp, prompt, CFG, 8))
    out, _ = speculative_generate(qp, draft, prompt, CFG, DRAFT, 8,
                                  n_draft=2)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_sampled_matches_target_distribution(rng):
    """The speculative-sampling theorem, empirically: with a DIFFERENT
    draft model, the first generated token must still be distributed
    exactly as the target's softmax.  4096 parallel rows, TV distance
    against the analytic target distribution."""
    vocab = 16
    cfg = tfm.TransformerConfig(vocab_size=vocab, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_len=8)
    dcfg = dataclasses.replace(cfg, d_model=8, d_ff=16)
    params = toy_params(cfg, 3)
    draft = toy_params(dcfg, 4)
    temp = 0.9
    b = 4096
    prompt = jnp.full((b, 1), 7, jnp.int32)
    # XLA:CPU takes minutes over the loop at 4096 rows, and the compile
    # cache dies (SIGSEGV) serializing what it made.
    with no_compile_cache():
        out, _ = speculative_generate(params, draft, prompt, cfg, dcfg, 1,
                                      n_draft=2, temperature=temp,
                                      key=jax.random.key(11))
    samples = np.asarray(out[:, 1])
    emp = np.bincount(samples, minlength=vocab) / b

    logits, _ = jtfm.apply(params, prompt[:1], cfg)
    target = np.asarray(jax.nn.softmax(logits[0, 0] / temp))
    tv = 0.5 * np.abs(emp - target).sum()
    assert tv < 0.05, (tv, emp, target)


def test_sampled_deterministic_per_key(rng):
    params, draft = _models()
    prompt = jnp.asarray(rng.integers(1, 64, (2, 4)), jnp.int32)
    kw = dict(n_draft=2, temperature=0.8, key=jax.random.key(5))
    a, _ = speculative_generate(params, draft, prompt, CFG, DRAFT, 6, **kw)
    b, _ = speculative_generate(params, draft, prompt, CFG, DRAFT, 6, **kw)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_jittable(rng):
    params, draft = _models()
    prompt = jnp.asarray(rng.integers(1, 64, (2, 4)), jnp.int32)
    fn = jax.jit(lambda tp, dp, pr: speculative_generate(
        tp, dp, pr, CFG, DRAFT, 8, n_draft=3))
    out, stats = fn(params, draft, prompt)
    ref = np.asarray(generate(params, prompt, CFG, 8))
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_validation_errors(rng):
    params, draft = _models()
    prompt = jnp.asarray(rng.integers(1, 64, (2, 4)), jnp.int32)
    with pytest.raises(ValueError, match="vocab"):
        speculative_generate(params, draft, prompt, CFG,
                             dataclasses.replace(DRAFT, vocab_size=32), 4)
    # Windowed configs are supported since round 5; their own bounds:
    with pytest.raises(ValueError, match="rejected tail"):
        speculative_generate(
            params, draft, prompt,
            dataclasses.replace(CFG, rope=True, attention_window=29,
                                max_len=32),
            DRAFT, 4, n_draft=4)  # 29 + 5 > 32
    with pytest.raises(ValueError, match="rope"):
        speculative_generate(
            params, draft, prompt,
            dataclasses.replace(CFG, attention_window=8, max_len=16),
            DRAFT, 20, n_draft=2)  # rolls past max_len without rope
    with pytest.raises(ValueError, match="slack"):
        speculative_generate(params, draft, prompt, CFG, DRAFT, 26,
                             n_draft=4)  # 4+26+4 > 32
    with pytest.raises(ValueError, match="PRNG"):
        speculative_generate(params, draft, prompt, CFG, DRAFT, 4,
                             temperature=0.5)
    with pytest.raises(ValueError, match="n_draft"):
        speculative_generate(params, draft, prompt, CFG, DRAFT, 4,
                             n_draft=0)


def test_eos_matches_generate(rng):
    """Sticky EOS parity: pick an eos token the model actually emits,
    then speculative greedy must equal generate's sticky-eos output,
    including the filled tail."""
    params, draft = _models()
    prompt = jnp.asarray(rng.integers(1, 64, (4, 5)), jnp.int32)
    plain = np.asarray(generate(params, prompt, CFG, 12))
    # A token emitted mid-generation on row 0 becomes the eos —
    # guaranteed to trigger for at least one row.
    eos = int(plain[0, 5 + 3])
    ref = np.asarray(generate(params, prompt, CFG, 12, eos_token=eos))
    out, stats = speculative_generate(params, draft, prompt, CFG, DRAFT,
                                      12, n_draft=3, eos_token=eos)
    np.testing.assert_array_equal(np.asarray(out), ref)
    assert int(stats["iterations"]) >= 1


def test_eos_stops_rows_early(rng):
    """EOS actually saves target passes: IDENTICAL prompt rows all emit
    the chosen eos as their first generated token, so the whole batch
    must finish in ONE pass (without early exit, 16 tokens at
    n_draft=4 need ceil(16/5) = 4)."""
    params, _ = _models()
    one = rng.integers(1, 64, (1, 4))
    prompt = jnp.asarray(np.repeat(one, 3, axis=0), jnp.int32)
    plain = np.asarray(generate(params, prompt, CFG, 16))
    eos = int(plain[0, 4])  # every row's first generated token
    assert (plain[:, 4] == eos).all()
    out, stats = speculative_generate(params, params, prompt, CFG, CFG,
                                      16, n_draft=4, eos_token=eos)
    ref = np.asarray(generate(params, prompt, CFG, 16, eos_token=eos))
    np.testing.assert_array_equal(np.asarray(out), ref)
    assert int(stats["iterations"]) == 1


def test_eos_validation(rng):
    params, draft = _models()
    prompt = jnp.asarray(rng.integers(1, 64, (2, 4)), jnp.int32)
    with pytest.raises(ValueError, match="eos_token"):
        speculative_generate(params, draft, prompt, CFG, DRAFT, 4,
                             eos_token=64)


def test_speculative_kv_int8_greedy_matches_generate_kv_int8(rng):
    """Speculative decoding with int8 caches on both models emits the
    same tokens as plain kv_int8 generate: quantization is per-token
    deterministic, so the verify-chunk cache and the slab-update cache
    hold identical int8 values."""
    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (3, 4)).astype(np.int32))
    ref = np.asarray(generate(params, prompt, CFG, 8, kv_int8=True))
    out, stats = speculative_generate(params, params, prompt, CFG, CFG,
                                      8, n_draft=3, kv_int8=True)
    np.testing.assert_array_equal(np.asarray(out), ref)
    assert float(stats["acceptance_rate"]) > 0.9  # self-draft


# ------------------------------------------------------ windowed / rolling

WIN = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, rope=True,
                            attention_window=6, max_len=16)
WIN_DRAFT = tfm.TransformerConfig(vocab_size=64, d_model=16, n_heads=2,
                                  n_layers=1, d_ff=32, rope=True,
                                  attention_window=6, max_len=16)


def test_windowed_greedy_matches_generate(rng):
    """Speculative decoding on rope + attention_window ring caches
    (round-5): greedy output equals windowed generate()'s, including
    ROLLING past max_len — both models' rings wrap mid-run and the
    verify chunks wrap mid-chunk."""
    params, draft = _models(WIN, WIN_DRAFT)
    prompt = jnp.asarray(rng.integers(1, 64, (2, 5)), jnp.int32)
    out, stats = speculative_generate(params, draft, prompt, WIN,
                                      WIN_DRAFT, 25, n_draft=3)
    ref = generate(params, prompt, WIN, 25)   # 5 + 25 = 30 >> 16
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert float(stats["acceptance_rate"]) >= 0.0


def test_windowed_mixed_draft_full_cache(rng):
    """Target on a ring, draft on a full cache (each model's budget is
    checked independently) — still exact vs windowed generate."""
    big_draft = dataclasses.replace(WIN_DRAFT, attention_window=None,
                                    max_len=40)
    params, draft = _models(WIN, big_draft)
    prompt = jnp.asarray(rng.integers(1, 64, (2, 4)), jnp.int32)
    out, _ = speculative_generate(params, draft, prompt, WIN,
                                  big_draft, 20, n_draft=3)
    ref = generate(params, prompt, WIN, 20)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("kv_int8", [False, True])
def test_windowed_small_ring_matches_big_cache_sampled(rng, kv_int8):
    """Sampled speculative decoding on a wrapping ring reproduces the
    non-wrapping big-cache run EXACTLY (same key -> same logits ->
    same accept/reject draws), with and without the int8 cache."""
    big = dataclasses.replace(WIN, max_len=64)
    big_d = dataclasses.replace(WIN_DRAFT, max_len=64)
    params, draft = _models(big, big_d)
    prompt = jnp.asarray(rng.integers(1, 64, (2, 5)), jnp.int32)
    kw = dict(n_draft=3, temperature=0.8, key=jax.random.key(11),
              kv_int8=kv_int8)
    ref, _ = speculative_generate(params, draft, prompt, big, big_d,
                                  25, **kw)
    out, _ = speculative_generate(params, draft, prompt, WIN,
                                  WIN_DRAFT, 25, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ------------------------- ring-cache-compatible serving fallback (PR 5)


@pytest.mark.chaos
def test_rolling_batcher_draft_fault_fallback_past_max_len(rng):
    """The ROADMAP follow-up closed by PR 5: a SpeculativeBatcher on
    rolling/ring-slot lanes must degrade to plain decode when the
    draft model faults, PRESERVING ring-slot state — the fallback
    inherits the lanes' unbounded positions and wrapped ring slabs
    mid-flight, so greedy parity with solo rolling generate holds past
    max_len through the degradation."""
    from distkeras_tpu.resilience import FaultInjected, FaultPlan
    from distkeras_tpu.serving import SpeculativeBatcher

    params, draft = _models(WIN, WIN_DRAFT)
    eng = SpeculativeBatcher(params, draft, WIN, WIN_DRAFT, lanes=2,
                             n_draft=3)
    pa = np.asarray(rng.integers(1, 64, (5,)), np.int32)
    pb = np.asarray(rng.integers(1, 64, (3,)), np.int32)
    la = eng.submit(pa, 25)          # 5 + 25 = 30 >> max_len=16: wraps
    for _ in range(4):               # healthy speculative rounds first:
        eng.step()                   # lane A's ring is mid-wrap
    lb = eng.submit(pb, 20)          # admitted while A wraps
    with FaultPlan().fail("serving.draft"):
        eng.step()                   # draft faults mid-wrap
    assert eng.degraded
    assert isinstance(eng.degraded_error, FaultInjected)
    while eng.running():
        eng.step()
    np.testing.assert_array_equal(
        eng.drain(la),
        np.asarray(generate(params, pa[None], WIN, 25))[0])
    np.testing.assert_array_equal(
        eng.drain(lb),
        np.asarray(generate(params, pb[None], WIN, 20))[0])
    # A degraded rolling engine still admits fresh wrapping requests.
    lc = eng.submit(pa, 18)
    while lc in eng.running():
        eng.step()
    np.testing.assert_array_equal(
        eng.drain(lc),
        np.asarray(generate(params, pa[None], WIN, 18))[0])


@pytest.mark.slow
def test_rolling_batcher_healthy_matches_solo_and_validates(rng):
    """Healthy rolling speculative lanes match solo rolling
    speculative_generate (== rolling generate, greedy); the engine's
    ring bound and rolling-eligibility checks reject loudly; rolling
    budgets cap only the PROMPT."""
    from distkeras_tpu.serving import SpeculativeBatcher

    params, draft = _models(WIN, WIN_DRAFT)
    eng = SpeculativeBatcher(params, draft, WIN, WIN_DRAFT, lanes=2,
                             n_draft=3)
    p = np.asarray(rng.integers(1, 64, (4,)), np.int32)
    lane = eng.submit(p, 24)         # no total-length cap on the ring
    while lane in eng.running():
        eng.step()
    np.testing.assert_array_equal(
        eng.drain(lane),
        np.asarray(generate(params, p[None], WIN, 24))[0])
    # Prompt must still fit the ring's admission chunk.
    with pytest.raises(ValueError, match="admission bucket"):
        eng.submit(np.asarray(rng.integers(1, 64, (17,)), np.int32), 2)
    # Mixed full/windowed model pairs stay rejected.
    full_draft = dataclasses.replace(WIN_DRAFT, attention_window=None)
    with pytest.raises(ValueError, match="agree"):
        SpeculativeBatcher(params, draft, WIN, full_draft, lanes=1,
                           n_draft=2)
    # The ring bound: window + n_draft + 1 must fit max_len.
    with pytest.raises(ValueError, match="rejected tail"):
        SpeculativeBatcher(params, draft, WIN, WIN_DRAFT, lanes=1,
                           n_draft=12)
    # Windowed without rope has no rolling semantics.
    norope = dataclasses.replace(WIN, rope=False)
    with pytest.raises(ValueError, match="rope"):
        SpeculativeBatcher(params, draft, norope, WIN_DRAFT, lanes=1,
                           n_draft=2)
