"""KV-cached decoding: must reproduce the training-path forward exactly."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import transformer as tfm
from helpers import generate, jgen, jtfm, toy_params

_decode_step, init_cache, prefill, beam_search = (
    jgen._decode_step, jgen.init_cache, jgen.prefill, jgen.beam_search)


CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=16)
MOE_CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_len=16,
                                num_experts=4, capacity_factor=1.25)
MOE2_CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=1, d_ff=64, max_len=16,
                                 num_experts=4, moe_top_k=2,
                                 capacity_factor=1.25)
ROPE_CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, max_len=16, rope=True)


@pytest.mark.parametrize("cfg", [CFG, MOE_CFG, MOE2_CFG, ROPE_CFG],
                         ids=["dense", "moe", "moe2", "rope"])
def test_cached_decode_matches_full_forward(rng, cfg):
    """Teacher-forcing through the cache == apply() at every position.

    The MoE case pins parity at a REALISTIC capacity factor (1.25):
    the batched forward scores with ``moe_dense_routing=True`` — the
    decode semantics — so nothing depends on capacity being large
    enough to never drop (a no-op flag for the dense/rope configs).
    """
    params = toy_params(cfg)
    toks = jnp.asarray(rng.integers(0, 64, (2, 12)).astype(np.int32))
    full_logits, _ = jtfm.apply(params, toks, cfg,
                               moe_dense_routing=bool(cfg.num_experts))

    cache = init_cache(cfg, 2)
    for pos in range(12):
        logits, cache = _decode_step(params, cache, toks[:, pos], pos, cfg)
        np.testing.assert_allclose(logits, full_logits[:, pos], atol=2e-4,
                                   rtol=2e-4)


def test_moe_capacity_vs_dense_divergence_bounded(rng):
    """Quantified train/serve routing contract on a TRAINED MoE.

    Trains briefly at capacity_factor=1.25 (tokens really drop), then
    measures the capacity-routing vs dense-routing eval NLL gap.  The
    served model (decode == dense routing by the parity test above)
    must track the training-time forward within a modest bound — this
    is the measured form of the divergence caveat in ``generate``'s
    docstring, asserted so a regression in either routing path shows
    up as a blown bound rather than silent quality drift.
    """
    import optax

    cfg = MOE_CFG
    params = toy_params(cfg, 3)
    opt = optax.adam(3e-3)
    step = jax.jit(tfm.make_train_step(cfg, opt))
    carry = (params, opt.init(params))
    toks = jnp.asarray(rng.integers(0, 64, (8, 13)).astype(np.int32))
    for _ in range(30):
        carry, _ = step(carry, toks)
    trained = carry[0]

    nll_cap = float(jtfm.lm_nll(trained, toks, cfg))
    nll_dense = float(jtfm.lm_nll(trained, toks, cfg,
                                 moe_dense_routing=True))
    # Routing genuinely differs at this capacity (the contract is a
    # bound, not equality)...
    assert nll_cap != nll_dense
    # ...but serving quality must track training quality: |gap| within
    # 5% relative.  Observed gap on this config is well under 1%; 5%
    # leaves headroom across seeds without letting real drift pass.
    assert abs(nll_dense - nll_cap) <= 0.05 * nll_cap, (nll_cap, nll_dense)


def test_generate_greedy_matches_argmax_rollout(rng):
    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)).astype(np.int32))
    out = generate(params, prompt, CFG, max_new_tokens=6)
    assert out.shape == (2, 10)
    np.testing.assert_array_equal(out[:, :4], prompt)

    # Reference rollout: full forward, argmax, append.
    seq = np.asarray(prompt)
    for _ in range(6):
        logits, _ = jtfm.apply(params, jnp.asarray(seq), CFG)
        nxt = np.asarray(logits[:, -1].argmax(-1), np.int32)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, seq)


def test_generate_deterministic_and_jittable(rng):
    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (1, 3)).astype(np.int32))
    g = jax.jit(lambda p, t: generate(p, t, CFG, max_new_tokens=5))
    np.testing.assert_array_equal(g(params, prompt), g(params, prompt))


def test_generate_temperature_needs_key(rng):
    params = toy_params(CFG)
    prompt = jnp.zeros((1, 3), jnp.int32)
    with pytest.raises(ValueError, match="PRNG key"):
        generate(params, prompt, CFG, 4, temperature=0.8)
    out = generate(params, prompt, CFG, 4, temperature=0.8,
                   key=jax.random.key(1))
    assert out.shape == (1, 7)


def test_generate_bfloat16_cache(rng):
    """bf16 compute config: cache updates must not dtype-clash."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_len=16,
                                dtype="bfloat16")
    params = toy_params(cfg)
    out = generate(params, jnp.zeros((1, 2), jnp.int32), cfg, 4)
    assert out.shape == (1, 6)


def test_generate_length_guard(rng):
    params = toy_params(CFG)
    with pytest.raises(ValueError, match="max_len"):
        generate(params, jnp.zeros((1, 10), jnp.int32), CFG, 10)
    with pytest.raises(ValueError, match="at least one token"):
        generate(params, jnp.zeros((1, 0), jnp.int32), CFG, 4)


def test_top_k_mask_keeps_exactly_k():
    from distkeras_tpu.models.generate import top_k_mask

    logits = jnp.asarray([[1.0, 5.0, 3.0, 2.0, 4.0]])
    out = np.asarray(top_k_mask(logits, 2))
    assert np.isfinite(out).sum() == 2
    assert np.isfinite(out[0, [1, 4]]).all()  # the two largest survive


def test_top_p_mask_nucleus():
    from distkeras_tpu.models.generate import top_p_mask

    # probs ~ [0.643, 0.236, 0.087, 0.032, 0.002]
    logits = jnp.log(jnp.asarray([[0.643, 0.236, 0.087, 0.032, 0.002]]))
    out = np.asarray(top_p_mask(logits, 0.8))
    # exclusive mass: 0, .643, .879 -> first two kept, rest dropped
    assert np.isfinite(out[0, :2]).all() and not np.isfinite(out[0, 2:]).any()
    # top token always survives even with tiny p
    out = np.asarray(top_p_mask(logits, 1e-6))
    assert np.isfinite(out[0, 0]) and not np.isfinite(out[0, 1:]).any()


def test_generate_topk1_equals_greedy(rng):
    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)).astype(np.int32))
    greedy = generate(params, prompt, CFG, max_new_tokens=6)
    k1 = generate(params, prompt, CFG, max_new_tokens=6, temperature=0.7,
                  top_k=1, key=jax.random.key(7))
    np.testing.assert_array_equal(greedy, k1)


def test_generate_tiny_top_p_equals_greedy(rng):
    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)).astype(np.int32))
    greedy = generate(params, prompt, CFG, max_new_tokens=6)
    p0 = generate(params, prompt, CFG, max_new_tokens=6, temperature=1.3,
                  top_p=1e-9, key=jax.random.key(11))
    np.testing.assert_array_equal(greedy, p0)


def test_generate_sampling_deterministic_per_key(rng):
    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (1, 3)).astype(np.int32))

    def g(seed):
        return generate(params, prompt, CFG, 5, temperature=1.0,
                        top_k=8, top_p=0.9, key=jax.random.key(seed))

    np.testing.assert_array_equal(g(3), g(3))
    assert not np.array_equal(np.asarray(g(3)), np.asarray(g(4)))


def test_generate_sampling_validation(rng):
    params = toy_params(CFG)
    prompt = jnp.zeros((1, 3), jnp.int32)
    with pytest.raises(ValueError, match="temperature > 0"):
        generate(params, prompt, CFG, 4, top_k=5)
    with pytest.raises(ValueError, match="top_k"):
        generate(params, prompt, CFG, 4, temperature=1.0, top_k=0,
                 key=jax.random.key(0))
    with pytest.raises(ValueError, match="top_p"):
        generate(params, prompt, CFG, 4, temperature=1.0, top_p=1.5,
                 key=jax.random.key(0))


def test_generate_rope_greedy_matches_rollout(rng):
    cfg = ROPE_CFG
    params = toy_params(cfg)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)).astype(np.int32))
    out = generate(params, prompt, cfg, max_new_tokens=6)
    seq = np.asarray(prompt)
    for _ in range(6):
        logits, _ = jtfm.apply(params, jnp.asarray(seq), cfg)
        nxt = np.asarray(logits[:, -1].argmax(-1), np.int32)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, seq)


@pytest.mark.parametrize("kv", [1, 2])
def test_gqa_cache_is_smaller_and_decode_matches(rng, kv):
    """kv=2 exercises the group->kv-head mapping proper (kv=1/MQA is
    grouping-invariant and would mask a reshape-order regression)."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_len=16,
                                n_kv_heads=kv, rope=True)
    cache = init_cache(cfg, batch=2)
    assert cache["k"].shape == (2, 2, 16, kv, 8)
    params = toy_params(cfg)
    toks_ = jnp.asarray(rng.integers(0, 64, (2, 10)).astype(np.int32))
    full_logits, _ = jtfm.apply(params, toks_, cfg)
    for pos in range(10):
        logits, cache = _decode_step(params, cache, toks_[:, pos], pos, cfg)
        np.testing.assert_allclose(logits, full_logits[:, pos],
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("cfg", [CFG, ROPE_CFG], ids=["table", "rope"])
def test_generate_ragged_batch_matches_individual(rng, cfg):
    """Right-padded prompts + prompt_lengths: every row decodes exactly
    as it would alone (left-pad alignment, masked pad, per-row position
    ids)."""
    params = toy_params(cfg)
    p1 = rng.integers(1, 64, (5,)).astype(np.int32)   # length 5
    p2 = rng.integers(1, 64, (2,)).astype(np.int32)   # length 2
    padded = np.zeros((2, 5), np.int32)
    padded[0] = p1
    padded[1, :2] = p2
    out = generate(params, jnp.asarray(padded), cfg, max_new_tokens=6,
                   prompt_lengths=np.array([5, 2]))
    solo1 = generate(params, jnp.asarray(p1[None]), cfg, max_new_tokens=6)
    solo2 = generate(params, jnp.asarray(p2[None]), cfg, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(out)[0, :11],
                                  np.asarray(solo1)[0])
    np.testing.assert_array_equal(np.asarray(out)[1, :8],
                                  np.asarray(solo2)[0])
    # Tail padding preserved in the input layout.
    np.testing.assert_array_equal(np.asarray(out)[1, 8:], 0)


def test_generate_ragged_validation(rng):
    params = toy_params(CFG)
    prompt = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="prompt_lengths"):
        generate(params, prompt, CFG, 4, prompt_lengths=np.array([4]))


def test_generate_ragged_length_range_checked(rng):
    params = toy_params(CFG)
    prompt = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match=r"\[1, 4\]"):
        generate(params, prompt, CFG, 4, prompt_lengths=np.array([4, 7]))
    with pytest.raises(ValueError, match=r"\[1, 4\]"):
        generate(params, prompt, CFG, 4, prompt_lengths=np.array([0, 4]))


def test_generate_eos_sticky(rng):
    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)).astype(np.int32))
    free = np.asarray(generate(params, prompt, CFG, max_new_tokens=8))
    eos = int(free[0, 4])  # row 0's first generated token
    out = np.asarray(generate(params, prompt, CFG, max_new_tokens=8,
                              eos_token=eos))
    # Row 0 finished at its first generated slot: the rest is eos fill.
    assert (out[0, 4:] == eos).all()
    # A row that never emits eos matches the unconstrained run.
    if eos not in free[1, 4:]:
        np.testing.assert_array_equal(out[1], free[1])
    with pytest.raises(ValueError, match="eos_token"):
        generate(params, prompt, CFG, 4, eos_token=64)


# ------------------------------------------------------------------ prefill

@pytest.mark.parametrize("cfg", [CFG, ROPE_CFG])
def test_prefill_matches_sequential_generate(rng, cfg):
    """The prefill/decode split is a pure optimization: outputs must
    equal teacher-forcing every prompt position through the cached
    step (same einsums, same dtype path)."""
    params = toy_params(cfg)
    prompt = jnp.asarray(rng.integers(0, 64, (3, 7)), jnp.int32)
    seq = generate(params, prompt, cfg, max_new_tokens=8,
                   use_prefill=False)
    pre = generate(params, prompt, cfg, max_new_tokens=8,
                   use_prefill=True)
    np.testing.assert_array_equal(np.asarray(pre), np.asarray(seq))


def test_prefill_matches_sequential_gqa(rng):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_len=32,
                                n_kv_heads=2, rope=True)
    params = toy_params(cfg, 1)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 11)), jnp.int32)
    seq = generate(params, prompt, cfg, max_new_tokens=6,
                   use_prefill=False)
    pre = generate(params, prompt, cfg, max_new_tokens=6,
                   use_prefill=True)
    np.testing.assert_array_equal(np.asarray(pre), np.asarray(seq))


def test_prefill_sampling_matches_sequential(rng):
    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 7)), jnp.int32)
    kw = dict(temperature=0.8, key=jax.random.key(5), top_k=8)
    seq = generate(params, prompt, CFG, 6, use_prefill=False, **kw)
    pre = generate(params, prompt, CFG, 6, use_prefill=True, **kw)
    np.testing.assert_array_equal(np.asarray(pre), np.asarray(seq))


def test_prefill_eos_matches_sequential(rng):
    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (4, 5)), jnp.int32)
    seq = generate(params, prompt, CFG, 10, eos_token=3,
                   use_prefill=False)
    pre = generate(params, prompt, CFG, 10, eos_token=3,
                   use_prefill=True)
    np.testing.assert_array_equal(np.asarray(pre), np.asarray(seq))


def test_prefill_rejections(rng):
    prompt = jnp.asarray(rng.integers(0, 64, (2, 5)), jnp.int32)
    # Ragged prompts keep the sequential path.
    params_d = toy_params(CFG)
    with pytest.raises(ValueError, match="use_prefill"):
        generate(params_d, prompt, CFG, 4, use_prefill=True,
                 prompt_lengths=np.array([3, 5]))


@pytest.mark.parametrize("cfg", [MOE_CFG, MOE2_CFG], ids=["top1", "top2"])
def test_prefill_moe_matches_sequential(rng, cfg):
    """MoE prompts prefill with decode-parity dense routing: outputs
    equal the all-sequential path exactly (same per-token math)."""
    params = toy_params(cfg, 1)
    prompt = jnp.asarray(rng.integers(0, 64, (3, 7)), jnp.int32)
    seq = generate(params, prompt, cfg, 6, use_prefill=False)
    pre = generate(params, prompt, cfg, 6, use_prefill=True)
    np.testing.assert_array_equal(np.asarray(pre), np.asarray(seq))
    auto = generate(params, prompt, cfg, 6)  # auto now prefills
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(seq))


def test_prefill_rejects_overlong_prompt(rng):

    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (2, CFG.max_len + 2)), jnp.int32)
    with pytest.raises(ValueError, match="max_len"):
        prefill(params, prompt, CFG)


# ---------------------------------------------------------------- int8 decode

def test_quantize_roundtrip_error_bound(rng):
    from distkeras_tpu.models.quant import quantize_params

    params = toy_params(CFG)
    qp = quantize_params(params)
    w = np.asarray(params["layers"]["attn"]["wq"])
    dq = np.asarray(qp["layers"]["attn"]["wq"].dequant())
    # Symmetric absmax int8: per-channel error <= scale/2 = amax/254.
    amax = np.abs(w).max(axis=1, keepdims=True)
    assert np.all(np.abs(dq - w) <= amax / 254 + 1e-7)


def test_quantized_decode_matches_f32_greedy(rng):
    """On a trained model the int8 decode must reproduce the f32 greedy
    tokens (easy task -> logit margins dwarf the ~0.4% rounding)."""
    import optax

    from distkeras_tpu.models.quant import quantize_params

    params = toy_params(CFG)
    opt = optax.adam(1e-2)
    step = jax.jit(tfm.make_train_step(CFG, opt))
    carry = (params, opt.init(params))
    data = jnp.asarray(np.repeat(rng.integers(0, 64, (32, 1)), 16, axis=1),
                       jnp.int32)
    for _ in range(30):
        carry, loss = step(carry, data)
    trained = carry[0]

    prompt = data[:4, :4]
    ref = generate(trained, prompt, CFG, 8, use_prefill=False)
    qp = quantize_params(trained)
    out = generate(qp, prompt, CFG, 8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_quantized_params_memory_and_guards(rng):
    from distkeras_tpu.models.quant import QTensor, quantize_params

    params = toy_params(CFG)
    qp = quantize_params(params)
    emb = qp["tok_emb"]
    assert isinstance(emb, QTensor) and emb.q.dtype == jnp.int8
    # int8 + per-row scales ~ 1/3.9 of the f32 bytes on the big mats.
    f32_bytes = np.asarray(params["tok_emb"]).nbytes
    q_bytes = (np.asarray(emb.q).nbytes + np.asarray(emb.s).nbytes)
    assert q_bytes < f32_bytes / 3.5
    # prefill wants full-precision weights.
    prompt = jnp.asarray(rng.integers(0, 64, (2, 6)), jnp.int32)
    with pytest.raises(ValueError, match="use_prefill"):
        generate(qp, prompt, CFG, 4, use_prefill=True)
    # MoE rejected.
    moe_params = toy_params(MOE_CFG, 1)
    with pytest.raises(ValueError, match="dense-FFN"):
        quantize_params(moe_params)


def test_quantized_decode_rope_gqa(rng):
    from distkeras_tpu.models.quant import quantize_params

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_len=32,
                                n_kv_heads=2, rope=True)
    params = toy_params(cfg, 1)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 5)), jnp.int32)
    out = generate(quantize_params(params), prompt, cfg, 6)
    assert out.shape == (2, 11)
    assert int(np.asarray(out).min()) >= 0


# ------------------------------------------------------------- beam search

def _seq_logprob(params, cfg, seq, start):
    """Sum of per-token log-probs of seq[start:] under the model."""
    from distkeras_tpu.models import transformer as tfm

    logits, _ = jtfm.apply(params, jnp.asarray(seq[None, :-1]), cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)[0]
    tgt = np.asarray(seq[1:])
    per = np.asarray(jnp.take_along_axis(
        logp, jnp.asarray(tgt)[:, None], axis=-1))[:, 0]
    return float(per[start - 1:].sum())


def test_beam_width_1_equals_greedy(rng):

    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (3, 5)), jnp.int32)
    greedy = generate(params, prompt, CFG, 8)
    seqs, scores = beam_search(params, prompt, CFG, 8, beam_width=1)
    np.testing.assert_array_equal(np.asarray(seqs[:, 0]),
                                  np.asarray(greedy))


def test_beam_scores_match_rescoring_and_beat_greedy(rng):

    params = toy_params(CFG, 1)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)), jnp.int32)
    n_new = 6
    seqs, scores = beam_search(params, prompt, CFG, n_new, beam_width=4)
    greedy = generate(params, prompt, CFG, n_new)
    for row in range(2):
        # Internal score bookkeeping == re-scoring with the training
        # forward (same math up to f32 reduction order).
        best = np.asarray(seqs[row, 0])
        np.testing.assert_allclose(
            float(scores[row, 0]), _seq_logprob(params, CFG, best, 4),
            atol=1e-3, rtol=1e-4)
        # Seeded regression property, not a theorem: vanilla beam
        # search can in principle prune the greedy path, but with this
        # pinned seed/width/config the best beam matches or beats the
        # greedy rollout's total log-prob (deterministic on CPU f32).
        g = _seq_logprob(params, CFG, np.asarray(greedy[row]), 4)
        assert float(scores[row, 0]) >= g - 1e-4, (float(scores[row, 0]), g)
        # Beams come back best-first.
        assert np.all(np.diff(np.asarray(scores[row])) <= 1e-6)


def test_beam_eos_freezes_score(rng):

    params = toy_params(CFG, 2)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 3)), jnp.int32)
    seqs, scores = beam_search(params, prompt, CFG, 8, beam_width=3,
                               eos_token=5)
    s = np.asarray(seqs)
    # After a generated eos, every later slot is eos (frozen beam).
    gen = s[:, :, 3:]
    for row in gen.reshape(-1, gen.shape[-1]):
        hits = np.nonzero(row == 5)[0]
        if hits.size:
            assert np.all(row[hits[0]:] == 5), row


def test_beam_validation_and_quantized(rng):
    from distkeras_tpu.models.quant import quantize_params

    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)), jnp.int32)
    with pytest.raises(ValueError, match="beam_width"):
        beam_search(params, prompt, CFG, 4, beam_width=0)
    with pytest.raises(ValueError, match="max_len"):
        beam_search(params, prompt, CFG, 64, beam_width=2)
    with pytest.raises(ValueError, match="use_prefill"):
        beam_search(quantize_params(params), prompt, CFG, 4,
                    beam_width=2, use_prefill=True)
    # Quantized tree works on the auto (sequential) path.
    seqs, _ = beam_search(quantize_params(params), prompt, CFG, 4,
                          beam_width=2)
    assert seqs.shape == (2, 2, 8)


def test_beam_prefill_matches_sequential(rng):

    params = toy_params(CFG, 3)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 6)), jnp.int32)
    s1, sc1 = beam_search(params, prompt, CFG, 5, beam_width=3,
                          use_prefill=True)
    s2, sc2 = beam_search(params, prompt, CFG, 5, beam_width=3,
                          use_prefill=False)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_allclose(np.asarray(sc1), np.asarray(sc2),
                               rtol=1e-5, atol=1e-5)


def test_beam_frozen_score_is_length_invariant(rng):
    """A beam that emits eos freezes: its score must not change as the
    scan keeps running (regression guard: frozen continuation adds 0,
    not logp(eos), each step)."""
    import optax


    # Constant-row training: the model emits token c forever; with
    # eos_token=c the best beam finishes at the first generated slot.
    c = 9
    params = toy_params(CFG)
    opt = optax.adam(1e-2)
    step = jax.jit(tfm.make_train_step(CFG, opt))
    carry = (params, opt.init(params))
    data = jnp.full((16, 16), c, jnp.int32)
    for _ in range(25):
        carry, _ = step(carry, data)
    trained = carry[0]
    prompt = jnp.full((2, 3), c, jnp.int32)
    _, s_short = beam_search(trained, prompt, CFG, 2, beam_width=2,
                             eos_token=c)
    _, s_long = beam_search(trained, prompt, CFG, 10, beam_width=2,
                            eos_token=c)
    np.testing.assert_allclose(np.asarray(s_long[:, 0]),
                               np.asarray(s_short[:, 0]),
                               rtol=1e-5, atol=1e-6)


def test_windowed_decode_matches_training_forward(rng):
    """KV-cached decode with attention_window reproduces the training
    forward's logits position by position (same banded mask)."""
    import dataclasses

    cfg = dataclasses.replace(ROPE_CFG, attention_window=4)
    params = toy_params(cfg)
    t = jnp.asarray(rng.integers(0, 64, (2, 10)), jnp.int32)
    full_logits, _ = jtfm.apply(params, t, cfg)
    cache = init_cache(cfg, 2)
    for pos in range(10):
        step_logits, cache = _decode_step(params, cache, t[:, pos], pos,
                                          cfg)
        np.testing.assert_allclose(
            np.asarray(step_logits), np.asarray(full_logits[:, pos]),
            atol=2e-4, rtol=2e-4)


def test_windowed_generate_prefill_matches_sequential(rng):
    import dataclasses

    cfg = dataclasses.replace(CFG, attention_window=3)
    params = toy_params(cfg, 1)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 7)), jnp.int32)
    pre = generate(params, prompt, cfg, 6, use_prefill=True)
    seq = generate(params, prompt, cfg, 6, use_prefill=False)
    np.testing.assert_array_equal(np.asarray(pre), np.asarray(seq))


def test_beam_length_penalty(rng):
    """alpha=0 is the raw ordering; alpha>0 re-ranks by the GNMT
    normalization and returns the normalized scores, consistent with
    each beam's generated length."""

    params = toy_params(CFG, 4)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)), jnp.int32)
    s0, sc0 = beam_search(params, prompt, CFG, 6, beam_width=4)
    s1, sc1 = beam_search(params, prompt, CFG, 6, beam_width=4,
                          length_penalty=0.0)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    np.testing.assert_allclose(np.asarray(sc0), np.asarray(sc1))

    # No eos: every beam generates exactly 6 tokens, so the alpha>0
    # ordering matches raw and scores divide by the same factor.
    s2, sc2 = beam_search(params, prompt, CFG, 6, beam_width=4,
                          length_penalty=1.0)
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(s0))
    np.testing.assert_allclose(np.asarray(sc2),
                               np.asarray(sc0) / ((5.0 + 6.0) / 6.0),
                               rtol=1e-5)
    # Scores come back sorted under the normalization too.
    assert np.all(np.diff(np.asarray(sc2), axis=1) <= 1e-6)

    with pytest.raises(ValueError, match="length_penalty"):
        beam_search(params, prompt, CFG, 4, beam_width=2,
                    length_penalty=-1.0)


def test_beam_length_penalty_frozen_lengths(rng):
    """Frozen (eos) beams stop accumulating length: with a model that
    emits eos immediately, the best beam's normalized score uses n=1."""
    import optax


    c = 9
    params = toy_params(CFG)
    opt = optax.adam(1e-2)
    step = jax.jit(tfm.make_train_step(CFG, opt))
    carry = (params, opt.init(params))
    data = jnp.full((16, 16), c, jnp.int32)
    for _ in range(25):
        carry, _ = step(carry, data)
    trained = carry[0]
    prompt = jnp.full((1, 3), c, jnp.int32)
    _, raw = beam_search(trained, prompt, CFG, 8, beam_width=2,
                         eos_token=c)
    _, norm = beam_search(trained, prompt, CFG, 8, beam_width=2,
                          eos_token=c, length_penalty=1.0)
    np.testing.assert_allclose(float(norm[0, 0]),
                               float(raw[0, 0]) / 1.0, rtol=1e-5)


# ----------------------------------------------------------- rolling decode

def test_rolling_decode_matches_large_cache(rng):
    """Generation past max_len on the ring-buffer cache must reproduce
    a non-wrapping run of the same windowed model with a big cache —
    the window makes everything beyond the last W positions irrelevant,
    so wrap-around must be invisible."""
    import dataclasses

    base = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, rope=True,
                                 attention_window=6, max_len=64)
    small = dataclasses.replace(base, max_len=16)  # will wrap
    params = toy_params(base)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 5)), jnp.int32)
    n_new = 35  # 5 + 35 = 40 > 16: several full wraps
    big = generate(params, prompt, base, n_new)
    rolled = generate(params, prompt, small, n_new)
    np.testing.assert_array_equal(np.asarray(rolled), np.asarray(big))


def test_rolling_decode_sampling_and_eos(rng):
    import dataclasses

    base = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, rope=True,
                                 attention_window=4, max_len=48,
                                 n_kv_heads=1)
    small = dataclasses.replace(base, max_len=12)
    params = toy_params(base, 1)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)), jnp.int32)
    kw = dict(temperature=0.8, key=jax.random.key(7), top_k=8, eos_token=3)
    big = generate(params, prompt, base, 25, **kw)
    rolled = generate(params, prompt, small, 25, **kw)
    np.testing.assert_array_equal(np.asarray(rolled), np.asarray(big))


def test_rolling_beam_matches_large_cache(rng):
    """Beam search past max_len on the ring-buffer cache (round-4)
    reproduces a non-wrapping run of the same windowed model with a
    big cache — on BOTH the ancestry path (slot-indexed ancestor map;
    stale entries retired as slots are rewritten) and the physical
    parent-gather, with eos and GQA in the mix."""
    import dataclasses


    base = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                 n_kv_heads=2, n_layers=2, d_ff=64,
                                 rope=True, attention_window=6,
                                 max_len=64)
    small = dataclasses.replace(base, max_len=16)  # will wrap
    params = toy_params(base, 2)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 5)), jnp.int32)
    n_new = 30  # 5 + 30 = 35 > 16: several full wraps
    for kw in [dict(), dict(eos_token=7),
               dict(beam_impl="physical")]:
        big_s, big_sc = beam_search(params, prompt, base, n_new,
                                    beam_width=3, **kw)
        roll_s, roll_sc = beam_search(params, prompt, small, n_new,
                                      beam_width=3, **kw)
        np.testing.assert_array_equal(np.asarray(roll_s),
                                      np.asarray(big_s), err_msg=str(kw))
        np.testing.assert_allclose(np.asarray(roll_sc),
                                   np.asarray(big_sc),
                                   atol=1e-5, rtol=1e-5)


def test_rolling_decode_requires_rope_and_window(rng):
    """Past-max_len decoding without the rolling prerequisites must
    still raise, including for ragged prompts."""
    import dataclasses

    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)), jnp.int32)
    with pytest.raises(ValueError, match="max_len"):
        generate(params, prompt, CFG, 20)  # no rope, no window
    win = dataclasses.replace(CFG, attention_window=4)  # window, no rope
    pw = toy_params(win)
    with pytest.raises(ValueError, match="max_len"):
        generate(pw, prompt, win, 20)
    roll = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=1, d_ff=64, rope=True,
                                 attention_window=4, max_len=12)
    pr = toy_params(roll)
    with pytest.raises(ValueError, match="max_len"):  # ragged: no rolling
        generate(pr, prompt, roll, 20, prompt_lengths=np.array([2, 4]))
    out = generate(pr, prompt, roll, 20)  # eligible: runs past max_len
    assert out.shape == (2, 24)


def test_rolling_decode_long_prompt_sequential_fallback(rng):
    """A prompt longer than max_len is rolling-eligible: auto path must
    fall back to sequential teacher-forcing (prefill cannot hold it)
    and still match the big-cache run."""
    import dataclasses

    base = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, rope=True,
                                 attention_window=4, max_len=48)
    small = dataclasses.replace(base, max_len=12)
    params = toy_params(base, 2)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 20)), jnp.int32)  # > 12
    big = generate(params, prompt, base, 10)
    rolled = generate(params, prompt, small, 10)
    np.testing.assert_array_equal(np.asarray(rolled), np.asarray(big))
    with pytest.raises(ValueError, match="fits the cache"):
        generate(params, prompt, small, 10, use_prefill=True)


def test_beam_search_windowed_cfg(rng):
    """Beam search composes with attention_window (the banded decode
    mask drives every beam's cache reads); width 1 == windowed greedy."""
    import dataclasses

    cfg = dataclasses.replace(ROPE_CFG, attention_window=4)
    params = toy_params(cfg, 3)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)), jnp.int32)

    greedy = generate(params, prompt, cfg, 6)
    seqs, _ = beam_search(params, prompt, cfg, 6, beam_width=1)
    np.testing.assert_array_equal(np.asarray(seqs[:, 0]),
                                  np.asarray(greedy))


def test_rolling_decode_quantized(rng):
    """int8 weights x rolling window cache: sequential decode past
    max_len with a quantized tree matches the quantized big-cache run."""
    import dataclasses

    from distkeras_tpu.models.quant import quantize_params

    base = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, rope=True,
                                 attention_window=4, max_len=40)
    small = dataclasses.replace(base, max_len=10)
    qp = quantize_params(toy_params(base, 5))
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)), jnp.int32)
    big = generate(qp, prompt, base, 20)
    rolled = generate(qp, prompt, small, 20)
    np.testing.assert_array_equal(np.asarray(rolled), np.asarray(big))


def test_min_p_mask_semantics():
    from distkeras_tpu.models.generate import min_p_mask

    logits = jnp.asarray([[0.0, -1.0, -10.0]])
    out = np.asarray(min_p_mask(logits, 0.5))
    # p1/pmax = e^-1 ~ 0.37 < 0.5 -> dropped; p2/pmax tiny -> dropped.
    assert np.isfinite(out[0, 0])
    assert np.isneginf(out[0, 1]) and np.isneginf(out[0, 2])
    out2 = np.asarray(min_p_mask(logits, 0.3))
    assert np.isfinite(out2[0, 1])  # 0.37 >= 0.3 survives
    with pytest.raises(ValueError, match="min_p"):
        min_p_mask(logits, 0.0)


def test_generate_min_p_sampling(rng):
    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 5)), jnp.int32)
    out = generate(params, prompt, CFG, 6, temperature=0.9, min_p=0.1,
                   key=jax.random.key(1))
    assert out.shape == (2, 11)
    # min_p=1.0 keeps only the argmax -> equals greedy.
    strict = generate(params, prompt, CFG, 6, temperature=0.9, min_p=1.0,
                      key=jax.random.key(1))
    greedy = generate(params, prompt, CFG, 6)
    np.testing.assert_array_equal(np.asarray(strict), np.asarray(greedy))
    with pytest.raises(ValueError, match="temperature"):
        generate(params, prompt, CFG, 6, min_p=0.1)


def test_beam_ancestry_equals_physical_reorder(rng):
    """The ancestry-attention beam path (cache never reordered; history
    resolved through the one-hot ancestor map) returns the same
    hypotheses and scores as the physical parent-gather it replaced —
    including under GQA grouping and an eos freeze."""
    import dataclasses


    gqa_cfg = dataclasses.replace(CFG, n_heads=4, n_kv_heads=2, rope=True)
    params = toy_params(gqa_cfg, 3)
    prompt = jnp.asarray(rng.integers(0, 64, (3, 5)), jnp.int32)
    for kw in [dict(), dict(eos_token=7), dict(length_penalty=0.8)]:
        seqs_a, sc_a = beam_search(params, prompt, gqa_cfg, 10,
                                   beam_width=3, **kw)
        seqs_p, sc_p = beam_search(params, prompt, gqa_cfg, 10,
                                   beam_width=3, beam_impl="physical",
                                   **kw)
        np.testing.assert_array_equal(np.asarray(seqs_a),
                                      np.asarray(seqs_p))
        np.testing.assert_allclose(np.asarray(sc_a), np.asarray(sc_p),
                                   atol=1e-5, rtol=1e-5)


def test_beam_impl_knob_and_ancestry_size_guard(rng, monkeypatch):
    """The public beam_impl knob: 'physical' matches 'ancestry' (both
    explicit), 'auto' falls back with a warning when the ancestry score
    intermediate would exceed the limit, explicit 'ancestry' raises at
    that size, and bad values are rejected.  (Windowed configs take
    ancestry too — test_beam_windowed_ancestry_equals_physical.)"""
    from distkeras_tpu.models import generate as gen

    params = toy_params(CFG, 5)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)).astype(np.int32))
    sa, sca = beam_search(params, prompt, CFG, 5, beam_width=3,
                          beam_impl="ancestry")
    sp, scp = beam_search(params, prompt, CFG, 5, beam_width=3,
                          beam_impl="physical")
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sp))
    np.testing.assert_allclose(np.asarray(sca), np.asarray(scp),
                               atol=1e-5, rtol=1e-5)

    # Shrink the limit below this config's estimate to exercise the
    # guard without allocating GBs.
    est = gen._ancestry_score_bytes(2, 3, CFG)
    monkeypatch.setattr(gen, "ANCESTRY_SCORE_LIMIT_BYTES", est // 2)
    with pytest.warns(UserWarning, match="falling back to the physical"):
        sf, scf = gen.beam_search(params, prompt, CFG, 5, beam_width=3)
    np.testing.assert_array_equal(np.asarray(sf), np.asarray(sp))
    with pytest.raises(ValueError, match="over the"):
        gen.beam_search(params, prompt, CFG, 5, beam_width=3,
                        beam_impl="ancestry")
    monkeypatch.undo()

    with pytest.raises(ValueError, match="beam_impl must be"):
        beam_search(params, prompt, CFG, 5, beam_width=3,
                    beam_impl="fast")


def test_beam_windowed_ancestry_equals_physical(rng):
    """Windowed (ring-buffer) beam search on the ancestry path matches
    the physical parent-gather exactly — beam search never decodes past
    max_len, so slots never wrap and the ancestor map indexes them
    directly; only the band mask differs from the full-cache path
    (round-4 extension; windowed beam previously always paid the
    per-step cache gather).  Covers rope + GQA + eos under a window
    shorter than the sequence."""
    import dataclasses


    cfg = dataclasses.replace(CFG, n_heads=4, n_kv_heads=2, rope=True,
                              attention_window=6)
    params = toy_params(cfg, 7)
    prompt = jnp.asarray(rng.integers(0, 64, (3, 5)), jnp.int32)
    for kw in [dict(), dict(eos_token=7), dict(length_penalty=0.6)]:
        sa, sca = beam_search(params, prompt, cfg, 10, beam_width=3,
                              beam_impl="ancestry", **kw)
        sp, scp = beam_search(params, prompt, cfg, 10, beam_width=3,
                              beam_impl="physical", **kw)
        np.testing.assert_array_equal(np.asarray(sa), np.asarray(sp))
        np.testing.assert_allclose(np.asarray(sca), np.asarray(scp),
                                   atol=1e-5, rtol=1e-5)


def test_top_k_mask_approx_path():
    """The approximate-threshold top-k (vocab large enough to engage
    approx_max_k) keeps ~k entries around the true threshold, and
    exact=True reproduces the pre-round-3 exact mask bit-for-bit."""
    from distkeras_tpu.models.generate import top_k_mask

    rng_l = np.random.default_rng(0)
    logits = jnp.asarray(rng_l.normal(size=(4, 4096)).astype(np.float32))
    k = 50
    approx = np.asarray(top_k_mask(logits, k))
    exact = np.asarray(top_k_mask(logits, k, exact=True))
    kept_a = np.isfinite(approx).sum(axis=-1)
    kept_e = np.isfinite(exact).sum(axis=-1)
    np.testing.assert_array_equal(kept_e, k)
    # NOTE: on CPU (this suite) approx_max_k lowers to an exact top-k,
    # so kept_a == k trivially and the band assertions below only
    # genuinely bite on TPU — they pin the CONTRACT the approx path is
    # allowed to exploit, not the TPU kernel's recall itself.
    # Approximate support sits in a small band around k, and every kept
    # logit is a genuinely large one (>= the exact threshold minus a
    # small slack).
    assert (np.abs(kept_a - k) <= max(5, k // 5)).all(), kept_a
    thresh = np.sort(np.asarray(logits), axis=-1)[:, -k]
    assert (approx[np.isfinite(approx)].min()
            >= thresh.min() - 0.5)
    # Small vocab (k > V/2) silently takes the exact path.
    small = jnp.asarray(rng_l.normal(size=(2, 64)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(top_k_mask(small, 40)),
        np.asarray(top_k_mask(small, 40, exact=True)))


# ---------------------------------------------------------- int8 KV cache

def test_kv_int8_decode_close_to_fp(rng):
    """int8 KV cache: teacher-forced logits track the full-precision
    decode within quantization noise, and greedy generation on a
    near-deterministic model is unchanged."""

    cfg = ROPE_CFG
    params = toy_params(cfg)
    toks = jnp.asarray(rng.integers(0, 64, (2, 12)).astype(np.int32))
    full_logits, _ = jtfm.apply(params, toks, cfg)

    cache = init_cache(cfg, 2, kv_int8=True)
    for pos in range(12):
        logits, cache = _decode_step(params, cache, toks[:, pos], pos, cfg)
        base = np.abs(np.asarray(full_logits[:, pos])).max()
        np.testing.assert_allclose(logits, full_logits[:, pos],
                                   atol=0.05 * base, rtol=0.1)


def test_kv_int8_generate_prefill_close_to_sequential(rng):
    """Prefill and sequential prompt paths under kv_int8 agree to
    quantization noise — NOT bit-exactly: prefill computes the prompt's
    attention in full precision and quantizes the K/V it writes, while
    the sequential path attends the already-quantized cache, so from
    layer 2 on the residual streams (and hence cached K/V) differ by
    int8 rounding.  The contract is closeness on logits (advisor
    round-3: token equality only held because greedy argmax absorbed
    the drift on a tiny model — fragile across seeds/backends)."""

    params = toy_params(CFG, 1)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 6)).astype(np.int32))
    _, last_p = prefill(params, prompt, CFG, last_logits=True,
                        kv_int8=True)
    cache_s = init_cache(CFG, 2, kv_int8=True)
    for pos in range(6):
        last_s, cache_s = _decode_step(params, cache_s, prompt[:, pos],
                                       pos, CFG)
    base = np.abs(np.asarray(last_p)).max()
    np.testing.assert_allclose(np.asarray(last_s), np.asarray(last_p),
                               atol=0.05 * base, rtol=0.1)


def test_kv_int8_beam_ancestry_equals_physical(rng):
    """Beam search runs on the int8 cache through BOTH the ancestry and
    physical paths with identical results."""

    params = toy_params(CFG, 2)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)).astype(np.int32))
    sa, sca = beam_search(params, prompt, CFG, 6, beam_width=3,
                          kv_int8=True)
    sp, scp = beam_search(params, prompt, CFG, 6, beam_width=3,
                          kv_int8=True, beam_impl="physical")
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sp))
    np.testing.assert_allclose(np.asarray(sca), np.asarray(scp),
                               atol=1e-5, rtol=1e-5)


def test_kv_int8_rolling_decode_matches_large_cache(rng):
    """kv_int8 on the ring-buffer cache (round-5: the scale slabs ride
    the same slot updates as the K/V): generation past max_len must
    EXACTLY reproduce a non-wrapping kv_int8 run with a big cache —
    quantization is per-token and slot-independent, so the wrap must
    stay invisible, int8 or not."""
    import dataclasses

    base = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, rope=True,
                                 attention_window=6, max_len=64)
    small = dataclasses.replace(base, max_len=16)  # will wrap
    params = toy_params(base)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 5)), jnp.int32)
    big = generate(params, prompt, base, 35, kv_int8=True,
                   use_prefill=False)
    rolled = generate(params, prompt, small, 35, kv_int8=True,
                      use_prefill=False)
    np.testing.assert_array_equal(np.asarray(rolled), np.asarray(big))


def test_kv_int8_rolling_beam_matches_large_cache(rng):
    """Rolling beam search on the int8 ring cache, both impls, vs a
    non-wrapping int8 big-cache run."""
    import dataclasses


    base = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                 n_kv_heads=2, n_layers=2, d_ff=64,
                                 rope=True, attention_window=6,
                                 max_len=64)
    small = dataclasses.replace(base, max_len=16)  # will wrap
    params = toy_params(base, 2)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 5)), jnp.int32)
    kw = dict(beam_width=3, kv_int8=True, use_prefill=False)
    bs, bsc = beam_search(params, prompt, base, 20, **kw)
    for impl in ("ancestry", "physical"):
        rs, rsc = beam_search(params, prompt, small, 20, beam_impl=impl,
                              **kw)
        np.testing.assert_array_equal(np.asarray(rs), np.asarray(bs))
        np.testing.assert_allclose(np.asarray(rsc), np.asarray(bsc),
                                   atol=1e-4, rtol=1e-4)


def test_kv_int8_ragged_rows_match_solo(rng):
    """Ragged prompts x kv_int8: each row decodes exactly as it would
    alone on the int8 cache (left-pad slots never attend; position ids
    count from the row's true start; per-token quantization makes the
    comparison exact, not just close)."""
    params = toy_params(ROPE_CFG, 3)
    p = 6
    rows = jnp.asarray(rng.integers(0, 64, (2, p)), jnp.int32)
    lens = [3, 6]
    out = generate(params, rows, ROPE_CFG, 5, kv_int8=True,
                   prompt_lengths=lens)
    for i, ln in enumerate(lens):
        alone = generate(params, rows[i:i + 1, :ln], ROPE_CFG, 5,
                         kv_int8=True, use_prefill=False)
        np.testing.assert_array_equal(np.asarray(out[i, :ln + 5]),
                                      np.asarray(alone[0]))


# ------------------------------------------------------- prompt/prefix cache

def test_prompt_cache_matches_full_prompt(rng):
    """Reusing a prefilled prefix cache (system-prompt pattern) emits
    EXACTLY the tokens of running the concatenated prompt from scratch
    — greedy and sampled (the position-keyed PRNG stream makes the
    sampled comparison exact), batch-matched and batch-1-broadcast."""

    params = toy_params(ROPE_CFG)
    prefix = jnp.asarray(rng.integers(0, 64, (2, 5)).astype(np.int32))
    tail = jnp.asarray(rng.integers(0, 64, (2, 3)).astype(np.int32))
    full = jnp.concatenate([prefix, tail], axis=1)

    ref = generate(params, full, ROPE_CFG, 6)
    cache, _ = prefill(params, prefix, ROPE_CFG, last_logits=False)
    out = generate(params, tail, ROPE_CFG, 6, prompt_cache=(cache, 5))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref[:, 5:]))

    k = jax.random.key(9)
    ref_s = generate(params, full, ROPE_CFG, 6, temperature=0.9, top_k=8,
                     key=k)
    out_s = generate(params, tail, ROPE_CFG, 6, temperature=0.9, top_k=8,
                     key=k, prompt_cache=(cache, 5))
    np.testing.assert_array_equal(np.asarray(out_s),
                                  np.asarray(ref_s[:, 5:]))

    # Batch-1 shared prefix fans out to the request batch.
    cache1, _ = prefill(params, prefix[:1], ROPE_CFG, last_logits=False)
    prefix_b = jnp.broadcast_to(prefix[:1], prefix.shape)
    ref_b = generate(params, jnp.concatenate([prefix_b, tail], axis=1),
                     ROPE_CFG, 6)
    out_b = generate(params, tail, ROPE_CFG, 6, prompt_cache=(cache1, 5))
    np.testing.assert_array_equal(np.asarray(out_b),
                                  np.asarray(ref_b[:, 5:]))


def test_prompt_cache_kv_int8_and_validation(rng):

    params = toy_params(CFG, 1)
    prefix = jnp.asarray(rng.integers(0, 64, (2, 4)).astype(np.int32))
    tail = jnp.asarray(rng.integers(0, 64, (2, 2)).astype(np.int32))
    qcache, _ = prefill(params, prefix, CFG, last_logits=False,
                        kv_int8=True)
    full = jnp.concatenate([prefix, tail], axis=1)
    ref = generate(params, full, CFG, 4, kv_int8=True)
    out = generate(params, tail, CFG, 4, kv_int8=True,
                   prompt_cache=(qcache, 4))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref[:, 4:]))

    with pytest.raises(ValueError, match="quantization must match"):
        generate(params, tail, CFG, 4, prompt_cache=(qcache, 4))
    with pytest.raises(ValueError, match="fit max_len"):
        generate(params, tail, CFG, 12, prompt_cache=(qcache, 4))
    bad = jax.tree.map(lambda a: jnp.repeat(a, 3, axis=1), qcache)
    with pytest.raises(ValueError, match="batch"):
        generate(params, tail, CFG, 4, kv_int8=True,
                 prompt_cache=(bad, 4))


def test_prompt_cache_single_token_tail_and_quantized(rng):
    """Code-review regressions: a 1-token tail and a quantized tree both
    work with prompt_cache (no _resolve_prefill interference), and the
    error messages distinguish empty prefixes from budget overflow."""
    from distkeras_tpu.models.quant import quantize_params

    params = toy_params(CFG, 1)
    prefix = jnp.asarray(rng.integers(0, 64, (2, 4)).astype(np.int32))
    tail = jnp.asarray(rng.integers(0, 64, (2, 1)).astype(np.int32))
    cache, _ = prefill(params, prefix, CFG, last_logits=False)
    full = jnp.concatenate([prefix, tail], axis=1)
    ref = generate(params, full, CFG, 4)
    out = generate(params, tail, CFG, 4, prompt_cache=(cache, 4))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref[:, 4:]))

    # Quantized tree + prompt_cache: the regression is that
    # _resolve_prefill's full-precision precondition no longer blocks
    # the call.  (The cache here holds full-precision prefix K/V while
    # the tail decodes through int8 weights — a legitimate mixed
    # deployment, but not bit-comparable to any single-precision
    # reference, so this is a smoke + shape check, not an equality.)
    qp = quantize_params(params)
    qout = generate(qp, tail, CFG, 4, prompt_cache=(cache, 4))
    assert qout.shape == (2, 5)
    np.testing.assert_array_equal(np.asarray(qout[:, :1]),
                                  np.asarray(tail))

    with pytest.raises(ValueError, match=">= 1"):
        generate(params, tail, CFG, 4, prompt_cache=(cache, 0))
    with pytest.raises(ValueError, match="no effect with prompt_cache"):
        generate(params, tail, CFG, 4, prompt_cache=(cache, 4),
                 use_prefill=True)


def test_beam_prompt_cache_matches_full_prompt(rng):
    """Beam search over a reused prefix cache returns exactly the
    hypotheses and scores of beaming the concatenated prompt — on both
    the ancestry and physical paths, and under kv_int8."""

    params = toy_params(ROPE_CFG)
    prefix = jnp.asarray(rng.integers(0, 64, (2, 4)).astype(np.int32))
    tail = jnp.asarray(rng.integers(0, 64, (2, 3)).astype(np.int32))
    full = jnp.concatenate([prefix, tail], axis=1)
    for kw in [dict(), dict(kv_int8=True),
               dict(_force_physical=True), dict(eos_token=5)]:
        cache, _ = prefill(params, prefix, ROPE_CFG, last_logits=False,
                           kv_int8=kw.get("kv_int8", False))
        ref_s, ref_sc = beam_search(params, full, ROPE_CFG, 6,
                                    beam_width=3, **kw)
        out_s, out_sc = beam_search(params, tail, ROPE_CFG, 6,
                                    beam_width=3,
                                    prompt_cache=(cache, 4), **kw)
        np.testing.assert_array_equal(np.asarray(out_s),
                                      np.asarray(ref_s[:, :, 4:]))
        # Scores are sums of token log-probs; the two prompt passes
        # (full prefill vs prefix-prefill + suffix chunk) reduce
        # attention in different orders, so logits differ ~1e-4/pos in
        # f32 — the HYPOTHESES must match exactly, the score sums to a
        # few 1e-3.
        np.testing.assert_allclose(np.asarray(out_sc),
                                   np.asarray(ref_sc), atol=1e-2,
                                   rtol=1e-4)
    with pytest.raises(ValueError, match="no effect with prompt_cache"):
        beam_search(params, tail, ROPE_CFG, 4, beam_width=2,
                    prompt_cache=(cache, 4), use_prefill=True)


def test_kv_int8_gqa_decode_close_to_fp(rng):
    """int8 KV scales are per-kv-head: the GQA cache (fewer kv heads
    than query heads) quantizes and dequantizes consistently."""
    import dataclasses


    cfg = dataclasses.replace(ROPE_CFG, n_heads=4, n_kv_heads=2)
    params = toy_params(cfg, 2)
    toks = jnp.asarray(rng.integers(0, 64, (2, 10)).astype(np.int32))
    full_logits, _ = jtfm.apply(params, toks, cfg)
    cache = init_cache(cfg, 2, kv_int8=True)
    for pos in range(10):
        logits, cache = _decode_step(params, cache, toks[:, pos], pos,
                                     cfg)
        base = np.abs(np.asarray(full_logits[:, pos])).max()
        np.testing.assert_allclose(logits, full_logits[:, pos],
                                   atol=0.05 * base, rtol=0.1)


def test_mask_validation_rejects_concrete_arrays_out_of_range():
    """Round-6 fix: _validate_unit_interval used to skip ALL non-scalar
    values, so a direct mask caller with a bad concrete array got
    silent NaN masking; now concrete arrays are range-checked (min_p
    arrays may carry 0.0, the serving engines' explicit no-op slot)."""
    from distkeras_tpu.models.generate import min_p_mask, top_p_mask

    logits = jnp.zeros((2, 4))
    with pytest.raises(ValueError, match="min_p"):
        min_p_mask(logits, np.asarray([[-0.2], [0.5]]))
    with pytest.raises(ValueError, match="top_p"):
        top_p_mask(logits, np.asarray([[0.0], [0.5]]))
    with pytest.raises(ValueError, match="top_p"):
        top_p_mask(logits, np.asarray([[1.5], [0.5]]))
    # The engines' no-op slot values stay legal in arrays...
    out = np.asarray(min_p_mask(logits, np.asarray([[0.0], [0.5]])))
    assert np.isfinite(out[0]).all()
    np.asarray(top_p_mask(logits, np.asarray([[1.0], [0.5]])))
    # ...and traced values still pass through to the caller's checks.
    jax.jit(lambda l, p: top_p_mask(l, p))(
        logits, jnp.asarray([[0.9], [0.5]]))


def test_generate_top_p_one_equals_no_filter(rng):
    """Round-6 parity fix: top_p=1.0 bypasses the nucleus mask exactly
    like top_p=None (the serving engines' contract), so a request
    copying its solo call's top_p=1.0 cannot diverge in the float
    corner where the sorted cumsum overshoots 1.0."""
    params = toy_params(CFG)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 5)), jnp.int32)
    k = jax.random.key(7)
    one = generate(params, prompt, CFG, 6, temperature=0.9, top_p=1.0,
                   key=k)
    none = generate(params, prompt, CFG, 6, temperature=0.9, key=k)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(none))
    # min_p=0.0 is the matching explicit no-op, and both no-op values
    # are legal on greedy calls too (submit() accepts them there).
    zero = generate(params, prompt, CFG, 6, temperature=0.9,
                    min_p=0.0, key=k)
    np.testing.assert_array_equal(np.asarray(zero), np.asarray(none))
    greedy = generate(params, prompt, CFG, 6)
    noop = generate(params, prompt, CFG, 6, top_p=1.0, min_p=0.0)
    np.testing.assert_array_equal(np.asarray(noop), np.asarray(greedy))
    with pytest.raises(ValueError, match="temperature"):
        generate(params, prompt, CFG, 6, top_p=0.9)  # real filter
    with pytest.raises(ValueError, match="min_p"):
        generate(params, prompt, CFG, 6, temperature=0.9, min_p=-0.1,
                 key=k)


# ------------------- the base block's features through the one body

BASE = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                             n_kv_heads=2, n_layers=2, d_ff=64, max_len=16,
                             rope=True)
RING = dataclasses.replace(BASE, attention_window=8)
MOE = dataclasses.replace(BASE, num_experts=4, moe_top_k=2)
# case -> (config, rows, tokens a row, positions (one int: every row
# there), int8 K/V, int8 weights, lane, extra arguments)
FEATURES = {
    "ring_token": (RING, 3, 1, [2, 17, 30], False, False, None, {}),
    "ring_chunk": (RING, 2, 4, 20, False, False, None, {}),
    "ring_rows": (RING, 2, 3, [14, 29], False, False, None, {}),
    "ring_lane": (RING, 3, 4, 0, False, False, 1, {}),
    "ragged": (BASE, 3, 1, 9, False, False, None,
               {"pad_lens": [0, 3, 6]}),
    "beam": (BASE, 4, 1, 9, False, False, None, {"beam": 2}),
    "beam_ring": (RING, 4, 1, 21, False, False, None, {"beam": 2}),
    "int8_kv": (BASE, 3, 1, [5, 11, 15], True, False, None, {}),
    "int8_kv_chunk": (BASE, 2, 4, 6, True, False, None, {}),
    "int8_kv_lane": (BASE, 3, 4, 4, True, False, 1, {}),
    "int8_kv_beam": (BASE, 4, 1, 9, True, False, None, {"beam": 2}),
    "int8_weights": (BASE, 3, 1, [3, 8, 12], False, True, None, {}),
    "int8_weights_lane": (BASE, 3, 4, 5, False, True, 2, {}),
    "moe": (MOE, 3, 1, [3, 8, 12], False, False, None, {}),
    "moe_chunk": (MOE, 2, 4, 6, False, False, None, {}),
    "partitioned": (BASE, 3, 1, [3, 8, 12], False, False, None,
                    {"partitioned": True}),
}


def _noise_cache(cfg, rows, kv_int8, rng):
    """A cache whose every slot holds something (a previous occupant's
    K/V): what a position mask must keep out, it holds."""
    cache = init_cache(cfg, rows, kv_int8=kv_int8)
    out = {}
    for name, a in cache.items():
        if a.dtype == jnp.int8:
            out[name] = jnp.asarray(rng.integers(-127, 128, a.shape), a.dtype)
        elif name.endswith("_scale"):
            out[name] = jnp.asarray(rng.uniform(0.005, 0.02, a.shape), a.dtype)
        else:
            out[name] = jnp.asarray(rng.normal(size=a.shape), a.dtype)
    return out


@pytest.mark.parametrize("case", sorted(FEATURES))
def test_base_features_take_the_scanned_body(case, devices):
    """Each feature only the base block has — a ring, ragged rows, beam
    ancestry, int8 K/V, int8 weights, an untyped MoE, a partitioned
    cache — is traced through the scanned body (a ``scan`` over the
    layers: the unrolled body is gone) and gives the logits and the
    cache of the body it was written in before (``helpers.
    base_decode_chunk``), on a cache of noise; ``lane=`` (one lane of a
    slab, in place) against that body on the lane cut out."""
    from distkeras_tpu.models import generate as gen
    from distkeras_tpu.models.quant import quantize_params
    from helpers import base_decode_chunk
    from jax.sharding import NamedSharding, PartitionSpec

    cfg, rows, t, pos, kv_int8, qw, lane, extra = FEATURES[case]
    rng = np.random.default_rng(sorted(FEATURES).index(case))
    params = toy_params(cfg)
    if qw:
        params = quantize_params(params)
    cache = _noise_cache(cfg, rows, kv_int8, rng)
    toks = jnp.asarray(rng.integers(0, 64, (1 if lane else rows, t)),
                       jnp.int32)
    pos0 = jnp.asarray(np.broadcast_to(pos, (1 if lane else rows,)),
                       jnp.int32)
    uniform = np.ndim(pos) == 0
    kw = {"uniform_pos": uniform}
    if "pad_lens" in extra:
        kw["pad_lens"] = jnp.asarray(extra["pad_lens"], jnp.int32)
    if "beam" in extra:
        w = extra["beam"]
        kw["beam_anc"] = (jnp.asarray(rng.integers(
            0, w, (rows // w, w, cfg.max_len)), jnp.int32), w)
    if extra.get("partitioned"):
        mesh = jax.sharding.Mesh(np.asarray(devices[:2]), ("model",))
        cache = jax.device_put(cache, NamedSharding(
            mesh, PartitionSpec(None, None, None, "model")))
    if lane is not None:
        kw["lane"] = jnp.int32(lane)

    def one_body(params, cache, toks, pos0):
        return gen._decode_chunk(params, cache, toks, pos0, cfg, **kw)

    eqns = jax.make_jaxpr(one_body)(params, cache, toks, pos0).eqns
    assert any(e.primitive.name == "scan" for e in eqns)
    logits, got = jax.jit(one_body)(params, cache, toks, pos0)

    oracle_kw = {k: v for k, v in kw.items() if k != "lane"}
    if lane is None:
        want_logits, want = base_decode_chunk(params, cache, toks, pos0, cfg,
                                              **oracle_kw)
    else:
        cut = jax.tree.map(lambda a: a[:, lane:lane + 1], cache)
        want_logits, cut = base_decode_chunk(params, cut, toks, pos0, cfg,
                                             **oracle_kw)
        want = jax.tree.map(lambda a, c: a.at[:, lane:lane + 1].set(c),
                            cache, cut)
    np.testing.assert_allclose(logits, want_logits, atol=1e-4, rtol=1e-4)
    assert sorted(got) == sorted(want)
    for name in want:
        a, b = np.asarray(got[name]), np.asarray(want[name])
        if a.dtype == np.int8:      # a rounding step apart at most
            assert np.abs(a.astype(int) - b.astype(int)).max() <= 1, name
        else:
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5,
                                       err_msg=name)
