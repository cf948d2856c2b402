"""Sharded decode: generate/beam_search under TP and FSDP param layouts.

The serve-a-model-bigger-than-one-chip scenario (the LM analogue of the
reference's sharded batch inference, reference: distkeras/predictors.py
ModelPredictor): the KV-cached decode loop runs under jit on a mesh
with parameters TP-sharded (Megatron layout over the ``model`` axis) or
FSDP-scattered (over ``data``), and must emit exactly the tokens the
single-device decode emits.  Cache and intermediate shardings are
propagated by GSPMD from the parameter/batch layout — no decode-specific
sharding code exists, which is the property under test.
"""

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.models.generate import beam_search, generate
from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
from distkeras_tpu.parallel.sharding import ShardingPlan
from jax.sharding import NamedSharding, PartitionSpec as P
from helpers import toy_params


CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=32)


def _prompt(rng, b=8, p=5):
    return jnp.asarray(rng.integers(1, CFG.vocab_size, (b, p)), jnp.int32)


def _tp_layout(devices, params):
    mesh = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    plan = ShardingPlan(rules=tfm.tp_rules())
    psh = plan.tree_shardings(mesh, params)
    return mesh, psh


def _fsdp_layout(devices, params):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    plan = ShardingPlan(rules=(), fsdp_axis="data")
    psh = plan.tree_shardings(mesh, params)
    # The layout must actually scatter something, or the test is vacuous.
    emb_spec = tuple(psh["tok_emb"].spec)
    assert "data" in emb_spec, emb_spec
    return mesh, psh


def _sharded_generate(params, prompt, mesh, psh, **kw):
    params_sh = jax.device_put(params, psh)
    prompt_sh = jax.device_put(prompt, NamedSharding(mesh, P("data", None)))
    fn = jax.jit(lambda pr, t: generate(pr, t, CFG, 10, **kw),
                 in_shardings=(psh, NamedSharding(mesh, P("data", None))))
    return np.asarray(fn(params_sh, prompt_sh))


def _sharded_beam(params, prompt, mesh, psh, **kw):
    params_sh = jax.device_put(params, psh)
    prompt_sh = jax.device_put(prompt, NamedSharding(mesh, P("data", None)))
    fn = jax.jit(lambda pr, t: beam_search(pr, t, CFG, 8, beam_width=4, **kw),
                 in_shardings=(psh, NamedSharding(mesh, P("data", None))))
    seqs, scores = fn(params_sh, prompt_sh)
    return np.asarray(seqs), np.asarray(scores)


def test_generate_greedy_tp_sharded_matches_single(devices, rng):
    params = toy_params(CFG)
    prompt = _prompt(rng)
    ref = np.asarray(generate(params, prompt, CFG, 10))
    mesh, psh = _tp_layout(devices, params)
    out = _sharded_generate(params, prompt, mesh, psh)
    np.testing.assert_array_equal(out, ref)


def test_generate_sampled_tp_sharded_matches_single(devices, rng):
    # Sampling draws through the position-keyed fold_in stream; the
    # sharded run must reproduce the same tokens (categorical over
    # near-identical logits with the identical key).
    params = toy_params(CFG)
    prompt = _prompt(rng)
    key = jax.random.key(7)
    kw = dict(temperature=0.8, key=key, top_k=20)
    ref = np.asarray(generate(params, prompt, CFG, 10, **kw))
    mesh, psh = _tp_layout(devices, params)
    out = _sharded_generate(params, prompt, mesh, psh, **kw)
    np.testing.assert_array_equal(out, ref)


def test_generate_greedy_fsdp_scattered_matches_single(devices, rng):
    params = toy_params(CFG, 1)
    prompt = _prompt(rng)
    ref = np.asarray(generate(params, prompt, CFG, 10))
    mesh, psh = _fsdp_layout(devices, params)
    out = _sharded_generate(params, prompt, mesh, psh)
    np.testing.assert_array_equal(out, ref)


def test_beam_search_tp_sharded_matches_single(devices, rng):
    params = toy_params(CFG, 2)
    prompt = _prompt(rng, b=4)
    ref_seqs, ref_scores = beam_search(params, prompt, CFG, 8, beam_width=4)
    mesh, psh = _tp_layout(devices, params)
    seqs, scores = _sharded_beam(params, prompt, mesh, psh)
    np.testing.assert_array_equal(seqs, np.asarray(ref_seqs))
    np.testing.assert_allclose(scores, np.asarray(ref_scores),
                               atol=1e-4, rtol=1e-4)


def test_beam_search_fsdp_scattered_matches_single(devices, rng):
    params = toy_params(CFG, 3)
    prompt = _prompt(rng, b=8)  # data=8 mesh: batch divisible by 8
    ref_seqs, ref_scores = beam_search(params, prompt, CFG, 8, beam_width=4,
                                       eos_token=3)
    mesh, psh = _fsdp_layout(devices, params)
    seqs, scores = _sharded_beam(params, prompt, mesh, psh, eos_token=3)
    np.testing.assert_array_equal(seqs, np.asarray(ref_seqs))
    np.testing.assert_allclose(scores, np.asarray(ref_scores),
                               atol=1e-4, rtol=1e-4)


def test_speculative_tp_sharded_matches_single(devices, rng):
    """Speculative decoding under a TP mesh: target and draft params
    both Megatron-sharded, tokens equal to the unsharded speculative
    run (which itself equals generate's greedy rollout)."""
    from distkeras_tpu.models.speculative import speculative_generate

    d_cfg = tfm.TransformerConfig(vocab_size=64, d_model=16, n_heads=2,
                                  n_layers=1, d_ff=32, max_len=32)
    params = toy_params(CFG, 4)
    draft = toy_params(d_cfg, 5)
    prompt = _prompt(rng, b=4, p=4)
    ref, _ = speculative_generate(params, draft, prompt, CFG, d_cfg, 9,
                                  n_draft=3)

    mesh, psh = _tp_layout(devices, params)
    dsh = ShardingPlan(rules=tfm.tp_rules()).tree_shardings(mesh, draft)
    tsh = NamedSharding(mesh, P("data", None))
    fn = jax.jit(
        lambda tp, dp, pr: speculative_generate(tp, dp, pr, CFG, d_cfg,
                                                9, n_draft=3)[0],
        in_shardings=(psh, dsh, tsh))
    out = fn(jax.device_put(params, psh), jax.device_put(draft, dsh),
             jax.device_put(prompt, tsh))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_prompt_cache_decode_under_tp(devices, rng):
    """Prefix-cache reuse composes with TP-sharded params: the prefix
    cache built by sharded prefill + the suffix chunked pass emit
    exactly the single-device concatenated-prompt tokens."""
    from distkeras_tpu.models.generate import prefill

    params = toy_params(CFG)
    prefix = _prompt(rng, b=8, p=4)
    tail = _prompt(rng, b=8, p=3)
    full = jnp.concatenate([prefix, tail], axis=1)
    ref = np.asarray(generate(params, full, CFG, 8))[:, 4:]

    mesh, psh = _tp_layout(devices, params)
    params_sh = jax.device_put(params, psh)
    dsh = NamedSharding(mesh, P("data", None))
    cache = jax.jit(
        lambda pr, t: prefill(pr, t, CFG, last_logits=False)[0],
        in_shardings=(psh, dsh))(params_sh, jax.device_put(prefix, dsh))
    out = jax.jit(
        lambda pr, t, c: generate(pr, t, CFG, 8, prompt_cache=(c, 4)),
        in_shardings=(psh, dsh, None))(
        params_sh, jax.device_put(tail, dsh), cache)
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_continuous_batcher_under_tp(devices, rng):
    """The serving engine runs with Megatron-TP-sharded params on the
    mesh — lane state sharding propagates via GSPMD — and every request
    matches its solo single-device generate run."""
    from distkeras_tpu.serving import ContinuousBatcher

    params = toy_params(CFG)
    prompts = [_prompt(rng, b=1, p=4)[0], _prompt(rng, b=1, p=7)[0]]
    refs = [np.asarray(generate(params, p[None], CFG, 6))[0]
            for p in prompts]

    mesh, psh = _tp_layout(devices, params)
    params_sh = jax.device_put(params, psh)
    eng = ContinuousBatcher(params_sh, CFG, lanes=2)
    lanes = [eng.submit(np.asarray(p), 6) for p in prompts]
    while eng.running():
        eng.step(2)
    for lane, ref in zip(lanes, refs):
        np.testing.assert_array_equal(eng.drain(lane), ref)


def test_beam_prompt_cache_under_tp(devices, rng):
    """Beam search over a reused prefix with TP-sharded params matches
    the single-device concatenated-prompt beam run."""
    from distkeras_tpu.models.generate import prefill

    params = toy_params(CFG)
    prefix = _prompt(rng, b=4, p=4)
    tail = _prompt(rng, b=4, p=3)
    full = jnp.concatenate([prefix, tail], axis=1)
    ref_s, _ = beam_search(params, full, CFG, 6, beam_width=2)
    ref_s = np.asarray(ref_s)[:, :, 4:]

    mesh, psh = _tp_layout(devices, params)
    params_sh = jax.device_put(params, psh)
    dsh = NamedSharding(mesh, P("data", None))
    cache = jax.jit(
        lambda pr, t: prefill(pr, t, CFG, last_logits=False)[0],
        in_shardings=(psh, dsh))(params_sh, jax.device_put(prefix, dsh))
    out, _ = jax.jit(
        lambda pr, t, c: beam_search(pr, t, CFG, 6, beam_width=2,
                                     prompt_cache=(c, 4)),
        in_shardings=(psh, dsh, None))(
        params_sh, jax.device_put(tail, dsh), cache)
    np.testing.assert_array_equal(np.asarray(out), ref_s)
