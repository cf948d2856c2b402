"""Transformer flagship: numerics, training, and every parallelism axis.

Sharded-vs-unsharded equality is the core contract: TP/EP/SP runs on
the 8-CPU mesh must reproduce the single-device forward bit-for-bit
(up to f32 reduction order).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
from distkeras_tpu.parallel.ring import make_ring_attention
from distkeras_tpu.parallel.sharding import ShardingPlan
from jax.sharding import NamedSharding, PartitionSpec as P
from helpers import jtfm, toy_params


CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=32)


def toks(rng, b=4, s=16, vocab=64):
    return rng.integers(0, vocab, (b, s)).astype(np.int32)


def test_forward_shape_and_determinism(rng):
    params = toy_params(CFG)
    t = toks(rng)
    out1, aux1 = jtfm.apply(params, t, CFG)
    out2, _ = jtfm.apply(params, t, CFG)
    assert out1.shape == (4, 16, 64)
    assert float(aux1) == 0.0  # dense model: no aux loss
    np.testing.assert_array_equal(out1, out2)


def test_train_step_learns_copy_task(rng):
    # Predict-previous-token: a transformer with causal attention can
    # solve this exactly; loss must fall fast.
    cfg = CFG
    params = toy_params(cfg)
    opt = optax.adam(1e-2)
    step = jax.jit(tfm.make_train_step(cfg, opt))
    carry = (params, opt.init(params))
    t = jnp.asarray(toks(rng, b=16, s=16))
    first = None
    for i in range(30):
        carry, loss = step(carry, t)
        first = first if first is not None else float(loss)
    assert float(loss) < first * 0.5, (first, float(loss))


def _sharded_apply(params, t, cfg, mesh, rules, attention_fn=None):
    plan = ShardingPlan(rules=rules)
    psh = plan.tree_shardings(mesh, params)
    params_sh = jax.device_put(params, psh)
    tsh = NamedSharding(mesh, P("data", None))
    fn = jax.jit(
        lambda p, t: jtfm.apply(p, t, cfg, attention_fn)[0],
        in_shardings=(psh, tsh))
    return fn(params_sh, jnp.asarray(t))


def test_tensor_parallel_matches_single(devices, rng):
    mesh = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    params = toy_params(CFG)
    t = toks(rng)
    ref, _ = jtfm.apply(params, jnp.asarray(t), CFG)
    out = _sharded_apply(params, t, CFG, mesh, tfm.tp_rules())
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_sequence_parallel_ring_matches_single(devices, rng):
    mesh = make_mesh(MeshSpec(data=2, seq=4), devices=devices)
    params = toy_params(CFG)
    t = toks(rng)
    ref, _ = jtfm.apply(params, jnp.asarray(t), CFG)
    ring = make_ring_attention(mesh, causal=True)
    out = _sharded_apply(params, t, CFG, mesh, [], attention_fn=ring)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_seq_len_over_max_len_raises(rng):
    params = toy_params(CFG)
    with pytest.raises(ValueError, match="max_len"):
        jtfm.apply(params, jnp.zeros((2, CFG.max_len + 4), jnp.int32), CFG)


MOE_CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_len=32,
                                num_experts=4, capacity_factor=4.0)


def test_moe_dispatch_matches_per_token_reference(rng):
    """Dense-dispatch einsum == a literal per-token expert loop (no drops
    at capacity_factor=4)."""
    params = toy_params(MOE_CFG, 1)
    lp = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    x = jnp.asarray(rng.normal(size=(2, 8, 32)).astype(np.float32))
    out, aux = tfm._moe_block(lp, x, MOE_CFG)

    flat = np.asarray(x.reshape(-1, 32), np.float32)
    router = flat @ np.asarray(lp["wg"])
    probs = np.exp(router - router.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ref = np.zeros_like(flat)
    for n in range(flat.shape[0]):
        e = int(probs[n].argmax())
        h = flat[n] @ np.asarray(lp["w1"][e])
        h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
        ref[n] = (h @ np.asarray(lp["w2"][e])) * probs[n].max()
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 32), ref,
                               atol=1e-4, rtol=1e-4)
    assert float(aux) > 0.0


def test_moe_capacity_drops_tokens(rng):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_len=32,
                                num_experts=4, capacity_factor=0.25)
    params = toy_params(cfg, 1)
    lp = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    x = jnp.asarray(rng.normal(size=(2, 8, 32)).astype(np.float32))
    out, _ = tfm._moe_block(lp, x, cfg)
    # capacity = 0.25 * 16 / 4 = 1 slot per expert -> at most 4 of 16
    # tokens routed; the rest must be exactly 0 (residual passthrough).
    nonzero = np.abs(np.asarray(out).reshape(16, -1)).sum(-1) > 0
    assert nonzero.sum() <= 4


def test_expert_parallel_matches_single(devices, rng):
    mesh = make_mesh(MeshSpec(data=2, expert=4), devices=devices)
    params = toy_params(MOE_CFG, 1)
    t = toks(rng)
    ref, _ = jtfm.apply(params, jnp.asarray(t), MOE_CFG)
    out = _sharded_apply(params, t, MOE_CFG, mesh, tfm.tp_rules())
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_moe_train_step_learns(rng):
    opt = optax.adam(1e-2)
    params = toy_params(MOE_CFG)
    step = jax.jit(tfm.make_train_step(MOE_CFG, opt))
    carry = (params, opt.init(params))
    t = jnp.asarray(toks(rng, b=16, s=16))
    losses = []
    for _ in range(30):
        carry, loss = step(carry, t)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[::10]


# --------------------------------------------------------------- top-2 MoE

MOE2_CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=1, d_ff=64, max_len=32,
                                 num_experts=4, moe_top_k=2,
                                 capacity_factor=4.0)


def test_moe_top2_dispatch_matches_per_token_reference(rng):
    """Top-2 capacity dispatch == a literal per-token two-expert loop
    with renormalized gates (no drops at capacity_factor=4)."""
    params = toy_params(MOE2_CFG, 1)
    lp = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    x = jnp.asarray(rng.normal(size=(2, 8, 32)).astype(np.float32))
    out, aux = tfm._moe_block(lp, x, MOE2_CFG)

    flat = np.asarray(x.reshape(-1, 32), np.float32)
    router = flat @ np.asarray(lp["wg"])
    probs = np.exp(router - router.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ref = np.zeros_like(flat)
    for n in range(flat.shape[0]):
        top2 = np.argsort(-probs[n])[:2]
        g = probs[n][top2] / probs[n][top2].sum()
        for gi, e in zip(g, top2):
            h = flat[n] @ np.asarray(lp["w1"][e])
            h = np.asarray(jax.nn.gelu(jnp.asarray(h)))
            ref[n] += gi * (h @ np.asarray(lp["w2"][e]))
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 32), ref,
                               atol=1e-4, rtol=1e-4)
    assert float(aux) > 0.0


def test_moe_top2_capacity_equals_dense_routing_when_nothing_drops(rng):
    """At generous capacity the capacity path and the decode-parity
    dense path compute the same function (the top-2 analogue of the
    cached-decode parity contract)."""
    params = toy_params(MOE2_CFG, 2)
    t = jnp.asarray(toks(rng))
    cap_logits, _ = jtfm.apply(params, t, MOE2_CFG)
    dense_logits, _ = jtfm.apply(params, t, MOE2_CFG,
                                moe_dense_routing=True)
    np.testing.assert_allclose(np.asarray(cap_logits),
                               np.asarray(dense_logits),
                               atol=2e-4, rtol=2e-4)


def test_moe_top2_second_choices_yield_capacity(rng):
    """First choices claim slots before ANY second choice: with one
    slot per expert, every surviving assignment must be a first choice
    wherever first-choice demand covers the expert."""
    import dataclasses

    cfg = dataclasses.replace(MOE2_CFG, capacity_factor=0.125)
    # cap = int(0.125 * 2 * 16 / 4) = 1 slot per expert.
    params = toy_params(cfg, 1)
    lp = jax.tree.map(lambda a: a[0], params["layers"])["moe"]
    x = jnp.asarray(rng.normal(size=(2, 8, 32)).astype(np.float32))
    out, _ = tfm._moe_block(lp, x, cfg)
    # <= 4 slots total; each carries one assignment, so at most 4 of
    # the 16 tokens produce nonzero output.
    nonzero = np.abs(np.asarray(out).reshape(16, -1)).sum(-1) > 0
    assert nonzero.sum() <= 4

    flat = np.asarray(x.reshape(-1, 32), np.float32)
    router = flat @ np.asarray(lp["wg"])
    probs = np.exp(router - router.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    first = probs.argmax(-1)
    # The expert of the FIRST token whose first choice is expert e must
    # have landed (its slot cannot be stolen by any second choice).
    for e in set(first.tolist()):
        n0 = int(np.nonzero(first == e)[0][0])
        assert nonzero[n0], (e, n0)


def test_moe_top2_expert_parallel_matches_single(devices, rng):
    mesh = make_mesh(MeshSpec(data=2, expert=4), devices=devices)
    params = toy_params(MOE2_CFG, 1)
    t = toks(rng)
    ref, _ = jtfm.apply(params, jnp.asarray(t), MOE2_CFG)
    out = _sharded_apply(params, t, MOE2_CFG, mesh, tfm.tp_rules())
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_moe_top_k_range_validated():
    import dataclasses

    import pytest

    for bad in (0, 5):
        cfg = dataclasses.replace(MOE_CFG, moe_top_k=bad)
        with pytest.raises(ValueError, match="moe_top_k"):
            toy_params(cfg)


ROPE_CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, max_len=32, rope=True)


def test_rope_params_have_no_pos_table():
    params = toy_params(ROPE_CFG)
    assert "pos_emb" not in params
    with pytest.raises(ValueError, match="even head_dim"):
        tfm.init_params(jax.random.key(0), tfm.TransformerConfig(
            vocab_size=64, d_model=30, n_heads=2, n_layers=1, d_ff=64,
            max_len=32, rope=True))


def test_rope_forward_and_learning(rng):
    params = toy_params(ROPE_CFG)
    t = toks(rng)
    out, _ = jtfm.apply(params, jnp.asarray(t), ROPE_CFG)
    assert out.shape == (4, 16, 64) and np.isfinite(np.asarray(out)).all()

    opt = optax.adam(1e-2)
    step = jax.jit(tfm.make_train_step(ROPE_CFG, opt))
    carry = (params, opt.init(params))
    data = jnp.asarray(toks(rng, b=16, s=16))
    first = None
    for _ in range(30):
        carry, loss = step(carry, data)
        first = first if first is not None else float(loss)
    assert float(loss) < first * 0.5


def test_rope_relative_position_invariance(rng):
    """With RoPE (no absolute table), causal attention over a prefix
    placed at different absolute offsets gives identical logits for the
    same relative context — the property a learned pos_emb cannot have.

    Construct: logits at the last position of sequence [a, b, c]
    must equal logits at the last position of [x, a, b, c] restricted
    to attending only {a, b, c}... which plain causal attention does
    not do; instead verify the cheap exact form: rotating *all*
    positions by a constant offset leaves attention scores unchanged.
    """
    params = toy_params(ROPE_CFG)
    q = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 8, 2, 16)), jnp.float32)
    base = tfm.rope_angles(jnp.arange(8), 16, 10000.0)[None, :, None, :]
    off = tfm.rope_angles(jnp.arange(8) + 13, 16, 10000.0)[None, :, None, :]

    def scores(ang):
        qr, kr = tfm.rope_rotate(q, ang), tfm.rope_rotate(k, ang)
        return jnp.einsum("bshk,bthk->bsht", qr, kr)

    np.testing.assert_allclose(scores(base), scores(off),
                               atol=1e-4, rtol=1e-4)


def test_rope_ring_matches_single(devices, rng):
    """SP: ring attention with global-position rotary == single-device."""
    mesh = make_mesh(MeshSpec(data=2, seq=4), devices=devices)
    params = toy_params(ROPE_CFG)
    t = toks(rng)
    ref, _ = jtfm.apply(params, jnp.asarray(t), ROPE_CFG)
    ring = make_ring_attention(mesh, causal=True)
    out = _sharded_apply(params, t, ROPE_CFG, mesh, [], attention_fn=ring)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_rope_pipelined_matches_single(devices, rng):
    """PP and PP x SP: stage-local rotary offsets must reproduce the
    un-pipelined forward exactly."""
    mesh = make_mesh(MeshSpec(data=2, pipeline=2, seq=2), devices=devices)
    params = toy_params(ROPE_CFG)
    t = jnp.asarray(toks(rng, b=4, s=16))
    ref, _ = jtfm.apply(params, t, ROPE_CFG)
    out, _ = jax.jit(lambda p, tk: tfm.apply_pipelined(
        p, tk, ROPE_CFG, mesh, microbatches=2, seq_axis="seq"))(params, t)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_rope_trains_past_max_len(rng):
    """No position table -> training length is unbounded by max_len
    (which only sizes the decode KV cache)."""
    params = toy_params(ROPE_CFG)
    long = jnp.asarray(toks(rng, b=2, s=ROPE_CFG.max_len * 2))
    out, _ = jtfm.apply(params, long, ROPE_CFG)
    assert out.shape == (2, ROPE_CFG.max_len * 2, 64)
    assert np.isfinite(np.asarray(out)).all()


GQA_CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_len=32,
                                n_kv_heads=2)


def test_gqa_shapes_and_learning(rng):
    params = toy_params(GQA_CFG)
    assert params["layers"]["attn"]["wk"].shape == (2, 32, 2, 8)
    assert params["layers"]["attn"]["wq"].shape == (2, 32, 4, 8)
    out, _ = jtfm.apply(params, jnp.asarray(toks(rng)), GQA_CFG)
    assert out.shape == (4, 16, 64) and np.isfinite(np.asarray(out)).all()

    opt = optax.adam(1e-2)
    step = jax.jit(tfm.make_train_step(GQA_CFG, opt))
    carry = (params, opt.init(params))
    data = jnp.asarray(toks(rng, b=16, s=16))
    first = None
    for _ in range(30):
        carry, loss = step(carry, data)
        first = first if first is not None else float(loss)
    assert float(loss) < first * 0.5


def test_gqa_equals_mha_when_kv_heads_full(rng):
    """n_kv_heads == n_heads must be bit-identical to the default."""
    full = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, max_len=32,
                                 n_kv_heads=2)
    p1 = toy_params(CFG)
    p2 = toy_params(full)
    t = jnp.asarray(toks(rng))
    np.testing.assert_array_equal(jtfm.apply(p1, t, CFG)[0],
                                  jtfm.apply(p2, t, full)[0])


def test_gqa_validation():
    with pytest.raises(ValueError, match="n_kv_heads"):
        tfm.init_params(jax.random.key(0), tfm.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
            max_len=32, n_kv_heads=3))


def test_gqa_ring_matches_single(devices, rng):
    mesh = make_mesh(MeshSpec(data=2, seq=4), devices=devices)
    params = toy_params(GQA_CFG)
    t = toks(rng)
    ref, _ = jtfm.apply(params, jnp.asarray(t), GQA_CFG)
    ring = make_ring_attention(mesh, causal=True)
    out = _sharded_apply(params, t, GQA_CFG, mesh, [], attention_fn=ring)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


DROP_CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, max_len=32,
                                 dropout=0.2)


def test_dropout_deterministic_per_key_and_off_without_rng(rng):
    params = toy_params(DROP_CFG)
    t = jnp.asarray(toks(rng))
    # No rng -> deterministic inference even with cfg.dropout > 0.
    a, _ = jtfm.apply(params, t, DROP_CFG)
    b, _ = jtfm.apply(params, t, DROP_CFG)
    np.testing.assert_array_equal(a, b)
    # Same key -> same masks; different key -> different activations.
    k1, k2 = jax.random.key(1), jax.random.key(2)
    d1, _ = jtfm.apply(params, t, DROP_CFG, dropout_rng=k1)
    d1b, _ = jtfm.apply(params, t, DROP_CFG, dropout_rng=k1)
    d2, _ = jtfm.apply(params, t, DROP_CFG, dropout_rng=k2)
    np.testing.assert_array_equal(d1, d1b)
    assert not np.array_equal(np.asarray(d1), np.asarray(d2))
    assert not np.array_equal(np.asarray(a), np.asarray(d1))


def test_dropout_training_learns(rng):
    params = toy_params(DROP_CFG)
    opt = optax.adam(1e-2)
    step = jax.jit(tfm.make_train_step(DROP_CFG, opt))
    carry = (params, opt.init(params))
    data = jnp.asarray(toks(rng, b=16, s=16))
    first = None
    for i in range(30):
        carry, loss = step(carry, data, jax.random.key(i))
        first = first if first is not None else float(loss)
    assert float(loss) < first * 0.6


def test_dropout_validation(rng):
    with pytest.raises(ValueError, match="dropout"):
        tfm.init_params(jax.random.key(0), tfm.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
            max_len=32, dropout=1.0))
    # A dropout config whose step is driven without an rng must refuse
    # rather than silently train unregularized.
    params = toy_params(DROP_CFG)
    opt = optax.adam(1e-2)
    step = tfm.make_train_step(DROP_CFG, opt)
    with pytest.raises(ValueError, match="dropout_rng"):
        step((params, opt.init(params)), jnp.asarray(toks(rng)))


# ---------------------------------------------------------------- chunked CE

def test_chunked_ce_loss_and_grads_match_full(rng):
    """ce_chunks is a pure optimization: loss AND gradients must equal
    the materialized-logits path (same math, reordered reduction)."""
    import dataclasses

    cfg_c = dataclasses.replace(CFG, ce_chunks=4)
    params = toy_params(CFG)
    t = jnp.asarray(toks(rng))
    l_full, g_full = jax.jit(jax.value_and_grad(tfm.lm_loss),
                              static_argnums=2)(params, t, CFG)
    l_chunk, g_chunk = jax.jit(jax.value_and_grad(tfm.lm_loss),
                              static_argnums=2)(params, t, cfg_c)
    np.testing.assert_allclose(float(l_chunk), float(l_full), rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, atol=1e-6, rtol=1e-5), g_full, g_chunk)


def test_chunked_ce_handles_nondivisible_token_count(rng):
    """B*(S-1) not divisible by ce_chunks: padding rows carry target -1
    and must contribute exactly zero."""
    import dataclasses

    cfg_c = dataclasses.replace(CFG, ce_chunks=7)  # 4*15=60 tokens, 7∤60
    params = toy_params(CFG)
    t = jnp.asarray(toks(rng))
    l_full = jtfm.lm_loss(params, t, CFG)
    l_chunk = jtfm.lm_loss(params, t, cfg_c)
    np.testing.assert_allclose(float(l_chunk), float(l_full), rtol=1e-6)


def test_chunked_ce_eval_nll_matches(rng):
    import dataclasses

    cfg_c = dataclasses.replace(CFG, ce_chunks=4)
    params = toy_params(CFG)
    t = jnp.asarray(toks(rng))
    np.testing.assert_allclose(
        float(jtfm.lm_nll(params, t, cfg_c)),
        float(jtfm.lm_nll(params, t, CFG)), rtol=1e-6)


def test_chunked_ce_under_tensor_parallel(devices, rng):
    """Chunked head under the Megatron plan: tok_emb is model-sharded,
    the per-chunk contraction psums over the mesh — loss must match the
    single-device full-logits value."""
    import dataclasses

    cfg_c = dataclasses.replace(CFG, ce_chunks=4)
    mesh = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    params = toy_params(CFG)
    t = jnp.asarray(toks(rng))
    ref = float(jtfm.lm_loss(params, t, CFG))
    plan = ShardingPlan(rules=tfm.tp_rules())
    psh = plan.tree_shardings(mesh, params)
    params_sh = jax.device_put(params, psh)
    tsh = NamedSharding(mesh, P("data", None))
    loss = jax.jit(lambda p, x: jtfm.lm_loss(p, x, cfg_c),
                   in_shardings=(psh, tsh))(params_sh, t)
    np.testing.assert_allclose(float(loss), ref, atol=2e-5, rtol=2e-5)


def test_chunked_ce_trains(rng):
    import dataclasses

    cfg_c = dataclasses.replace(CFG, ce_chunks=4)
    params = toy_params(cfg_c)
    opt = optax.adam(1e-2)
    step = jax.jit(tfm.make_train_step(cfg_c, opt))
    carry = (params, opt.init(params))
    t = jnp.asarray(toks(rng, b=16, s=16))
    first = None
    for _ in range(30):
        carry, loss = step(carry, t)
        first = first if first is not None else float(loss)
    assert float(loss) < first * 0.5, (first, float(loss))


def test_chunked_ce_pipelined_matches_unpipelined(devices, rng):
    """PP trunk + chunked head (hidden_fn route) == single-device full
    logits loss, dense config (MoE capacity differs per microbatch)."""
    import dataclasses

    cfg = dataclasses.replace(CFG, n_layers=2, ce_chunks=4)
    mesh = make_mesh(MeshSpec(data=2, pipeline=2), devices=devices[:4])
    params = toy_params(cfg)
    t = jnp.asarray(toks(rng, b=4, s=16))
    ref = float(jtfm.lm_loss(params, t, dataclasses.replace(cfg, ce_chunks=0)))
    hidden_fn = lambda p, x: tfm.apply_pipelined(
        p, x, cfg, mesh, microbatches=2, return_hidden=True)
    with mesh:
        loss = jax.jit(lambda p, x: jtfm.lm_loss(p, x, cfg,
                                                hidden_fn=hidden_fn))(params, t)
    np.testing.assert_allclose(float(loss), ref, atol=2e-5, rtol=2e-5)


def test_chunked_ce_pipelined_trains_via_lm_trainer(devices, rng):
    import dataclasses

    import distkeras_tpu as dk
    from distkeras_tpu.parallel.mesh import MeshSpec as MS, make_mesh as mm

    cfg = dataclasses.replace(CFG, n_layers=2, ce_chunks=4)
    mesh = mm(MS(data=2, pipeline=2, seq=2), devices=devices)
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=8, num_epoch=4,
                      mesh=mesh, microbatches=2)
    tokens = np.repeat(
        rng.integers(0, CFG.vocab_size, (64, 1)), 17, axis=1).astype(np.int32)
    tr.train(tokens)
    assert tr.history[-1] < tr.history[0] * 0.5, (
        tr.history[0], tr.history[-1])


def test_lm_loss_rejects_both_forward_hooks(rng):
    params = toy_params(CFG)
    t = jnp.asarray(toks(rng))
    dummy = lambda p, x: (None, None)
    with pytest.raises(ValueError, match="not both"):
        jtfm.lm_loss(params, t, CFG, apply_fn=dummy, hidden_fn=dummy)
    # Same guard on the eval entry point: silently preferring apply_fn
    # would materialize the logits the caller asked ce_chunks to avoid.
    with pytest.raises(ValueError, match="not both"):
        jtfm.lm_nll(params, t, CFG, apply_fn=dummy, hidden_fn=dummy)


# -------------------------------------------------------------------- z-loss

def test_z_loss_chunked_matches_full(rng):
    """z-loss on the chunked head == the materialized head, and both
    strictly exceed the unregularized loss."""
    import dataclasses

    z = dataclasses.replace(CFG, z_loss_coef=1e-3)
    zc = dataclasses.replace(CFG, z_loss_coef=1e-3, ce_chunks=4)
    params = toy_params(CFG)
    t = jnp.asarray(toks(rng))
    base = float(jtfm.lm_loss(params, t, CFG))
    l_full, g_full = jax.jit(jax.value_and_grad(tfm.lm_loss),
                              static_argnums=2)(params, t, z)
    l_chunk, g_chunk = jax.jit(jax.value_and_grad(tfm.lm_loss),
                              static_argnums=2)(params, t, zc)
    assert float(l_full) > base
    np.testing.assert_allclose(float(l_chunk), float(l_full), rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, atol=1e-6, rtol=1e-5), g_full, g_chunk)


def test_z_loss_excluded_from_eval_nll(rng):
    import dataclasses

    z = dataclasses.replace(CFG, z_loss_coef=1e-2)
    params = toy_params(CFG)
    t = jnp.asarray(toks(rng))
    np.testing.assert_allclose(float(jtfm.lm_nll(params, t, z)),
                               float(jtfm.lm_nll(params, t, CFG)),
                               rtol=1e-7)


def test_z_loss_trains_and_shrinks_normalizer(rng):
    """With z-loss the trained model's mean logsumexp^2 must come out
    smaller than without (the regularizer does its one job)."""
    import dataclasses

    def train(cfg):
        params = toy_params(cfg)
        opt = optax.adam(1e-2)
        step = jax.jit(tfm.make_train_step(cfg, opt))
        carry = (params, opt.init(params))
        t = jnp.asarray(toks(rng_local, b=16, s=16))
        for _ in range(40):
            carry, loss = step(carry, t)
        logits, _ = jtfm.apply(carry[0], t[:, :-1], cfg)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return float(loss), float(jnp.square(lse).mean())

    rng_local = np.random.default_rng(0)
    loss0, z0 = train(CFG)
    rng_local = np.random.default_rng(0)
    loss1, z1 = train(dataclasses.replace(CFG, z_loss_coef=1e-2))
    assert z1 < z0, (z0, z1)
    assert loss1 < 3.0  # still learns the copy task


# ---------------------------------------------------------- sliding window

def test_attention_window_matches_manual_mask(rng):
    """apply() with attention_window == materialized attention with the
    same banded mask (oracle via naive windowed attention)."""
    import dataclasses

    from distkeras_tpu.ops.attention import naive_attention

    w = 5
    cfg_w = dataclasses.replace(CFG, attention_window=w)
    params = toy_params(CFG)
    t = jnp.asarray(toks(rng))
    ref, _ = jtfm.apply(params, t, CFG,
                       attention_fn=lambda q, k, v: naive_attention(
                           q, k, v, causal=True, window=w))
    out, _ = jtfm.apply(params, t, cfg_w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    # window >= seq degenerates to full causal
    cfg_big = dataclasses.replace(CFG, attention_window=64)
    full, _ = jtfm.apply(params, t, CFG)
    big, _ = jtfm.apply(params, t, cfg_big)
    np.testing.assert_allclose(np.asarray(big), np.asarray(full),
                               atol=1e-5, rtol=1e-5)


def test_attention_window_trains(rng):
    import dataclasses

    cfg = dataclasses.replace(CFG, attention_window=4)
    params = toy_params(cfg)
    opt = optax.adam(1e-2)
    step = jax.jit(tfm.make_train_step(cfg, opt))
    carry = (params, opt.init(params))
    t = jnp.asarray(toks(rng, b=16, s=16))
    first = None
    for _ in range(30):
        carry, loss = step(carry, t)
        first = first if first is not None else float(loss)
    assert float(loss) < first * 0.5


def test_attention_window_ring_matches_single(rng, devices):
    """Windowed ring attention (global-position band per hop) == the
    single-device windowed forward, with the windowed cfg flowing
    through apply (the handles_window marker admits the ring fn)."""
    import dataclasses

    w = 5
    cfg = dataclasses.replace(CFG, attention_window=w)
    mesh = make_mesh(MeshSpec(data=2, seq=4), devices=devices)
    params = toy_params(CFG)
    t = toks(rng)
    ref, _ = jtfm.apply(params, jnp.asarray(t), cfg)
    ring = make_ring_attention(mesh, causal=True, window=w)
    assert ring.handles_window
    out = _sharded_apply(params, t, cfg, mesh, [], attention_fn=ring)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_attention_window_lm_trainer_ring(rng, devices):
    """LMTrainer on a dp x sp mesh with attention_window trains (the
    trainer builds the window-aware ring itself)."""
    import dataclasses

    import distkeras_tpu as dk
    from distkeras_tpu.parallel.mesh import MeshSpec as MS, make_mesh as mm

    cfg = dataclasses.replace(CFG, attention_window=4, max_len=17)
    mesh = mm(MS(data=2, seq=2), devices=devices[:4])
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=8, num_epoch=4,
                      mesh=mesh)
    tokens = np.repeat(
        rng.integers(0, CFG.vocab_size, (64, 1)), 17, axis=1
    ).astype(np.int32)
    tr.train(tokens)
    assert tr.history[-1] < tr.history[0] * 0.5


def test_attention_window_rejects_custom_attention_fn(rng):
    import dataclasses

    from distkeras_tpu.ops.attention import naive_attention

    cfg = dataclasses.replace(CFG, attention_window=4)
    params = toy_params(cfg)
    with pytest.raises(ValueError, match="attention_fn"):
        jtfm.apply(params, jnp.asarray(toks(rng)), cfg,
                  attention_fn=lambda q, k, v: naive_attention(
                      q, k, v, causal=True))


def test_attention_window_rejects_mismatched_ring(rng, devices):
    """A ring built with a DIFFERENT window than cfg must be refused —
    a mismatched band would silently diverge train from decode."""
    import dataclasses

    cfg = dataclasses.replace(CFG, attention_window=4)
    mesh = make_mesh(MeshSpec(data=2, seq=4), devices=devices)
    params = toy_params(CFG)
    ring8 = make_ring_attention(mesh, causal=True, window=8)
    with pytest.raises(ValueError, match="mismatch"):
        jtfm.apply(params, jnp.asarray(toks(rng)), cfg, attention_fn=ring8)
    # The unchecked direction: a windowed fn with a window-less cfg is
    # equally a silent train/decode divergence and must be refused.
    with pytest.raises(ValueError, match="mismatch"):
        jtfm.apply(params, jnp.asarray(toks(rng)), CFG, attention_fn=ring8)


def test_attention_window_composes_with_moe(rng):
    """Window + MoE: the band applies at attention level, routing is
    untouched — loss finite and training moves."""
    import dataclasses

    cfg = dataclasses.replace(MOE_CFG, attention_window=4)
    params = toy_params(cfg)
    opt = optax.adam(1e-2)
    step = jax.jit(tfm.make_train_step(cfg, opt))
    carry = (params, opt.init(params))
    t = jnp.asarray(toks(rng, b=8, s=16))
    first = None
    for _ in range(15):
        carry, loss = step(carry, t)
        first = first if first is not None else float(loss)
    assert np.isfinite(float(loss)) and float(loss) < first


def test_attention_window_pipelined_ring_matches_single(devices, rng):
    """PP x SP x window: the pipeline's per-stage ring body carries the
    band (global positions per hop) — must reproduce the un-pipelined
    windowed forward exactly."""
    import dataclasses

    cfg = dataclasses.replace(ROPE_CFG, attention_window=5)
    mesh = make_mesh(MeshSpec(data=2, pipeline=2, seq=2), devices=devices)
    params = toy_params(cfg)
    t = jnp.asarray(toks(rng, b=4, s=16))
    ref, _ = jtfm.apply(params, t, cfg)
    out, _ = jax.jit(lambda p, tk: tfm.apply_pipelined(
        p, tk, cfg, mesh, microbatches=2, seq_axis="seq"))(params, t)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)
