"""Pipeline parallelism: schedule numerics + differentiability + the
pipelined transformer trunk vs the single-device forward."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
from distkeras_tpu.parallel.pipeline import make_pipeline
from helpers import toy_params


def test_pipeline_matches_sequential(devices, rng):
    """4 affine stages over the pipeline == their sequential composition."""
    mesh = make_mesh(MeshSpec(data=1, pipeline=4), devices=devices[:4])
    w = rng.normal(size=(4, 8, 8)).astype(np.float32) * 0.5
    b = rng.normal(size=(4, 8)).astype(np.float32)
    x = rng.normal(size=(16, 8)).astype(np.float32)

    def stage_fn(p, u):
        return jnp.tanh(u @ p["w"] + p["b"]), jnp.zeros((), jnp.float32)

    pipe = jax.jit(make_pipeline(stage_fn, mesh, microbatches=4))
    out, _ = pipe({"w": jnp.asarray(w), "b": jnp.asarray(b)}, jnp.asarray(x))

    ref = x
    for i in range(4):
        ref = np.tanh(ref @ w[i] + b[i])
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_pipeline_rejects_misstacked_params(devices, rng):
    """Leading axis != n_stages must fail loudly, not drop layers."""
    mesh = make_mesh(MeshSpec(data=1, pipeline=4), devices=devices[:4])
    w = jnp.asarray(rng.normal(size=(8, 8, 8)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    pipe = make_pipeline(lambda p, u: (u @ p, jnp.zeros((), jnp.float32)),
                         mesh, microbatches=4)
    with pytest.raises(ValueError, match="n_stages"):
        jax.jit(pipe)(w, x)


def test_pipeline_gradients(devices, rng):
    """grad through the pipeline == grad through sequential composition."""
    mesh = make_mesh(MeshSpec(data=1, pipeline=2), devices=devices[:2])
    w = jnp.asarray(rng.normal(size=(2, 4, 4)).astype(np.float32) * 0.5)
    x = jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))

    def stage_fn(p, u):
        return jnp.tanh(u @ p), jnp.zeros((), jnp.float32)

    pipe = make_pipeline(stage_fn, mesh, microbatches=4)
    g = jax.jit(jax.grad(lambda w: pipe(w, x)[0].sum()))(w)

    def seq(w):
        u = x
        for i in range(2):
            u = jnp.tanh(u @ w[i])
        return u.sum()

    g_ref = jax.grad(seq)(w)
    np.testing.assert_allclose(g, g_ref, atol=1e-5, rtol=1e-5)


def test_pipelined_transformer_matches_single(devices, rng):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=4, d_ff=64, max_len=32)
    mesh = make_mesh(MeshSpec(data=2, pipeline=4), devices=devices)
    params = toy_params(cfg)
    t = jnp.asarray(rng.integers(0, 64, (8, 16)).astype(np.int32))
    ref, _ = tfm.apply(params, t, cfg)
    out, _ = jax.jit(
        lambda p, t: tfm.apply_pipelined(p, t, cfg, mesh, microbatches=4)
    )(params, t)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_pipelined_transformer_trains(devices, rng):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_len=32)
    mesh = make_mesh(MeshSpec(data=2, pipeline=2), devices=devices[:4])
    params = toy_params(cfg)
    opt = optax.adam(1e-2)
    step = jax.jit(tfm.make_train_step(
        cfg, opt, apply_fn=lambda p, t: tfm.apply_pipelined(
            p, t, cfg, mesh, microbatches=2)))
    carry = (params, opt.init(params))
    t = jnp.asarray(rng.integers(0, 64, (8, 16)).astype(np.int32))
    losses = []
    for _ in range(20):
        carry, loss = step(carry, t)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses[::5]


def test_pipelined_moe_aux_flows_into_loss(devices, rng):
    """The router's load-balancing aux must survive pipelining: stage
    outputs carry (activation, aux) and lm_loss sees nll + aux."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_len=32,
                                num_experts=2, capacity_factor=2.0)
    mesh = make_mesh(MeshSpec(data=2, pipeline=2, expert=2), devices=devices)
    params = toy_params(cfg)
    t = jnp.asarray(rng.integers(0, 64, (8, 17)).astype(np.int32))

    apply_fn = lambda p, tk: tfm.apply_pipelined(p, tk, cfg, mesh,
                                                 microbatches=2)
    logits, aux = jax.jit(apply_fn)(params, t[:, :-1])
    _, ref_aux = tfm.apply(params, t[:, :-1], cfg)
    assert float(aux) > 0
    # Same scale as the un-pipelined forward (capacity is per-microbatch
    # under PP, so routing may drop slightly differently).
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=0.5)

    loss = jax.jit(lambda p, tk: tfm.lm_loss(p, tk, cfg, apply_fn=apply_fn))(
        params, t)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, t[:, 1:][..., None], axis=-1).mean()
    np.testing.assert_allclose(float(loss), float(nll) + float(aux),
                               rtol=1e-5)


def test_pipelined_ring_attention_matches_single(devices, rng):
    """PP x SP: the pipeline manual over {pipeline, seq} running the
    ring attention body per stage reproduces the plain single-device
    forward — and its gradient."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_len=32)
    mesh = make_mesh(MeshSpec(data=2, pipeline=2, seq=2), devices=devices)
    params = toy_params(cfg, 1)
    t = jnp.asarray(rng.integers(0, 64, (8, 17)).astype(np.int32))
    apply_fn = lambda p, tk: tfm.apply_pipelined(
        p, tk, cfg, mesh, microbatches=2, seq_axis="seq")
    ref, _ = tfm.apply(params, t[:, :-1], cfg)
    out, _ = jax.jit(apply_fn)(params, t[:, :-1])
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)

    g = jax.jit(jax.grad(
        lambda p: tfm.lm_loss(p, t, cfg, apply_fn=apply_fn)))(params)
    g_ref = jax.grad(lambda p: tfm.lm_loss(p, t, cfg))(params)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)


def test_pipelined_moe_with_seq_axis_aux_consistent(devices, rng):
    """dp x pp x sp x ep with MoE: per-seq-shard router aux must be
    reduced over seq (not silently claimed replicated) and the loss must
    differentiate."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_len=32,
                                num_experts=2, capacity_factor=4.0)
    mesh = make_mesh(MeshSpec(data=1, pipeline=2, seq=2, expert=2),
                     devices=devices)
    params = toy_params(cfg)
    t = jnp.asarray(rng.integers(0, 64, (8, 17)).astype(np.int32))
    apply_fn = lambda p, tk: tfm.apply_pipelined(
        p, tk, cfg, mesh, microbatches=2, seq_axis="seq")
    _, aux = jax.jit(apply_fn)(params, t[:, :-1])
    _, ref_aux = tfm.apply(params, t[:, :-1], cfg)
    assert float(aux) > 0
    # Routing/capacity is per seq shard under SP, so only same-scale.
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=0.5)
    g = jax.jit(jax.grad(
        lambda p: tfm.lm_loss(p, t, cfg, apply_fn=apply_fn)))(params)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))
