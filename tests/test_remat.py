"""Rematerialization: identical numerics, O(1)-block activation memory."""

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models import transformer as tfm
from helpers import jtfm, toy_params


def test_transformer_remat_matches_plain(rng):
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                d_ff=64, max_len=32)
    cfg = tfm.TransformerConfig(**base)
    cfg_r = tfm.TransformerConfig(**base, remat=True)
    params = toy_params(cfg)
    toks = jnp.asarray(rng.integers(0, 64, (4, 17)).astype(np.int32))

    l1, g1 = jax.jit(jax.value_and_grad(tfm.lm_loss),
                        static_argnums=2)(params, toks, cfg)
    l2, g2 = jax.jit(jax.value_and_grad(tfm.lm_loss),
                        static_argnums=2)(params, toks, cfg_r)
    np.testing.assert_allclose(l1, l2, atol=1e-6, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_transformer_pipelined_remat(devices, rng):
    """remat composes with the pipelined trunk."""
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh

    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                d_ff=64, max_len=32)
    cfg = tfm.TransformerConfig(**base)
    cfg_r = tfm.TransformerConfig(**base, remat=True)
    mesh = make_mesh(MeshSpec(data=2, pipeline=2), devices=devices[:4])
    params = toy_params(cfg)
    toks = jnp.asarray(rng.integers(0, 64, (4, 16)).astype(np.int32))
    ref, _ = jax.jit(lambda p, t: tfm.apply_pipelined(p, t, cfg, mesh, 2))(
        params, toks)
    out, _ = jax.jit(lambda p, t: tfm.apply_pipelined(p, t, cfg_r, mesh, 2))(
        params, toks)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_remat_policy_matches_plain_remat(rng):
    """Selective remat changes what the backward saves, never the math:
    loss and grads must match full remat and no remat exactly."""
    import dataclasses

    from distkeras_tpu.models import transformer as tfm

    base = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, max_len=32)
    params = toy_params(base)
    t = jnp.asarray(rng.integers(0, 64, (4, 16)), jnp.int32)
    ref_l, ref_g = jax.jit(jax.value_and_grad(tfm.lm_loss),
                        static_argnums=2)(params, t, base)
    for kw in ({"remat": True},
               {"remat": True, "remat_policy": "dots"},
               {"remat": True, "remat_policy": "dots_no_batch"}):
        cfg = dataclasses.replace(base, **kw)
        l, g = jax.jit(jax.value_and_grad(tfm.lm_loss),
                        static_argnums=2)(params, t, cfg)
        np.testing.assert_allclose(float(l), float(ref_l), rtol=1e-6,
                                   err_msg=str(kw))
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, atol=1e-5, rtol=1e-5), ref_g, g)


def test_remat_policy_validation(rng):
    import dataclasses

    import pytest

    from distkeras_tpu.models import transformer as tfm

    base = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=1, d_ff=64, max_len=16)
    with pytest.raises(ValueError, match="remat_policy"):
        tfm.init_params(jax.random.key(0),
                        dataclasses.replace(base, remat=True,
                                            remat_policy="bogus"))
    # A policy without remat=True would be silently inert; refuse it.
    with pytest.raises(ValueError, match="remat=False"):
        tfm.init_params(jax.random.key(0),
                        dataclasses.replace(base, remat_policy="dots"))


def test_remat_policy_inert_when_remat_disabled_post_init(rng):
    """dataclasses.replace(cfg, remat=False) on a trained config is the
    natural eval move; the leftover policy must be inert, not an error."""
    import dataclasses

    from distkeras_tpu.models import transformer as tfm

    train_cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                      n_layers=1, d_ff=64, max_len=16,
                                      remat=True, remat_policy="dots")
    params = toy_params(train_cfg)
    eval_cfg = dataclasses.replace(train_cfg, remat=False)
    t = jnp.asarray(rng.integers(0, 64, (2, 8)), jnp.int32)
    logits, _ = jtfm.apply(params, t, eval_cfg)  # must not raise
    assert logits.shape == (2, 8, 64)
