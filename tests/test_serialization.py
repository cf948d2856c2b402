import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import serialize_keras_model, deserialize_keras_model
from distkeras_tpu.utils.misc import to_dense_vector, uniform_weights
from helpers import toy_params


def test_round_trip(mlp):
    blob = serialize_keras_model(mlp)
    assert isinstance(blob["model"], str)
    m2 = deserialize_keras_model(blob)
    for a, b in zip(mlp.get_weights(), m2.get_weights()):
        np.testing.assert_array_equal(a, b)


def test_blob_is_picklable(mlp):
    import pickle

    blob = serialize_keras_model(mlp)
    m2 = deserialize_keras_model(pickle.loads(pickle.dumps(blob)))
    for a, b in zip(mlp.get_weights(), m2.get_weights()):
        np.testing.assert_array_equal(a, b)


def test_to_dense_vector():
    out = to_dense_vector(2, 4)
    np.testing.assert_array_equal(out, [0, 0, 1, 0])
    out = to_dense_vector([0, 3], 4)
    assert out.shape == (2, 4)
    assert out[1, 3] == 1.0


def test_uniform_weights(mlp):
    uniform_weights(mlp, bounds=(-0.1, 0.1), seed=0)
    for w in mlp.get_weights():
        assert w.min() >= -0.1 and w.max() <= 0.1


def test_save_load_lm_round_trip(tmp_path, rng):
    import distkeras_tpu as dk
    from distkeras_tpu.models import transformer as tfm
    from distkeras_tpu.models.generate import generate

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_len=24,
                                rope=True, n_kv_heads=1, remat=True,
                                remat_policy="dots", ce_chunks=2)
    params = toy_params(cfg)
    path = str(tmp_path / "lm.npz")
    dk.save_lm(path, params, cfg)
    loaded, cfg2 = dk.load_lm(path)
    assert cfg2 == cfg
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), params, loaded)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 5)), jnp.int32)
    # loaded leaves are host numpy by contract: hand them to a jitted
    # generate (jit places arguments), as the load_lm docstring says.
    gen = jax.jit(lambda p, pr: generate(p, pr, cfg2, 6))
    np.testing.assert_array_equal(
        np.asarray(gen(loaded, prompt)),
        np.asarray(generate(params, prompt, cfg, 6)))


def test_save_lm_rejects_quantized(tmp_path):
    import pytest

    import distkeras_tpu as dk
    from distkeras_tpu.models import transformer as tfm
    from distkeras_tpu.models.quant import quantize_params

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_len=16)
    qp = quantize_params(toy_params(cfg))
    with pytest.raises(ValueError, match="full-precision"):
        dk.save_lm(str(tmp_path / "q.npz"), qp, cfg)


def test_load_lm_decodes_eagerly_without_jit(tmp_path, rng):
    """load_lm's host-numpy tree must decode WITHOUT an explicit outer
    jit: generate's scan closes over the params, and a raw numpy leaf
    cannot be fancy-indexed by traced tokens (regression — the decode
    entries coerce the tree with _device_tree)."""
    import distkeras_tpu as dk
    from distkeras_tpu.models import transformer as tfm
    from distkeras_tpu.models.generate import beam_search, generate

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_len=24)
    params = toy_params(cfg, 1)
    path = str(tmp_path / "lm.npz")
    dk.save_lm(path, params, cfg)
    loaded, cfg2 = dk.load_lm(path)
    prompt = jnp.asarray(rng.integers(0, 64, (2, 4)), jnp.int32)
    want = np.asarray(generate(params, prompt, cfg, 5))
    np.testing.assert_array_equal(
        np.asarray(generate(loaded, prompt, cfg2, 5)), want)
    seqs, _ = beam_search(loaded, prompt, cfg2, 4, beam_width=2)
    assert np.asarray(seqs).shape == (2, 2, 8)
