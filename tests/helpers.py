"""Shared test fixtures' builders, importable without conftest's
environment mutation (conftest appends XLA_FLAGS at import, which a
subprocess that configured its own device count must not re-run)."""

import contextlib
import functools

import numpy as np


def make_blobs(n=512, dim=16, classes=4, seed=0):
    """Linearly separable gaussian blobs — learnable in a few steps."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 4.0, (classes, dim))
    labels = rng.integers(0, classes, n)
    feats = centers[labels] + rng.normal(0, 0.5, (n, dim))
    return feats.astype(np.float32), labels.astype(np.int64)


def make_mlp(dim=16, classes=4, hidden=32, seed=0):
    import keras

    keras.utils.set_random_seed(seed)
    return keras.Sequential([
        keras.Input((dim,)),
        keras.layers.Dense(hidden, activation="relu"),
        keras.layers.Dense(classes),
    ])


@contextlib.contextmanager
def no_compile_cache():
    """The body compiles past the checkout's compile cache: for a
    test whose program the cache cannot serialize."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


# ------------------------------------------------------------ toy LMs
# The configurations the serving and LM tests mean, stated once: two
# files that build the same engine from the same weights lower the
# same programs, and the checkout's compile cache (conftest.py) then
# compiles each once.  A config that differs on purpose stays in its
# test and says why.  Imports are lazy for the subprocess importers
# (see the module docstring).

SERVE_KW = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                d_ff=64, max_len=32, rope=True)


def serve_cfg(**changes):
    """The 2-layer rope model of test_serving*/disagg/router/
    weight_push/obs_live; ``changes`` name a variant (rolling:
    ``max_len=12, attention_window=5``; ``spec_draft_cfg`` below)."""
    from distkeras_tpu.models import transformer as tfm

    return tfm.TransformerConfig(**{**SERVE_KW, **changes})


def spec_draft_cfg(**changes):
    """The 1-layer speculative draft of the model above."""
    return serve_cfg(**{"d_model": 16, "n_layers": 1, "d_ff": 32,
                        **changes})


# Arguments of the model's entry points that are Python values, not
# arrays: static under jit.
_STATIC = frozenset((
    "cfg", "draft_cfg", "max_new_tokens", "temperature", "top_k", "top_p",
    "min_p", "eos_token", "use_prefill", "exact_top_k", "kv_int8",
    "beam_width", "length_penalty", "beam_impl", "_force_physical",
    "n_draft", "moe_dense_routing", "uniform_pos", "last_logits", "batch",
    "dtype", "attention_fn", "apply_fn", "hidden_fn"))


@functools.cache
def jitted(fn):
    """``fn`` as one program a call shape instead of one a primitive
    (an eager oracle recompiles every primitive at every new shape and
    retraces its scans on every call).  An oracle's meaning does not
    change, only how many programs it is.  The trace is cached: not for
    a test that patches what ``fn`` calls between two calls of it.
    ``prompt_lengths`` and ``prompt_cache`` are read on the host, so
    such calls run ``fn`` itself."""
    import inspect

    import jax

    under_jit = jax.jit(fn, static_argnames=[
        a for a in inspect.signature(fn).parameters if a in _STATIC])

    def call(*args, **kw):
        host_read = any(kw.get(k) is not None
                        for k in ("prompt_lengths", "prompt_cache"))
        return (fn if host_read else under_jit)(*args, **kw)

    return call


class _JittedModule:
    """``jgen.prefill`` is ``jitted(generate.prefill)``."""

    def __init__(self, name):
        self._name = name

    def __getattr__(self, attr):
        import importlib

        return jitted(getattr(importlib.import_module(self._name), attr))


jgen = _JittedModule("distkeras_tpu.models.generate")
jtfm = _JittedModule("distkeras_tpu.models.transformer")


def toy_params(cfg, seed=0):
    """``init_params(key(seed), cfg)`` as ONE program a config (eagerly
    ~60 single-primitive programs); fresh buffers every call, so a
    donating train step cannot delete another test's weights."""
    import jax

    return jtfm.init_params(jax.random.key(seed), cfg)


def generate(*args, **kw):
    """``models.generate.generate``, the solo oracle of every engine
    parity test, under :func:`jitted`."""
    return jgen.generate(*args, **kw)
