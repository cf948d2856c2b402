"""Small array helpers mirroring reference distkeras/utils.py extras."""

from __future__ import annotations

import numpy as np


def to_dense_vector(label, num_classes: int) -> np.ndarray:
    """Integer label -> one-hot dense vector.

    Reference parity: distkeras/utils.py::to_dense_vector.  Vectorized:
    accepts a scalar or an array of labels.
    """
    labels = np.asarray(label, dtype=np.int64)
    return np.eye(num_classes, dtype=np.float32)[labels]


def uniform_weights(model, bounds=(-0.5, 0.5), seed: int | None = None):
    """Re-initialize every weight of ``model`` uniformly in ``bounds``.

    Reference parity: distkeras/utils.py::uniform_weights.
    """
    rng = np.random.default_rng(seed)
    low, high = bounds
    model.set_weights(
        [rng.uniform(low, high, size=w.shape).astype(w.dtype)
         for w in model.get_weights()])
    return model


def configure_compile_cache() -> str:
    """Give JAX's persistent compilation cache a fixed home; returns
    the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is touched here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``, derived from this file's location: a
    directory that moves between runs (tempfile, pid, time) never hits.
    The entry points call this first (chip_smoke.py, bench.py, the
    bench scripts, examples/_common.py); importing the package sets no
    cache.
    """
    import os

    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def nll_to_perplexity(mean_nll: float) -> float:
    """exp(mean NLL) with the overflow guard — the ONE definition of
    the perplexity formula (LMTrainer's eval hook and
    PerplexityEvaluator must stay numerically identical)."""
    import math

    return math.exp(mean_nll) if mean_nll < 700 else float("inf")
