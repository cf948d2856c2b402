"""Shared example plumbing: device setup + synthetic datasets.

The reference's examples are Jupyter notebooks against a Spark
`local[N]` master (reference: examples/workflow.ipynb, mnist notebook);
these are scripts against either the real TPU (default) or an N-device
CPU mesh — set ``DKT_EXAMPLE_DEVICES=8`` to force the CPU mesh, the
moral equivalent of `local[8]`.

Datasets are synthetic (this environment has no network): shaped and
sized like the originals, separable enough that every trainer reaches
high accuracy in seconds.
"""

import os
import sys

import numpy as np

# Examples run from a checkout without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup_devices():
    """Place the compile cache and honor DKT_EXAMPLE_DEVICES before jax
    initializes; return devices."""
    from distkeras_tpu.utils.misc import configure_compile_cache

    configure_compile_cache()
    n = os.environ.get("DKT_EXAMPLE_DEVICES")
    if n:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_count={n}")
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    return jax.devices()


def synthetic_mnist(n=8192, seed=0):
    """784-dim 10-class data shaped like flattened MNIST."""
    rng = np.random.default_rng(seed)
    protos = rng.normal(0, 1.0, (10, 784))
    y = rng.integers(0, 10, n)
    x = (protos[y] + rng.normal(0, 2.0, (n, 784))).astype(np.float32)
    return x, y.astype(np.int64)


def synthetic_higgs(n=16384, dim=28, seed=0):
    """Tabular binary task shaped like the ATLAS Higgs features, with
    feature scales spread out so MinMaxTransformer matters."""
    rng = np.random.default_rng(seed)
    w = rng.normal(0, 1, (dim,))
    scales = np.exp(rng.normal(0, 1, (dim,)))
    x_raw = rng.normal(0, 1, (n, dim))
    y = (x_raw @ w + 0.3 * rng.normal(0, 1, n) > 0).astype(np.int64)
    return (x_raw * scales).astype(np.float32), y
