"""Model zoo builders + driver entry points."""

import numpy as np
import pytest

from distkeras_tpu.models import zoo
from distkeras_tpu.models.adapter import ModelAdapter


def test_mnist_mlp_forward():
    m = zoo.mnist_mlp(seed=0)
    out = m(np.zeros((2, 784), np.float32))
    assert out.shape == (2, 10)


def test_cifar_cnn_forward():
    m = zoo.cifar_cnn(seed=0)
    out = m(np.zeros((2, 32, 32, 3), np.float32))
    assert out.shape == (2, 10)


def test_higgs_mlp_forward():
    m = zoo.higgs_mlp(seed=0)
    out = m(np.zeros((2, 28), np.float32))
    assert out.shape == (2, 2)


def test_imdb_lstm_forward():
    m = zoo.imdb_lstm(vocab_size=100, embed_dim=8, lstm_units=8, maxlen=16,
                      seed=0)
    out = m(np.zeros((2, 16), np.int32))
    assert out.shape == (2, 1)


def test_graft_entry_single(devices):
    import importlib.util, pathlib

    spec = importlib.util.spec_from_file_location(
        "graft_entry", pathlib.Path(__file__).parent.parent / "__graft_entry__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    import jax

    fn, args = mod.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (4, 128, 128)  # [B, S, vocab] transformer logits


def test_graft_entry_multichip(devices):
    """The 8-device dryrun on the virtual CPU mesh, asked for
    explicitly and in a FRESH subprocess.

    In-process, this is the suite's single heaviest XLA-CPU compile; a
    40-minute full-suite run once segfaulted inside backend_compile at
    ~86% with exactly this test on the stack while the test passes
    standalone — accumulated backend state, not the program, is the
    trigger (docs/xla_cpu_compile_crash.md).  A subprocess gives the
    compile a clean backend every time (the same isolation
    test_deploy.py uses for the multi-host runtime) and makes the full
    suite one-command green."""
    import os
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).parent.parent
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(repo) + os.pathsep + env.get("PYTHONPATH", "")
    child = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import importlib.util\n"
        f"spec = importlib.util.spec_from_file_location("
        f"'graft_entry', {str(repo / '__graft_entry__.py')!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        "mod.dryrun_multichip(8)\n"
        "print('DRYRUN OK', flush=True)\n")
    out = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "DRYRUN OK" in out.stdout


def test_graft_entry_multichip_raises_without_the_devices(devices):
    """More devices asked for than the process has: an error naming
    both counts — never a quiet move to another backend or a child
    process."""
    import importlib.util, pathlib

    spec = importlib.util.spec_from_file_location(
        "graft_entry", pathlib.Path(__file__).parent.parent / "__graft_entry__.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with pytest.raises(RuntimeError, match="needs 16 devices.*has 8"):
        mod.dryrun_multichip(16)
