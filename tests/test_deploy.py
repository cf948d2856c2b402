"""Job deployment spec (reference parity: distkeras/job_deployment.py)."""

import shlex
import subprocess
import sys

import numpy as np
import pytest

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.data.transformers import StandardScaleTransformer
from distkeras_tpu.deploy import Job


def test_command_lines_per_host():
    job = Job(script="train.py", num_hosts=4,
              coordinator="10.0.0.1:8476", env={"FOO": "bar", "SEED": 42},
              args=("--epochs", 3))
    cmds = job.command_lines()
    assert len(cmds) == 4
    for h, cmd in enumerate(cmds):
        assert f"DKT_HOST_ID={h}" in cmd
        assert "DKT_NUM_HOSTS=4" in cmd
        assert "DKT_COORDINATOR=10.0.0.1:8476" in cmd
        assert "FOO=bar" in cmd
        assert "SEED=42" in cmd  # non-str env values are coerced
        # Remote commands name a portable interpreter, not this
        # machine's sys.executable.
        assert "python3 train.py --epochs 3" in cmd
        assert sys.executable not in cmd or sys.executable == "python3"
        # Must be valid shell.
        shlex.split(cmd)


def test_env_for_range_checked():
    job = Job(script="t.py", num_hosts=2)
    with pytest.raises(ValueError):
        job.env_for(2)


def test_run_local_executes(tmp_path):
    script = tmp_path / "probe.py"
    script.write_text(
        "import os, sys\n"
        "assert os.environ['DKT_NUM_HOSTS'] == '1'\n"
        "assert os.environ['DKT_HOST_ID'] == '0'\n"
        "sys.exit(0)\n")
    Job(script=str(script)).run_local()


def test_run_local_rejects_multihost():
    with pytest.raises(ValueError):
        Job(script="t.py", num_hosts=2).run_local()


def test_run_local_propagates_nonzero_returncode(tmp_path):
    script = tmp_path / "fail.py"
    script.write_text("import sys\nsys.exit(3)\n")
    with pytest.raises(RuntimeError, match="returncode 3"):
        Job(script=str(script)).run_local()
    # check=False restores the inspect-the-proc escape hatch
    proc = Job(script=str(script)).run_local(check=False)
    assert proc.returncode == 3


def test_run_local_timeout_kills_child(tmp_path):
    script = tmp_path / "hang.py"
    script.write_text("import time\ntime.sleep(600)\n")
    with pytest.raises(TimeoutError, match="did not finish"):
        Job(script=str(script)).run_local(timeout=1.0)


def test_init_from_env_noop_single_host(monkeypatch):
    from distkeras_tpu import deploy

    monkeypatch.delenv("DKT_NUM_HOSTS", raising=False)
    deploy.init_from_env()  # must not raise / touch jax.distributed


def test_standard_scale_transformer():
    rng = np.random.default_rng(0)
    x = rng.normal(3.0, 5.0, (256, 4)).astype(np.float32) * [1, 10, 100, 1000]
    t = StandardScaleTransformer(input_col="features")
    out = t.transform(Dataset({"features": x}))["features"]
    np.testing.assert_allclose(out.mean(axis=0), 0.0, atol=1e-4)
    np.testing.assert_allclose(out.std(axis=0), 1.0, atol=1e-4)
    # Fit-once: a second dataset reuses the first dataset's statistics.
    x2 = x + 100.0
    out2 = t.transform(Dataset({"features": x2}))["features"]
    np.testing.assert_allclose(out2, out + 100.0 / np.maximum(x.std(0), 1e-12),
                               atol=1e-3)


# Shared bootstrapping for every multihost child template: CPU
# platform before any backend init, repo on sys.path, join the
# jax.distributed runtime from the Job env contract.
CHILD_PREAMBLE = """\
import os, sys
os.environ["KERAS_BACKEND"] = "jax"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
from distkeras_tpu.deploy import init_from_env
init_from_env()  # joins the multi-process runtime from the Job env vars
"""


MULTIHOST_CHILD = """{preamble}

import numpy as np
import distkeras_tpu as dk
from helpers import make_blobs, make_mlp

assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 8, jax.devices()
assert jax.local_device_count() == 4

x, y = make_blobs(n=256)
host = int(os.environ["DKT_HOST_ID"])
ds = dk.Dataset.from_arrays(x, y).shard(host, 2)
assert len(ds) == 128

t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
            worker_optimizer="sgd", learning_rate=0.05, batch_size=8,
            communication_window=2, num_workers=8, num_epoch=1)
trained = t.train(ds)
assert len(t.history) == 2, t.history
if host == 0:
    np.savez({out!r}, *[np.asarray(w) for w in trained.get_weights()],
             losses=np.asarray(t.history))
print("HOST", host, "OK", flush=True)
"""


def test_two_process_adag_matches_single_process(tmp_path, devices):
    """The multi-host runtime for real: two OS processes join via
    jax.distributed (deploy.Job env contract -> init_from_env), form one
    8-device global mesh, and train ADAG on Dataset.shard-ed data.  The
    strided shard makes every global microbatch the same row *set* as
    the single-process run, and mean-gradients are permutation
    invariant, so the trained weights must match."""
    out = str(tmp_path / "host0.npz")
    _spawn_hosts(MULTIHOST_CHILD, num_hosts=2, devs_per_host=4, out=out)

    # Single-process reference: same data, same global batch math.
    import distkeras_tpu as dk
    from helpers import make_blobs, make_mlp

    x, y = make_blobs(n=256)
    ds = dk.Dataset.from_arrays(x, y)
    t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="sgd", learning_rate=0.05, batch_size=8,
                communication_window=2, num_workers=8, num_epoch=1)
    ref = t.train(ds)

    got = np.load(out)
    ref_w = [np.asarray(w) for w in ref.get_weights()]
    got_w = [got[k] for k in got.files if k != "losses"]
    assert len(got_w) == len(ref_w)
    for a, b in zip(got_w, ref_w):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got["losses"], np.asarray(t.history),
                               rtol=1e-4)


MULTIHOST_ELASTIC_CHILD = """{preamble}

import numpy as np
import distkeras_tpu as dk
from helpers import make_blobs, make_mlp

assert jax.process_count() == 2
host = int(os.environ["DKT_HOST_ID"])

x, y = make_blobs(n=512)
# Exact replica assignment: single-process round r gives replica i the
# rows block[r, i]; host h owns replicas [h*4, h*4+4), so its stream is
# the same blocks restricted to its replica range, in round order.
n, w, B = 8, 2, 8
R = len(x) // (n * w * B)
xb = x[:R*n*w*B].reshape(R, n, w*B, -1)
yb = y[:R*n*w*B].reshape(R, n, w*B)
nl = n // 2
xh = xb[:, host*nl:(host+1)*nl].reshape(-1, x.shape[1])
yh = yb[:, host*nl:(host+1)*nl].reshape(-1)
ds = dk.Dataset.from_arrays(xh, yh)

t = dk.DOWNPOUR(make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="sgd", learning_rate=0.05, batch_size=B,
                communication_window=w, num_workers=n, num_epoch=1)
trained = t.train(ds)
assert len(t.history) == R, t.history
if host == 0:
    np.savez({out!r}, *[np.asarray(wt) for wt in trained.get_weights()],
             losses=np.asarray(t.history))
print("HOST", host, "OK", flush=True)
"""


def test_two_process_downpour_matches_single_process(tmp_path, devices):
    """The replica-stacked elastic family on the real multi-process
    runtime: per-host local replica slabs assembled into the global
    stacked state, sync collective spanning both hosts.  With the
    replica->host row assignment made explicit, the trained center must
    equal the single-process run's bitwise-ish (same math, same order).
    """
    out = str(tmp_path / "host0.npz")
    _spawn_hosts(MULTIHOST_ELASTIC_CHILD, num_hosts=2, devs_per_host=4,
                 out=out)

    import distkeras_tpu as dk
    from helpers import make_blobs, make_mlp

    x, y = make_blobs(n=512)
    t = dk.DOWNPOUR(make_mlp(), loss="sparse_categorical_crossentropy",
                    worker_optimizer="sgd", learning_rate=0.05, batch_size=8,
                    communication_window=2, num_workers=8, num_epoch=1)
    ref = t.train(dk.Dataset.from_arrays(x, y))

    got = np.load(out)
    ref_w = [np.asarray(w) for w in ref.get_weights()]
    got_w = [got[k] for k in got.files if k != "losses"]
    assert len(ref_w) == len(got_w)
    for a, b in zip(ref_w, got_w):
        np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-6)
    np.testing.assert_allclose(got["losses"], np.asarray(t.history),
                               rtol=1e-5)


MULTIHOST_LM_CHILD = """{preamble}

import numpy as np
import distkeras_tpu as dk
from distkeras_tpu.models.transformer import TransformerConfig

assert jax.process_count() == 2
host = int(os.environ["DKT_HOST_ID"])

rng = np.random.default_rng(0)
tokens = np.repeat(rng.integers(0, 64, (64, 1)), 17, axis=1).astype(np.int32)
cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=17)
tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16, num_epoch=1)
params = tr.train(tokens[host::2])  # strided per-host shard
assert len(tr.history) == 4, tr.history
if host == 0:
    flat = {{"/".join(map(str, p)): np.asarray(v)
            for p, v in jax.tree_util.tree_flatten_with_path(params)[0]}}
    np.savez({out!r}, losses=np.asarray(tr.history), **flat)
print("HOST", host, "OK", flush=True)
"""


def test_two_process_lm_trainer_matches_single_process(tmp_path, devices):
    """The flagship LMTrainer on the real multi-process runtime: each
    host feeds its strided row shard, the global batch is assembled
    from process-local slabs (make_array_from_process_local_data), and
    the optimizer state is built under jit with global shardings.  A
    step's global batch is the same row *set* as the single-process
    run's (strided shard + contiguous blocks), and mean-loss gradients
    are permutation invariant, so losses and trained params must match.
    """
    out = str(tmp_path / "host0.npz")
    _spawn_hosts(MULTIHOST_LM_CHILD, num_hosts=2, devs_per_host=4, out=out)

    # Single-process reference on the full dataset.
    import distkeras_tpu as dk
    from distkeras_tpu.models.transformer import TransformerConfig

    rng = np.random.default_rng(0)
    tokens = np.repeat(rng.integers(0, 64, (64, 1)), 17,
                       axis=1).astype(np.int32)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=17)
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16, num_epoch=1)
    params = tr.train(tokens)

    import jax as jx

    got = np.load(out)
    np.testing.assert_allclose(got["losses"], np.asarray(tr.history),
                               rtol=1e-4, atol=1e-5)
    ref = {"/".join(map(str, p)): np.asarray(v)
           for p, v in jx.tree_util.tree_flatten_with_path(params)[0]}
    for k, v in ref.items():
        np.testing.assert_allclose(got[k], v, rtol=1e-4, atol=1e-5,
                                   err_msg=k)


# ------------------------------------------------------------------ hard cases
# (round-3: model axis across the process boundary, orbax checkpoint
# save+resume under the multi-process runtime, >2 processes)

def _spawn_hosts(child_src, num_hosts, devs_per_host, timeout=300, **fmt):
    """Run ``child_src`` (a .format template) as ``num_hosts`` OS
    processes joined via a free-port jax.distributed coordinator;
    returns after all exit, raising with the failing hosts' output."""
    import os
    import socket
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tests = os.path.join(repo, "tests")
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    job = Job(script="<inline>", num_hosts=num_hosts,
              coordinator=f"localhost:{port}")
    procs = []
    for h in range(num_hosts):
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devs_per_host}")
        env.update(job.env_for(h))
        # Two-stage format: {preamble} expands to the shared bootstrap,
        # whose own {repo!r}/{tests!r} need their values in the same
        # call — so the preamble is pre-formatted here.
        script = child_src.format(
            repo=repo, tests=tests,
            preamble=CHILD_PREAMBLE.format(repo=repo, tests=tests), **fmt)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    fail = []
    for h, p in enumerate(procs):
        try:
            stdout, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        if p.returncode != 0:
            fail.append(f"host {h} rc={p.returncode}\n"
                        f"{stdout.decode(errors='replace')[-3000:]}")
    assert not fail, "\n---\n".join(fail)


MULTIHOST_TP_CHILD = """{preamble}

import numpy as np
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh, global_batch
from distkeras_tpu.parallel.sharding import ShardingPlan

assert jax.process_count() == 2
host = int(os.environ["DKT_HOST_ID"])

cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=8, n_layers=2,
                            d_ff=64, max_len=17)
host_params = tfm.init_params(jax.random.key(0), cfg)
# model=8 over 2 processes x 4 devices: every Megatron psum crosses the
# process boundary (the ICI/DCN split on a real pod).
mesh = make_mesh(MeshSpec(data=1, model=8))
plan = ShardingPlan(rules=tfm.tp_rules())
psh = plan.tree_shardings(mesh, host_params)
params = jax.tree.map(
    lambda a, sh: jax.make_array_from_callback(
        np.shape(a), sh, lambda idx, a=a: np.asarray(a)[idx]),
    host_params, psh)
opt = optax.adam(1e-2)
opt_state = opt.init(params)
step = jax.jit(tfm.make_train_step(cfg, opt))

rng = np.random.default_rng(0)
tokens = rng.integers(0, 64, (8, 17)).astype(np.int32)
tokens = global_batch(tokens, NamedSharding(mesh, P("data", None)))
losses = []
carry = (params, opt_state)
for _ in range(3):
    carry, loss = step(carry, tokens)
    losses.append(float(loss))
rep = jax.tree.map(
    lambda sh: NamedSharding(mesh, P()), psh)
full = jax.jit(lambda p: p, out_shardings=rep)(carry[0])
if host == 0:
    flat = {{"/".join(map(str, p)): np.asarray(v)
            for p, v in jax.tree_util.tree_flatten_with_path(full)[0]}}
    np.savez({out!r}, losses=np.asarray(losses), **flat)
print("HOST", host, "OK", flush=True)
"""


def test_two_process_model_axis_crosses_boundary(tmp_path, devices):
    """Megatron TP with the ``model`` axis spanning BOTH processes: the
    per-block psum pair runs over the process boundary (on a real pod,
    over DCN), not just the data-axis gradient mean.  Losses and the
    trained params must match the single-process run."""
    import jax as jx
    import optax

    from distkeras_tpu.models import transformer as tfm

    out = str(tmp_path / "host0.npz")
    _spawn_hosts(MULTIHOST_TP_CHILD, num_hosts=2, devs_per_host=4, out=out)

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=8,
                                n_layers=2, d_ff=64, max_len=17)
    params = tfm.init_params(jx.random.key(0), cfg)
    opt = optax.adam(1e-2)
    step = jx.jit(tfm.make_train_step(cfg, opt))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, (8, 17)).astype(np.int32)
    carry = (params, opt.init(params))
    losses = []
    for _ in range(3):
        carry, loss = step(carry, tokens)
        losses.append(float(loss))

    got = np.load(out)
    np.testing.assert_allclose(got["losses"], losses, rtol=2e-4, atol=1e-5)
    ref = {"/".join(map(str, p)): np.asarray(v)
           for p, v in jx.tree_util.tree_flatten_with_path(carry[0])[0]}
    for k, v in ref.items():
        np.testing.assert_allclose(got[k], v, rtol=2e-3, atol=2e-4,
                                   err_msg=k)


MULTIHOST_CKPT_CHILD = """{preamble}

import numpy as np
import distkeras_tpu as dk
from distkeras_tpu.models.transformer import TransformerConfig

assert jax.process_count() == 2
host = int(os.environ["DKT_HOST_ID"])

rng = np.random.default_rng(0)
tokens = rng.integers(0, 64, (64, 17)).astype(np.int32)
cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=17)
tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16,
                  num_epoch={num_epoch}, checkpoint_dir={ckdir!r},
                  checkpoint_every=2, resume={resume})
params = tr.train(tokens[host::2])
if host == 0:
    flat = {{"/".join(map(str, p)): np.asarray(v)
            for p, v in jax.tree_util.tree_flatten_with_path(params)[0]}}
    np.savez({out!r}, losses=np.asarray(tr.history), **flat)
print("HOST", host, "OK", flush=True)
"""


def test_two_process_checkpoint_save_and_resume(tmp_path, devices):
    """Orbax checkpointing under the real multi-process runtime: run A
    (2 processes) trains one epoch writing sharded checkpoints; run B
    (2 fresh processes) resumes from them for a second epoch.  The
    resumed params must equal an uninterrupted single-process 2-epoch
    run — checkpoint write AND restore both happen with every array
    global and every host holding only its shards."""
    import jax as jx

    import distkeras_tpu as dk
    from distkeras_tpu.models.transformer import TransformerConfig

    ckdir = str(tmp_path / "ckpt")
    out_a = str(tmp_path / "a.npz")
    out_b = str(tmp_path / "b.npz")
    _spawn_hosts(MULTIHOST_CKPT_CHILD, num_hosts=2, devs_per_host=4,
                 ckdir=ckdir, out=out_a, num_epoch=1, resume=False)
    steps = sorted(int(d) for d in __import__("os").listdir(ckdir)
                   if d.isdigit())
    assert steps == [2, 4], steps  # periodic at 2, final at 4
    _spawn_hosts(MULTIHOST_CKPT_CHILD, num_hosts=2, devs_per_host=4,
                 ckdir=ckdir, out=out_b, num_epoch=2, resume=True)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, (64, 17)).astype(np.int32)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=17)
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16, num_epoch=2)
    params = tr.train(tokens)

    got = np.load(out_b)
    # Run B only executed epoch 2's four rounds.
    assert len(got["losses"]) == 4, got["losses"]
    np.testing.assert_allclose(got["losses"], np.asarray(tr.history)[4:],
                               rtol=1e-4, atol=1e-5)
    ref = {"/".join(map(str, p)): np.asarray(v)
           for p, v in jx.tree_util.tree_flatten_with_path(params)[0]}
    for k, v in ref.items():
        # rtol 1e-3: 8 adam steps amplify multi- vs single-process
        # reduction-order noise slightly past 1e-4 on a few elements;
        # a broken restore is orders of magnitude off.
        np.testing.assert_allclose(got[k], v, rtol=1e-3, atol=1e-4,
                                   err_msg=k)


MULTIHOST_4P_CHILD = """{preamble}

import numpy as np
import distkeras_tpu as dk
from distkeras_tpu.models.transformer import TransformerConfig

assert jax.process_count() == 4, jax.process_count()
assert len(jax.devices()) == 8
assert jax.local_device_count() == 2
host = int(os.environ["DKT_HOST_ID"])

rng = np.random.default_rng(0)
tokens = rng.integers(0, 64, (64, 17)).astype(np.int32)
cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=17)
tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16, num_epoch=1)
tr.train(tokens[host::4])
assert len(tr.history) == 4, tr.history
assert all(np.isfinite(tr.history)), tr.history
if host == 0:
    np.savez({out!r}, losses=np.asarray(tr.history))
print("HOST", host, "OK", flush=True)
"""


def test_four_process_smoke(tmp_path, devices):
    """4 processes x 2 devices: the runtime scales past the 2-process
    pair — coordinator join, global mesh assembly, strided per-host data
    feeding, and the loss collective all run with process_count=4.  The
    losses must match the single-process run (same global row sets)."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.transformer import TransformerConfig

    out = str(tmp_path / "host0.npz")
    _spawn_hosts(MULTIHOST_4P_CHILD, num_hosts=4, devs_per_host=2, out=out)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, (64, 17)).astype(np.int32)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=17)
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16, num_epoch=1)
    tr.train(tokens)
    got = np.load(out)
    np.testing.assert_allclose(got["losses"], np.asarray(tr.history),
                               rtol=1e-4, atol=1e-5)


MULTIHOST_PACKED_CHILD = """{preamble}

import numpy as np
import distkeras_tpu as dk
from distkeras_tpu.models.transformer import TransformerConfig

assert jax.process_count() == 2
host = int(os.environ["DKT_HOST_ID"])

rng = np.random.default_rng(3)
docs = [rng.integers(1, 64, (int(n),)).tolist()
        for n in rng.integers(5, 28, 96)]
rows, segs = dk.pack_documents(docs, seq_len=16)
n = (len(rows) // 16) * 16
rows, segs = rows[:n], segs[:n]
cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                        d_ff=64, max_len=17, rope=True)
tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16, num_epoch=1)
params = tr.train(rows[host::2], segments=segs[host::2])
if host == 0:
    flat = {{"/".join(map(str, p)): np.asarray(v)
            for p, v in jax.tree_util.tree_flatten_with_path(params)[0]}}
    np.savez({out!r}, losses=np.asarray(tr.history), **flat)
print("HOST", host, "OK", flush=True)
"""


def test_two_process_packed_training_matches_single(tmp_path, devices):
    """Packed-sequence training on the real multi-process runtime: each
    host feeds its strided shard of rows AND segments; losses and the
    trained params must match the single-process run (same global row
    sets, permutation-invariant mean loss)."""
    import jax as jx

    import distkeras_tpu as dk
    from distkeras_tpu.models.transformer import TransformerConfig

    out = str(tmp_path / "host0.npz")
    _spawn_hosts(MULTIHOST_PACKED_CHILD, num_hosts=2, devs_per_host=4,
                 out=out)

    rng = np.random.default_rng(3)
    docs = [rng.integers(1, 64, (int(n),)).tolist()
            for n in rng.integers(5, 28, 96)]
    rows, segs = dk.pack_documents(docs, seq_len=16)
    n = (len(rows) // 16) * 16
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=17, rope=True)
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16, num_epoch=1)
    params = tr.train(rows[:n], segments=segs[:n])

    got = np.load(out)
    np.testing.assert_allclose(got["losses"], np.asarray(tr.history),
                               rtol=1e-4, atol=1e-5)
    ref = {"/".join(map(str, p)): np.asarray(v)
           for p, v in jx.tree_util.tree_flatten_with_path(params)[0]}
    for k, v in ref.items():
        np.testing.assert_allclose(got[k], v, rtol=1e-4, atol=1e-5,
                                   err_msg=k)


MULTIHOST_EVAL_CHILD = """{preamble}

import numpy as np
import distkeras_tpu as dk
from helpers import make_blobs, make_mlp

assert jax.process_count() == 2
host = int(os.environ["DKT_HOST_ID"])

x, y = make_blobs(n=256)
ex, ey = make_blobs(n=128, seed=7)
ds = dk.Dataset.from_arrays(x, y).shard(host, 2)
eval_ds = dk.Dataset.from_arrays(ex, ey).shard(host, 2)

t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
            worker_optimizer="sgd", learning_rate=0.05, batch_size=8,
            communication_window=2, num_workers=8, num_epoch=1,
            metrics=("accuracy",), eval_every=1)
t.train(ds, eval_dataset=eval_ds)
assert len(t.eval_history) == 3, t.eval_history  # rounds 1, 2, final

# The replica-stacked family's eval view slices ntv out of the global
# replica stack — an eager a[0] cannot read non-addressable shards, so
# this exercises the jitted replicated slice (code-review regression).
d = dk.DOWNPOUR(make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="sgd", learning_rate=0.05, batch_size=8,
                communication_window=2, num_workers=8, num_epoch=1,
                metrics=("accuracy",), eval_every=1)
d.train(ds, eval_dataset=eval_ds)
assert len(d.eval_history) == 3, d.eval_history  # rounds 1, 2, final
assert all(np.isfinite(m["loss"]) for _, m in d.eval_history)

# A ragged eval shard (not a multiple of the chunk size) must WARN
# about the dropped tail (advisor round-4) — and still run.
import warnings
rag = dk.Dataset.from_arrays(ex[:68], ey[:68]).shard(host, 2)
w = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
            worker_optimizer="sgd", learning_rate=0.05, batch_size=8,
            communication_window=2, num_workers=8, num_epoch=1,
            eval_every=1)
with warnings.catch_warnings(record=True) as caught:
    warnings.simplefilter("always")
    w.train(ds, eval_dataset=rag)
assert any("excluded from eval metrics" in str(c.message)
           for c in caught), [str(c.message) for c in caught]

np.savez({out!r} + f".h{{host}}.npz",
         rounds=np.asarray([r for r, _ in t.eval_history]),
         loss=np.asarray([m["loss"] for _, m in t.eval_history]),
         accuracy=np.asarray([m["accuracy"]
                              for _, m in t.eval_history]),
         d_loss=np.asarray([m["loss"] for _, m in d.eval_history]),
         d_acc=np.asarray([m["accuracy"] for _, m in d.eval_history]))
print("HOST", host, "OK", flush=True)
"""


def test_two_process_eval_dataset_matches_single(tmp_path, devices):
    """Mid-training evaluation on the real multi-process runtime
    (round-3 verdict: the eval_dataset ValueError is gone): each host
    stages its eval shard as globally-sharded chunks, the jitted eval
    fn reduces across hosts via the compiled collectives, and the
    recorded history must match the single-process run over the full
    eval set (same rows, permutation-invariant means)."""
    out = str(tmp_path / "evalhist")
    _spawn_hosts(MULTIHOST_EVAL_CHILD, num_hosts=2, devs_per_host=4,
                 out=out)

    import distkeras_tpu as dk
    from helpers import make_blobs, make_mlp

    x, y = make_blobs(n=256)
    ex, ey = make_blobs(n=128, seed=7)
    t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="sgd", learning_rate=0.05, batch_size=8,
                communication_window=2, num_workers=8, num_epoch=1,
                metrics=("accuracy",), eval_every=1)
    t.train(dk.Dataset.from_arrays(x, y),
            eval_dataset=dk.Dataset.from_arrays(ex, ey))

    got = np.load(out + ".h0.npz")
    np.testing.assert_array_equal(
        got["rounds"], [r for r, _ in t.eval_history])
    np.testing.assert_allclose(
        got["loss"], [m["loss"] for _, m in t.eval_history],
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        got["accuracy"], [m["accuracy"] for _, m in t.eval_history],
        rtol=1e-4, atol=1e-5)
    # Both hosts must record IDENTICAL histories (replicated eval
    # outputs) — for ADAG and for the replica-stacked DOWNPOUR.
    h1 = np.load(out + ".h1.npz")
    for k in ("rounds", "loss", "accuracy", "d_loss", "d_acc"):
        np.testing.assert_array_equal(got[k], h1[k], err_msg=k)


MULTIHOST_DEVICE_DATA_CHILD = """{preamble}

import numpy as np
import distkeras_tpu as dk
from helpers import make_blobs, make_mlp

assert jax.process_count() == 2
host = int(os.environ["DKT_HOST_ID"])

x, y = make_blobs(n=256)
ds = dk.Dataset.from_arrays(x, y).shard(host, 2)

t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
            worker_optimizer="sgd", learning_rate=0.05, batch_size=8,
            communication_window=2, num_workers=8, num_epoch=1,
            device_data=True)
trained = t.train(ds)
assert len(t.history) == 2, t.history
if host == 0:
    np.savez({out!r}, *[np.asarray(w) for w in trained.get_weights()],
             losses=np.asarray(t.history))
print("HOST", host, "OK", flush=True)
"""


def test_two_process_device_data_adag_matches_single(tmp_path, devices):
    """The device-resident data plane across hosts (round-3 verdict:
    device_data=True was single-process-only): each host stages its
    shard in replica-stream layout, gathers are replica-local under
    shard_map, and the trained weights must match the single-process
    streaming run (each global microbatch is the same row set; mean
    gradients are permutation invariant)."""
    out = str(tmp_path / "host0.npz")
    _spawn_hosts(MULTIHOST_DEVICE_DATA_CHILD, num_hosts=2,
                 devs_per_host=4, out=out)

    import distkeras_tpu as dk
    from helpers import make_blobs, make_mlp

    x, y = make_blobs(n=256)
    t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="sgd", learning_rate=0.05, batch_size=8,
                communication_window=2, num_workers=8, num_epoch=1)
    ref = t.train(dk.Dataset.from_arrays(x, y))

    got = np.load(out)
    ref_w = [np.asarray(w) for w in ref.get_weights()]
    got_w = [got[k] for k in got.files if k != "losses"]
    assert len(got_w) == len(ref_w)
    for a, b in zip(got_w, ref_w):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got["losses"], np.asarray(t.history),
                               rtol=1e-4)
