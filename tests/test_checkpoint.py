"""Checkpoint/resume (SURVEY.md §5: capability the reference lacks).

Resume correctness is tested as *bit-for-bit determinism*: training N
epochs straight through must equal training 1 epoch, checkpointing, and
resuming for the remaining epochs from disk.
"""

import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.checkpoint import CheckpointManager

from conftest import make_blobs, make_mlp


def _weights(model):
    return [np.asarray(w) for w in model.get_weights()]


def test_manager_roundtrip(tmp_path):
    import jax.numpy as jnp

    state = {"a": jnp.arange(12.0).reshape(3, 4), "b": [jnp.zeros(5)],
             "step": jnp.asarray(7, jnp.int32)}
    with CheckpointManager(str(tmp_path / "ckpt")) as mngr:
        assert mngr.latest_step() is None
        mngr.save(state, step=3)
        mngr.wait_until_finished()
        assert mngr.latest_step() == 3
        template = {"a": jnp.zeros((3, 4)), "b": [jnp.ones(5)],
                    "step": jnp.asarray(0, jnp.int32)}
        out = mngr.restore(template)
    np.testing.assert_array_equal(out["a"], state["a"])
    np.testing.assert_array_equal(out["b"][0], state["b"][0])
    assert int(out["step"]) == 7


def test_manager_missing_raises(tmp_path):
    with CheckpointManager(str(tmp_path / "empty")) as mngr:
        with pytest.raises(FileNotFoundError):
            mngr.restore({"x": np.zeros(2)})


def test_manager_max_to_keep(tmp_path):
    import jax.numpy as jnp

    with CheckpointManager(str(tmp_path / "k"), max_to_keep=2) as mngr:
        for s in (1, 2, 3):
            mngr.save({"v": jnp.asarray(float(s))}, step=s, force=True)
        mngr.wait_until_finished()
        assert mngr.all_steps() == [2, 3]


@pytest.mark.parametrize("trainer_cls,kw", [
    (dk.SingleTrainer, {}),
    (dk.ADAG, {"communication_window": 2, "num_workers": 4}),
    (dk.AEASGD, {"communication_window": 2, "num_workers": 4}),
])
def test_resume_matches_straight_run(tmp_path, trainer_cls, kw):
    x, y = make_blobs(n=256)
    ds = dk.Dataset.from_arrays(x, y)
    common = dict(loss="sparse_categorical_crossentropy",
                  worker_optimizer="sgd", learning_rate=0.05, batch_size=16)

    straight = trainer_cls(make_mlp(), num_epoch=2, **common, **kw)
    ref = straight.train(ds)

    d = str(tmp_path / "ckpt")
    first = trainer_cls(make_mlp(), num_epoch=1, checkpoint_dir=d,
                        **common, **kw)
    first.train(ds)
    resumed = trainer_cls(make_mlp(), num_epoch=2, checkpoint_dir=d,
                          resume=True, **common, **kw)
    out = resumed.train(ds)

    for wr, wo in zip(_weights(ref), _weights(out)):
        np.testing.assert_allclose(wr, wo, rtol=1e-5, atol=1e-6)
    # The resumed run only executed epoch 2's rounds.
    assert len(resumed.history) == len(straight.history) - len(first.history)


def test_resume_past_end_returns_trained_model(tmp_path):
    x, y = make_blobs(n=128)
    ds = dk.Dataset.from_arrays(x, y)
    d = str(tmp_path / "ckpt")
    common = dict(loss="sparse_categorical_crossentropy", batch_size=16,
                  learning_rate=0.05)
    t1 = dk.SingleTrainer(make_mlp(), num_epoch=1, checkpoint_dir=d, **common)
    ref = t1.train(ds)
    t2 = dk.SingleTrainer(make_mlp(), num_epoch=1, checkpoint_dir=d,
                          resume=True, **common)
    out = t2.train(ds)  # nothing left to train; must not raise
    for wr, wo in zip(_weights(ref), _weights(out)):
        np.testing.assert_allclose(wr, wo, rtol=1e-6)


def test_final_round_collides_with_periodic(tmp_path):
    # checkpoint_every divides the round count: the final save must not
    # re-save the same step (orbax raises StepAlreadyExists otherwise).
    x, y = make_blobs(n=64)
    ds = dk.Dataset.from_arrays(x, y)
    t = dk.SingleTrainer(make_mlp(), loss="sparse_categorical_crossentropy",
                         batch_size=16, num_epoch=1,
                         checkpoint_dir=str(tmp_path / "c"), checkpoint_every=4)
    t.train(ds)  # 4 rounds; round 4 is both periodic and final


def test_resume_with_unseeded_shuffle_rejected(tmp_path):
    with pytest.raises(ValueError, match="seed"):
        dk.SingleTrainer(make_mlp(), checkpoint_dir=str(tmp_path / "c"),
                         resume=True, shuffle=True)


def test_resume_without_dir_rejected():
    with pytest.raises(ValueError, match="checkpoint_dir"):
        dk.SingleTrainer(make_mlp(), resume=True)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        dk.SingleTrainer(make_mlp(), checkpoint_every=5)


def test_retrain_into_populated_dir_fails_fast(tmp_path):
    x, y = make_blobs(n=64)
    ds = dk.Dataset.from_arrays(x, y)
    d = str(tmp_path / "c")
    common = dict(loss="sparse_categorical_crossentropy", batch_size=16)
    dk.SingleTrainer(make_mlp(), checkpoint_dir=d, **common).train(ds)
    with pytest.raises(ValueError, match="resume=True"):
        dk.SingleTrainer(make_mlp(), checkpoint_dir=d, **common).train(ds)


def test_periodic_checkpoints_written(tmp_path):
    x, y = make_blobs(n=256)
    ds = dk.Dataset.from_arrays(x, y)
    d = str(tmp_path / "ckpt")
    t = dk.SingleTrainer(make_mlp(), loss="sparse_categorical_crossentropy",
                         batch_size=16, num_epoch=1, checkpoint_dir=d,
                         checkpoint_every=5, max_checkpoints=100)
    t.train(ds)
    with CheckpointManager(d) as mngr:
        steps = mngr.all_steps()
    assert steps == [5, 10, 15, 16]  # every 5 rounds + final (16 rounds)


CRASH_CHILD = """
import os, sys
os.environ["KERAS_BACKEND"] = "jax"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
sys.path.insert(0, {tests!r})
from helpers import make_blobs, make_mlp
import distkeras_tpu as dk

x, y = make_blobs(n=128)
ds = dk.Dataset.from_arrays(x, y)
t = dk.SingleTrainer(make_mlp(), loss="sparse_categorical_crossentropy",
                     worker_optimizer="sgd", learning_rate=0.05,
                     batch_size=16, num_epoch=100,
                     checkpoint_dir={ckdir!r}, checkpoint_every=1,
                     max_checkpoints=3)
t.train(ds)
print("CHILD FINISHED")  # the parent kills us long before this
"""


def _committed_steps(ckdir):
    import os

    if not os.path.isdir(ckdir):
        return []
    return sorted(int(d) for d in os.listdir(ckdir) if d.isdigit())


def test_sigkill_midrun_then_resume_matches_straight(tmp_path):
    """The SURVEY §5 failure story: durability comes from
    checkpoint/restart.  A training process is SIGKILLed mid-run (no
    cleanup, like a preemption); resuming from its checkpoints must land
    exactly where an uninterrupted run does."""
    import os
    import signal
    import subprocess
    import sys
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tests = os.path.join(repo, "tests")
    ckdir = str(tmp_path / "ckpt")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    child = subprocess.Popen(
        [sys.executable, "-c",
         CRASH_CHILD.format(repo=repo, tests=tests, ckdir=ckdir)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if child.poll() is not None:
                out = child.stdout.read().decode(errors="replace")
                raise AssertionError(
                    f"child exited (rc={child.returncode}) before the kill "
                    f"— make the run longer.\n{out[-2000:]}")
            steps = _committed_steps(ckdir)
            if steps and steps[-1] >= 20:
                break
            time.sleep(0.05)
        else:
            raise AssertionError("no checkpoint reached step 20 in time")
        child.send_signal(signal.SIGKILL)  # no atexit, no orbax cleanup
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=30)
    assert child.returncode == -signal.SIGKILL

    killed_at = _committed_steps(ckdir)[-1]
    assert 0 < killed_at < 800, "child was not killed mid-run"

    x, y = make_blobs(n=128)
    ds = dk.Dataset.from_arrays(x, y)
    common = dict(loss="sparse_categorical_crossentropy",
                  worker_optimizer="sgd", learning_rate=0.05,
                  batch_size=16, num_epoch=100)
    ref = dk.SingleTrainer(make_mlp(), **common).train(ds)
    resumed = dk.SingleTrainer(make_mlp(), checkpoint_dir=ckdir, resume=True,
                               **common)
    out = resumed.train(ds)
    for wr, wo in zip(_weights(ref), _weights(out)):
        np.testing.assert_allclose(wr, wo, rtol=1e-5, atol=1e-6)
    # The resume really started from the crash point, not from scratch.
    assert len(resumed.history) <= 800 - killed_at
