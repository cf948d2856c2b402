"""Mid-training evaluation: metrics=('accuracy',) + eval_every produce
an eval_history of (round, {"loss", "accuracy"}) across the trainer
family — observability the reference does not have (its only signal is
the worker loss history, reference: distkeras/workers.py)."""

import numpy as np
import pytest

import distkeras_tpu as dk
from helpers import make_blobs, make_mlp, toy_params


def _sets(blobs):
    feats, labels = blobs
    train = dk.Dataset({"features": feats[:384], "label": labels[:384]})
    evals = dk.Dataset({"features": feats[384:], "label": labels[384:]})
    return train, evals


def test_single_trainer_eval_history(blobs):
    train, evals = _sets(blobs)
    t = dk.SingleTrainer(make_mlp(), loss="sparse_categorical_crossentropy",
                         worker_optimizer="adam", learning_rate=1e-2,
                         batch_size=32, num_epoch=4,
                         metrics=("accuracy",), eval_every=6)
    t.train(train, eval_dataset=evals)
    rounds = [r for r, _ in t.eval_history]
    assert rounds[0] == 6 and rounds[-1] == -1  # periodic + final
    first, last = t.eval_history[0][1], t.eval_history[-1][1]
    assert set(first) == {"loss", "accuracy"}
    assert last["accuracy"] > 0.9 and last["accuracy"] > first["accuracy"] - 0.05
    assert last["loss"] < first["loss"]


def test_adag_eval_history(devices, blobs):
    train, evals = _sets(blobs)
    t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="adam", learning_rate=1e-2,
                batch_size=8, num_epoch=4, communication_window=2,
                metrics=("accuracy",), eval_every=2)
    t.train(train, eval_dataset=evals)
    assert len(t.eval_history) >= 2
    assert t.eval_history[-1][1]["accuracy"] > 0.9


def test_downpour_evaluates_center(devices, blobs):
    train, evals = _sets(blobs)
    t = dk.DOWNPOUR(make_mlp(), loss="sparse_categorical_crossentropy",
                    worker_optimizer="adam", learning_rate=1e-2,
                    batch_size=8, num_epoch=6, communication_window=2,
                    metrics=("accuracy",), eval_every=1)
    t.train(train, eval_dataset=evals)
    accs = [m["accuracy"] for _, m in t.eval_history]
    assert accs[-1] > 0.85


def test_eval_without_dataset_and_unknown_metric(blobs):
    train, evals = _sets(blobs)
    t = dk.SingleTrainer(make_mlp(), loss="sparse_categorical_crossentropy",
                         worker_optimizer="adam", eval_every=2)
    with pytest.raises(ValueError, match="eval_dataset"):
        t.train(train)
    # Unknown metrics fail at construction, before any training runs.
    with pytest.raises(ValueError, match="unknown metric"):
        dk.SingleTrainer(make_mlp(), worker_optimizer="adam",
                         metrics=("f1",))


def test_one_hot_labels_accuracy(blobs):
    feats, labels = blobs
    onehot = np.eye(4, dtype=np.float32)[labels]
    train = dk.Dataset({"features": feats[:384], "label": onehot[:384]})
    evals = dk.Dataset({"features": feats[384:], "label": onehot[384:]})
    t = dk.SingleTrainer(make_mlp(), loss="categorical_crossentropy",
                         worker_optimizer="adam", learning_rate=1e-2,
                         batch_size=32, num_epoch=4, metrics=("accuracy",))
    t.train(train, eval_dataset=evals)
    assert t.eval_history[-1][1]["accuracy"] > 0.9


def test_eval_batches_not_monolithic(blobs):
    """The hook feeds the eval set in training-batch-size chunks (a
    large eval split must never run as one monolithic forward)."""
    feats, labels = blobs
    t = dk.SingleTrainer(make_mlp(), loss="sparse_categorical_crossentropy",
                         worker_optimizer="adam", batch_size=32,
                         metrics=("accuracy",))
    seen = []
    state = t.adapter.init_state()
    t._eval_batch = (feats[:100], labels[:100])  # 3 full chunks + 4 rows
    t._eval_fn = (lambda tv, ntv, x, y:
                  (seen.append(len(x)) or
                   {"loss": np.float32(0.0), "accuracy": np.float32(1.0)}))
    t._eval_hook(state, rnd=None, final=True)
    assert seen == [32, 32, 32, 4]
    assert t.eval_history[-1][1]["accuracy"] == 1.0


def test_final_eval_without_eval_every(blobs):
    train, evals = _sets(blobs)
    t = dk.SingleTrainer(make_mlp(), loss="sparse_categorical_crossentropy",
                         worker_optimizer="adam", learning_rate=1e-2,
                         batch_size=32, num_epoch=2, metrics=("accuracy",))
    t.train(train, eval_dataset=evals)
    assert len(t.eval_history) == 1 and t.eval_history[0][0] == -1


def test_ensemble_rejects_eval(blobs):
    train, evals = _sets(blobs)
    with pytest.raises(ValueError, match="[Ee]nsemble"):
        dk.EnsembleTrainer(make_mlp(), num_models=2, eval_every=2)
    t = dk.EnsembleTrainer(make_mlp(), num_models=2,
                           loss="sparse_categorical_crossentropy",
                           worker_optimizer="sgd", batch_size=8)
    with pytest.raises(ValueError, match="[Ee]nsemble"):
        t.train(train, eval_dataset=evals)


def test_binary_accuracy(blobs):
    feats, labels = blobs
    import keras

    keras.utils.set_random_seed(0)
    model = keras.Sequential([keras.Input((16,)),
                              keras.layers.Dense(16, activation="relu"),
                              keras.layers.Dense(1)])
    binary = (labels % 2).astype(np.float32)
    train = dk.Dataset({"features": feats[:384], "label": binary[:384]})
    evals = dk.Dataset({"features": feats[384:], "label": binary[384:]})
    t = dk.SingleTrainer(model, loss="binary_crossentropy",
                         worker_optimizer="adam", learning_rate=1e-2,
                         batch_size=32, num_epoch=2, metrics=("accuracy",))
    t.train(train, eval_dataset=evals)
    assert 0.0 <= t.eval_history[-1][1]["accuracy"] <= 1.0


def test_perplexity_evaluator_matches_trainer_eval(rng):
    """Standalone PerplexityEvaluator == the eval_every machinery's
    final number (same chunks, same NLL)."""
    import distkeras_tpu as dk
    from distkeras_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=1, d_ff=64, max_len=17)
    tokens = np.repeat(rng.integers(0, 64, (64, 1)), 17,
                       axis=1).astype(np.int32)
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16, num_epoch=1,
                      eval_every=2)
    params = tr.train(tokens, eval_tokens=tokens[:32])
    ev = dk.PerplexityEvaluator(params, cfg, batch_size=16)
    ppl = ev.evaluate(tokens[:32])
    np.testing.assert_allclose(
        ppl, tr.eval_history[-1][1]["perplexity"], rtol=1e-6)
    # Dataset-column form.
    ppl2 = ev.evaluate(dk.Dataset({"tokens": tokens[:32]}))
    np.testing.assert_allclose(ppl2, ppl, rtol=1e-12)


def test_perplexity_evaluator_validation(rng):
    import pytest

    import distkeras_tpu as dk
    from distkeras_tpu.models import transformer as tfm


    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_len=17)
    params = toy_params(cfg)
    ev = dk.PerplexityEvaluator(params, cfg, batch_size=16)
    with pytest.raises(ValueError, match="one batch needs"):
        ev.evaluate(np.zeros((4, 17), np.int32))
    with pytest.raises(ValueError, match="seq"):
        ev.evaluate(np.zeros((32,), np.int32))
    with pytest.raises(ValueError, match="batch_size"):
        dk.PerplexityEvaluator(params, cfg, batch_size=0)
