"""LMTrainer: the transformer under the trainer-family contract, across
mesh configurations, plus streaming prediction."""

import jax
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
from helpers import toy_params


CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=32)


def tokens(rng, n=64, s=16):
    return rng.integers(0, 64, (n, s + 1)).astype(np.int32)


def _loss_falls(history):
    assert history[-1] < history[0] * 0.85, history[::max(1, len(history)//5)]


def test_lm_trainer_dp(devices, rng):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16, num_epoch=8,
                     mesh=mesh)
    params = t.train(dk.Dataset({"tokens": tokens(rng)}))
    assert t.training_time > 0 and len(t.history) == 32
    _loss_falls(t.history)
    assert params["tok_emb"].shape == (64, 32)


def test_lm_trainer_tp_sp(devices, rng):
    mesh = make_mesh(MeshSpec(data=2, model=2, seq=2), devices=devices)
    t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16, num_epoch=8,
                     mesh=mesh)
    t.train(tokens(rng))
    _loss_falls(t.history)


def test_lm_trainer_pp_ep(devices, rng):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_len=32,
                                num_experts=2, capacity_factor=2.0)
    mesh = make_mesh(MeshSpec(data=2, pipeline=2, expert=2), devices=devices)
    t = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16, num_epoch=8,
                     mesh=mesh)
    t.train(tokens(rng))
    _loss_falls(t.history)


def test_lm_trainer_pp_sp(devices, rng):
    """PP x SP composed: pipelined trunk with the nested ring inside."""
    mesh = make_mesh(MeshSpec(data=2, pipeline=2, seq=2), devices=devices)
    t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16, num_epoch=8,
                     mesh=mesh)
    t.train(tokens(rng))
    _loss_falls(t.history)


def test_lm_trainer_validates_batch(devices, rng):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    with pytest.raises(ValueError, match="batch_size"):
        dk.LMTrainer(CFG, batch_size=12, mesh=mesh).train(tokens(rng))


def test_lm_trainer_unknown_optimizer(devices):
    with pytest.raises(ValueError, match="unknown optimizer"):
        dk.LMTrainer(CFG, optimizer="lion")


def test_predict_stream(devices, rng):
    import keras

    keras.utils.set_random_seed(0)
    model = keras.Sequential([keras.Input((8,)),
                              keras.layers.Dense(4)])
    pred = dk.ModelPredictor(model, batch_size=16)
    stream = [rng.normal(size=(n, 8)).astype(np.float32) for n in (5, 16, 33)]
    outs = list(pred.predict_stream(iter(stream)))
    assert [len(o) for o in outs] == [5, 16, 33]
    # Matches the batch path.
    ref = pred.predict(dk.Dataset.from_arrays(stream[2]))["prediction"]
    np.testing.assert_allclose(outs[2], ref, atol=1e-6)


def test_lm_trainer_accepts_optax_optimizers(devices, rng):
    import optax

    mesh = make_mesh(MeshSpec(data=2), devices=devices[:2])
    # Prebuilt GradientTransformation.
    t = dk.LMTrainer(CFG, optimizer=optax.lion(1e-3), batch_size=8,
                     num_epoch=1, mesh=mesh)
    t.train(tokens(rng, n=16))
    # Factory callable gets learning_rate applied.
    t2 = dk.LMTrainer(CFG, optimizer=optax.lion, learning_rate=1e-3,
                      batch_size=8, num_epoch=1, mesh=mesh)
    t2.train(tokens(rng, n=16))


def test_lm_trainer_microbatches_requires_pipeline(devices):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    with pytest.raises(ValueError, match="pipeline"):
        dk.LMTrainer(CFG, mesh=mesh, microbatches=4)


def test_predict_stream_empty_poll(devices, rng):
    import keras

    keras.utils.set_random_seed(0)
    model = keras.Sequential([keras.Input((8,)), keras.layers.Dense(4)])
    pred = dk.ModelPredictor(model, batch_size=16)
    outs = list(pred.predict_stream([np.zeros((0, 8), np.float32),
                                     rng.normal(size=(3, 8)).astype(np.float32)]))
    assert outs[0].shape == (0, 4)
    assert outs[1].shape == (3, 4)


def test_single_trainer_loss_positional_not_shadowed(devices):
    from tests.conftest import make_mlp
    from distkeras_tpu import SingleTrainer

    t = SingleTrainer(make_mlp(), "sparse_categorical_crossentropy",
                      learning_rate=0.1, batch_size=16)
    assert t.steps_per_call == 1


def test_lm_trainer_rejects_mesh_missing_axes(devices):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(devices).reshape(8), ("batch",))
    with pytest.raises(ValueError, match="missing axes"):
        dk.LMTrainer(CFG, mesh=mesh)


def test_lm_trainer_rejects_indivisible_seq(devices, rng):
    mesh = make_mesh(MeshSpec(data=4, seq=2), devices=devices)
    t = dk.LMTrainer(CFG, batch_size=8, mesh=mesh)
    with pytest.raises(ValueError, match="seq axis"):
        t.train(tokens(rng, s=15))  # 15 positions, seq axis 2


def test_lm_trainer_shuffle_deterministic(devices, rng):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    toks = tokens(rng, n=64)
    runs = []
    for _ in range(2):
        t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16, num_epoch=2,
                         mesh=mesh, shuffle=True, seed=7)
        runs.append(t.train(toks.copy()))
    for a, b in zip(jax.tree.leaves(runs[0]), jax.tree.leaves(runs[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_lm_trainer_resume_matches_straight_run(tmp_path, devices, rng):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    toks = tokens(rng, n=64)
    common = dict(learning_rate=1e-2, batch_size=16, mesh=mesh,
                  shuffle=True, seed=3)

    straight = dk.LMTrainer(CFG, num_epoch=4, **common)
    ref = straight.train(dk.Dataset({"tokens": toks}))

    d = str(tmp_path / "ckpt")
    first = dk.LMTrainer(CFG, num_epoch=2, checkpoint_dir=d, **common)
    first.train(dk.Dataset({"tokens": toks}))
    resumed = dk.LMTrainer(CFG, num_epoch=4, checkpoint_dir=d, resume=True,
                           **common)
    out = resumed.train(dk.Dataset({"tokens": toks}))

    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    assert len(resumed.history) == len(straight.history) - len(first.history)


def test_lm_eval_perplexity(devices, rng):
    """Held-out NLL/perplexity every eval_every steps + at the end; the
    eval loss is pure NLL so exp(loss) is an honest perplexity."""
    import math

    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    # Learnable structure shared by train and eval: cyclic sequences.
    offs = rng.integers(0, 64, 96)
    data = ((offs[:, None] + np.arange(17)) % 64).astype(np.int32)
    t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16, num_epoch=6,
                     mesh=mesh, eval_every=4)
    t.train(data[:64], eval_tokens=data[64:])
    rounds = [r for r, _ in t.eval_history]
    # Final state always evaluated: as -1 unless the last step already
    # hit the eval_every cadence (24 steps / eval_every=4 does).
    assert rounds[0] == 4 and rounds[-1] in (-1, len(t.history))
    assert rounds.count(rounds[-1]) == 1  # no duplicate final eval
    first, last = t.eval_history[0][1], t.eval_history[-1][1]
    assert last["loss"] < first["loss"]
    assert abs(last["perplexity"] - math.exp(last["loss"])) < 1e-9
    # Vocab 64, random tokens: NLL can't beat ln(64) by much but must
    # be finite and positive.
    assert 0 < last["loss"] < 10


def test_lm_eval_moe_excludes_aux(devices, rng):
    """For MoE the eval loss must be below the training loss signal
    that includes the router aux term (same params, same data)."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_len=32,
                                num_experts=2, capacity_factor=2.0)
    mesh = make_mesh(MeshSpec(data=2, expert=2), devices=devices[:4])
    data = tokens(rng, n=48)
    t = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16, num_epoch=1,
                     mesh=mesh)
    params = t.train(data[:32], eval_tokens=data[:32])
    # eval on the same rows the last step trained on: nll < nll + aux
    import jax as _jax

    full = float(_jax.jit(lambda p, tk: tfm.lm_loss(p, tk, cfg))(
        params, data[:16].astype(np.int32)))
    nll = float(_jax.jit(lambda p, tk: tfm.lm_nll(p, tk, cfg))(
        params, data[:16].astype(np.int32)))
    assert nll < full  # aux > 0 strictly separates them
    assert t.eval_history and t.eval_history[-1][0] == -1


def test_lm_eval_validation(devices, rng):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    with pytest.raises(ValueError, match="eval_tokens"):
        dk.LMTrainer(CFG, batch_size=16, mesh=mesh,
                     eval_every=2).train(tokens(rng))
    with pytest.raises(ValueError, match="eval batch"):
        dk.LMTrainer(CFG, batch_size=16, mesh=mesh, eval_every=2).train(
            tokens(rng), eval_tokens=tokens(rng, n=8))


def test_lm_grad_accum_matches_large_batch(devices, rng):
    """With SGD, accumulating 2 microbatches == one 2x batch step."""
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    data = tokens(rng, n=64)

    def run(**kw):
        t = dk.LMTrainer(CFG, optimizer="sgd", learning_rate=1e-2,
                         num_epoch=4, mesh=mesh, **kw)
        t.train(data)
        return t.history

    big = run(batch_size=32)
    accum = run(batch_size=16, grad_accum=2)
    assert len(big) == len(accum)
    # Same updates; the logged loss differs only in reduction order
    # (mean of two microbatch means == full-batch mean for equal sizes).
    np.testing.assert_allclose(accum, big, rtol=2e-5)


def test_lm_grad_clip(devices, rng):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    data = tokens(rng, n=32)
    free = dk.LMTrainer(CFG, optimizer="sgd", learning_rate=1e-2,
                        batch_size=16, num_epoch=2, mesh=mesh)
    p_free = free.train(data)
    clipped = dk.LMTrainer(CFG, optimizer="sgd", learning_rate=1e-2,
                           batch_size=16, num_epoch=2, mesh=mesh,
                           grad_clip_norm=1e-6)
    p_clip = clipped.train(data)
    init = dk.LMTrainer(CFG, mesh=mesh).init_params()
    # A vanishing clip norm freezes training; no clip moves params.
    move_free = float(np.abs(np.asarray(p_free["tok_emb"])
                             - np.asarray(init["tok_emb"])).max())
    move_clip = float(np.abs(np.asarray(p_clip["tok_emb"])
                             - np.asarray(init["tok_emb"])).max())
    assert move_clip < 1e-6 < move_free


def test_lm_grad_knob_validation(devices):
    with pytest.raises(ValueError, match="grad_accum"):
        dk.LMTrainer(CFG, grad_accum=0)
    with pytest.raises(ValueError, match="grad_clip_norm"):
        dk.LMTrainer(CFG, grad_clip_norm=-1.0)


def test_lm_dropout_trains_and_is_reproducible(devices, rng):
    mesh = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_len=32,
                                dropout=0.1)
    data = tokens(rng, n=64)

    def run():
        t = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16,
                         num_epoch=4, mesh=mesh, seed=5)
        t.train(data)
        return t.history

    h1, h2 = run(), run()
    np.testing.assert_allclose(h1, h2, rtol=1e-6)  # same dropout stream
    assert h1[-1] < h1[0] * 0.85
    # And it differs from the no-dropout trajectory.
    plain = dk.LMTrainer(tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_len=32), learning_rate=1e-2, batch_size=16, num_epoch=4,
        mesh=mesh, seed=5)
    plain.train(data)
    assert not np.allclose(h1, plain.history, rtol=1e-4)


def test_lm_dropout_resume_matches_straight(tmp_path, devices, rng):
    """The dropout stream is keyed on the round, so resume replays it."""
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_len=32,
                                dropout=0.1)
    data = tokens(rng, n=64)
    common = dict(learning_rate=1e-2, batch_size=16, mesh=mesh, seed=3)
    straight = dk.LMTrainer(cfg, num_epoch=4, **common)
    ref = straight.train(data)
    d = str(tmp_path / "ck")
    dk.LMTrainer(cfg, num_epoch=2, checkpoint_dir=d, **common).train(data)
    resumed = dk.LMTrainer(cfg, num_epoch=4, checkpoint_dir=d, resume=True,
                           **common)
    out = resumed.train(data)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(out)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_lm_dropout_rejects_pipeline(devices):
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_len=32,
                                dropout=0.1)
    mesh = make_mesh(MeshSpec(data=4, pipeline=2), devices=devices)
    with pytest.raises(ValueError, match="dropout.*pipeline"):
        dk.LMTrainer(cfg, mesh=mesh)


def test_lm_weight_decay_masks_norm_scales(devices):
    t = dk.LMTrainer(CFG, optimizer="adamw", learning_rate=1e-2,
                     weight_decay=0.5)
    params = t.init_params()
    zero_g = jax.tree.map(lambda a: np.zeros_like(np.asarray(a)), params)
    upd, _ = t.optimizer.update(zero_g, t.optimizer.init(params), params)
    # Norm scales: no decay -> zero update under zero gradients.
    assert float(np.abs(np.asarray(upd["ln_f_scale"])).max()) == 0.0
    assert float(np.abs(np.asarray(upd["layers"]["ln1_scale"])).max()) == 0.0
    # Weights do decay.
    assert float(np.abs(np.asarray(upd["tok_emb"])).max()) > 0.0
    assert float(np.abs(
        np.asarray(upd["layers"]["attn"]["wq"])).max()) > 0.0
    with pytest.raises(ValueError, match="weight_decay"):
        dk.LMTrainer(CFG, optimizer="sgd", weight_decay=0.1)


def test_lm_profile_dir_writes_trace(tmp_path, devices, rng):
    import glob as _glob

    d = str(tmp_path / "prof")
    mesh = make_mesh(MeshSpec(data=2), devices=devices[:2])
    t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=8, num_epoch=2,
                     mesh=mesh, profile_dir=d, profile_steps=2)
    t.train(tokens(rng, n=32))
    traces = _glob.glob(d + "/**/*.trace.json.gz", recursive=True)
    assert traces, f"no trace written under {d}"
    with pytest.raises(ValueError, match="profile_steps"):
        dk.LMTrainer(CFG, profile_steps=0)


def test_ema_decay_matches_manual_shadow():
    """ema_decay: one optimizer step gives shadow == decay*init +
    (1-decay)*params_1 exactly; the EMA tree serves (finite NLL,
    differs from raw params); knob validation."""
    rows = np.random.default_rng(0).integers(
        0, CFG.vocab_size, (8, CFG.max_len)).astype(np.int32)
    decay = 0.7

    tr1 = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=8,
                       num_epoch=1, seed=3, ema_decay=decay)
    init = tr1.init_params()
    # Snapshot before train(): the jitted step donates its carry, which
    # invalidates the original device buffers.
    init_np = jax.tree.map(lambda a: np.asarray(a, np.float32), init)
    p1 = tr1.train(rows, params=init)
    ema = tr1.ema_params
    expect = jax.tree.map(lambda i, p: decay * i
                          + (1 - decay) * np.asarray(p, np.float32),
                          init_np, p1)
    for a, b in zip(jax.tree.leaves(ema), jax.tree.leaves(expect)):
        np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                   atol=1e-5, rtol=1e-4)

    tr = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=8,
                      num_epoch=2, seed=3, ema_decay=decay)
    params = tr.train(rows)
    nll_raw = float(tfm.lm_nll(params, rows, CFG))
    nll_ema = float(tfm.lm_nll(tr.ema_params, rows, CFG))
    assert np.isfinite(nll_ema) and nll_ema != nll_raw

    with pytest.raises(ValueError, match="ema_decay"):
        dk.LMTrainer(CFG, ema_decay=1.5)
    with pytest.raises(ValueError, match="ema_decay"):
        dk.LMTrainer(CFG).ema_params


def test_ema_resume_matches_straight_run(tmp_path, devices, rng):
    """The EMA shadow rides the optimizer state, so checkpoint/resume
    reproduces the straight run's EMA tree exactly — the design claim
    behind _with_ema."""
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    toks = tokens(rng, n=64)
    common = dict(learning_rate=1e-2, batch_size=16, mesh=mesh,
                  shuffle=True, seed=3, ema_decay=0.9)

    straight = dk.LMTrainer(CFG, num_epoch=4, **common)
    straight.train(dk.Dataset({"tokens": toks}))

    d = str(tmp_path / "ckpt")
    first = dk.LMTrainer(CFG, num_epoch=2, checkpoint_dir=d, **common)
    first.train(dk.Dataset({"tokens": toks}))
    resumed = dk.LMTrainer(CFG, num_epoch=4, checkpoint_dir=d,
                           resume=True, **common)
    resumed.train(dk.Dataset({"tokens": toks}))

    for a, b in zip(jax.tree.leaves(straight.ema_params),
                    jax.tree.leaves(resumed.ema_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_lora_trainer_rejects_ema(devices):
    base = toy_params(CFG)
    with pytest.raises(ValueError, match="ema_decay is not supported"):
        dk.LoRATrainer(CFG, base, lora_rank=2, ema_decay=0.9)


def test_lm_device_data_matches_streaming(devices, rng):
    """device_data=True reproduces the streaming run's losses exactly:
    the staged stream layout + on-device gather feed the unchanged
    train step the same rows in the same order (dp, TP+grad_accum,
    FSDP, and pipeline meshes)."""
    toks = tokens(rng, n=96)

    def run(spec, **kw):
        t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16,
                         num_epoch=2, mesh=make_mesh(spec, devices=devices),
                         **kw)
        t.train(toks)
        return t.history

    for spec, kw in [(MeshSpec(data=8), {}),
                     (MeshSpec(data=4, model=2), {"grad_accum": 2}),
                     (MeshSpec(data=4, model=2), {"fsdp": True}),
                     (MeshSpec(data=4, pipeline=2), {})]:
        np.testing.assert_allclose(run(spec, device_data=True, **kw),
                                   run(spec, **kw), rtol=1e-6,
                                   err_msg=f"{spec} {kw}")


def test_lm_device_data_packed_segments(devices, rng):
    """device_data gathers the segment rows with the same index block
    as the tokens, so packed training matches streaming exactly."""
    docs = [rng.integers(1, 64, (int(k),)).tolist()
            for k in rng.integers(5, 14, 64)]
    rows, segs = dk.pack_documents(docs, seq_len=16)
    n = (len(rows) // 16) * 16
    mesh = make_mesh(MeshSpec(data=8), devices=None)

    def run(**kw):
        t = dk.LMTrainer(tfm.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
            max_len=17), learning_rate=1e-2, batch_size=16, num_epoch=2,
            mesh=mesh, **kw)
        t.train(rows[:n], segments=segs[:n])
        return t.history

    np.testing.assert_allclose(run(device_data=True), run(), rtol=1e-6)


def test_device_data_staging_guard_raises_with_figure(rng, monkeypatch):
    """Round-6 fix: when the staged token stream cannot fit device
    memory, device_data=True fails fast with the MiB figure and the
    streaming fallback named — not a raw XLA allocation error deep in
    _global_batch.  CPU reports no budget, so the test injects one."""
    from distkeras_tpu.trainers import lm as lm_mod

    monkeypatch.setattr(lm_mod, "_device_bytes_limit", lambda: 256)
    t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16,
                     device_data=True)
    with pytest.raises(ValueError, match=r"MiB.*device_data=False"):
        t.train(tokens(rng))
    # A budget that fits stages normally (guard stays quiet).
    monkeypatch.setattr(lm_mod, "_device_bytes_limit", lambda: 1 << 30)
    t2 = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16,
                      device_data=True)
    t2.train(tokens(rng))
    assert len(t2.history) == 4
