"""Gradient-exchange layer (parallel/exchange.py, docs/lowcomm.md):
Adasum merging, local-SGD periodic sync, and error-feedback
compression on the 8-CPU mesh.

Acceptance contract: every variant's final loss lands within the
DECLARED tolerance of the replicated-DP baseline (``TOL_LOSS`` — the
same bound ``bench_suite.py``'s convergence rows report against);
seeded runs are bit-for-bit deterministic; error-feedback residual
state round-trips both checkpoint backends; and the Supervisor's
kill/resume harness stays bit-for-bit under ``sync_every > 1``.  The
wire-bytes and collective-count claims are proved separately from the
compiled census in tests/test_budget_guards.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.parallel import collectives as cl
from distkeras_tpu.parallel import exchange as ex
from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
from distkeras_tpu.resilience import FaultPlan, Supervisor
from jax.sharding import NamedSharding, PartitionSpec as P

from helpers import make_blobs, make_mlp, toy_params

CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=32)

# The DECLARED convergence tolerance: a lossy exchange (int8/top-k
# quantization, adasum's adaptive weights, local-SGD's stale period)
# is allowed to land within this absolute final-loss distance of the
# replicated-DP baseline on these seeded toy problems.  bench_suite's
# lowcomm_* rows report against the same bound.
TOL_LOSS = 0.05


def lm_tokens(n=128, s=16):
    return np.random.default_rng(0).integers(0, 64, (n, s + 1)).astype(
        np.int32)


# --------------------------------------------------------- primitives


def test_adasum_pair_mean_for_agreeing_sum_for_orthogonal():
    a = jnp.asarray([1.0, 2.0, 3.0, 0.0])
    # Identical inputs: adasum == the value itself (what mean-reduce
    # of agreeing replicas gives) — the "replicas agree" fallback.
    np.testing.assert_allclose(ex.adasum_combine(jnp.stack([a, a])),
                               a, rtol=1e-6)
    # Orthogonal inputs: the plain sum.
    b = jnp.asarray([0.0, 0.0, 0.0, 5.0])
    np.testing.assert_allclose(ex.adasum_combine(jnp.stack([a, b])),
                               a + b, rtol=1e-6)
    # Zero gradients: plain sum (no NaN from the norm division).
    z = jnp.zeros_like(a)
    np.testing.assert_allclose(ex.adasum_combine(jnp.stack([z, a])),
                               a, rtol=1e-6)


def test_adasum_combine_odd_stack():
    a = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [1.0, 1.0]])
    out = ex.adasum_combine(a)
    assert out.shape == (2,) and bool(jnp.all(jnp.isfinite(out)))


def test_adasum_reduce_primitive(devices):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None)))
    # Identical addends on every replica -> the addend itself.  Both
    # cases through ONE jit: adasum_reduce builds a fresh shard_map per
    # call, so separate calls would compile the gather tree twice.
    same = jax.device_put(jnp.broadcast_to(x[0], (8, 16)),
                          NamedSharding(mesh, P("data", None)))
    out, out_same = jax.jit(lambda a, b: (cl.adasum_reduce(a, mesh),
                                          cl.adasum_reduce(b, mesh)))(
        xs, same)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ex.adasum_combine(x)),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out_same),
                               np.asarray(x[0]), rtol=1e-5)
    with pytest.raises(ValueError, match="axis"):
        cl.adasum_reduce(jnp.ones((4, 16)), mesh)


def test_int8_codec_roundtrip_error_bound(rng):
    x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    q, scale = ex.int8_encode(x)
    assert q.dtype == jnp.int8 and scale.shape == (8, 1)
    err = np.abs(np.asarray(ex.int8_decode(q, scale) - x))
    # Symmetric quantization error is bounded by half a step per row.
    bound = np.asarray(scale)[:, 0:1] * 0.5 + 1e-7
    assert (err <= bound).all()
    # All-zero rows encode exactly.
    qz, sz = ex.int8_encode(jnp.zeros((2, 4)))
    assert not np.asarray(ex.int8_decode(qz, sz)).any()


def test_exchange_config_validation():
    with pytest.raises(ValueError, match="merge_rule"):
        ex.ExchangeConfig(merge_rule="median")
    with pytest.raises(ValueError, match="compress"):
        ex.ExchangeConfig(compress="fp4")
    with pytest.raises(ValueError, match="sync_every"):
        ex.ExchangeConfig(sync_every=0)
    with pytest.raises(ValueError, match="topk_frac"):
        ex.ExchangeConfig(compress="topk", topk_frac=0.0)
    with pytest.raises(ValueError, match="mean"):
        ex.ExchangeConfig(merge_rule="adasum", compress="int8")
    with pytest.raises(ValueError, match="local-SGD"):
        ex.ExchangeConfig(sync_every=2, compress="int8")
    assert ex.ExchangeConfig().is_default
    assert ex.ExchangeConfig(sync_every=4).label() == "localsgd4"
    assert ex.ExchangeConfig(compress="int8").label() == "int8ef"


def test_wire_bytes_ring_model_matches_census_ratios():
    """The analytic wire accounting (exchange.wire_bytes — what the
    obs gauges and bench rows report) uses the census's ring model, so
    its ratios match the compiled truth: int8 ~4x below the f32
    baseline (scales cost the remainder), mean == baseline, adasum
    costs n/2 x MORE (the whole-stack gather), zero1 legs consistent."""
    n = 8
    leaves = [jax.ShapeDtypeStruct((1024, 64), jnp.float32)]
    layout = cl.Zero1Layout.for_tree(leaves, n, 4.0)
    f32, mean_w = ex.wire_bytes(layout, ex.ExchangeConfig())
    assert mean_w == f32 > 0
    _, int8_w = ex.wire_bytes(layout, ex.ExchangeConfig(compress="int8"))
    assert 3.9 <= f32 / int8_w <= 4.0
    z_f32, z_int8 = ex.wire_bytes(layout,
                                  ex.ExchangeConfig(compress="int8"),
                                  zero1=True)
    assert z_f32 == f32 / 2  # one RS leg vs the AR's two
    assert 3.9 <= z_f32 / z_int8 <= 4.0
    _, ada_w = ex.wire_bytes(layout,
                             ex.ExchangeConfig(merge_rule="adasum"))
    assert ada_w == f32 * n / 2  # gather of n stacks vs 2 AR legs
    _, topk_w = ex.wire_bytes(
        layout, ex.ExchangeConfig(compress="topk", topk_frac=0.01))
    assert 0 < topk_w < int8_w


# ----------------------------------------------- ADAG family variants


def _adag(blobs, **kw):
    feats, labels = blobs
    ds = dk.Dataset({"features": feats, "label": labels})
    t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="adam", learning_rate=0.05,
                batch_size=8, num_epoch=2, communication_window=4, **kw)
    state = t._fit(ds)
    return t, state


@pytest.fixture(scope="module")
def adag_base(devices):
    """The replicated-DP ADAG baseline on the shared blobs problem —
    one run, shared by every parity/accounting test (make_blobs() is
    deterministic, so this matches the function-scoped ``blobs``)."""
    return _adag(make_blobs())


@pytest.fixture(scope="module")
def lm_base(devices):
    """The replicated-DP LMTrainer baseline on the 8-way data mesh."""
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    return _lm(mesh) + (mesh,)


@pytest.mark.parametrize("opts", [
    {"merge_rule": "adasum"},
    {"compress": "int8"},
    {"compress": "topk", "topk_frac": 0.1},
    {"sync_every": 4},
    {"zero1": True, "compress": "int8"},
])
def test_adag_variant_converges_to_baseline(devices, blobs, adag_base,
                                            opts):
    """Convergence parity: each exchange variant's final loss within
    the declared tolerance of replicated DP on the blobs MLP."""
    if opts.get("sync_every", 1) > 1:
        # One local-SGD round consumes sync_every x the rows: H=4
        # needs 1024 rows for a round (8 batch x 8 workers x 4 window
        # x 4 local rounds) — the shared 512-row fixture is too small.
        blobs = make_blobs(n=1024)
        base, _ = _adag(blobs)
    else:
        base, _ = adag_base
    t, _ = _adag(blobs, **opts)
    assert abs(t.history[-1] - base.history[-1]) <= TOL_LOSS, (
        opts, t.history[-1], base.history[-1])


def test_adag_variants_deterministic(devices, blobs):
    """Seeded determinism: two identical runs, bit-for-bit histories
    (quantization and the adasum tree are deterministic functions of
    the data; the local-SGD leg is covered bit-for-bit by the
    Supervisor harness below, which trains its config twice)."""
    for opts in ({"compress": "int8"}, {"merge_rule": "adasum"}):
        a, _ = _adag(blobs, **opts)
        b, _ = _adag(blobs, **opts)
        assert a.history == b.history, opts


def test_adag_localsgd_round_accounting(devices, blobs, adag_base):
    """sync_every=H consumes H x the rows per round: half the rounds
    at H=2, and the optimizer step counter advances H per round."""
    base, s0 = adag_base
    t, s1 = _adag(blobs, sync_every=2)
    assert len(t.history) == len(base.history) // 2
    assert int(s1.step) == int(s0.step)  # same optimizer steps total


def test_adag_probe_metrics(devices, blobs, adag_base):
    """The opt-in in-graph probe: same losses as the unprobed run (the
    probe only ADDS outputs), finite grad-norm series, recorded into
    obs at end of run.  The compile-budget delta is zero extra
    programs — pinned by scripts/check_compile_counts.py's sessions
    (the probed step is still ONE program)."""
    base, _ = adag_base
    with dk.obs.session() as sess:
        t, _ = _adag(blobs, probe_metrics=True)
    assert t.history == base.history
    assert len(t.probe_history) == len(t.history)
    assert all(np.isfinite(p["grad_norm"]) for p in t.probe_history)
    snap = sess.registry.compact()
    assert any(k.startswith("train.grad_norm") for k in snap)


def test_adag_int8ef_residual_diagnostic(devices, blobs):
    with dk.obs.session() as sess:
        t, state = _adag(blobs, compress="int8")
    assert np.isfinite(t.residual_norm) and t.residual_norm >= 0
    assert any(k.startswith("exchange.residual_norm")
               for k in sess.registry.compact())
    # The residual state rides the optimizer state as an ExchangeState.
    assert ex.residual_norm_of(state.opt_state) is not None


# ------------------------------------------------------- LM variants


def _lm(mesh, **kw):
    t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16,
                     num_epoch=2, mesh=mesh, **kw)
    params = t.train(lm_tokens())
    return t, params


def test_lm_int8ef_converges_and_is_deterministic(lm_base):
    base, _, mesh = lm_base
    a, _ = _lm(mesh, compress="int8")
    b, _ = _lm(mesh, compress="int8")
    assert abs(a.history[-1] - base.history[-1]) <= TOL_LOSS
    assert a.history == b.history


def test_lm_sync_every_1_and_4_converge(lm_base):
    """sync_every=1 IS the synchronous baseline (the default config);
    sync_every=4 runs 1/4 the rounds and lands within tolerance."""
    base, _, mesh = lm_base
    t, _ = _lm(mesh, sync_every=4)
    # sync_every=1 IS the default exchange — the baseline run covers it.
    assert ex.ExchangeConfig(sync_every=1).is_default
    assert base.exchange.is_default
    assert len(t.history) == len(base.history) // 4
    assert abs(t.history[-1] - base.history[-1]) <= TOL_LOSS


def test_lm_adasum_and_zero1_int8_converge(lm_base):
    base, _, mesh = lm_base
    for opts in ({"merge_rule": "adasum"},
                 {"zero1": True, "compress": "int8"}):
        t, _ = _lm(mesh, **opts)
        assert abs(t.history[-1] - base.history[-1]) <= TOL_LOSS, opts


def test_lm_zero1_int8_shards_opt_memory(devices):
    """zero1 x int8: the inner moments still scatter (the memory win
    survives the codec) and the residuals shard over their replica
    axis — nothing replicated that shouldn't be."""
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16, mesh=mesh,
                     zero1=True, compress="int8")
    params = t.init_params()
    opt_shapes = jax.eval_shape(t.optimizer.init, params)
    psh, osh = t._state_shardings(params, opt_shapes)
    opt_state = jax.jit(t.optimizer.init, out_shardings=osh)(params)
    n_param_bytes = sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
    inner_state, exs = opt_state
    # The EF residuals are ~1x params/device by construction (each
    # replica's quantization error on its local contribution); the
    # memory claim is about the INNER moments, so exclude them.
    resid_ids = {id(l) for l in jax.tree.leaves(exs)}
    per_dev = sum(
        l.addressable_shards[0].data.nbytes
        for l in jax.tree.leaves(opt_state)
        if hasattr(l, "addressable_shards") and id(l) not in resid_ids)
    # adamw mu+nu ~= 2x params replicated; scattered they must stay
    # far under that figure.
    assert per_dev < 2 * n_param_bytes / 2.0, (per_dev, n_param_bytes)
    for e in exs.e1:
        assert e.sharding.spec == P("data", None, None)
    for e in exs.e2:
        assert e.sharding.spec == P("data", None)


def test_adag_int8ef_checkpoint_resume(devices, tmp_path, blobs):
    """Error-feedback residual state round-trips the pickle backend:
    the resumed ADAG run continues the uninterrupted run's loss
    trajectory bit-for-bit (a dropped/zeroed residual would fork it).
    The LM spelling (both backends) runs in the merge gate."""
    feats, labels = blobs
    ds = dk.Dataset({"features": feats, "label": labels})
    kw = dict(loss="sparse_categorical_crossentropy",
              worker_optimizer="adam", learning_rate=0.05,
              batch_size=8, communication_window=4, compress="int8",
              checkpoint_backend="pickle")
    full = dk.ADAG(make_mlp(), num_epoch=2,
                   **{k: v for k, v in kw.items()
                      if k != "checkpoint_backend"})
    full.train(ds)
    d = str(tmp_path / "ck")
    first = dk.ADAG(make_mlp(), num_epoch=1, checkpoint_dir=d,
                    checkpoint_every=1, **kw)
    first.train(ds)
    resumed = dk.ADAG(make_mlp(), num_epoch=2, checkpoint_dir=d,
                      checkpoint_every=1, resume=True, **kw)
    resumed.train(ds)
    assert resumed.history == full.history[len(first.history):]


@pytest.mark.parametrize("backend", [
    # Both legs run in the merge gate (LM compiles are the fast gate's
    # scarcest budget); tests/conftest.py SLOW carries the demotion.
    # The fast-gate residual-round-trip representative is the ADAG
    # pickle test above.
    "pickle",
    "orbax",
])
def test_lm_int8ef_checkpoint_resume(devices, tmp_path, backend):
    """Error-feedback residual state round-trips both checkpoint
    backends: the resumed run continues the uninterrupted run's loss
    trajectory (a dropped/zeroed residual would fork it)."""
    if backend == "orbax":
        pytest.importorskip("orbax.checkpoint")
    d = str(tmp_path / "ck")
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    data = lm_tokens()
    kw = dict(learning_rate=1e-2, batch_size=16, mesh=mesh,
              compress="int8", checkpoint_backend=backend)
    full = dk.LMTrainer(CFG, num_epoch=2, **{k: v for k, v in kw.items()
                                             if k != "checkpoint_backend"})
    full.train(data)
    first = dk.LMTrainer(CFG, num_epoch=1, checkpoint_dir=d,
                         checkpoint_every=1, **kw)
    first.train(data)
    resumed = dk.LMTrainer(CFG, num_epoch=2, checkpoint_dir=d,
                           checkpoint_every=1, resume=True, **kw)
    resumed.train(data)
    np.testing.assert_allclose(
        resumed.history, full.history[len(first.history):], rtol=1e-5)


@pytest.mark.chaos
def test_adag_localsgd_supervisor_bit_for_bit(devices, tmp_path, blobs):
    """The resilience acceptance harness under sync_every > 1: an
    injected kill mid-run + Supervisor auto-resume reproduces the
    uninterrupted run's loss trajectory bit-for-bit — a sync period is
    a round, so the checkpoint boundary is always a post-merge state."""
    feats, labels = blobs
    ds = dk.Dataset({"features": feats, "label": labels})
    kw = dict(loss="sparse_categorical_crossentropy",
              worker_optimizer="adam", learning_rate=0.05,
              batch_size=8, num_epoch=2, communication_window=4,
              sync_every=2)

    straight = dk.ADAG(make_mlp(), **kw)
    ref = straight.train(ds)

    t = dk.ADAG(make_mlp(), checkpoint_dir=str(tmp_path / "c"),
                checkpoint_every=1, checkpoint_backend="pickle", **kw)
    sup = Supervisor(t, max_retries=2, backoff=0.0, max_backoff=0.0,
                     jitter=0.0)
    with FaultPlan().fail("train.round", at=2):
        out = sup.run(ds)

    assert t.history == straight.history[1:]  # bit-for-bit
    for wr, wo in zip(ref.get_weights(), out.get_weights()):
        np.testing.assert_allclose(wr, wo, rtol=1e-5, atol=1e-6)
    assert [a.outcome for a in sup.attempts] == ["fault", "ok"]


# ----------------------------------------------------------- guards


def test_exchange_rejections(devices):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    with pytest.raises(ValueError, match="exchange"):
        dk.AEASGD(make_mlp(), merge_rule="adasum")
    with pytest.raises(ValueError, match="exchange"):
        dk.DOWNPOUR(make_mlp(), compress="int8")
    with pytest.raises(ValueError, match="device_data"):
        dk.ADAG(make_mlp(), compress="int8", device_data=True)
    with pytest.raises(ValueError, match="fsdp"):
        dk.ADAG(make_mlp(), compress="int8", fsdp=True)
    with pytest.raises(ValueError, match="int8"):
        dk.ADAG(make_mlp(), zero1=True, merge_rule="adasum")
    with pytest.raises(ValueError, match="int8"):
        dk.LMTrainer(CFG, mesh=mesh, zero1=True, sync_every=2)
    with pytest.raises(ValueError, match="dropout"):
        dk.LMTrainer(tfm.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64,
            max_len=32, dropout=0.1), mesh=mesh, compress="int8")
    with pytest.raises(ValueError, match="grad_accum"):
        dk.LMTrainer(CFG, mesh=mesh, sync_every=2, grad_accum=2)
    tp = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    with pytest.raises(ValueError, match="data"):
        dk.LMTrainer(CFG, mesh=tp, merge_rule="adasum")
    with pytest.raises(ValueError, match="LoRATrainer"):
        dk.LoRATrainer(CFG, base_params=toy_params(CFG), compress="int8")
    with pytest.raises(ValueError, match="segments"):
        t = dk.LMTrainer(CFG, mesh=mesh, compress="int8")
        rows = lm_tokens(32)
        t.train(rows, segments=np.ones_like(rows))
    # BatchNorm carries non-trainable training state: rejected.
    import keras

    keras.utils.set_random_seed(0)
    bn = keras.Sequential([keras.Input((16,)),
                           keras.layers.Dense(8),
                           keras.layers.BatchNormalization(),
                           keras.layers.Dense(4)])
    with pytest.raises(ValueError, match="non-trainable"):
        dk.ADAG(bn, compress="int8")
    # zero1_bucket_mb threads into the exchange layout on BOTH trainer
    # families (under zero1 x int8 the one knob governs both layouts).
    t = dk.ADAG(make_mlp(), zero1=True, compress="int8",
                zero1_bucket_mb=1.0)
    assert t.exchange.bucket_mb == 1.0
    t = dk.LMTrainer(CFG, mesh=mesh, zero1=True, compress="int8",
                     zero1_bucket_mb=1.0)
    assert t.exchange.bucket_mb == 1.0


def test_exports():
    assert dk.ExchangeConfig is ex.ExchangeConfig
    assert dk.exchange_optimizer is ex.exchange_optimizer
    assert dk.exchange is ex
    assert cl.adasum_reduce is not None
