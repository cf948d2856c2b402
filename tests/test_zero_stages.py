"""ZeRO stages 2/3 (docs/zero1.md) and the regex partition-rule engine
(parallel/rules.py): stage-2 sharded gradient accumulators and stage-3
gather-on-use parameters are math-identical to replicated DP on the
8-CPU mesh for both trainer families; stage-3 per-device
param+grad+opt bytes drop ~num_workers x (asserted from addressable
shards); the scattered state round-trips checkpoints and the
Supervisor's bit-for-bit resume; and the rule engine resolves
partition specs and per-bucket exchange codecs first-match-wins with
unmatched-leaf errors.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import distkeras_tpu as dk
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.parallel import collectives as cl
from distkeras_tpu.parallel import rules as pr
from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
from distkeras_tpu.resilience import FaultPlan, Supervisor
from jax.sharding import NamedSharding, PartitionSpec as P
from helpers import toy_params


CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=32)

# Same bound as tests/test_zero1.py: <= 1e-6 where reduction order
# legitimately differs, rtol on the well-scaled elements.
TOL = dict(rtol=2e-5, atol=1e-6)


def tokens(rng, n=64, s=16):
    return rng.integers(0, 64, (n, s + 1)).astype(np.int32)


def tree_close(a, b, **kw):
    kw = kw or TOL
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **kw)


# -------------------------------------------------------- rule engine


def test_match_partition_rules_first_match_wins():
    tree = {"tok_emb": jnp.ones((8, 4)),
            "layers": [{"wq": jnp.ones((4, 4)), "scale": jnp.ones((4,))}],
            "step": jnp.ones(())}
    specs = pr.match_partition_rules(
        [("emb", P("data", None)),
         (r"wq$", P(None, "model")),
         (r".*", P())], tree)
    assert specs["tok_emb"] == P("data", None)
    assert specs["layers"][0]["wq"] == P(None, "model")
    assert specs["layers"][0]["scale"] == P()
    # Scalars replicate even when an earlier rule would match them.
    specs2 = pr.match_partition_rules(
        [(r".*", P("data"))], {"s": jnp.ones(())})
    assert specs2["s"] == P()


def test_match_rules_unmatched_leaf_raises_naming_it():
    tree = {"layers": [{"wq": jnp.ones((4, 4))}], "tok_emb": jnp.ones((8,))}
    with pytest.raises(pr.UnmatchedLeafError, match="layers/0/wq"):
        pr.match_partition_rules([("emb", P())], tree)
    # Typos in patterns raise at compile, not mid-trace.
    with pytest.raises(Exception):
        pr.compile_rules([("([unclosed", P())])


def test_callable_rule_values_can_decline():
    calls = []

    def only_matrices(name, leaf):
        calls.append(name)
        return "mat" if len(leaf.shape) == 2 else None

    out = pr.match_rules([(r".*", only_matrices), (r".*", "other")],
                         {"w": jnp.ones((2, 2)), "b": jnp.ones((2,))})
    assert out == {"w": "mat", "b": "other"}


def test_zero_state_shardings_rules_match_legacy_rule(devices):
    """The rule-engine spelling reproduces the shape-keyed ZeRO state
    rule the plans used to hand-build."""
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    params = [jnp.ones((16, 8)), jnp.ones((24,))]
    opt = optax.adam(1e-3)
    layout = cl.Zero1Layout.for_tree(params, 8)
    state = jax.eval_shape(opt.init, layout.shard_views(params))
    sh = cl.zero1_state_shardings(params, state, mesh)
    for leaf, s in zip(jax.tree.leaves(state), jax.tree.leaves(sh)):
        want = (P("data", None) if tuple(leaf.shape) in layout.shard_shapes
                else P())
        assert s.spec == want, (leaf.shape, s.spec)


# ------------------------------------------------- ADAG stages 2 and 3


def _adag(blobs, **kw):
    feats, labels = blobs
    ds = dk.Dataset({"features": feats, "label": labels})
    from helpers import make_mlp

    t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="adam", learning_rate=0.05,
                batch_size=8, num_epoch=2, communication_window=4, **kw)
    state = t._fit(ds)
    return t, state


def test_adag_zero2_matches_replicated(devices, blobs):
    base, s0 = _adag(blobs)
    z, s1 = _adag(blobs, zero=2)
    np.testing.assert_allclose(z.history, base.history, **TOL)
    tree_close(s1.tv, s0.tv)
    # The persistent optimizer state is the scattered view layout.
    for l in jax.tree.leaves(s1.opt_state):
        if hasattr(l, "addressable_shards") and l.ndim == 2:
            assert l.sharding.spec == P("data", None)


def test_adag_zero3_matches_replicated(devices, blobs):
    base, s0 = _adag(blobs)
    z, s1 = _adag(blobs, zero=3)
    np.testing.assert_allclose(z.history, base.history, **TOL)
    tree_close(z._zero_unview_state(s1).tv, s0.tv)


def test_adag_zero3_shards_param_and_opt_memory(devices, blobs):
    """Acceptance: stage-3 per-device params+opt bytes land ~n x below
    the replicated state, asserted from addressable shards (the
    transient in-scan grad accumulator is scattered by construction —
    the declared-exchange proof in test_budget_guards pins it)."""
    base, s0 = _adag(blobs)
    z, s1 = _adag(blobs, zero=3)

    def per_device(tree):
        return sum(l.addressable_shards[0].data.nbytes
                   for l in jax.tree.leaves(tree)
                   if hasattr(l, "addressable_shards"))

    rep = per_device([list(s0.tv), s0.opt_state])
    sharded = per_device([list(s1.tv), s1.opt_state])
    assert rep / sharded > 6.0, (rep, sharded)
    for l in jax.tree.leaves(list(s1.tv)):
        assert l.sharding.spec == P("data", None)
        assert l.addressable_shards[0].data.shape[0] == 1


def test_adag_zero_stages_device_data_match_streaming(devices, blobs):
    """The HBM-staged indexed data plane composes with stages 2 and 3:
    same math, same data order as streaming."""
    base, s0 = _adag(blobs)
    for stage in (2, 3):
        z, _ = _adag(blobs, zero=stage, device_data=True)
        np.testing.assert_allclose(z.history, base.history, **TOL)


@pytest.mark.chaos
def test_adag_zero3_supervisor_bit_for_bit(devices, tmp_path, blobs):
    """The resilience acceptance harness over the stage-3 path: an
    injected kill mid-run + Supervisor auto-resume reproduces the
    uninterrupted run's loss trajectory bit-for-bit — the scattered
    view params AND scattered optimizer state restore exactly."""
    from helpers import make_mlp

    feats, labels = blobs
    ds = dk.Dataset({"features": feats, "label": labels})
    kw = dict(loss="sparse_categorical_crossentropy",
              worker_optimizer="adam", learning_rate=0.05,
              batch_size=8, num_epoch=2, communication_window=4,
              zero=3)

    straight = dk.ADAG(make_mlp(), **kw)
    ref = straight.train(ds)

    t = dk.ADAG(make_mlp(), checkpoint_dir=str(tmp_path / "c"),
                checkpoint_every=1, checkpoint_backend="pickle", **kw)
    sup = Supervisor(t, max_retries=2, backoff=0.0, max_backoff=0.0,
                     jitter=0.0)
    with FaultPlan().fail("train.round", at=3):
        out = sup.run(ds)

    assert t.history == straight.history[2:]  # bit-for-bit
    for wr, wo in zip(ref.get_weights(), out.get_weights()):
        np.testing.assert_allclose(wr, wo, rtol=1e-5, atol=1e-6)
    assert [a.outcome for a in sup.attempts] == ["fault", "ok"]


# --------------------------------------------------- LM stages 2 and 3


def _lm(mesh, rng, **kw):
    t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16, num_epoch=2,
                     mesh=mesh, **kw)
    params = t.train(tokens(rng))
    return t, params


def test_lm_zero2_matches_dp(devices):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    base, p0 = _lm(mesh, np.random.default_rng(0))
    z, p1 = _lm(mesh, np.random.default_rng(0), zero=2)
    np.testing.assert_allclose(z.history, base.history, **TOL)
    tree_close(p1, p0)


def test_lm_zero3_matches_dp(devices):
    """Stage-3 parity AND layout: the trained tree comes back in
    parameter layout, while the persistent carry trained as scattered
    ``[n, cols]`` views."""
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    base, p0 = _lm(mesh, np.random.default_rng(0))
    z, p1 = _lm(mesh, np.random.default_rng(0), zero=3)
    np.testing.assert_allclose(z.history, base.history, **TOL)
    tree_close(p1, p0)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p0)):
        assert a.shape == b.shape


def test_lm_zero3_grad_accum_matches_dp(devices):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    base, p0 = _lm(mesh, np.random.default_rng(0), grad_accum=2)
    z, p1 = _lm(mesh, np.random.default_rng(0), grad_accum=2, zero=3)
    np.testing.assert_allclose(z.history, base.history, **TOL)
    tree_close(p1, p0)


def test_lm_zero3_clip_ema_matches_dp(devices):
    """The whole optax chain (global-norm clip + the EMA shadow) runs
    on shard views; ema_params comes back in parameter layout."""
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    kw = dict(grad_clip_norm=1.0, ema_decay=0.9)
    base, p0 = _lm(mesh, np.random.default_rng(0), **kw)
    z, p1 = _lm(mesh, np.random.default_rng(0), zero=3, **kw)
    np.testing.assert_allclose(z.history, base.history, **TOL)
    tree_close(p1, p0)
    tree_close(z.ema_params, base.ema_params)
    for a, b in zip(jax.tree.leaves(base.ema_params),
                    jax.tree.leaves(z.ema_params)):
        assert a.shape == b.shape


def test_lm_zero3_shards_param_grad_opt_memory(devices):
    """The acceptance criterion: per-device param+opt bytes of the
    stage-3 persistent state land ~n x (8-way mesh) below the
    replicated layout, measured from addressable shards built exactly
    the way train() builds them."""
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16, mesh=mesh,
                     zero=3)
    params = t.init_params()
    layout = t._layout()
    opt_shapes = jax.eval_shape(
        lambda p: t.optimizer.init(layout.shard_views(p)), params)
    v_struct = jax.eval_shape(layout.shard_views, params)
    psh, osh = t._state_shardings(v_struct, opt_shapes)
    opt_state = jax.jit(lambda p: t.optimizer.init(layout.shard_views(p)),
                        out_shardings=osh)(params)
    views = jax.jit(layout.shard_views, out_shardings=psh)(params)

    n_param_bytes = sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(params))
    per_dev = sum(l.addressable_shards[0].data.nbytes
                  for l in jax.tree.leaves((views, opt_state))
                  if hasattr(l, "addressable_shards"))
    # adamw: params + mu + nu ~= 3x params replicated; the scattered
    # state must land near 3x/8 (pad costs a little).
    assert per_dev < 3 * n_param_bytes / 6.0, (per_dev, n_param_bytes)
    for l in jax.tree.leaves(views):
        assert l.sharding.spec == P("data", None)


def test_lm_zero3_device_data_matches_streaming(devices):
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    base, p0 = _lm(mesh, np.random.default_rng(0))
    z, p1 = _lm(mesh, np.random.default_rng(0), zero=3,
                device_data=True)
    np.testing.assert_allclose(z.history, base.history, **TOL)
    tree_close(p1, p0)


def test_lm_zero3_eval_matches_dp(devices):
    """The eval plane gathers the views per chunk (never per step):
    eval_history is identical to the replicated run's."""
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    data = tokens(np.random.default_rng(0))
    ev = tokens(np.random.default_rng(1), n=32)

    def run(**kw):
        t = dk.LMTrainer(CFG, learning_rate=1e-2, batch_size=16,
                         num_epoch=1, mesh=mesh, eval_every=2, **kw)
        t.train(data, eval_tokens=ev)
        return t

    base, z = run(), run(zero=3)
    assert [r for r, _ in z.eval_history] == [r for r, _ in
                                              base.eval_history]
    for (_, m1), (_, m0) in zip(z.eval_history, base.eval_history):
        np.testing.assert_allclose(m1["loss"], m0["loss"], **TOL)


@pytest.mark.parametrize("backend", ["pickle", "orbax"])
def test_lm_zero3_checkpoint_resume(devices, tmp_path, backend):
    """The stage-3 view state round-trips: gather-on-save for the
    pickle backend, shard-native for orbax; the resumed run continues
    the uninterrupted run's loss trajectory."""
    if backend == "orbax":
        pytest.importorskip("orbax.checkpoint")
    d = str(tmp_path / "ck")
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    data = tokens(np.random.default_rng(0))
    kw = dict(learning_rate=1e-2, batch_size=16, mesh=mesh, zero=3,
              checkpoint_backend=backend)
    full = dk.LMTrainer(CFG, num_epoch=2, **{k: v for k, v in kw.items()
                                             if k != "checkpoint_backend"})
    full.train(data)

    first = dk.LMTrainer(CFG, num_epoch=1, checkpoint_dir=d,
                         checkpoint_every=1, **kw)
    first.train(data)
    resumed = dk.LMTrainer(CFG, num_epoch=2, checkpoint_dir=d,
                           checkpoint_every=1, resume=True, **kw)
    p2 = resumed.train(data)
    np.testing.assert_allclose(
        resumed.history, full.history[len(first.history):], rtol=1e-5)
    jax.block_until_ready(jax.tree.leaves(p2)[0])


# ----------------------------------------------- per-bucket codec rules


def test_adag_codec_rules_converge_and_mix_codecs(devices, blobs):
    """compress=[(pattern, codec)] rules: the Keras trainer resolves
    them over its VARIABLE PATHS (kernels int8, biases top-k here),
    buckets stay codec-homogeneous, and training converges with the
    replicated baseline within the lowcomm tolerance."""
    base, s0 = _adag(blobs)
    z, s1 = _adag(blobs, compress=[(r"kernel$", "int8"),
                                   (r".*", "topk")])
    assert abs(z.history[-1] - base.history[-1]) < 0.2
    from distkeras_tpu.parallel.exchange import exchange_layout

    layout = exchange_layout(
        [jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype))
         for v in z.adapter.model.trainable_variables],
        8, z.exchange, names=z.adapter.tv_paths)
    assert set(layout.bucket_groups) == {"int8", "topk"}
    # Residual geometry: e1 per codec'd bucket, e2 ONLY for the int8
    # buckets — a top-k bucket must not persist a dead bucket-sized
    # f32 e2 slot in the optimizer state.
    from distkeras_tpu.parallel.exchange import ExchangeState

    ex_states = [l for l in jax.tree.leaves(
        s1.opt_state,
        is_leaf=lambda x: isinstance(x, ExchangeState))
        if isinstance(l, ExchangeState)]
    assert len(ex_states) == 1
    n_int8 = sum(1 for g in layout.bucket_groups if g == "int8")
    assert len(ex_states[0].e1) == len(layout.bucket_cols)
    assert len(ex_states[0].e2) == n_int8 < len(layout.bucket_cols)


def test_codec_rules_unmatched_leaf_raises(devices, blobs):
    from helpers import make_mlp

    feats, labels = blobs
    ds = dk.Dataset({"features": feats, "label": labels})
    t = dk.ADAG(make_mlp(), loss="sparse_categorical_crossentropy",
                worker_optimizer="adam", batch_size=8,
                communication_window=4,
                compress=[(r"kernel$", "int8")])
    with pytest.raises(pr.UnmatchedLeafError, match="bias"):
        t._fit(ds)


def test_codec_rules_config_validation():
    from distkeras_tpu.parallel.exchange import ExchangeConfig

    with pytest.raises(ValueError, match="codec"):
        ExchangeConfig(compress=[("x", "gzip")])
    with pytest.raises(ValueError, match="ambiguous"):
        ExchangeConfig(compress=[])
    cfg = ExchangeConfig(compress=[("emb", "topk"), (".*", "int8")])
    assert cfg.label() == "rulesef"
    # Rules never compose with the ZeRO stages.
    with pytest.raises(ValueError, match="ZeRO"):
        dk.LMTrainer(CFG, zero=1,
                     compress=[("emb", "topk"), (".*", "int8")])


def test_lm_codec_rules_wire_geometry():
    """The analytic wire model accounts per bucket: the rules layout's
    wire bytes sit between uniform-int8 (all buckets compressed 4x)
    and uniform-topk."""
    from distkeras_tpu.parallel import exchange as ex

    params = jax.eval_shape(
        lambda: toy_params(CFG))
    n = 8
    rules_cfg = ex.ExchangeConfig(compress=(("emb", "topk"),
                                            (".*", "int8")))
    int8_cfg = ex.ExchangeConfig(compress="int8")
    lay_rules = ex.exchange_layout(params, n, rules_cfg)
    lay_int8 = ex.exchange_layout(params, n, int8_cfg)
    f32_r, wire_r = ex.wire_bytes(lay_rules, rules_cfg)
    f32_i, wire_i = ex.wire_bytes(lay_int8, int8_cfg)
    assert f32_r == f32_i            # same gradient volume
    assert 0 < wire_r < f32_r        # compressed overall
    assert wire_r != wire_i          # but not the uniform-int8 wire


# --------------------------------------------------- guards / wiring


def test_zero_flag_wiring_and_rejections(devices, blobs):
    from helpers import make_mlp

    # zero1=True is the alias of zero=1 and cannot contradict zero=.
    with pytest.raises(ValueError, match="alias"):
        dk.ADAG(make_mlp(), zero1=True, zero=2)
    with pytest.raises(ValueError, match="alias"):
        dk.LMTrainer(CFG, zero1=True, zero=3)
    with pytest.raises(ValueError, match="zero must be"):
        dk.ADAG(make_mlp(), zero=4)
    with pytest.raises(ValueError, match="only one of"):
        dk.ADAG(make_mlp(), zero=2, fsdp=True)
    with pytest.raises(ValueError, match="exclusive"):
        dk.LMTrainer(CFG, zero=3, fsdp=True)
    mesh = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    with pytest.raises(ValueError, match="data axis only"):
        dk.LMTrainer(CFG, mesh=mesh, zero=2)
    with pytest.raises(ValueError, match="zero"):
        dk.AEASGD(make_mlp(), zero=2)
    with pytest.raises(ValueError, match="zero"):
        dk.LoRATrainer(CFG, base_params=toy_params(CFG), zero=3)
    with pytest.raises(ValueError, match="zero_bucket_mb"):
        dk.ADAG(make_mlp(), zero_bucket_mb=8.0)
    with pytest.raises(ValueError, match="only one of zero_bucket_mb"):
        dk.ADAG(make_mlp(), zero=2, zero_bucket_mb=8.0,
                zero1_bucket_mb=8.0)


def test_zero3_plan_spelling_matches_flag(devices, blobs):
    """plan=zero3_plan() is the explicit spelling of zero=3."""
    base, s0 = _adag(blobs)
    z, s1 = _adag(blobs, plan=dk.zero3_plan())
    assert z.zero == 3
    np.testing.assert_allclose(z.history, base.history, **TOL)
    tree_close(z._zero_unview_state(s1).tv, s0.tv)


def test_construction_rejects_non_elementwise_naming_offender(blobs):
    """Satellite: the elementwise check runs at construction for every
    stage and names the offending optax transform."""
    from helpers import make_mlp

    for stage in (1, 2, 3):
        with pytest.raises(ValueError, match="scale_by_trust_ratio"):
            dk.LMTrainer(CFG, optimizer=optax.lamb(1e-3), zero=stage)
    with pytest.raises(ValueError, match="scale_by_trust_ratio"):
        dk.ADAG(make_mlp(), worker_optimizer=optax.lars(1e-1), zero=2)


def test_construction_recognizes_prebuilt_elementwise_chains():
    """A prebuilt adam/adamw (or clip+adam chain) is now verified
    elementwise by closure inspection — no warning; a transform the
    inspector cannot attribute still warns."""
    import warnings

    from distkeras_tpu.ops.optimizers import (zero1_compatible,
                                              zero1_offender)

    assert zero1_compatible(optax.adam(1e-3)) is True
    assert zero1_compatible(
        optax.chain(optax.clip_by_global_norm(1.0),
                    optax.adamw(1e-3))) is True
    assert zero1_compatible(optax.lamb(1e-3)) is False
    assert zero1_offender(optax.lamb(1e-3)) == "scale_by_trust_ratio"
    opaque = optax.GradientTransformation(
        lambda p: (), lambda g, s, p=None: (g, s))
    assert zero1_compatible(opaque) is None
    # The recipe must never conclude "safe" AROUND an uninspectable
    # nested transform: a chain of recognized factories plus one
    # opaque member is uninspectable, not safe (and a known-bad
    # member nested next to opaque bits is still named).
    assert zero1_compatible(
        optax.chain(optax.scale(1.0), opaque)) is None
    mixed = optax.chain(opaque, optax.lamb(1e-3))
    assert zero1_compatible(mixed) is False
    assert zero1_offender(mixed) == "scale_by_trust_ratio"
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        dk.LMTrainer(CFG, optimizer=optax.adam(1e-3), zero=1)
        assert not [x for x in w if "elementwise" in str(x.message)]
    with pytest.warns(UserWarning, match="elementwise"):
        dk.LMTrainer(CFG, optimizer=opaque, zero=2)


def test_exports():
    assert dk.zero3_plan is not None
    assert dk.match_partition_rules is pr.match_partition_rules
    assert dk.rules is pr
    from distkeras_tpu.parallel import Zero3Plan, gather_bucket

    assert Zero3Plan is not None
    assert gather_bucket is cl.gather_bucket
