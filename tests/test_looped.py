"""The extended block and the looped stack (models/transformer.py
``TransformerConfig.extended``; models/generate.py ``_chunk_in_place``,
the cached body of every plain call) against the plain reference ``benchmarks/reference_ouro.py``, loaded by
path: float32, seeded weights, tiny sizes.

(a) the full forward: every pass's logits and the exit distribution;
(b) logits THROUGH THE CACHE — prefill then decode, all-sequential,
    and the engine's pattern (admission into a lane of a slab in
    chunks, per-row decode at different positions) — and the engine
    itself against solo ``generate`` and the reference;
(c) weights for L layers, a cache of R·L planes;
(d) the comparison is tight: a pass short, or a pass reading the
    previous pass's plane, fails the tolerance by far;
(e) the switches off: the parent commit's forward, bit for bit, and
    its tokens through the cache — by the same in-place body;
(f) every path that does not run an extended config says so by name;
    what shares ``block_apply`` and lacks only the pass loop
    (``lm_loss``, ``LMTrainer``) takes one pass of it.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import obs
from distkeras_tpu.models import generate as gen
from distkeras_tpu.models import speculative as spec
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.obs import read_trace
from helpers import generate, jgen, jtfm, toy_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TC = dict(vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=96,
          max_len=64, rope=True, rope_theta=1e6, ffn_gated=True,
          tie_head=False, post_norms=True, fused_qkv=True, n_passes=3)
CFG = tfm.TransformerConfig(**TC)
TOL = 2e-4          # float32 against float32: rounding order only


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(REPO, "benchmarks", "reference_ouro.py")
    s = importlib.util.spec_from_file_location("reference_ouro", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every norm scale and the gate's bias moved
    off their initial 1 and 0, so that a misplaced norm shows."""
    p = toy_params(CFG)
    leaves, treedef = jax.tree.flatten(p)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    return jax.tree.unflatten(treedef, [
        a + 0.3 * jax.random.normal(k, a.shape) if a.ndim <= 2
        and (a == a.reshape(-1)[0]).all() else a
        for a, k in zip(leaves, keys)])


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 128, n).astype(np.int32)


# ------------------------------------------------ (a) the full forward


def test_every_pass_logits_and_exit_distribution(ref, params):
    toks = _tokens(23)
    logits, probs = jtfm.apply_passes(params, jnp.asarray(toks)[None], CFG)
    want_l, want_p = ref.all_passes(params, TC, toks)
    assert logits.shape == (3, 1, 23, 128) and probs.shape == (3, 1, 23)
    np.testing.assert_allclose(logits[:, 0], want_l, atol=TOL)
    np.testing.assert_allclose(probs[:, 0], want_p, atol=1e-5)
    np.testing.assert_allclose(probs.sum(0), 1.0, atol=1e-6)
    assert 0.02 < float(probs[0].mean()) < 0.98     # the gate does gate
    served, _ = jtfm.apply(params, jnp.asarray(toks)[None], CFG)
    np.testing.assert_array_equal(served, logits[-1])


# ------------------------------------------- (b) logits through the cache


def _ref_logits(ref, params, seq):
    return ref.logits_at(params, ref.forward(params, TC, seq),
                         np.arange(len(seq)))


@pytest.mark.parametrize("use_prefill", [True, False])
def test_prefill_then_decode_logits(ref, params, use_prefill):
    """Two rows, 9 prompt positions, 6 more teacher-forced through
    ``_decode_step``: the logits at every position the cache served."""
    seqs = np.stack([_tokens(15, 1), _tokens(15, 2)])
    want = np.stack([_ref_logits(ref, params, s) for s in seqs])
    if use_prefill:
        cache, last = jgen.prefill(params, jnp.asarray(seqs[:, :9]), CFG)
        np.testing.assert_allclose(last, want[:, 8], atol=TOL)
        start = 9
    else:
        cache, start = jgen.init_cache(CFG, 2), 0
    for pos in range(start, 15):
        lg, cache = jgen._decode_step(params, cache,
                                     jnp.asarray(seqs[:, pos]), pos, CFG)
        np.testing.assert_allclose(lg, want[:, pos], atol=TOL)


def test_lane_admission_in_chunks_and_per_row_decode_logits(ref, params):
    """The engine's pattern on a slab of three lanes: A (20 positions)
    is admitted into lane 2 in chunks [0, 8), [8, 16), [12, 20) — the
    backed-up tail re-writes four slots — B (5) into lane 0 in one
    bucket of 8 whose last three positions are padding; then both
    decode at DIFFERENT positions in one per-row step, lane 1 idling
    on garbage.  Every logit the cache served against the reference."""
    a, b = _tokens(26, 3), _tokens(11, 4)
    want_a, want_b = (_ref_logits(ref, params, s) for s in (a, b))
    cache = jgen.init_cache(CFG, 3)

    def admit(cache, toks, lane, start):
        lg, cache = jgen._decode_chunk(
            params, cache, jnp.asarray(toks)[None],
            jnp.full((1,), start, jnp.int32), CFG, uniform_pos=True,
            lane=jnp.int32(lane))
        return lg[0], cache

    for start in (0, 8, 12):
        lg, cache = admit(cache, a[start:start + 8], 2, start)
        np.testing.assert_allclose(lg, want_a[start:start + 8], atol=TOL)
    lg, cache = admit(cache, np.concatenate([b[:5], [0, 0, 0]]), 0, 0)
    np.testing.assert_allclose(lg[:5], want_b[:5], atol=TOL)
    for i in range(6):
        cur = jnp.asarray([b[5 + i], 7, a[20 + i]], jnp.int32)
        pos = jnp.asarray([5 + i, 0, 20 + i], jnp.int32)
        lg, cache = jgen._decode_chunk(params, cache, cur[:, None], pos, CFG)
        np.testing.assert_allclose(lg[0, 0], want_b[5 + i], atol=TOL)
        np.testing.assert_allclose(lg[2, 0], want_a[20 + i], atol=TOL)


@pytest.fixture(scope="module")
def served(params, tmp_path_factory):
    """Four requests through a two-lane engine with chunked prefill
    (lanes are reused; the 30-token prompt takes four chunks):
    ``[(prompt, generated)]`` and the trace's records."""
    path = str(tmp_path_factory.mktemp("looped") / "t.jsonl")
    prompts = [_tokens(n, 10 + n) for n in (9, 21, 3, 30)]
    with obs.session(trace_path=path):
        eng = dk.ContinuousBatcher(params, CFG, lanes=2, hot_swap=True,
                                   prefill_chunk=8, prompt_buckets=(4, 8),
                                   max_queue=8)
        ids = [eng.enqueue(p, 10) for p in prompts]
        while eng.running() or eng._pending:
            eng.step()
        out = [eng.take(i) for i in ids]
    assert all(r.ok for r in out)
    return ([(p, np.asarray(r.generated)) for p, r in zip(prompts, out)],
            read_trace(path))


def test_engine_equals_solo_generate(served, params):
    for prompt, got in served[0]:
        solo = generate(params, jnp.asarray(prompt)[None], CFG, 10)
        np.testing.assert_array_equal(got, solo[0, len(prompt):])


def test_engine_tokens_are_the_references_best(served, ref, params):
    """As the benchmark's ``check_serving``: teacher-forced through
    the reference, every token the engine chose is its best logit, or
    within the tolerance of it."""
    for prompt, got in served[0]:
        seq = np.concatenate([prompt, got])
        lg = _ref_logits(ref, params, seq[:-1])[len(prompt) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(got)), got]
        assert gap.max() < TOL


def test_engine_says_its_kv_layout_and_passes(served):
    """``serving.kv_layout`` turns ``kv_live`` slots into bytes and
    says how many passes a step runs (once: an engine's rounds all run
    the same, so ``serving.round`` does not repeat it)."""
    (ev,) = [r for r in served[1] if r.get("name") == "serving.kv_layout"]
    f = ev["fields"]
    per_slot = 2 * 6 * 4 * 16 * 4      # k and v, 6 planes, 4 heads of 16, f32
    assert {k: f[k] for k in ("passes", "layers", "planes", "bytes_per_slot",
                              "slots", "slab_bytes")} == {
        "passes": 3, "layers": 2, "planes": 6, "bytes_per_slot": per_slot,
        "slots": 2 * 64, "slab_bytes": per_slot * 2 * 64}
    rounds = [r for r in served[1] if r.get("name") == "serving.round"]
    assert rounds and all("passes" not in r["fields"] for r in rounds)


def test_float32_weights_under_bfloat16_activations_decode():
    """The scan's carry keeps the compute dtype when the weights are
    wider (the trainers' float32 master weights served as they are)."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    p = toy_params(cfg)
    out = generate(p, jnp.asarray(_tokens(9))[None], cfg, 5)
    lg, _ = jtfm.apply(p, out[:, :-1], cfg)
    gap = lg[0, 8:].max(-1) - jnp.take_along_axis(
        lg[0, 8:], out[0, 9:, None], -1)[:, 0]
    assert out.shape == (1, 14) and float(gap.max()) < 0.25


# --------------------------------------------- (c) L layers, R·L planes


def test_weights_for_l_layers_and_a_cache_of_r_times_l_planes(params):
    d, f, v = 64, 96, 128
    per_layer = 4 * d * d + 3 * d * f + 4 * d
    want = 2 * per_layer + 2 * v * d + d + d + 1   # + final norm, gate w, b
    assert sum(a.size for a in jax.tree.leaves(params)) == want
    assert all(a.shape[0] == 2 for a in jax.tree.leaves(params["layers"]))
    cache = jgen.init_cache(CFG, 5)
    assert CFG.kv_planes == 6
    assert cache["k"].shape == cache["v"].shape == (6, 5, 64, 4, 16)


# ----------------------------------------------- (d) the comparison is tight


@pytest.mark.parametrize("fault", ["one_pass_short", "previous_plane"])
def test_a_wrong_reference_fails_the_tolerance(ref, params, fault):
    """The same comparison against a reference that runs R - 1 passes,
    or whose pass r attends pass r - 1's keys and values: off by
    hundreds of tolerances, so neither mistake in the program could
    pass (a)-(b)."""
    toks = _tokens(23)
    served, _ = jtfm.apply(params, jnp.asarray(toks)[None], CFG)
    wrong = ref.logits_at(params, ref.forward(params, TC, toks, fault=fault),
                          np.arange(23))
    assert np.abs(np.asarray(served[0]) - wrong).max() > 100 * TOL


# ------------------------------------------------- (e) the switches off


OLD = {
    "learned": (dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                     d_ff=64, max_len=64),
                ["0x1.7c48060000000p+0", "-0x1.2f46be0000000p-2",
                 "-0x1.f782100000000p-1", "-0x1.5d2ff00000000p+0",
                 "0x1.8a85a20000000p-4", "0x1.8c5f1e0000000p-1"],
                [[37, 54, 43, 43, 43, 43], [32, 32, 46, 32, 0, 0]],
                [5, 5, 5, 5, 5]),
    "rope_gqa": (dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
                      n_layers=2, d_ff=64, max_len=64, rope=True),
                 ["-0x1.0b7ed80000000p+0", "0x1.c4bb680000000p+0",
                  "-0x1.0801740000000p-1", "-0x1.461a840000000p-2",
                  "-0x1.20d28a0000000p+0", "0x1.a0d1000000000p+0"],
                 [[30, 24, 50, 1, 1, 1], [23, 6, 6, 6, 6, 5]],
                 [34, 38, 38, 5, 32]),
}


@pytest.mark.parametrize("name", sorted(OLD))
def test_switches_off_is_the_parent_bit_for_bit(name, monkeypatch):
    """``n_passes=1`` and every new switch at its default: the forward
    gives what commit 42e8483 (the parent of the PR that added the
    switches) gave on these toy configs, logits to the last bit; solo
    ``generate`` and the engine, which now decode through the in-place
    body, give that commit's tokens.  (Recorded from that commit under
    conftest's XLA flags, PR 29: without
    ``--xla_backend_optimization_level=0`` it and this tree both end
    0x1.7c480a..., with it both 0x1.7c4806...; the tokens are the same.)"""
    kwargs, logits_hex, tokens, engine_tokens = OLD[name]
    cfg = tfm.TransformerConfig(**kwargs, n_passes=1, ffn_gated=False,
                                tie_head=True, post_norms=False,
                                fused_qkv=False)
    assert cfg == tfm.TransformerConfig(**kwargs) and not cfg.extended
    # Eager throughout, not toy_params and jtfm: the recorded bits are
    # those of a primitive at a time (a fused program rounds otherwise).
    p = tfm.init_params(jax.random.key(0), cfg)
    prompt = (jnp.arange(14).reshape(2, 7) * 5) % 64
    lg, _ = tfm.apply(p, prompt, cfg)
    assert [float.hex(float(x)) for x in np.asarray(lg[1, -1, :6])] \
        == logits_hex
    assert np.asarray(gen.generate(p, prompt, cfg, 6))[:, 7:].tolist() \
        == tokens
    eng = dk.ContinuousBatcher(p, cfg, lanes=2, max_queue=4, prefill_chunk=8,
                               prompt_buckets=(8,), hot_swap=True)
    rid = eng.enqueue(np.arange(21) % 64, 5)
    while eng.running() or eng._pending:
        eng.step()
    assert [int(t) for t in eng.take(rid).generated] == engine_tokens


# ---------------------------------- (f) what rejects an extended config


def _engine(**kw):
    return lambda p: dk.ContinuousBatcher(p, CFG, lanes=2, **kw)


def _prompt():
    return jnp.asarray(_tokens(6))[None]


WINDOWED = dataclasses.replace(CFG, attention_window=16)
REJECTED = {
    "PagedBatcher": lambda p: dk.PagedBatcher(p, CFG, lanes=2, block=16),
    "PrefixPool": lambda p: dk.PrefixPool(CFG, slots=2),
    "SpeculativeBatcher": lambda p: dk.SpeculativeBatcher(
        p, p, CFG, CFG, lanes=2),
    "speculative_generate": lambda p: spec.speculative_generate(
        p, p, _prompt(), CFG, CFG, 4),
    "beam_search": lambda p: gen.beam_search(p, _prompt(), CFG, 4),
    "kv_int8": lambda p: generate(p, _prompt(), CFG, 4, kv_int8=True),
    "ContinuousBatcher with kv_int8=": _engine(kv_int8=True),
    "rolling lanes": lambda p: dk.ContinuousBatcher(p, WINDOWED, lanes=2),
    "windowed (rolling)": lambda p: generate(p, _prompt(), WINDOWED, 4),
    "ragged-prompt": lambda p: generate(
        p, _prompt(), CFG, 4, prompt_lengths=np.asarray([4])),
    "prompt_cache": lambda p: generate(
        p, _prompt(), CFG, 4,
        prompt_cache=(jgen.init_cache(CFG, 1), 6)),
    "ContinuousBatcher with prompt_cache=": _engine(
        prompt_cache=(None, 4)),
    "ContinuousBatcher with prefix_pool=": _engine(prefix_pool=object()),
    "ContinuousBatcher with lane_tiers=": _engine(lane_tiers=(2, 4),
                                                  max_queue=4),
    "serving_plan": _engine(plan=object(), mesh=object()),
    "apply_pipelined": lambda p: tfm.apply_pipelined(
        p, _prompt(), CFG, mesh=None, microbatches=1),
    "lm_loss": lambda p: tfm.lm_loss(p, _prompt(), CFG),
    "lm_nll": lambda p: tfm.lm_nll(p, _prompt(), CFG),
    "LMTrainer": lambda p: dk.LMTrainer(CFG),
    "LMTrainer (fused_qkv": lambda p: dk.LMTrainer(
        tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                              n_layers=2, d_ff=48, fused_qkv=True)),
    "apply_pipelined (gated": lambda p: tfm.apply_pipelined(
        p, _prompt(), dataclasses.replace(CFG, n_passes=1), mesh=None,
        microbatches=1),
    "MoE": lambda p: tfm.init_params(
        jax.random.key(0), dataclasses.replace(CFG, num_experts=4)),
}


@pytest.mark.parametrize("path", sorted(REJECTED))
def test_rejected_path_raises_and_names_itself(params, path):
    with pytest.raises(ValueError) as err:
        REJECTED[path](params)
    assert path.split(" (")[0] in str(err.value), str(err.value)
    assert "does not support" in str(err.value)


def test_multi_token_chunks_at_per_row_positions(ref, params):
    """Speculative verification's shape — rows at DIFFERENT positions,
    several tokens each — through the in-place body: one window a row
    over all planes."""
    a, b = _tokens(20, 5), _tokens(20, 6)
    want = [_ref_logits(ref, params, s) for s in (a, b)]
    cache = jgen.init_cache(CFG, 2)
    for row, (seq, n) in enumerate(((a, 9), (b, 4))):
        _, cache = jgen._decode_chunk(
            params, cache, jnp.asarray(seq[:n])[None],
            jnp.zeros((1,), jnp.int32), CFG, uniform_pos=True,
            lane=jnp.int32(row))
    lg, cache = jgen._decode_chunk(
        params, cache, jnp.asarray(np.stack([a[9:12], b[4:7]])),
        jnp.asarray([9, 4], jnp.int32), CFG)
    np.testing.assert_allclose(lg[0], want[0][9:12], atol=TOL)
    np.testing.assert_allclose(lg[1], want[1][4:7], atol=TOL)
    lg, _ = jgen._decode_chunk(
        params, cache, jnp.asarray([[a[12]], [b[7]]]),
        jnp.asarray([12, 7], jnp.int32), CFG)
    np.testing.assert_allclose(lg[0, 0], want[0][12], atol=TOL)
    np.testing.assert_allclose(lg[1, 0], want[1][7], atol=TOL)


LAYOUTS = {
    "split": dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
                  n_layers=2, d_ff=64, max_len=32, rope=True),
    "fused_gated": dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
                        n_layers=2, d_ff=48, max_len=32, rope=True,
                        ffn_gated=True, fused_qkv=True),
    "split_gated_untied": dict(vocab_size=64, d_model=32, n_heads=2,
                               n_layers=2, d_ff=48, max_len=32,
                               ffn_gated=True, tie_head=False,
                               post_norms=True),
}


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_in_place_body_equals_the_full_forward(name):
    """Either layout of the projections, gated or not, learned or
    rotary positions: logits through the cache (a chunk, then single
    tokens) are ``apply``'s."""
    cfg = tfm.TransformerConfig(**LAYOUTS[name])
    p = toy_params(cfg, 2)
    assert ("wqkv" in p["layers"]["attn"]) == cfg.fused_qkv
    toks = jnp.asarray(_tokens(12, 8) % 64)[None]
    want, _ = jtfm.apply(p, toks, cfg)
    lg, cache = jgen._decode_chunk(p, jgen.init_cache(cfg, 1), toks[:, :7],
                                  jnp.zeros((1,), jnp.int32), cfg,
                                  uniform_pos=True)
    np.testing.assert_allclose(lg[0], want[0, :7], atol=TOL)
    for pos in range(7, 12):
        lg, cache = jgen._decode_step(p, cache, toks[:, pos], pos, cfg)
        np.testing.assert_allclose(lg[0], want[0, pos], atol=TOL)


def test_fused_layout_is_the_split_layout_rearranged():
    """``fused_qkv`` is a layout: the same draws, the same function."""
    kw = dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
              n_layers=2, d_ff=64, max_len=32, rope=True)
    split, fused = (tfm.TransformerConfig(**kw, fused_qkv=f)
                    for f in (False, True))
    ps, pf = (toy_params(c, 3) for c in (split, fused))
    a = ps["layers"]["attn"]
    np.testing.assert_array_equal(
        pf["layers"]["attn"]["wqkv"],
        jnp.concatenate([a[w].reshape(2, 32, -1)
                         for w in ("wq", "wk", "wv")], -1))
    toks = jnp.asarray(_tokens(10, 9) % 64)[None]
    np.testing.assert_allclose(jtfm.apply(pf, toks, fused)[0],
                               jtfm.apply(ps, toks, split)[0], atol=1e-5)


@pytest.mark.parametrize("fsdp", [False, True])
def test_extended_block_trains_through_lm_trainer(devices, fsdp):
    """One pass of the gated, untied, sandwich-normed block under
    ``LMTrainer``, data-parallel and FSDP: the loss falls and the new
    leaves are trained."""
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=48, max_len=32,
                                ffn_gated=True, tie_head=False,
                                post_norms=True)
    mesh = make_mesh(MeshSpec(data=8), devices=devices)
    t = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=16, num_epoch=8,
                     mesh=mesh, fsdp=fsdp)
    data = np.random.default_rng(0).integers(0, 64, (64, 17)).astype(np.int32)
    p0 = toy_params(cfg)
    p = t.train(dk.Dataset({"tokens": data}))
    assert t.history[-1] < t.history[0] * 0.9, t.history[::8]
    assert set(p) == set(p0) and p["head"].shape == (64, 32)
    for leaf in ("head", "ln_f_scale"):
        assert float(jnp.abs(p[leaf] - p0[leaf]).max()) > 0
    for leaf in ("ln1_post_scale", "ln2_post_scale"):
        assert float(jnp.abs(p["layers"][leaf] - 1).max()) > 0
    assert float(jnp.abs(p["layers"]["ffn"]["w3"]
                         - p0["layers"]["ffn"]["w3"]).max()) > 0


def test_gated_unlooped_block_trains_through_lm_loss():
    """One pass of the gated, untied, sandwich-normed block shares
    ``block_apply`` with the trunk: its loss is the cross-entropy of
    its own logits and has gradients for the new leaves."""
    cfg = dataclasses.replace(CFG, n_passes=1)
    p = toy_params(cfg)
    toks = jnp.asarray(_tokens(17))[None]
    loss, grads = jax.jit(jax.value_and_grad(tfm.lm_loss),
                          static_argnums=2)(p, toks, cfg)
    logits, _ = jtfm.apply(p, toks[:, :-1], cfg)
    logp = jax.nn.log_softmax(logits, -1)
    want = -jnp.take_along_axis(logp, toks[:, 1:, None], -1).mean()
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    for leaf in (grads["head"], grads["layers"]["ffn"]["w3"],
                 grads["layers"]["ln1_post_scale"]):
        assert float(jnp.abs(leaf).max()) > 0
