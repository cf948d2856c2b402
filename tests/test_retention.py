"""Power-retention layers (models/transformer.py ``layer_types``
``"retention"``; ops/retention.py; the state planes of
models/generate.py ``init_cache`` / ``_chunk_in_place``; the lane
engine's ``live`` mask) against the plain reference
``benchmarks/reference_brumby.py``, loaded by path: float32, seeded
weights, a tiny preset (3 layers, d_model 64, 4 heads of 16, 2 K/V
heads).

(a) the three forms of one function: recurrent = attention = chunked;
    the kernel (interpreter) = the recurrent form, and it leaves a
    lane that does not decode alone;
(b) ``apply`` = the reference; each wrong reference fails by far;
(c) through ``ContinuousBatcher``: chunked prefill with bucket padding
    through the state, decode, more requests than lanes = the
    reference's full forward; a lane that is not decoding keeps its
    state bit for bit; ``generate`` = the engine;
(d) what the engine says of its state planes; the paths that do not
    run the kind say so by name.
"""

import dataclasses
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import obs
from distkeras_tpu.models import generate as gen
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.obs import read_trace
from distkeras_tpu.ops import retention as ret
from helpers import generate, jgen, jtfm, toy_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TC = dict(vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, n_layers=3,
          d_ff=96, max_len=64, rope=True, rope_theta=1e6, dtype="float32",
          ffn_gated=True, tie_head=False, fused_qkv=True, qk_norm=True,
          layer_types=["retention"] * 3, ffn_types=["dense"] * 3)
CFG = tfm.TransformerConfig(**TC)
TOL = 2e-4          # float32 against float32: rounding order only
FAULTS = ["degree_1", "no_gate", "no_normaliser", "state_bf16", "kv_float8",
          "matmul_float8", "no_rope", "wrong_kv_head"]


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(REPO, "benchmarks", "reference_brumby.py")
    s = importlib.util.spec_from_file_location("reference_brumby", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    """Every norm scale moved off its initial 1, so that a misplaced
    or missing norm shows."""
    leaves, treedef = jax.tree.flatten(toy_params(CFG))
    keys = jax.random.split(jax.random.key(1), len(leaves))
    return jax.tree.unflatten(treedef, [
        a + 0.3 * jax.random.normal(k, a.shape) if a.ndim <= 2
        and (a == a.reshape(-1)[0]).all() else a
        for a, k in zip(leaves, keys)])


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


def _ref_logits(ref, params, seq, fault=None, carry=None):
    normed = ref.forward(params, TC, seq, fault=fault, carry=carry)
    return ref.logits_at(params, normed, np.arange(len(seq)))


# ------------------------------------------- (a) three forms, one function


def _qkvg(s=40, h=4, kv=2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    logg = jax.nn.log_sigmoid(3.0 + draw(s, kv))
    return draw(s, h, d), draw(s, kv, d), draw(s, kv, d), logg


def _by_steps(q, k, v, logg):
    """The recurrent form, a token at a time, in lane 0 of plane 1 of a
    slab of two planes and three lanes of which lane 1 does not decode."""
    s, h, d = q.shape
    kv = k.shape[1]
    rows = ret.phi_rows(d)
    s_all = jnp.zeros((2, 3, kv, rows, d, d))
    z_all = jnp.zeros((2, 3, kv, rows, d))
    wide = lambda a: jnp.broadcast_to(a, (3,) + a.shape)
    out = []
    for t in range(s):
        ops = ret.step_operands(wide(q[t]), wide(k[t]), wide(v[t]),
                                wide(logg[t]), jnp.zeros((3,), bool))
        y, s_all, z_all = ret.retention_step(ops, s_all, z_all, jnp.int32(1),
                                             jnp.asarray([1, 0, 1]))
        out.append(y[0, :, :h // kv].reshape(h, d))
    assert not s_all[0].any() and not s_all[1, 1].any()   # left alone
    return jnp.stack(out), s_all[1, 0], z_all[1, 0]


def _by_chunks(q, k, v, logg, chunk):
    """The chunked form, the last chunk padded."""
    s, _, d = q.shape
    kv = k.shape[1]
    rows = ret.phi_rows(d)
    st, z = jnp.zeros((kv, rows, d, d)), jnp.zeros((kv, rows, d))
    out = []
    for c0 in range(0, s, chunk):
        n = min(chunk, s - c0)
        cut = lambda a: jnp.pad(a[c0:c0 + n], ((0, chunk - n),)
                                + ((0, 0),) * (a.ndim - 1))
        y, st, z = ret.retention_chunk(cut(q), cut(k), cut(v), cut(logg),
                                       st, z, n_real=jnp.int32(n))
        out.append(y[:n])
    return jnp.concatenate(out), st, z


@pytest.mark.parametrize("form", ["steps", "chunks_of_8", "chunks_of_32"])
def test_recurrent_and_chunked_forms_are_the_attention_form(form):
    q, k, v, logg = _qkvg()
    want = ret.retention_attention(q[None], k[None], v[None], logg[None])[0]
    steps = _by_steps(q, k, v, logg)
    got = steps if form == "steps" else _by_chunks(
        q, k, v, logg, int(form.rsplit("_", 1)[1]))
    np.testing.assert_allclose(got[0], want, atol=1e-4)
    # ... and every form leaves the same state behind
    np.testing.assert_allclose(got[1], steps[1], atol=1e-4)
    np.testing.assert_allclose(got[2], steps[2], atol=1e-4)


def test_phi_is_the_symmetric_square():
    rng = np.random.default_rng(3)
    for d in (16, 128):
        q, k = (jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
                for _ in range(2))
        qs, ks = ret.scale_qk(q, k)
        assert ret.phi(qs).shape == (5, d // 2 + 1, d)
        np.testing.assert_allclose(
            (ret.phi(qs) * ret.phi(ks)).sum(axis=(1, 2)),
            np.square((q * k).sum(axis=1) / np.sqrt(d)), rtol=1e-4,
            atol=1e-6)
        # d (d + 1) / 2 products, d / 2 zeros
        assert int((ret._phi_coef(d) == 0).sum()) == d // 2


@pytest.mark.parametrize("live", [(1, 0, 1), (0, 1, 0), (0, 0, 0), (0, 0, 1)])
def test_kernel_is_the_recurrent_form_and_skips_other_lanes(live):
    """``ret_state_step`` in the TPU interpreter at the width it is
    built for (heads of 128): the recurrent form's numbers, and a lane
    the mask does not name keeps its state bit for bit — leading,
    between and trailing such lanes, and no lane at all."""
    rng = np.random.default_rng(0)
    b, kv, h, d = 3, 2, 10, 128
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    ops = ret.step_operands(draw(b, h, d), draw(b, kv, d), draw(b, kv, d),
                            jax.nn.log_sigmoid(3.0 + draw(b, kv)),
                            jnp.asarray([False, False, True]))
    s0, z0 = draw(2, b, kv, 65, d, d), 3.0 + draw(2, b, kv, 65, d)
    live = jnp.asarray(live)
    y1, s1, z1 = ret.retention_step(ops, s0, z0, jnp.int32(1), live)
    y2, s2, z2 = ret.ret_state_step(ops, s0 + 0, z0 + 0, jnp.int32(1), live,
                                    interpret=True)
    on = np.asarray(live, bool)
    np.testing.assert_allclose(s2, s1, atol=1e-5)
    np.testing.assert_allclose(z2, z1, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y2)[on], np.asarray(y1)[on],
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_array_equal(s2[0], s0[0])          # the other plane
    np.testing.assert_array_equal(np.asarray(s2[1])[~on],
                                  np.asarray(s0[1])[~on])
    np.testing.assert_array_equal(np.asarray(z2[1])[~on],
                                  np.asarray(z0[1])[~on])


def test_kernel_lowers_for_the_tpu_at_the_published_widths():
    """22 lanes of 8 K/V heads of 128: the Mosaic lowering accepts the
    kernel (its rotations, transposes and aliases) with no chip."""
    sd = jax.ShapeDtypeStruct
    text = jax.jit(ret.ret_state_step.__wrapped__).trace(
        sd((22, 8, 8, 128), jnp.float32),
        sd((8, 22, 8, 65, 128, 128), jnp.float32),
        sd((8, 22, 8, 65, 128), jnp.float32), sd((), jnp.int32),
        sd((22,), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "ret_state_step" in text


def _chunk_inputs(n, kv=2, g=5, d=128, seed=0):
    """``n`` positions at the width the chunk kernel is built for, and
    the slabs ``[2, 3, kv, ...]`` whose lane 2 of plane 1 holds the
    state 48 earlier positions leave (so the normaliser is a sum of
    squares, as an engine's always is); every other block random."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    gate = lambda m: jax.nn.log_sigmoid(3.0 + draw(m, kv))
    _, s, z = jax.jit(ret.retention_chunk)(
        draw(48, kv * g, d), draw(48, kv, d), draw(48, kv, d), gate(48),
        jnp.zeros((kv, 65, d, d)), jnp.zeros((kv, 65, d)))
    s_all = draw(2, 3, kv, 65, d, d).at[1, 2].set(s)
    z_all = draw(2, 3, kv, 65, d).at[1, 2].set(z)
    return (draw(n, kv * g, d), draw(n, kv, d), draw(n, kv, d), gate(n),
            s_all, z_all)


@pytest.mark.parametrize("case", [
    "chunk_of_64", "chunk_of_512", "short_of_the_chunk",
    "fresh_over_garbage", "two_chunks_in_a_row"])
def test_chunk_kernel_is_the_chunked_form(case):
    """``ret_chunk_fwd`` in the TPU interpreter, float32, against
    ``retention_chunk`` on the lane's state cut out of the slabs: the
    outputs, the new state, and every other (plane, lane) of the slabs
    bit for bit what it was."""
    c_len = 512 if case == "chunk_of_512" else 64
    n_real = 41 if case == "short_of_the_chunk" else c_len
    fresh = case in ("fresh_over_garbage", "two_chunks_in_a_row")
    q, k, v, logg, s0, z0 = _chunk_inputs(
        2 * c_len, kv=1 if c_len == 512 else 2)
    if case == "fresh_over_garbage":      # an earlier occupant's sums
        s0, z0 = s0.at[1, 2].multiply(1e6), z0.at[1, 2].multiply(-1e6)
    one, two = slice(0, c_len), slice(c_len, 2 * c_len)
    kernel = lambda part, s, z, n, new: ret.ret_chunk_fwd(
        q[part], k[part], v[part], logg[part], s, z, jnp.int32(1),
        jnp.int32(2), jnp.int32(n), jnp.bool_(new), interpret=True)
    y, s1, z1 = kernel(one, s0 + 0, z0 + 0, n_real, fresh)
    before = ((jnp.zeros_like(s0[1, 2]), jnp.zeros_like(z0[1, 2])) if fresh
              else (s0[1, 2], z0[1, 2]))
    want = jax.jit(ret.retention_chunk)(
        q[one], k[one], v[one], logg[one], *before, jnp.int32(n_real))
    close = lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                    atol=1e-5)
    close(y[:n_real], want[0][:n_real])
    close(s1[1, 2], want[1])
    close(z1[1, 2], want[2])
    if case == "two_chunks_in_a_row":     # = the attention form over both
        y2, s1, z1 = kernel(two, s1, z1, c_len, False)
        whole = ret.retention_attention(q[None], k[None], v[None], logg[None])
        close(jnp.concatenate([y, y2]), whole[0])
    untouched = np.ones((2, 3), bool)
    untouched[1, 2] = False
    for new, old in ((s1, s0), (z1, z0)):
        np.testing.assert_array_equal(np.asarray(new)[untouched],
                                      np.asarray(old)[untouched])


@pytest.mark.parametrize("where,picks", [
    ("the_cell", True), ("head_of_64", False), ("odd_length", False),
    ("on_the_cpu", False)])
def test_the_shapes_pick_the_chunk_kernel(monkeypatch, where, picks):
    """An admission chunk of a retention layer lowers to the Mosaic
    call ``ret_chunk_fwd`` at the cell's shapes (heads of 128, 5 query
    heads a K/V head, one lane, an engine's bucket) on the TPU, and to
    ``retention_chunk``'s plain operations at another head size, a
    length no bucket has, or off the TPU: the shapes decide, no knob."""
    if where != "on_the_cpu":
        monkeypatch.setattr(ret, "_on_tpu", lambda: True)
    head = 64 if where == "head_of_64" else 128
    t_len = 72 if where == "odd_length" else 64
    cfg = tfm.TransformerConfig(**{
        **TC, "d_model": 128, "n_heads": 10, "n_kv_heads": 2, "d_head": head,
        "n_layers": 1, "max_len": 128, "dtype": "bfloat16",
        "layer_types": ["retention"], "ffn_types": ["dense"]})
    assert ret.use_ret_chunk_kernel(head, 5, t_len, 1, jnp.float32) == picks
    assert not ret.use_ret_chunk_kernel(128, 5, 64, 2, jnp.float32)
    assert not ret.use_ret_chunk_kernel(128, 5, 64, 1, jnp.float32,
                                        sharded=True)
    sd = jax.ShapeDtypeStruct
    shapes = lambda tree: jax.tree.map(lambda a: sd(a.shape, a.dtype), tree)
    text = jax.jit(
        lambda p, c, rows, lane, off, n: gen._decode_chunk(
            p, c, rows, off[None], cfg, uniform_pos=True, lane=lane,
            n_real=n)).trace(
        shapes(jax.eval_shape(lambda: toy_params(cfg))),
        shapes(jax.eval_shape(lambda: gen.init_cache(cfg, 3))),
        sd((1, t_len), jnp.int32), sd((), jnp.int32), sd((), jnp.int32),
        sd((), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
    assert ("ret_chunk_fwd" in text) == picks
    assert ("tpu_custom_call" in text) == picks


# ------------------------------------------------ (b) the full forward


def test_apply_equals_the_reference(ref, params):
    toks = _tokens(37)
    logits, _ = jtfm.apply(params, jnp.asarray(toks)[None], CFG)
    assert logits.shape == (1, 37, 96)
    np.testing.assert_allclose(logits[0], _ref_logits(ref, params, toks),
                               atol=TOL)


@pytest.mark.parametrize("fault", FAULTS + ["stale_state"])
def test_a_wrong_reference_fails_the_tolerance(ref, params, fault):
    """Each planted fault moves the logits by tens of tolerances."""
    toks = _tokens(60)
    right = _ref_logits(ref, params, toks)
    wrong = _ref_logits(ref, params, toks, fault=fault, carry=_tokens(20, 9))
    assert np.abs(wrong - right).max() > 20 * TOL


def test_the_references_recurrent_form_is_its_attention_form(ref, params,
                                                             monkeypatch):
    """``state_bf16`` is the reference's own second derivation (the
    full outer-product state); without the rounding it is the first."""
    toks = _tokens(300, 4)       # two blocks: a state is carried
    monkeypatch.setattr(jnp, "bfloat16", jnp.float32)
    ref._layer_fns.cache_clear()
    try:
        got = _ref_logits(ref, params, toks, fault="state_bf16")
    finally:
        ref._layer_fns.cache_clear()
    np.testing.assert_allclose(got, _ref_logits(ref, params, toks), atol=TOL)


def test_the_reference_compiles_one_program_whatever_the_length(ref, params):
    """With ``s_max`` the layer's and the head's programs have one
    shape for every sequence: a benchmark run compiles them once, not
    once a request it checks (a run of the cell once outlasted the
    driver's limit on the reference's compiles alone)."""
    ref._layer_fns.cache_clear()
    ref._head_fn.cache_clear()
    try:
        for n in (20, 300, 700):
            toks = _tokens(n, n)
            normed = ref.forward(params, TC, toks, keep_from=n // 2,
                                 s_max=700)
            assert normed.shape[0] >= n - n // 2
            ref.gaps_at(params, normed, np.arange(n - n // 2),
                        toks[n // 2:])
        kv_fn, block_fn, _ = ref._layer_fns(
            tuple(sorted(ref._spec(TC).items())), None)
        assert kv_fn._cache_size() == 1 and block_fn._cache_size() == 1
        assert ref._head_fn(True)._cache_size() == 1
    finally:
        ref._layer_fns.cache_clear()
        ref._head_fn.cache_clear()


# ----------------------------------------------- (c) through the engine


def _engine(params, **kw):
    return dk.ContinuousBatcher(params, CFG, lanes=2, hot_swap=True,
                                prefill_chunk=8, prompt_buckets=(4, 8),
                                max_queue=8, **kw)


@pytest.fixture(scope="module")
def served(params, tmp_path_factory):
    """Six requests through a two-lane engine with chunked prefill: the
    30-token prompt takes three chunks and a padded tail while the
    other lane decodes; a one-token prompt starts in a lane another
    request held.  ``[(prompt, generated)]`` and the trace's records."""
    path = str(tmp_path_factory.mktemp("ret") / "t.jsonl")
    prompts = [_tokens(n, 10 + n) for n in (9, 30, 3, 1, 21, 14)]
    with obs.session(trace_path=path):
        eng = _engine(params)
        ids = [eng.enqueue(p, 12) for p in prompts]
        while eng.running() or eng._pending:
            eng.step()
        out = [eng.take(i) for i in ids]
    assert all(r.ok for r in out)
    return ([(p, np.asarray(r.generated)) for p, r in zip(prompts, out)],
            read_trace(path))


def test_engine_tokens_are_the_references_best(served, ref, params):
    """As the benchmark's ``check_serving``: teacher-forced through the
    reference's full forward, every token the engine chose — prefilled
    in padded chunks through the state while the other lane decoded,
    decoded beside an admitting lane, in a lane another request held
    before — is its best logit, or within the tolerance of it."""
    for prompt, got in served[0]:
        seq = np.concatenate([prompt, got])
        lg = _ref_logits(ref, params, seq[:-1])[len(prompt) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(got)), got]
        assert gap.max() < TOL


def test_generate_is_the_engine(served, params):
    for prompt, got in served[0]:
        solo = generate(params, jnp.asarray(prompt)[None], CFG, len(got))
        np.testing.assert_array_equal(np.asarray(solo)[0, len(prompt):], got)
    prompt = served[0][0][0]
    seq = jgen.generate(params, jnp.asarray(prompt)[None], CFG, 5,
                        use_prefill=False)
    np.testing.assert_array_equal(np.asarray(seq)[0, len(prompt):],
                                  served[0][0][1][:5])


def test_a_lane_that_is_not_decoding_keeps_its_state(params):
    """Lane 0 decodes; lane 1 is free, then admitting (a 30-token
    prompt: four chunks).  A decode round changes lane 0's state and
    not one bit of lane 1's; an admission chunk changes lane 1's and
    not lane 0's."""
    eng = _engine(params)
    eng.enqueue(_tokens(5, 1), 30)
    state = lambda lane: tuple(np.asarray(eng.cache[k][:, lane])
                               for k in ("s", "z"))
    same = lambda a, b: all((x == y).all() for x, y in zip(a, b))
    eng.step()
    free, mine = state(1), state(0)
    eng.step()
    assert same(free, state(1)) and not same(mine, state(0))
    eng.enqueue(_tokens(30, 2), 4)           # its first chunk ran
    seen = 0
    while eng._admitting:
        held, mine = state(1), state(0)
        n_chunks = len(eng._lane_state[1].chunks)
        eng._dispatch_step(1)                 # a decode round alone
        assert same(held, state(1)) and not same(mine, state(0))
        mine = state(0)
        eng._run_pending_chunk()              # an admission chunk alone
        assert not same(held, state(1)) and same(mine, state(0))
        seen += n_chunks > 0
    assert seen >= 3


# ------------------------------------ (d) what the engine says; the edges


def test_engine_says_its_state_planes(served):
    (ev,) = [r for r in served[1] if r.get("name") == "serving.kv_layout"]
    f = ev["fields"]
    per_lane = 3 * 2 * (9 * 16 * 16 + 9 * 16) * 4   # layers, K/V heads, s + z
    assert (f["planes_state"], f["state_bytes_per_lane"], f["state_dtype"]) \
        == (3, per_lane, "float32")
    assert (f["planes"], f["planes_full"], f["planes_window"]) == (0, 0, 0)
    assert f["slab_bytes"] == 2 * per_lane + 0 * f["slots"]
    rounds = [r["fields"] for r in served[1]
              if r.get("name") == "serving.round"]
    assert all(0 <= r["state_lanes"] <= r["lanes_busy"] <= 2 for r in rounds)
    # a round that decodes one lane beside one that is admitting
    assert any(r["state_lanes"] == 1 and r["lanes_admitting"] == 1
               for r in rounds)
    assert all(r.get("fields", {}).get("attended", 0) == 0 for r in served[1])


def test_retention_programs_hold_their_scopes(params):
    eng = _engine(params)
    for spec in eng.traced_for_analysis():
        text = spec.fn.lower(*spec.args).as_text(debug_info=True)
        (module,) = re.findall(r"module @(\S+)", text)
        step = spec.name.endswith("decode_step")
        assert re.search("step_n" if step else "_admit", module), module
        locs = " ".join(set(re.findall(r'loc\("([^"]+)"', text)))
        for scope in ("ret_gate", "ret_state"):
            assert re.search(rf"attn/{scope}(?![\w.])", locs), scope
        # (a chunk's scope stands in the body of its loop over the K/V
        # heads, whose locations start anew)
        assert step or re.search(r"ret_chunk(?![\w.])", locs)
    assert tfm.RET_SCOPES == ("ret_gate", "ret_state", "ret_chunk")


def test_a_retention_stack_counts_its_planes():
    mixed = dataclasses.replace(
        CFG, layer_types=("retention", "full", "retention"))
    assert (CFG.state_planes, CFG.kv_planes, CFG.kv_ring_planes) == (3, 0, 0)
    assert (mixed.state_planes, mixed.kv_planes) == (2, 1)
    assert mixed.layer_runs == (("retention.dense", 0, 1),
                                ("full.dense", 0, 1),
                                ("retention.dense", 1, 1))
    cache = jax.eval_shape(lambda: gen.init_cache(mixed, 4))
    assert cache["s"].shape == (2, 4, 2, 9, 16, 16)
    assert cache["z"].shape == (2, 4, 2, 9, 16)
    assert cache["s"].dtype == cache["z"].dtype == jnp.float32
    assert cache["k"].shape == (1, 4, 2, 64, 16)
    assert "s" not in jax.eval_shape(lambda: gen.init_cache(
        dataclasses.replace(CFG, layer_types=("full", "window", "full"),
                            sliding_window=8), 4))


def test_state_and_kv_planes_in_one_stack(params):
    """A retention layer beside a full-attention layer: chunk then
    decode through both kinds of plane = ``apply``."""
    mixed = dataclasses.replace(
        CFG, layer_types=("retention", "full", "retention"))
    p = toy_params(mixed)
    toks = jnp.asarray(_tokens(2 * 20).reshape(2, 20))
    full, _ = jtfm.apply(p, toks, mixed)
    lg, cache = jgen._decode_chunk(p, gen.init_cache(mixed, 2), toks[:, :12],
                                   jnp.zeros((2,), jnp.int32), mixed,
                                   uniform_pos=True)
    np.testing.assert_allclose(lg, full[:, :12], atol=TOL)
    for t in range(12, 20):
        lg, cache = jgen._decode_chunk(p, cache, toks[:, t:t + 1],
                                       jnp.full((2,), t, jnp.int32), mixed)
        np.testing.assert_allclose(lg[:, 0], full[:, t], atol=TOL)


@pytest.mark.parametrize("path", [
    "lm_loss", "LMTrainer", "PagedBatcher", "PrefixPool",
    "SpeculativeBatcher", "plan", "kv_int8", "beam_search", "six_heads"])
def test_rejected_path_raises_and_names_the_kind(params, path):
    toks = jnp.asarray(_tokens(12))[None]
    with pytest.raises(ValueError) as err:
        if path == "lm_loss":
            tfm.lm_loss(params, toks, CFG)
        elif path == "LMTrainer":
            dk.LMTrainer(CFG)
        elif path == "PagedBatcher":
            dk.PagedBatcher(params, CFG, lanes=2)
        elif path == "PrefixPool":
            dk.PrefixPool(CFG)
        elif path == "SpeculativeBatcher":
            dk.SpeculativeBatcher(params, params, CFG, CFG)
        elif path == "plan":
            from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
            from distkeras_tpu.parallel.sharding import serving_plan

            dk.ContinuousBatcher(params, CFG, lanes=2, plan=serving_plan(),
                                 mesh=make_mesh(MeshSpec(model=1),
                                                devices=jax.devices()[:1]))
        elif path == "kv_int8":
            dk.ContinuousBatcher(params, CFG, lanes=2, kv_int8=True)
        elif path == "beam_search":
            gen.beam_search(params, toks, CFG, 4)
        else:
            tfm.init_params(jax.random.key(0), dataclasses.replace(
                CFG, n_heads=12, n_kv_heads=2, d_head=16))
    assert "retention" in str(err.value)
