"""Selective state-space (Mamba) layers among full-attention layers
(models/transformer.py ``layer_types`` ``"ssm"``; ops/ssm.py; the ssm
planes of models/generate.py ``init_cache`` / ``_chunk_in_place``; the
lane engine's ``live`` mask) against the plain reference
``benchmarks/reference_jamba.py``, loaded by path: float32, seeded
weights, a tiny Jamba-shaped preset (6 layers, attention at 2 and 5,
d_model 64, inner width 128, 16 states, conv of 4, dt rank 8, 4 heads
of 16 over one K/V head, a tied head, no position encoding).

(a) the forms of one recurrence: step by step = chunked; both kernels
    (interpreter) = their ``jax.numpy`` twins, and the decode kernel
    leaves a lane that does not decode alone;
(b) ``apply`` = the reference; each wrong reference fails by far;
(c) through ``ContinuousBatcher``: chunked prefill with bucket padding
    through the state and the conv's tail, decode beside the full
    planes, more requests than lanes = the reference's full forward; a
    lane reused by a new request starts from a cleared state and tail;
    ``generate`` = the engine;
(d) what the engine says of its ssm planes; the paths that do not run
    the kind say so by name.
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
from distkeras_tpu.ops import ssm
from helpers import generate, jgen, jtfm, toy_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ["ssm", "ssm", "full", "ssm", "ssm", "full"]
TC = dict(vocab_size=96, d_model=64, n_heads=4, n_kv_heads=1, n_layers=6,
          d_ff=96, max_len=64, rope=True, rope_layer_types=[],
          dtype="float32", ffn_gated=True, tie_head=True, fused_qkv=True,
          layer_types=KINDS, ffn_types=["dense"] * 6, ssm_state=16,
          ssm_conv=4, ssm_dt_rank=8, ssm_expand=2)
CFG = tfm.TransformerConfig(**TC)
TOL = 2e-4          # float32 against float32: rounding order only
FAULTS = ["matmul_float8", "state_bf16", "no_dt_norm", "no_b_norm",
          "no_c_norm", "no_d_term", "no_conv_bias", "conv_shift", "no_gate",
          "rope"]


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(REPO, "benchmarks", "reference_jamba.py")
    s = importlib.util.spec_from_file_location("reference_jamba", path)
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


def _draw(rng, *shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _scan_inputs(t_len, n=16, e=256, seed=0):
    """``t_len`` positions of the recurrence's inputs at a Jamba-like
    scale: step sizes around softplus(-3), the published ``A``."""
    rng = np.random.default_rng(seed)
    a = ssm.state_matrix(jnp.log(jnp.broadcast_to(
        jnp.arange(1, n + 1, dtype=jnp.float32)[:, None], (n, e))))
    return (_draw(rng, t_len, e), jax.nn.softplus(_draw(rng, t_len, e) - 3),
            _draw(rng, t_len, n), _draw(rng, t_len, n), a, _draw(rng, e),
            _draw(rng, n, e))


# ------------------------------------------- (a) the forms of one recurrence


@pytest.mark.parametrize("t_len,n_real", [(40, None), (16, None), (37, 21)])
def test_chunked_form_is_the_step_by_step_form(t_len, n_real):
    """Blocks of parallel-prefix scans = one position after the other;
    rows past ``n_real`` neither decay the state nor add to it."""
    x, dt, b, c, a, d, s0 = _scan_inputs(t_len)
    y, s = jax.jit(ssm.ssm_chunked)(x, dt, b, c, a, d, s0, n_real)
    k = t_len if n_real is None else n_real
    y1, s1 = jax.jit(ssm.ssm_steps)(x[:k], dt[:k], b[:k], c[:k], a, d, s0)
    np.testing.assert_allclose(y[:k], y1, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s, s1, rtol=1e-5, atol=1e-5)


def test_conv_tail_is_the_conv_over_the_whole_sequence():
    """The conv of two chunks through the tail the first leaves = the
    conv of the sequence at once; a padded chunk's tail holds its last
    REAL rows; ``keep`` False starts from zeros."""
    rng = np.random.default_rng(3)
    u, w, b = _draw(rng, 2, 20, 8), _draw(rng, 4, 8), _draw(rng, 8)
    zero = jnp.zeros((2, 3 * 8))
    whole, _ = ssm.causal_conv(u, zero, w, b)
    x1, tail = ssm.causal_conv(u[:, :12], zero, w, b)
    x2, _ = ssm.causal_conv(u[:, 12:], tail, w, b)
    np.testing.assert_allclose(jnp.concatenate([x1, x2], 1), whole, atol=1e-6)
    # one row at a time (a decode step's form) is the same conv
    steps, tail = [], zero
    for t in range(20):
        xt, tail = ssm.causal_conv(u[:, t:t + 1], tail, w, b)
        steps.append(xt)
    np.testing.assert_allclose(jnp.concatenate(steps, 1), whole, atol=1e-6)
    _, tail = ssm.causal_conv(jnp.pad(u[:, :9], ((0, 0), (0, 7), (0, 0))),
                              zero, w, b, n_real=9)
    np.testing.assert_array_equal(tail, u[:, 6:9].reshape(2, 3 * 8))
    garbage = 1e6 + zero
    keep = jnp.asarray([False, False])
    fresh, _ = ssm.causal_conv(u, garbage, w, b, keep=keep)
    np.testing.assert_array_equal(fresh, whole)
    fresh, _ = ssm.causal_conv(u[:, :1], garbage, w, b, keep=keep)
    np.testing.assert_array_equal(fresh, whole[:, :1])


@pytest.mark.parametrize("live", [(1, 0, 1), (0, 1, 0), (0, 0, 0), (0, 0, 1)])
def test_step_kernel_is_the_step_and_skips_other_lanes(live):
    """``ssm_state_step`` in the TPU interpreter: the one-step form's
    numbers, a fresh lane's garbage cleared, and a lane the mask does
    not name keeps its state bit for bit — leading, between and
    trailing such lanes, and no lane at all."""
    rng = np.random.default_rng(0)
    b, n, e = 3, 16, 256
    x, dt, bm, cm, a, d, _ = _scan_inputs(b, n, e)
    rows, bc = ssm.step_operands(x, dt, bm, cm,
                                 jnp.asarray([True, False, True]))
    s0 = _draw(rng, 2, b, n, e)
    live = jnp.asarray(live)
    y1, s1 = ssm.ssm_step(rows, bc, a, d, s0, jnp.int32(1), live)
    y2, s2 = ssm.ssm_state_step(rows, bc, a, d, s0 + 0, jnp.int32(1), live,
                                interpret=True)
    on = np.asarray(live, bool)
    np.testing.assert_allclose(s2, s1, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y2)[on], np.asarray(y1)[on],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(s2[0], s0[0])            # the other plane
    np.testing.assert_array_equal(np.asarray(s2[1])[~on],
                                  np.asarray(s0[1])[~on])
    if on[1]:                                              # the fresh lane
        want, _ = ssm.ssm_steps(x[1:2], dt[1:2], bm[1:2], cm[1:2], a, d,
                                jnp.zeros((n, e)))
        np.testing.assert_allclose(y2[1], want[0], rtol=1e-5, atol=1e-5)


def test_kernels_lower_for_the_tpu_at_the_published_widths():
    """540 lanes of 16 x 5120 states over 26 planes, a 512-row chunk:
    the Mosaic lowering accepts both kernels with no chip."""
    sd = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    slab = sd((26, 540, 16, 5120), f32)
    text = jax.jit(ssm.ssm_state_step.__wrapped__).trace(
        sd((540, 2, 5120), f32), sd((540, 16, 3), f32), sd((16, 5120), f32),
        sd((5120,), f32), slab, sd((), i32), sd((540,), i32)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "ssm_state_step" in text
    text = jax.jit(ssm.ssm_chunk_fwd.__wrapped__).trace(
        sd((512, 5120), f32), sd((512, 5120), f32), sd((512, 16), f32),
        sd((512, 16), f32), sd((16, 5120), f32), sd((5120,), f32), slab,
        sd((), i32), sd((), i32), sd((), i32), sd((), jnp.bool_)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "ssm_chunk_fwd" in text


CHUNK_CASES = {                  # case: (rows of the chunk, n_real, fresh)
    "chunk_of_64": (64, 64, False),
    "short_of_the_chunk": (64, 41, False),
    "fresh_over_garbage": (64, 64, True),
    "two_chunks_in_a_row": (64, 64, True),
    "one_row": (64, 1, False),
    "under_a_group": (64, 7, False),
    "one_group": (64, 8, False),
    "one_past_a_group": (64, 9, False),
    "chunk_of_128": (128, 128, False),
    "fresh_short_of_a_group": (64, 45, True)}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_chunk_kernel_is_the_chunked_form(case):
    """``ssm_chunk_fwd`` in the TPU interpreter against the chunked form
    on the lane's state cut out of the slab: the outputs, the new state,
    and every other (plane, lane) of the slab bit for bit what it was —
    at row counts under, at, and one past a group of the kernel's row
    loop (``ssm.GROUP``), a whole chunk, and a ``fresh`` one whose last
    group is part padding."""
    t_len, n_real, fresh = CHUNK_CASES[case]
    x, dt, bm, cm, a, d, s = _scan_inputs(128, seed=1)
    s0 = _draw(np.random.default_rng(2), 2, 3, 16, 256).at[1, 2].set(s)
    if fresh:
        s0 = s0.at[1, 2].multiply(1e6)
    one, two = slice(0, t_len), slice(64, 128)
    kernel = lambda part, slab, k, new: ssm.ssm_chunk_fwd(
        x[part], dt[part], bm[part], cm[part], a, d, slab, jnp.int32(1),
        jnp.int32(2), jnp.int32(k), jnp.bool_(new), interpret=True)
    y, s1 = kernel(one, s0 + 0, n_real, fresh)
    before = jnp.zeros_like(s) if fresh else s
    want_y, want_s = ssm.ssm_chunked(x[one], dt[one], bm[one], cm[one], a, d,
                                     before, n_real)
    close = lambda p, q: np.testing.assert_allclose(p, q, rtol=1e-5,
                                                    atol=1e-5)
    close(y[:n_real], want_y[:n_real])
    close(s1[1, 2], want_s)
    np.testing.assert_array_equal(y[n_real:], 0.0)
    if case == "two_chunks_in_a_row":     # = the step-by-step form over both
        y2, s1 = kernel(two, s1, 64, False)
        whole, _ = ssm.ssm_steps(x, dt, bm, cm, a, d, jnp.zeros_like(s))
        close(jnp.concatenate([y, y2]), whole)
    untouched = np.ones((2, 3), bool)
    untouched[1, 2] = False
    np.testing.assert_array_equal(np.asarray(s1)[untouched],
                                  np.asarray(s0)[untouched])


@pytest.mark.parametrize("where,picks", [
    ("the_cell", True), ("odd_length", False), ("on_the_cpu", False)])
def test_the_shapes_pick_the_kernels(monkeypatch, where, picks):
    """An admission chunk of an ssm layer lowers to ``ssm_chunk_fwd`` and
    a decode step to ``ssm_state_step`` on the TPU at an engine's shapes;
    a length no bucket has keeps the chunked form, and off the TPU both
    are plain operations: the shapes decide, no knob."""
    if where != "on_the_cpu":
        monkeypatch.setattr(ssm, "_on_tpu", lambda: True)
    t_len = 72 if where == "odd_length" else 64
    cfg = dataclasses.replace(CFG, d_model=128, n_heads=2, dtype="bfloat16",
                              max_len=128)
    assert not ssm.use_ssm_chunk_kernel(256, 64, 2, jnp.float32)
    assert not ssm.use_ssm_kernel(256, jnp.float32, sharded=True)
    sd = jax.ShapeDtypeStruct
    shapes = lambda tree: jax.tree.map(lambda a: sd(a.shape, a.dtype), tree)
    p = shapes(jax.eval_shape(lambda: toy_params(cfg)))
    c = shapes(jax.eval_shape(lambda: gen.init_cache(cfg, 3)))
    text = jax.jit(
        lambda p, c, rows, lane, off, n: gen._decode_chunk(
            p, c, rows, off[None], cfg, uniform_pos=True, lane=lane,
            n_real=n)).trace(
        p, c, sd((1, t_len), jnp.int32), sd((), jnp.int32), sd((), jnp.int32),
        sd((), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
    assert ("ssm_chunk_fwd" in text) == picks
    text = jax.jit(lambda p, c, tok, pos, live: gen._decode_chunk(
        p, c, tok, pos, cfg, live=live)).trace(
        p, c, sd((3, 1), jnp.int32), sd((3,), jnp.int32),
        sd((3,), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
    assert ("ssm_state_step" in text) == (where != "on_the_cpu")


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


def test_the_reference_compiles_one_program_whatever_the_length(ref, params):
    """With ``s_max`` the layers' and the head's programs have one
    shape for every sequence: a benchmark run compiles them once, not
    once a request it checks."""
    ref._layer_fns.cache_clear()
    ref._head_fn.cache_clear()
    try:
        for n in (20, 600, 700):
            toks = _tokens(n, n)
            normed = ref.forward(params, TC, toks, keep_from=n // 2,
                                 s_max=1024)
            assert normed.shape[0] >= n - n // 2
            ref.gaps_at(params, normed, np.arange(n - n // 2),
                        toks[n // 2:])
        fns = ref._layer_fns(tuple(sorted(ref._spec(TC).items())), None)
        assert [f._cache_size() for f in fns] == [1, 1, 1]
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
    path = str(tmp_path_factory.mktemp("ssm") / "t.jsonl")
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


def test_engine_logits_are_the_references(ref, params):
    """The engine's own logits, admission chunk by chunk (with padded
    tails) and then step by step, = the reference's full forward:
    chunked prefill through the state, the conv's tail and the full
    planes, then decode against all three."""
    prompt = _tokens(27, 5)
    eng = _engine(params)
    rid = eng.enqueue(prompt, 10)
    while eng.running() or eng._pending:
        eng.step()
    got = np.asarray(eng.take(rid).generated)
    seq = np.concatenate([prompt, got])
    want = _ref_logits(ref, params, seq[:-1])
    lg, cache = jgen._decode_chunk(
        params, gen.init_cache(CFG, 1), jnp.asarray(seq[None, :16]),
        jnp.zeros((1,), jnp.int32), CFG, uniform_pos=True)
    np.testing.assert_allclose(lg[0], want[:16], atol=TOL)
    lg, cache = jgen._decode_chunk(
        params, cache, jnp.asarray(np.pad(seq[16:27], (0, 5))[None]),
        jnp.full((1,), 16, jnp.int32), CFG, uniform_pos=True,
        n_real=jnp.int32(11))
    np.testing.assert_allclose(lg[0, :11], want[16:27], atol=TOL)
    for t in range(27, len(seq) - 1):
        lg, cache = jgen._decode_chunk(params, cache,
                                       jnp.asarray(seq[None, t:t + 1]),
                                       jnp.full((1,), t, jnp.int32), CFG)
        np.testing.assert_allclose(lg[0, 0], want[t], atol=TOL)
    assert (want[26:].argmax(-1) == got).all()


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


def test_a_reused_lane_starts_from_a_cleared_state(params, ref):
    """A lane's second occupant: its state and conv tail at the end of
    its first chunk are what the same chunk leaves in a fresh engine,
    and its tokens are the reference's whatever the first left."""
    first, second = _tokens(13, 7), _tokens(10, 8)
    eng = dk.ContinuousBatcher(params, CFG, lanes=1, hot_swap=True,
                               prefill_chunk=8, prompt_buckets=(4, 8),
                               max_queue=8)
    done = []
    for p in (first, second):
        rid = eng.enqueue(p, 6)
        while eng.running() or eng._pending:
            eng.step()
        done.append(np.asarray(eng.take(rid).generated))
    fresh = dk.ContinuousBatcher(params, CFG, lanes=1, hot_swap=True,
                                 prefill_chunk=8, prompt_buckets=(4, 8),
                                 max_queue=8)
    rid = fresh.enqueue(second, 6)
    while fresh.running() or fresh._pending:
        fresh.step()
    np.testing.assert_array_equal(np.asarray(fresh.take(rid).generated),
                                  done[1])
    for key in ("ssm", "conv"):
        np.testing.assert_allclose(eng.cache[key], fresh.cache[key],
                                   atol=1e-6)
    seq = np.concatenate([second, done[1]])
    lg = _ref_logits(ref, params, seq[:-1])[len(second) - 1:]
    assert (lg.argmax(-1) == done[1]).all()


def test_a_lane_that_is_not_decoding_keeps_its_state(params):
    """Lane 0 decodes; lane 1 is free, then admitting (a 30-token
    prompt: four chunks).  A decode round changes lane 0's state and
    tail and not one bit of lane 1's; an admission chunk changes lane
    1's and not lane 0's."""
    eng = _engine(params)
    eng.enqueue(_tokens(5, 1), 30)
    state = lambda lane: tuple(np.asarray(eng.cache[k][:, lane])
                               for k in ("ssm", "conv"))
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


def test_engine_says_its_ssm_planes(served):
    (ev,) = [r for r in served[1] if r.get("name") == "serving.kv_layout"]
    f = ev["fields"]
    per_lane = 4 * (16 * 128 * 4 + 3 * 128 * 4)      # layers, state + tail
    assert (f["planes_ssm"], f["ssm_bytes_per_lane"]) == (4, per_lane)
    assert (f["planes"], f["planes_full"], f["planes_state"]) == (2, 2, 0)
    kv_lane = 2 * 2 * 64 * 16 * 4          # two planes of k and v, 64 slots
    assert f["slab_bytes"] == 2 * (per_lane + kv_lane)
    rounds = [r["fields"] for r in served[1]
              if r.get("name") == "serving.round"]
    assert all(0 <= r["state_lanes"] <= r["lanes_busy"] <= 2 for r in rounds)
    assert any(r["state_lanes"] == 1 and r["lanes_admitting"] == 1
               for r in rounds)


def test_ssm_programs_hold_their_scopes(params):
    eng = _engine(params)
    for spec in eng.traced_for_analysis():
        text = spec.fn.lower(*spec.args).as_text(debug_info=True)
        locs = " ".join(set(re.findall(r'loc\("([^"]+)"', text)))
        assert re.search(r"attn_proj/ssm_proj(?![\w.])", locs)
        for scope in ("ssm_conv", "ssm_scan"):
            assert re.search(rf"attn/{scope}(?![\w.])", locs), scope
    assert tfm.SSM_SCOPES == ("ssm_proj", "ssm_conv", "ssm_scan")


def test_an_ssm_stack_counts_its_planes():
    assert (CFG.ssm_planes, CFG.kv_planes, CFG.state_planes) == (4, 2, 0)
    assert CFG.holds_state and CFG.ssm_inner == 128
    assert CFG.layer_runs == (("ssm.dense", 0, 2), ("full.dense", 0, 1),
                              ("ssm.dense", 2, 2), ("full.dense", 1, 1))
    cache = jax.eval_shape(lambda: gen.init_cache(CFG, 5))
    assert cache["ssm"].shape == (4, 5, 16, 128)
    assert cache["conv"].shape == (4, 5, 3 * 128)
    assert cache["ssm"].dtype == jnp.float32
    assert cache["k"].shape == (2, 5, 1, 64, 16)
    # no position encoding anywhere: no table, no layer rotates
    assert "pos_emb" not in jax.eval_shape(lambda: toy_params(CFG))
    assert not any(tfm.layer_rotates(CFG, k) for k in CFG.layer_kinds)


@pytest.mark.parametrize("bad", ["no_dt_rank", "keys_without_layers",
                                 "conv_of_one"])
def test_ssm_keys_go_together(bad):
    with pytest.raises(ValueError, match="ssm"):
        if bad == "no_dt_rank":
            cfg = dataclasses.replace(CFG, ssm_dt_rank=None)
        elif bad == "keys_without_layers":
            cfg = dataclasses.replace(CFG, layer_types=("full",) * 6)
        else:
            cfg = dataclasses.replace(CFG, ssm_conv=1)
        tfm.init_params(jax.random.key(0), cfg)


@pytest.mark.parametrize("path", [
    "lm_loss", "LMTrainer", "PagedBatcher", "PrefixPool",
    "SpeculativeBatcher", "plan", "prompt_cache", "kv_int8", "beam_search",
    "apply_pipelined", "kv_slab_specs"])
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
        elif path in ("plan", "apply_pipelined"):
            from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
            from distkeras_tpu.parallel.sharding import serving_plan

            if path == "plan":
                dk.ContinuousBatcher(
                    params, CFG, lanes=2, plan=serving_plan(),
                    mesh=make_mesh(MeshSpec(model=1),
                                   devices=jax.devices()[:1]))
            else:
                tfm.apply_pipelined(
                    params, toks, CFG, make_mesh(
                        MeshSpec(pipeline=1), devices=jax.devices()[:1]), 1)
        elif path == "prompt_cache":
            dk.ContinuousBatcher(params, CFG, lanes=2, prompt_cache=object())
        elif path == "kv_int8":
            dk.ContinuousBatcher(params, CFG, lanes=2, kv_int8=True)
        elif path == "beam_search":
            gen.beam_search(params, toks, CFG, 4)
        else:
            from distkeras_tpu.parallel.rules import kv_slab_specs

            kv_slab_specs(gen.init_cache(CFG, 2), "model")
    assert "ssm" in str(err.value)
