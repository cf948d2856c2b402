"""Latent attention (models/transformer.py ``layer_types: "latent"``;
models/generate.py ``_chunk_in_place``'s absorbed path over a plane of
latent rows; ops/latent.py; the lane slab's fourth kind of plane)
against the plain reference ``benchmarks/reference_joyai.py`` — the
EXPANDED form, no cache — loaded by path: float32, seeded weights, tiny
sizes, a latent + sparse stack.

(a) ``apply`` = the reference; each wrong reference fails by far;
(b) the cached path = the reference's full forward: a chunked prefill
    with a bucket-padded tail, decode beside a parked lane, a lane with
    an earlier occupant; absorbed = expanded on the same weights;
(c) ``mla_decode_attention`` in interpret mode = its ``jax.numpy``
    twin;
(d) through ``ContinuousBatcher``; what the engine says of its slab
    and its rounds;
(e) the paths that do not run the kind say so by name.
"""

import dataclasses
import importlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import obs
from distkeras_tpu.models import generate as gen
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.obs import read_trace
from distkeras_tpu.ops import latent as mla
from helpers import jgen, jtfm, toy_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TC = dict(vocab_size=96, d_model=64, n_heads=4, n_layers=3, d_ff=96,
          max_len=64, rope=True, rope_theta=1e4, dtype="float32",
          ffn_gated=True, tie_head=False, norm_eps=1e-6,
          layer_types=["latent"] * 3, ffn_types=["dense", "sparse", "sparse"],
          q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
          qk_rope_head_dim=8, v_head_dim=16, num_experts=16, moe_top_k=4,
          moe_held=[0, 1, 2, 3], moe_d_ff=48, moe_shared=1,
          moe_route_scale=2.5)
CFG = tfm.TransformerConfig(**TC)
TOL = 2e-4          # float32 against float32: rounding order only
FAULTS = ["kv_float8", "matmul_float8", "scale_nope", "no_kv_norm",
          "no_q_norm", "rope_halves", "no_k_rope", "top_k_less_one",
          "no_select_bias", "no_route_scale", "no_shared_expert"]


@pytest.fixture(scope="module")
def ref():
    """``benchmarks/reference_joyai.py`` as the benchmark imports it
    (its directory on the path: it takes the router and the head from
    its neighbours), in blocks that fit the tiny sequences."""
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        mod = importlib.import_module("reference_joyai")
    finally:
        sys.path.remove(os.path.join(REPO, "benchmarks"))
    mod.Q_BLOCK, mod.K_BLOCK = 16, 32
    return mod


@pytest.fixture(scope="module")
def params():
    return toy_params(CFG)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


def _ref_logits(ref, params, seq, fault=None):
    normed = ref.forward(params, TC, seq, fault=fault)
    return ref.logits_at(params, normed, np.arange(len(seq)))


# ------------------------------------------------ (a) the full forward


def test_apply_equals_the_reference(ref, params):
    toks = _tokens(37)
    logits, _ = jtfm.apply(params, jnp.asarray(toks)[None], CFG)
    assert logits.shape == (1, 37, 96)
    np.testing.assert_allclose(logits[0], _ref_logits(ref, params, toks),
                               atol=TOL)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_wrong_reference_fails_the_tolerance(ref, params, fault):
    """Each planted fault moves the logits by hundreds of tolerances."""
    toks = _tokens(37)
    right = _ref_logits(ref, params, toks)
    wrong = _ref_logits(ref, params, toks, fault=fault)
    assert np.abs(wrong - right).max() > 100 * TOL


# ------------------------------------------- (b) through the latent cache


def test_prefill_and_decode_through_the_cache_equal_the_reference(ref,
                                                                  params):
    """Lane 1 of three: an earlier occupant's 30 rows, then the request
    — a chunk of 8, one of 8, a bucket-padded tail of 8 for 5 tokens
    (its padding lands past the frontier, masked), then four decode
    steps beside a lane parked at ``max_len - 1`` that reads nothing
    and a lane that decodes a request of its own: every logit is the
    expanded reference's."""
    chunk = jax.jit(gen._decode_chunk, static_argnames=(
        "cfg", "uniform_pos"))
    at = lambda p: jnp.asarray([p], jnp.int32)
    cache = gen.init_cache(CFG, 3)
    stale, mine, other = _tokens(30, 1), _tokens(25, 2), _tokens(9, 3)
    _, cache = chunk(params, cache, stale[None], at(0), cfg=CFG,
                     uniform_pos=True, lane=jnp.int32(1))
    rows = np.zeros((1, 24), np.int32)
    rows[0, :21] = mine[:21]
    got = []
    for lo in (0, 8, 16):
        lg, cache = chunk(params, cache, rows[:, lo:lo + 8], at(lo),
                          cfg=CFG, uniform_pos=True, lane=jnp.int32(1))
        got.append(lg[0])
    _, cache = chunk(params, cache, other[None, :8], at(0), cfg=CFG,
                     uniform_pos=True, lane=jnp.int32(2))
    want = _ref_logits(ref, params, mine)
    np.testing.assert_allclose(jnp.concatenate(got)[:21], want[:21],
                               atol=TOL)
    want_other = _ref_logits(ref, params, other)
    for t in range(21, 25):
        cur = jnp.asarray([0, mine[t], other[8]], jnp.int32)[:, None]
        pos = jnp.asarray([63, t, 8], jnp.int32)
        lg, _ = chunk(params, cache, cur, pos, cfg=CFG,
                      live=jnp.asarray([0, 1, 1]))
        # (lane 2's step is not kept: it decodes position 8 each time)
        lg1, cache = chunk(params, cache, cur, pos, cfg=CFG,
                           live=jnp.asarray([0, 1, 0]))
        np.testing.assert_allclose(lg1[1, 0], want[t], atol=TOL)
        np.testing.assert_allclose(lg[1, 0], want[t], atol=TOL)
        np.testing.assert_allclose(lg[2, 0], want_other[8], atol=TOL)
    assert np.isfinite(np.asarray(lg1)).all()   # the lanes that read nothing


def test_a_chunk_through_the_expanded_kernel_equals_the_reference(
        ref, params, monkeypatch):
    """What a TPU's admission runs (``latent_chunk_expands``), in the
    interpreter: the chunk's rows written into the slab in place, layer
    by layer, and ``mla_prefix_attention`` reading the lane's blocks
    from it — behind an earlier occupant's rows, three chunks into lane
    1 of two; lane 0 and the slots past the chunks keep what they
    held."""
    import functools

    monkeypatch.setattr(gen, "use_mla_prefix", lambda *a, **k: True)
    monkeypatch.setattr(gen, "mla_prefix_attention", functools.partial(
        mla.mla_prefix_attention, block_q=8, block_k=16, interpret=True))
    at = lambda p: jnp.asarray([p], jnp.int32)
    mine = _tokens(24, 2)
    cache = gen.init_cache(CFG, 2)
    cache["lat"] = cache["lat"] + 7.0          # an earlier occupant's
    got = []
    for lo in (0, 8, 16):
        lg, cache = gen._decode_chunk(params, cache, mine[None, lo:lo + 8],
                                      at(lo), CFG, uniform_pos=True,
                                      lane=jnp.int32(1))
        got.append(lg[0])
    np.testing.assert_allclose(jnp.concatenate(got),
                               _ref_logits(ref, params, mine), atol=TOL)
    lat = np.asarray(cache["lat"])
    assert (lat[:, 0] == 7.0).all() and (lat[:, 1, 24:] == 7.0).all()
    assert (lat[:, 1, :24, 40:] == 0).all()    # zeros past the 40 values


def test_absorbed_equals_expanded_on_the_same_weights(params):
    """``generate`` (prefill as a chunk, then the absorbed decode
    through the cache) picks what ``apply`` (the expanded form, no
    cache) scores best, token for token."""
    prompt = _tokens(11, 4)
    out = np.asarray(jgen.generate(params, jnp.asarray(prompt)[None], CFG, 9))
    assert out.shape == (1, 20) and (out[0, :11] == prompt).all()
    logits, _ = jtfm.apply(params, jnp.asarray(out[:, :-1]), CFG)
    gap = logits[0].max(-1) - logits[0][np.arange(19), out[0, 1:]]
    assert float(gap[10:].max()) < TOL


# ---------------------------------------------------- (c) the kernel


@pytest.mark.parametrize("block_k", [16, 64])
def test_mla_decode_kernel_equals_its_twin(block_k):
    """In the interpreter: lanes at position 0 (nothing read), 1, a
    block's edge, inside a last block's part, the whole row; the plane
    by its index; float32 rows."""
    ks = jax.random.split(jax.random.key(0), 2)
    lat = jax.random.normal(ks[0], (2, 6, 64, 256), jnp.float32)
    q = jax.random.normal(ks[1], (6, 8, 256), jnp.float32)
    pos = jnp.asarray([0, 1, 16, 37, 64, 0], jnp.int32)
    for plane in (0, 1):
        want, want_lse = mla.mla_decode_twin(q, lat, plane, pos, 0.1, 128)
        got, lse = mla.mla_decode_attention(
            q, lat, jnp.int32(plane), pos, scale=0.1, values=128,
            block_k=block_k, interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-5)
        np.testing.assert_allclose(lse, want_lse, atol=1e-5)
        assert (np.asarray(got)[[0, 5]] == 0).all()
        assert (np.asarray(lse)[[0, 5]] == mla.NEG_INF).all()


@pytest.mark.parametrize("heads", [
    2 * mla.MLA_PREFIX_HEAD_GROUP, 2 * mla.MLA_PREFIX_HEAD_GROUP + 1,
    mla.MLA_PREFIX_HEAD_GROUP - 1], ids=["whole_groups", "remainder",
                                          "under_a_group"])
@pytest.mark.parametrize("off,t_len,block_q", [
    (0, 16, 8),        # the lane's first chunk
    (32, 16, 16),      # at a block's edge: whole blocks before it
    (20, 16, 8),       # inside a block: spans two
    (48, 16, 16),      # the plane's last rows
    (56, 8, 8),        # a last chunk of one query block
], ids=["first", "edge", "inside", "end", "last_rows"])
def test_mla_prefix_kernel_equals_its_twin(off, t_len, block_q, heads):
    """In the interpreter, float32: a chunk of ``t_len`` rows at ``off``
    in lane 2 of plane 1, the slots past it stale and large (a block
    masked wrongly reads them), heads a whole number of the kernel's
    groups, one more (the remainder, one at a time) and fewer than a
    group (one group of them all)."""
    rank, nope, rope, v, width, s_len = 32, 16, 8, 16, 128, 64
    ks = jax.random.split(jax.random.key(off), 4)
    lat = jax.random.normal(ks[0], (2, 3, s_len, width), jnp.float32)
    lat = lat.at[..., rank + rope:].set(0.0).at[1, 2, off + t_len:].set(1e4)
    q_nope = jax.random.normal(ks[1], (t_len, heads, nope))
    q_pe = jax.random.normal(ks[2], (t_len, heads, rope))
    wkv_b = jax.random.normal(ks[3], (rank, heads * (nope + v))) / 6
    want = mla.mla_prefix_twin(q_nope, q_pe, wkv_b, lat, 1, 2, off, 0.2,
                               rank)
    got = mla.mla_prefix_attention(
        q_nope, q_pe, wkv_b, lat, jnp.int32(1), jnp.int32(2),
        jnp.int32(off), scale=0.2, rank=rank, block_q=block_q, block_k=16,
        interpret=True)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_mla_kernels_lower_for_the_tpu_under_their_names():
    """The decode kernel's Pallas call is ``mla_decode_fwd`` at the
    benchmark's shapes, the chunk's ``mla_prefix_fwd``; no older
    reader's pattern takes either (nor their patterns an older name,
    nor each other's)."""
    import json

    sd = jax.ShapeDtypeStruct
    text = mla.mla_decode_attention.trace(
        sd((4, 32, 640), jnp.bfloat16), sd((2, 4, 1024, 640), jnp.bfloat16),
        sd((), jnp.int32), sd((4,), jnp.int32), scale=0.07, values=512
    ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert 'kernel_name = "mla_decode_fwd"' in text
    assert mla.mla_decode_block(32, 32768, 640, 512, jnp.bfloat16) == 512
    assert mla.mla_decode_block(32, 32768, 576, 512, jnp.bfloat16) is None
    metrics = os.path.join(REPO, "benchmarks", "layer_metrics")
    patterns = {}
    for name in os.listdir(metrics):
        with open(os.path.join(metrics, name)) as f:
            m = json.load(f)
        if "pattern" in m.get("args", {}):
            patterns[m["name"]] = m["args"]["pattern"]
    mine = patterns.pop("step_mla_decode_roofline")
    chunk = patterns.pop("step_mla_prefix_roofline")
    assert re.search(mine, "mla_decode_fwd.3")
    assert re.search(chunk, "mla_prefix_fwd.7")
    assert not re.search(chunk, "mla_decode_fwd.3")
    for name, pat in patterns.items():
        assert not re.search(pat, "mla_decode_fwd.3"), name
        assert not re.search(pat, "mla_prefix_fwd.7"), name
    for other in ("jit_step_n_p", "jit__admit", "gmm.2", "flash_decode_fwd",
                  "flash_prefix_fwd.1", "ret_state_step", "ret_chunk_fwd"):
        assert not re.search(mine, other), other
        assert not re.search(chunk, other), other
    assert not re.search(mine, "mla_prefix_fwd")
    # ... and the chunk's kernel lowers under its own name
    text = mla.mla_prefix_attention.trace(
        sd((64, 32, 128), jnp.bfloat16), sd((64, 32, 64), jnp.bfloat16),
        sd((512, 8192), jnp.bfloat16), sd((2, 4, 1024, 640), jnp.bfloat16),
        *(sd((), jnp.int32),) * 3, scale=0.07, rank=512
    ).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert 'kernel_name = "mla_prefix_fwd"' in text


# ------------------------------------------------ (d) through the engine


@pytest.fixture(scope="module")
def served(params, tmp_path_factory):
    """Five requests through a two-lane engine with chunked prefill:
    the 30-token prompt takes three chunks and a backed-up tail; lanes
    are vacated and re-admitted.  ``[(prompt, generated)]``, the
    trace's records and the engine's two programs lowered."""
    path = str(tmp_path_factory.mktemp("latent") / "t.jsonl")
    prompts = [_tokens(n, 10 + n) for n in (9, 30, 3, 1, 21)]
    with obs.session(trace_path=path):
        eng = dk.ContinuousBatcher(params, CFG, lanes=2, hot_swap=True,
                                   prefill_chunk=8, prompt_buckets=(4, 8),
                                   max_queue=8)
        ids = [eng.enqueue(p, 12) for p in prompts]
        while eng.running() or eng._pending:
            eng.step()
        out = [eng.take(i) for i in ids]
    assert all(r.ok for r in out)
    texts = {("decode_step" if spec.name.endswith("decode_step") else "admit"):
             spec.fn.lower(*spec.args).as_text(debug_info=True)
             for spec in eng.traced_for_analysis()}
    return ([(p, np.asarray(r.generated)) for p, r in zip(prompts, out)],
            read_trace(path), texts)


def test_engine_tokens_are_the_references_best(served, ref, params):
    """As the benchmark's ``check_serving``: teacher-forced through the
    reference's full forward, every token the engine chose — prefilled
    in chunks into the latent planes, decoded absorbed beside another
    lane, in a lane another request held before — is its best logit,
    or within the tolerance of it."""
    for prompt, got in served[0]:
        seq = np.concatenate([prompt, got])
        lg = _ref_logits(ref, params, seq[:-1])[len(prompt) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(got)), got]
        assert gap.max() < TOL


@pytest.fixture(scope="module")
def decoding(params):
    """An engine stopped in mid-run, as the benchmark's loop is when its
    window ends: two lanes decoding, each request with the tokens read
    so far — what ``reference_joyai.check_rows`` is handed."""
    from types import SimpleNamespace as Obj

    eng = dk.ContinuousBatcher(params, CFG, lanes=2, hot_swap=True,
                               prefill_chunk=8, prompt_buckets=(4, 8),
                               max_queue=8)
    by_lane = {}
    for n in (21, 9):
        before = set(eng.free_lanes())
        r = Obj(prompt=_tokens(n, 40 + n), tokens=[])
        eng.enqueue(r.prompt, 12)
        (lane,) = before - set(eng.free_lanes())
        by_lane[lane] = r
    while min(len(r.tokens) for r in by_lane.values()) < 3:
        for lane, toks in eng.step().items():
            by_lane[lane].tokens.extend(toks)
    ctx = Obj(cell={"correct": {"latent_row_tol": TOL, "row_lanes": 2,
                                "row_layers": [0, 1, 2]}},
              conf={"transformer_config": TC, "rope_interleave": True},
              mix={}, seed=5)
    return ctx, Obj(by_lane=by_lane, engine=eng)


@pytest.mark.parametrize("fault", [None, "kv_float8", "no_k_rope"])
def test_the_engines_cached_rows_are_the_references(decoding, ref, params,
                                                    fault):
    """The rows the engine's cache HOLDS — written by chunks and by
    decode steps, the rotary columns de-interleaved — are the rows the
    reference computes of the same tokens, every layer (float32 on both
    sides: the routers agree); a reference whose latent is in float8
    (as a cache a precision below would be) or whose shared key is not
    rotated is told apart by them."""
    ctx, loop = decoding
    out = ref.check_rows(ctx, params, loop, fault=fault)
    assert len(out["by_lane"]) == 2 * 3
    assert {r["rows"] for r in out["by_lane"]} == {
        len(r.prompt) + len(r.tokens) - 1 for r in loop.by_lane.values()}
    if fault is None:
        assert out["ok"] is True and out["latent_row_err"] < TOL
    else:
        assert out["ok"] is False and out["latent_row_err"] > 0.01
        key = max(r["shared_key"] for r in out["by_lane"])
        assert (key > 0.5) == (fault == "no_k_rope")
    assert ref.check_rows(ctx, params, None)["ok"] is False


def test_engine_says_its_latent_planes(served):
    (ev,) = [r for r in served[1] if r.get("name") == "serving.kv_layout"]
    f = ev["fields"]
    # 32 + 8 values a row, rounded up to a lane tile of 128; float32
    assert {k: f[k] for k in (
        "planes", "planes_full", "planes_window", "planes_state",
        "planes_latent", "latent_width", "bytes_per_slot_latent", "slots",
        "slab_bytes", "bytes_per_slot")} == {
        "planes": 0, "planes_full": 0, "planes_window": 0, "planes_state": 0,
        "planes_latent": 3, "latent_width": 128,
        "bytes_per_slot_latent": 3 * 128 * 4, "slots": 2 * 64,
        "slab_bytes": 2 * 64 * 3 * 128 * 4, "bytes_per_slot": 3 * 128 * 4}


def test_rounds_and_steps_count_what_the_latent_planes_hold(served):
    """``kv_live`` counts a chunked prefill's progress; a step's
    ``attended`` the decoding lanes' positions and NOTHING for a lane
    that does not decode (off the TPU the twin reads whole rows: 64 a
    decoding lane); an admission's ``attended`` is ``max_len`` on the
    dense body."""
    spans = [r for r in served[1] if r.get("kind") == "span"]
    rounds = [r["fields"] for r in spans if r["name"] == "serving.round"]
    assert any(r["lanes_admitting"] and r["kv_live"] > 0 for r in rounds)
    assert max(r["kv_live"] for r in rounds) <= 2 * 64
    steps = [r["fields"] for r in spans if r["name"] == "serving.step"]
    assert {s["attended"] for s in steps} == {64, 128}
    admits = [r["fields"] for r in spans
              if r["name"] in ("serving.admit", "serving.admit_chunk")]
    assert admits and all(a["attended"] == 64 for a in admits)
    routed = [r for r in rounds if "moe_assigned" in r]
    assert routed and all(r["moe_assigned"] % 8 == 0 for r in routed)


@pytest.mark.parametrize("program,metric", [
    ("decode_step", "decode_step_ms"), ("admit", "prefill_ms_per_ktok")])
def test_latent_programs_keep_their_names_and_hold_the_mla_scopes(
        served, program, metric):
    """The programs are read by the readers that read every other
    engine's (names unchanged); the low-rank queries and the joint
    projection stand under ``attn_proj``, the absorbed products under
    ``attn``, each in its own scope (``transformer.MLA_SCOPES``)."""
    import json

    text = served[2][program]
    (module,) = re.findall(r"module @(\S+)", text)
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           metric + ".json")) as f:
        assert re.search(json.load(f)["args"]["pattern"], module), module
    locs = " ".join(set(re.findall(r'loc\("([^"]+)"', text)))
    assert tfm.MLA_SCOPES == ("mla_q", "mla_kv", "mla_absorb")
    for path in ("attn_proj/mla_q", "attn_proj/mla_kv", "attn/mla_absorb"):
        assert re.search(rf"{path}(?![\w.])", locs), path
    for scope in tfm.MOE_SCOPES:
        assert re.search(rf"mlp/{scope}(?![\w.])", locs), scope


# ------------------------------------------------------ (e) rejections


def _speculative():
    from distkeras_tpu.serving import SpeculativeBatcher

    return SpeculativeBatcher


@pytest.mark.parametrize("path", [
    "lm_loss", "LMTrainer", "PagedBatcher", "SpeculativeBatcher",
    "prompt_cache", "prefix_pool", "kv_int8", "beam_search", "plan",
    "serving_kv_axis", "kv_slab_specs", "mixed_kinds", "missing_key",
    "keys_without_layers"])
def test_rejected_path_raises_and_names_the_kind(params, path):
    from distkeras_tpu.parallel import rules
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
    from distkeras_tpu.parallel.sharding import serving_plan

    toks = jnp.asarray(_tokens(12))[None]
    new = lambda **kw: tfm.init_params(jax.random.key(0),
                                       tfm.TransformerConfig(**{**TC, **kw}))
    with pytest.raises(ValueError) as err:
        if path == "lm_loss":
            tfm.lm_loss(params, toks, CFG)
        elif path == "LMTrainer":
            dk.LMTrainer(CFG)
        elif path == "PagedBatcher":
            dk.PagedBatcher(params, CFG, lanes=2)
        elif path == "SpeculativeBatcher":
            _speculative()(params, params, CFG, CFG, lanes=2)
        elif path == "prompt_cache":
            gen.generate(params, toks, CFG, 2, prompt_cache=(
                gen.init_cache(CFG, 1), 4))
        elif path == "prefix_pool":
            dk.serving.PrefixPool(CFG, slots=2)
        elif path == "kv_int8":
            dk.ContinuousBatcher(params, CFG, lanes=2, kv_int8=True)
        elif path == "beam_search":
            gen.beam_search(params, toks, CFG, 2, beam_width=2)
        elif path == "plan":
            mesh = make_mesh(MeshSpec(model=1), devices=jax.devices()[:1])
            dk.ContinuousBatcher(params, CFG, lanes=2, plan=serving_plan(),
                                 mesh=mesh)
        elif path == "serving_kv_axis":
            mesh = make_mesh(MeshSpec(model=1), devices=jax.devices()[:1])
            rules.serving_kv_axis(serving_plan(), mesh, CFG)
        elif path == "kv_slab_specs":
            rules.kv_slab_specs(jax.eval_shape(
                lambda: gen.init_cache(CFG, 2)), "model")
        elif path == "mixed_kinds":
            new(layer_types=["latent", "full", "latent"])
        elif path == "missing_key":
            new(v_head_dim=None)
        else:
            new(layer_types=None, ffn_types=None, num_experts=0,
                moe_held=None)
    want = ("latent" if path not in ("missing_key", "keys_without_layers")
            else "v_head_dim" if path == "missing_key" else "q_lora_rank")
    assert want in str(err.value), str(err.value)


def test_a_latent_stack_counts_its_planes():
    assert (CFG.latent_planes, CFG.kv_planes, CFG.kv_ring_planes,
            CFG.state_planes) == (3, 0, 0, 0)
    assert CFG.latent_width == 128 and CFG.rope_dim == 8
    wide = dataclasses.replace(CFG, kv_lora_rank=512, qk_rope_head_dim=64)
    assert wide.latent_width == 640          # 576 values in 5 lane tiles
    cache = jax.eval_shape(lambda: gen.init_cache(wide, 2))
    assert cache["lat"].shape == (3, 2, 64, 640)
    assert cache["k"].shape[0] == cache["k_win"].shape[0] == 0
