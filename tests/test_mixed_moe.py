"""The typed stack (models/transformer.py ``layer_types`` /
``ffn_types``: window and full attention mixed, the routed feed-forward
told which experts it holds; models/generate.py ``_chunk_in_place``;
the lane slab of full planes and rings) against the plain reference
``benchmarks/reference_kexaone.py``, loaded by path: float32, seeded
weights, tiny sizes (heads of 32 over a 64-wide stream, so that
``head_dim`` is not ``d_model / n_heads``).

(a) ``apply`` = the reference; each wrong reference fails by far;
(b) through ``ContinuousBatcher``: prefill in chunks that wrap the
    ring, then decode, lanes vacated and re-admitted = the reference's
    full forward; what the engine says of its slab and its rounds;
(c) the shares add up: every share's routed part and the shared
    expert once = the uncut layer; no token is dropped when the
    routing is forced onto one expert;
(d) a stack typed full and dense everywhere is the untyped stack; the
    paths that do not run a typed stack say so by name.
"""

import dataclasses
import functools
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu import obs
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.obs import read_trace
from helpers import jtfm, toy_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TC = dict(vocab_size=96, d_model=64, n_heads=4, d_head=32, n_kv_heads=2,
          n_layers=5, d_ff=96, max_len=64, rope=True, rope_theta=1e6,
          dtype="float32", ffn_gated=True, tie_head=False, post_norms="only",
          fused_qkv=True, qk_norm=True, norm_eps=1e-5,
          layer_types=["window"] * 4 + ["full"], sliding_window=8,
          rope_layer_types=["window"],
          ffn_types=["dense"] + ["sparse"] * 4, num_experts=16, moe_top_k=4,
          moe_held=[0, 1, 2, 3], moe_d_ff=48, moe_shared=1,
          moe_route_scale=2.5)
CFG = tfm.TransformerConfig(**TC)
TOL = 2e-4          # float32 against float32: rounding order only


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(REPO, "benchmarks", "reference_kexaone.py")
    s = importlib.util.spec_from_file_location("reference_kexaone", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def _off_default(p, seed=1):
    """Every norm scale moved off its initial 1, so that a misplaced
    or missing norm shows."""
    leaves, treedef = jax.tree.flatten(p)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        a + 0.3 * jax.random.normal(k, a.shape) if a.ndim <= 2
        and (a == a.reshape(-1)[0]).all() else a
        for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def params():
    return _off_default(toy_params(CFG))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 96, n).astype(np.int32)


def _ref_logits(ref, params, seq, tc=TC, fault=None):
    normed = ref.forward(params, tc, seq, fault=fault)
    return ref.logits_at(params, normed, np.arange(len(seq)))


# ------------------------------------------------ (a) the full forward


def test_apply_equals_the_reference(ref, params):
    toks = _tokens(37)
    logits, _ = jtfm.apply(params, jnp.asarray(toks)[None], CFG)
    assert logits.shape == (1, 37, 96)
    np.testing.assert_allclose(logits[0], _ref_logits(ref, params, toks),
                               atol=TOL)


@pytest.mark.parametrize("fault", [
    "window_half", "top_k_less_one", "no_route_scale", "no_shared_expert",
    "no_select_bias", "rope_on_full", "kv_float8", "matmul_float8"])
def test_a_wrong_reference_fails_the_tolerance(ref, params, fault):
    """Each planted fault moves the logits by hundreds of tolerances."""
    toks = _tokens(37)
    right = _ref_logits(ref, params, toks)
    wrong = _ref_logits(ref, params, toks, fault=fault)
    assert np.abs(wrong - right).max() > 100 * TOL


# ----------------------------------------------- (b) through the engine


@pytest.fixture(scope="module")
def served(params, tmp_path_factory):
    """Five requests through a two-lane engine with chunked prefill:
    the 30-token prompt takes three chunks and a padded tail and wraps
    the ring three times; lanes are vacated and re-admitted.
    ``[(prompt, generated)]`` and the trace's records."""
    path = str(tmp_path_factory.mktemp("mixed") / "t.jsonl")
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
    return ([(p, np.asarray(r.generated)) for p, r in zip(prompts, out)],
            read_trace(path))


def test_engine_tokens_are_the_references_best(served, ref, params):
    """As the benchmark's ``check_serving``: teacher-forced through
    the reference's full forward, every token the engine chose —
    prefilled in chunks through rings and the full plane, decoded
    beside another lane, in a lane another request held before — is
    its best logit, or within the tolerance of it."""
    for prompt, got in served[0]:
        seq = np.concatenate([prompt, got])
        lg = _ref_logits(ref, params, seq[:-1])[len(prompt) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(got)), got]
        assert gap.max() < TOL


def test_engine_says_its_two_kinds_of_plane(served):
    (ev,) = [r for r in served[1] if r.get("name") == "serving.kv_layout"]
    f, ring = ev["fields"], 8
    per = 2 * 2 * 32 * 4                   # k and v, 2 heads of 32, f32
    slab = 2 * per * (64 + 4 * (ring + 16))
    assert {k: f[k] for k in (
        "planes", "planes_full", "planes_window", "window", "ring_slots",
        "bytes_per_slot_full", "bytes_per_slot_window", "slots",
        "slab_bytes", "bytes_per_slot")} == {
        "planes": 1, "planes_full": 1, "planes_window": 4, "window": 8,
        "ring_slots": ring, "bytes_per_slot_full": per,
        "bytes_per_slot_window": 4 * per, "slots": 2 * 64,
        "slab_bytes": slab, "bytes_per_slot": slab // (2 * 64)}


def test_rounds_count_window_slots_and_assignments(served):
    rounds = [r["fields"] for r in served[1]
              if r.get("name") == "serving.round"]
    assert all(0 <= r["kv_live_window"] <= min(r["kv_live"], 2 * 8)
               for r in rounds)
    assert any(r["kv_live_window"] < r["kv_live"] for r in rounds)
    routed = [r for r in rounds if "moe_assigned" in r]
    assert routed
    for r in routed:
        # decoding lanes x top-4 x 4 sparse layers, of the round READ
        assert r["moe_assigned"] % 16 == 0 and 16 <= r["moe_assigned"] <= 32
        assert 0 <= r["moe_max"] <= r["moe_held"] <= r["moe_assigned"]
        assert r["moe_max"] <= r["moe_assigned"] // 16
    # 4 of 16 experts held: about a quarter of the assignments land
    share = (sum(r["moe_held"] for r in routed)
             / sum(r["moe_assigned"] for r in routed))
    assert 0.1 < share < 0.45


def test_typed_programs_keep_their_names_and_hold_the_moe_scopes(params):
    """The typed step and admission are read by the readers that read
    every other engine's (program names unchanged), and a sparse
    layer's three scopes stand inside ``mlp``."""
    eng = dk.ContinuousBatcher(params, CFG, lanes=2, hot_swap=True,
                               prefill_chunk=8, prompt_buckets=(4, 8))
    for spec in eng.traced_for_analysis():
        text = spec.fn.lower(*spec.args).as_text(debug_info=True)
        (module,) = re.findall(r"module @(\S+)", text)
        assert re.search("step_n" if spec.name.endswith("decode_step")
                         else "_admit", module), module
        locs = " ".join(set(re.findall(r'loc\("([^"]+)"', text)))
        for scope in tfm.MOE_SCOPES:
            assert re.search(rf"mlp/{scope}(?![\w.])", locs), scope


# ------------------------------------------------ (c) the shares add up


def _one_sparse_layer(p, group="full.sparse"):
    return jax.tree.map(lambda a: a[0], p["layers"][group])


# The stack above, and a latent + sparse stack at the router's width of
# the latent cell (benchmarks/configs/joyai-llm-flash_l10-ep8.json): 256
# experts in 8 shares of 32, top-8.
LATENT_TC = dict(vocab_size=96, d_model=64, n_heads=4, n_layers=2, d_ff=96,
                 max_len=64, rope=True, dtype="float32", ffn_gated=True,
                 tie_head=False, layer_types=["latent"] * 2,
                 ffn_types=["dense", "sparse"], q_lora_rank=48,
                 kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, num_experts=256,
                 moe_top_k=8, moe_held=list(range(32)), moe_d_ff=16,
                 moe_shared=1, moe_route_scale=2.5)


@pytest.fixture(scope="module")
def ref_joyai():
    import importlib
    import sys

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    try:
        return importlib.import_module("reference_joyai")
    finally:
        sys.path.remove(os.path.join(REPO, "benchmarks"))


@pytest.mark.parametrize("model,tc,group,shares", [
    ("kexaone", TC, "full.sparse", 4),
    ("joyai", LATENT_TC, "latent.sparse", 8)])
def test_the_shares_add_up_to_the_uncut_layer(request, model, tc, group,
                                              shares):
    """``shares`` chips of as many experts each: the routed parts that
    the shares compute, with the shared expert counted once, are the
    uncut reference's layer.  A share's weights are the whole model's
    for its experts (``init_params`` draws an expert by its id)."""
    ref = request.getfixturevalue("ref" if model == "kexaone"
                                  else "ref_joyai")
    cfg0 = tfm.TransformerConfig(**tc)
    n = cfg0.num_experts // shares
    whole_tc = {**tc, "moe_held": None}
    whole = _one_sparse_layer(
        toy_params(tfm.TransformerConfig(**whole_tc)), group)
    mine = _one_sparse_layer(toy_params(cfg0), group)["moe"]
    for name in ("w13", "w2"):
        np.testing.assert_array_equal(mine[name],
                                      np.asarray(whole["moe"][name])[:n])
    h = np.random.default_rng(5).normal(size=(23, 64)).astype(np.float32)

    @functools.partial(jax.jit, static_argnames="cfg")
    def share(lp, h, cfg):
        expert, gates = tfm.moe_route(lp["moe"], h, cfg)
        part, local = tfm.moe_held_experts(lp["moe"], h, expert, gates, cfg)
        return part, local, expert, tfm.moe_ffn(lp, h, cfg)

    total = np.zeros_like(h)
    for chip in range(shares):
        held = list(range(n * chip, n * chip + n))
        cfg = dataclasses.replace(cfg0, moe_held=tuple(held))
        lp = {**whole, "moe": {**whole["moe"], **{
            name: whole["moe"][name][np.asarray(held)]
            for name in ("w13", "w2")}}}
        part, local, expert, alone = share(lp, jnp.asarray(h), cfg)
        assert ((np.asarray(local) < n)
                == np.isin(np.asarray(expert), held)).all()
        total += np.asarray(part)
        # ... and a share alone is the reference given the same share
        np.testing.assert_allclose(
            np.asarray(alone),
            ref.sparse_layer(lp, {**tc, "moe_held": held}, h), atol=TOL)
    shared = np.asarray(tfm.ffn_apply(whole["shared"], jnp.asarray(h), cfg0))
    np.testing.assert_allclose(total + shared,
                               ref.sparse_layer(whole, whole_tc, h),
                               atol=TOL)


def test_no_token_is_dropped_when_one_expert_takes_them_all(ref):
    """A selection bias that sends every token to held expert 2 (and
    to three more by score): its group is every token, and the layer
    is still the reference's — there is no capacity to exceed."""
    lp = _one_sparse_layer(toy_params(CFG))
    lp["moe"]["bias"] = lp["moe"]["bias"].at[2].set(10.0)
    h = np.random.default_rng(6).normal(size=(40, 64)).astype(np.float32)
    out, local = tfm.moe_ffn(lp, jnp.asarray(h), CFG, with_routes=True)
    assert ((np.asarray(local) == 2).sum(axis=-1) == 1).all()
    np.testing.assert_allclose(np.asarray(out),
                               ref.sparse_layer(lp, TC, h), atol=TOL)


def test_a_held_expert_no_token_reached_keeps_its_tile(ref, monkeypatch):
    """A selection bias that keeps every token off held expert 3: its
    group is empty and still one row tile of the grouped products (its
    weights are read whatever the routing: the products' time follows
    the shapes), and the layer is still the reference's."""
    lp = _one_sparse_layer(toy_params(CFG))
    lp["moe"]["bias"] = lp["moe"]["bias"].at[3].set(-10.0)
    h = np.random.default_rng(7).normal(size=(40, 64)).astype(np.float32)
    seen = []
    product = tfm.grouped_matmul
    monkeypatch.setattr(
        tfm, "grouped_matmul",
        lambda lhs, rhs, sizes: seen.append(np.asarray(sizes))
        or product(lhs, rhs, sizes))
    out, local = tfm.moe_ffn(lp, jnp.asarray(h), CFG, with_routes=True)
    assert not (np.asarray(local) == 3).any()
    assert len(seen) == 2 and all(
        (sizes >= tfm.GROUP_TILE).all() and not (sizes % tfm.GROUP_TILE).any()
        for sizes in seen)
    np.testing.assert_allclose(np.asarray(out),
                               ref.sparse_layer(lp, TC, h), atol=TOL)


# ------------------------------------------- (d) the edges of the stack


def test_a_stack_typed_full_and_dense_is_the_untyped_stack():
    plain = tfm.TransformerConfig(vocab_size=96, n_layers=3)
    listed = tfm.TransformerConfig(vocab_size=96, n_layers=3,
                                   layer_types=["full"] * 3,
                                   ffn_types=["dense"] * 3)
    assert listed == plain and not listed.typed and not listed.extended
    assert CFG.typed and CFG.extended and CFG.head_dim == 32
    assert CFG.layer_runs == (("window.dense", 0, 1), ("window.sparse", 0, 3),
                              ("full.sparse", 0, 1))
    assert (CFG.kv_planes, CFG.kv_ring_planes) == (1, 4)
    hash(CFG)   # lists from JSON became tuples: a static jit argument


@pytest.mark.parametrize("path", ["lm_loss", "kv_int8", "lane_tiers",
                                  "looped", "bad_ring"])
def test_rejected_path_raises_and_names_itself(params, path):
    toks = jnp.asarray(_tokens(12))[None]
    with pytest.raises(ValueError) as err:
        if path == "lm_loss":
            tfm.lm_loss(params, toks, CFG)
        elif path == "kv_int8":
            dk.ContinuousBatcher(params, CFG, lanes=2, kv_int8=True)
        elif path == "lane_tiers":
            dk.ContinuousBatcher(params, CFG, lane_tiers=(2, 4), max_queue=4)
        elif path == "looped":
            tfm.init_params(jax.random.key(0),
                            dataclasses.replace(CFG, n_passes=2))
        else:
            tfm.init_params(jax.random.key(0),
                            dataclasses.replace(CFG, sliding_window=6))
    want = {"lm_loss": "layer_types", "kv_int8": "kv_int8",
            "lane_tiers": "lane_tiers", "looped": "typed stack",
            "bad_ring": "sliding_window"}[path]
    assert want in str(err.value)
