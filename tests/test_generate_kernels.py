"""On a TPU backend ``_decode_chunk`` and ``_chunk_in_place`` attend
through the bounded kernels (the interpreter here), and compute what
their dense bodies compute.  (Apart from test_generate.py so that
neither file is the gate's tail: these are kernel-legal widths.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.models import transformer as tfm
from helpers import jgen, toy_params

init_cache = jgen.init_cache


def _traced_now(fn, cfg, **kw):
    """``fn`` under a ``jit`` of its own, so that it is traced HERE,
    under whatever the test has patched by now."""
    return jax.jit(lambda p, cache, toks, pos0: fn(p, cache, toks, pos0,
                                                   cfg, **kw))


# --------------------------------- chunked prefill's bounded attention

# Kernel-legal widths (a head of 128, 128 slots, float32: chunks in
# whole tiles of 8) at the smallest size that has them.
GATE_CFG = tfm.TransformerConfig(vocab_size=64, d_model=256, n_heads=2,
                                 n_kv_heads=1, n_layers=1, d_ff=64,
                                 max_len=128)


def _gate_call(case, rng, cfg=GATE_CFG):
    """``(params, cache, tokens, pos0, cfg, kwargs)`` of one
    ``_decode_chunk`` call of the named shape."""
    import dataclasses

    rows, t, kw = 2, 8, {"uniform_pos": True}
    pos0 = jnp.full((rows,), 13, jnp.int32)
    kv_int8 = False
    if case == "one_token":
        t = 1
    elif case == "per_row":
        kw, pos0 = {}, jnp.asarray([13, 40], jnp.int32)
    elif case == "windowed":
        cfg = dataclasses.replace(cfg, attention_window=32)
    elif case == "int8":
        kv_int8 = True
    elif case == "beam":
        t = 1
        kw["beam_anc"] = (jnp.zeros((1, rows, cfg.max_len), jnp.int32),
                          rows)
    params = toy_params(cfg)
    toks = jnp.asarray(rng.integers(0, 64, (rows, t)), jnp.int32)
    return (params, init_cache(cfg, rows, kv_int8=kv_int8), toks, pos0,
            cfg, kw)


@pytest.mark.parametrize("case", ["uniform_chunk", "one_token", "per_row",
                                  "windowed", "int8", "beam"])
def test_decode_chunk_gate(rng, monkeypatch, case):
    """On a TPU backend a uniform multi-token chunk — an admission, a
    prefix warm-up — attends through the blocked prefix kernel, a
    decode step (T = 1) and a chunk at per-row positions through the
    per-lane bounded kernel over the slab, and both compute what the
    dense body computes; a ring, an int8 cache and beam ancestry keep
    the dense body, as every call does on another backend."""
    from distkeras_tpu.models import generate as gen
    from distkeras_tpu.ops import attention

    calls = []

    def kernel(name):
        def run(q, *rest):
            calls.append((name, q.shape))
            return getattr(attention, name)(q, *rest, interpret=True)
        return run
    for name in ("flash_prefix_attention", "flash_decode_attention"):
        monkeypatch.setattr(gen, name, kernel(name))
    params, cache, toks, pos0, cfg, kw = _gate_call(case, rng)
    dense_logits, dense_cache = _traced_now(gen._decode_chunk, cfg, **kw)(
        params, cache, toks, pos0)
    assert calls == []                      # this backend is no TPU
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    logits, new_cache = _traced_now(gen._decode_chunk, cfg, **kw)(
        params, cache, toks, pos0)
    assert calls == {
        "uniform_chunk": [("flash_prefix_attention", (2, 8, 2, 128))],
        "one_token": [("flash_decode_attention", (2, 1, 2, 128))],
        "per_row": [("flash_decode_attention", (2, 8, 2, 128))],
    }.get(case, [])
    np.testing.assert_allclose(logits, dense_logits, atol=1e-4, rtol=1e-4)
    for leaf, want in zip(jax.tree.leaves(new_cache),
                          jax.tree.leaves(dense_cache)):
        np.testing.assert_array_equal(leaf, want)


# ------------------------------- the decode step's per-lane bounded read

# The two serving cells' head layouts at the smallest kernel-legal
# size, float32: query heads on ONE K/V head (the tile rule's block is
# the lane, read by quarters of 256 slots), and 8 K/V heads with groups
# of 1 in a looped, extended stack — 2 passes x 2 layers, so four
# planes, each holding other keys (two blocks of 128 slots a lane,
# quarters of 32).
LANE_CFGS = {
    "multi_query": tfm.TransformerConfig(
        vocab_size=64, d_model=256, n_heads=2, n_kv_heads=1, n_layers=2,
        d_ff=64, max_len=1024),
    "groups_of_1_looped": tfm.TransformerConfig(
        vocab_size=64, d_model=1024, n_heads=8, n_kv_heads=8, n_layers=2,
        d_ff=64, max_len=256, rope=True, ffn_gated=True, tie_head=False,
        post_norms=True, fused_qkv=True, n_passes=2),
}


@pytest.mark.parametrize("t", [1, 4], ids=["token", "chunk4"])
@pytest.mark.parametrize("name", sorted(LANE_CFGS))
def test_chunk_in_place_per_lane_kernel_is_the_dense_body(rng, monkeypatch,
                                                          name, t):
    """``_chunk_in_place`` through the per-lane bounded kernel (the
    interpreter, the tile rule's own block) against its dense body, on
    a slab whose every slot holds something: rows at 0, 1, a copy's
    edge, past the next and the last position a chunk fits, one token and a
    per-row chunk of four.  Same logits; the slab written alike — and
    read nowhere at or past a row's position, in no other plane: the
    dense body masks those, the kernel must not see them."""
    from distkeras_tpu.models import generate as gen
    from distkeras_tpu.ops import attention

    cfg = LANE_CFGS[name]
    unit = gen.decode_read_unit(cfg, t, {"k": jnp.zeros((), jnp.float32)})
    assert unit and cfg.max_len // unit >= 4
    pos0 = jnp.asarray([0, 1, unit, 2 * unit + 3, cfg.max_len - t],
                       jnp.int32)
    params = toy_params(cfg)
    cache = {k: jnp.asarray(rng.normal(size=v.shape), v.dtype)
             for k, v in init_cache(cfg, len(pos0)).items()}
    toks = jnp.asarray(rng.integers(0, 64, (len(pos0), t)), jnp.int32)
    want, want_cache = _traced_now(gen._chunk_in_place, cfg)(
        params, cache, toks, pos0)

    calls = []

    def kernel(q, *rest):
        calls.append(q.shape)
        return attention.flash_decode_attention(q, *rest, interpret=True)
    monkeypatch.setattr(gen, "flash_decode_attention", kernel)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    got, got_cache = _traced_now(gen._chunk_in_place, cfg)(
        params, cache, toks, pos0)
    assert calls == [(len(pos0), t, cfg.n_heads, cfg.head_dim)]  # one trace
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    for leaf, ref in zip(jax.tree.leaves(got_cache),
                         jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(leaf, ref, atol=2e-5, rtol=2e-5)
