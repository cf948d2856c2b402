"""Attention tier equivalence: naive == blockwise == pallas(interpret) == ring.

The contract: every implementation computes identical math, so the
Pallas kernel and the ring-parallel version are validated against the
materialized-logits oracle (SURVEY.md §4 test strategy: numerics vs a
hand-rolled reference).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.ops.attention import (
    NEG_INF,
    blockwise_attention,
    decode_block,
    flash_attention,
    flash_decode_attention,
    flash_prefix_attention,
    naive_attention,
    prefix_blocks,
    _flash_pallas,
)
from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
from distkeras_tpu.parallel.ring import make_ring_attention, \
    sequence_sharding


def qkv(rng, b=2, l=32, h=2, d=8, lk=None):
    shape_q = (b, l, h, d)
    shape_k = (b, lk or l, h, d)
    return (rng.normal(size=shape_q).astype(np.float32),
            rng.normal(size=shape_k).astype(np.float32),
            rng.normal(size=shape_k).astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_k", [8, 16, 32])
def test_blockwise_matches_naive(rng, causal, block_k):
    q, k, v = qkv(rng)
    ref = naive_attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=block_k)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_blockwise_cross_attention(rng):
    q, k, v = qkv(rng, l=16, lk=48)
    ref = naive_attention(q, k, v)
    out = blockwise_attention(q, k, v, block_k=16)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_flash_fallback_any_length(rng):
    """Non-divisible KV lengths must clamp block_k, not raise."""
    q, k, v = qkv(rng, l=24, lk=40)  # gcd(512, 40) -> block_k 40... etc.
    ref = naive_attention(q, k, v)
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    out = blockwise_attention(q, k, v, block_k=512)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_grads_match_naive(rng, causal):
    q, k, v = qkv(rng, b=1, l=16, h=1, d=4)

    def loss_ref(q, k, v):
        return naive_attention(q, k, v, causal=causal).sum()

    def loss_blk(q, k, v):
        return blockwise_attention(q, k, v, causal=causal, block_k=8).sum()

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    g_blk = jax.jit(jax.grad(loss_blk, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fallback_and_vjp(rng, causal):
    """On CPU flash_attention routes to blockwise; VJP must still work."""
    q, k, v = qkv(rng, l=16)
    ref = naive_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    g = jax.jit(jax.grad(
        lambda q: flash_attention(q, k, v, causal).sum()))(q)
    g_ref = jax.jit(jax.grad(
        lambda q: naive_attention(q, k, v, causal=causal).sum()))(q)
    np.testing.assert_allclose(g, g_ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_kernel_interpret(rng, causal):
    """The TPU kernel's logic, run via the Pallas interpreter on CPU."""
    q, k, v = qkv(rng, b=1, l=16, h=1, d=128)
    ref = naive_attention(q, k, v, causal=causal)
    out, lse = _flash_pallas(q, k, v, causal, 1.0 / np.sqrt(128), block_q=8,
                             block_k=8, interpret=True)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)
    # lse residual: matches the materialized logits' logsumexp.
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(128)
    if causal:
        mask = np.tril(np.ones((16, 16), bool))
        logits = np.where(mask[None, None], logits, -1e30)
    ref_lse = np.log(np.exp(logits).sum(-1)).reshape(1, 1, 16)  # [B, H, Lq]
    np.testing.assert_allclose(lse, ref_lse, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_kernels_interpret(rng, causal):
    """FA2 dQ/dK/dV kernels vs autodiff of the naive oracle (interpreter)."""
    from distkeras_tpu.ops.attention import _flash_pallas_bwd, _scale_for

    q, k, v = qkv(rng, b=1, l=16, h=2, d=128)
    scale = _scale_for(q, None)
    out, lse = _flash_pallas(q, k, v, causal, scale, block_q=8, block_k=8,
                             interpret=True)
    g = rng.normal(size=out.shape).astype(np.float32)
    dq, dk, dv = _flash_pallas_bwd(q, k, v, np.asarray(out), lse, g, causal,
                                   scale, block_q=8, block_k=8,
                                   interpret=True)
    _, vjp = jax.vjp(
        lambda q, k, v: naive_attention(q, k, v, causal=causal), q, k, v)
    dq_ref, dk_ref, dv_ref = vjp(jnp.asarray(g))
    np.testing.assert_allclose(dq, dq_ref, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(dk, dk_ref, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(dv, dv_ref, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4)])
def test_ring_attention_matches_full(devices, rng, causal, mesh_shape):
    data, seq = mesh_shape
    mesh = make_mesh(MeshSpec(data=data, seq=seq), devices=devices)
    q, k, v = qkv(rng, b=2, l=32, h=2, d=8)
    ref = naive_attention(q, k, v, causal=causal)
    # Pre-placing with sequence_sharding must match the ring's in_specs
    # (pins the helper's [B, L, ...] contract).
    sh = sequence_sharding(mesh)
    q, k, v = (jax.device_put(a, sh) for a in (q, k, v))
    ring = jax.jit(make_ring_attention(mesh, causal=causal))
    out = ring(q, k, v)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_ring_attention_grads(devices, rng):
    mesh = make_mesh(MeshSpec(data=1, seq=4), devices=devices[:4])
    q, k, v = qkv(rng, b=1, l=16, h=1, d=4)
    ring = make_ring_attention(mesh, causal=True)
    g = jax.jit(jax.grad(lambda q, k, v: ring(q, k, v).sum(),
                         argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: naive_attention(q, k, v, causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# ------------------------------------------------------- sliding window

@pytest.mark.parametrize("window", [1, 3, 7, 16, 64])
def test_blockwise_window_matches_naive(rng, window):
    q, k, v = qkv(rng, b=2, l=16, h=2, d=8)
    ref = naive_attention(q, k, v, causal=True, window=window)
    out = blockwise_attention(q, k, v, causal=True, block_k=4,
                              window=window)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
    if window >= 16:  # window >= L degenerates to plain causal
        full = naive_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, full, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("window", [2, 5, 8])
def test_pallas_window_interpret(rng, window):
    """Windowed flash kernel (incl. dead-block skipping) == naive, via
    the TPU-semantics interpreter on CPU."""
    q, k, v = qkv(rng, b=1, l=16, h=1, d=128)
    ref = naive_attention(q, k, v, causal=True, window=window)
    out, _ = _flash_pallas(q, k, v, True, 1.0 / np.sqrt(128), block_q=8,
                           block_k=8, interpret=True, window=window)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("window", [3, 8])
def test_pallas_window_backward_interpret(rng, window):
    """Windowed FA2 backward kernels == autodiff of the blockwise
    windowed reference."""
    from distkeras_tpu.ops.attention import _flash_pallas_bwd

    q, k, v = qkv(rng, b=1, l=16, h=1, d=128)
    scale = 1.0 / np.sqrt(128)
    out, lse = _flash_pallas(q, k, v, True, scale, block_q=8, block_k=8,
                             interpret=True, window=window)
    g = np.asarray(jax.random.normal(jax.random.key(0), out.shape),
                   np.float32)
    dq, dk, dv = _flash_pallas_bwd(q, k, v, out, lse, g, True, scale,
                                   8, 8, interpret=True, window=window)
    ref, vjp = jax.vjp(
        lambda q, k, v: blockwise_attention(q, k, v, causal=True,
                                            scale=scale, block_k=4,
                                            window=window), q, k, v)
    rdq, rdk, rdv = vjp(g)
    np.testing.assert_allclose(dq, rdq, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(dk, rdk, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(dv, rdv, atol=2e-3, rtol=2e-3)


def test_window_validation(rng):
    q, k, v = qkv(rng, b=1, l=8, h=1, d=8)
    with pytest.raises(ValueError, match="causal"):
        naive_attention(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        blockwise_attention(q, k, v, causal=True, window=0)
    from distkeras_tpu.ops.attention import flash_attention

    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, None, 8, 8, 4)


def test_flash_attention_window_grads_fallback(rng):
    """flash_attention with a window on the non-TPU fallback: value and
    grads match the naive windowed oracle."""
    from distkeras_tpu.ops.attention import flash_attention

    q, k, v = qkv(rng, b=2, l=12, h=2, d=8)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, True, None, 8, 4, 5).sum()

    def f_naive(q, k, v):
        return naive_attention(q, k, v, causal=True, window=5).sum()

    np.testing.assert_allclose(float(f_flash(q, k, v)),
                               float(f_naive(q, k, v)), rtol=1e-5)
    g1 = jax.jit(jax.grad(f_flash, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(f_naive, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("bq,bk", [(8, 16), (16, 8), (8, 8)])
@pytest.mark.parametrize("window", [3, 9, 20])
def test_pallas_window_banded_grid_asymmetric_blocks(rng, bq, bk, window):
    """The banded index maps must stay exact for block_q != block_k and
    windows spanning multiple blocks (fwd + both backward kernels)."""
    from distkeras_tpu.ops.attention import _flash_pallas_bwd

    q, k, v = qkv(rng, b=1, l=32, h=1, d=128)
    scale = 1.0 / np.sqrt(128)
    ref = naive_attention(q, k, v, causal=True, window=window)
    out, lse = _flash_pallas(q, k, v, True, scale, block_q=bq, block_k=bk,
                             interpret=True, window=window)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)

    g = np.asarray(jax.random.normal(jax.random.key(1), out.shape),
                   np.float32)
    dq, dk, dv = _flash_pallas_bwd(q, k, v, out, lse, g, True, scale,
                                   bq, bk, interpret=True, window=window)
    _, vjp = jax.vjp(
        lambda q, k, v: blockwise_attention(q, k, v, causal=True,
                                            scale=scale, block_k=8,
                                            window=window), q, k, v)
    rdq, rdk, rdv = vjp(g)
    np.testing.assert_allclose(dq, rdq, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(dk, rdk, atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(dv, rdv, atol=2e-3, rtol=2e-3)


def test_fit_block_divisor_logic():
    """Oversized defaults fit down to the largest lane-aligned divisor
    instead of pushing the length off the Pallas path (code-review
    regression: (1024, 1024) defaults must not exile seq 1536)."""
    from distkeras_tpu.ops.attention import _fit_block

    assert _fit_block(1024, 4096) == 1024     # divides exactly
    assert _fit_block(1024, 1536) == 768      # largest x128 divisor
    assert _fit_block(1024, 1280) == 640
    assert _fit_block(1024, 512) == 512       # short row: one block
    assert _fit_block(1024, 200) == 200       # short unaligned row
    assert _fit_block(8, 16) == 8             # explicit test blocks keep
    assert _fit_block(1024, 1288) is None     # nothing lane-aligned tiles

    # strict (explicitly requested blocks): honored exactly or None —
    # never a substituted divisor (advisor round-3: a sweep asking for
    # block 512 at length 768 must not silently time a 384 block).
    assert _fit_block(512, 768, strict=True) is None
    assert _fit_block(1024, 1536, strict=True) is None
    assert _fit_block(512, 1024, strict=True) == 512   # divides: kept
    assert _fit_block(1024, 512, strict=True) == 512   # whole-row clamp


def test_pallas_fitted_blocks_interpret(rng):
    """A length the tuned defaults don't divide (1536) still runs the
    kernel — with fitted 768-blocks — and matches the naive reference."""
    q, k, v = qkv(rng, b=1, l=1536, h=1, d=128)
    ref = naive_attention(q, k, v, causal=True)
    out, _ = _flash_pallas(q, k, v, True, 1.0 / np.sqrt(128),
                           block_q=1024, block_k=1024, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_explicit_small_block_k_honored_and_unfittable_raises(rng):
    """Explicit small blocks reach the kernel (the sweep must be able to
    time any grid point); unfittable direct launches raise instead of
    silently leaving tail rows unwritten (code-review regressions).
    ``_pallas_blocks`` is the backend-independent decision, so this
    runs fully on the CPU suite."""
    from distkeras_tpu.ops.attention import _pallas_blocks, _require_fit

    # Explicit block_k=128 tiles lk=4096 — accepted when the caller
    # asked for it (gate off), rejected on the defaulted path (gate on)
    # unless block_q fitted to >=1024 (sweep: (1024, 128) alone beats
    # the fallback).
    assert _pallas_blocks(4096, 4096, 128, 512, 128) == (512, 128)
    assert _pallas_blocks(4096, 4096, 128, 512, 128,
                          gate_small_bk=True) is None
    assert _pallas_blocks(4096, 4096, 128, 1024, 128,
                          gate_small_bk=True) == (1024, 128)
    # Defaulted seq 2176 = 17x128: both blocks fit only to 128 -> the
    # (128, 128)-class kernel is pathological, fallback wins.
    assert _pallas_blocks(2176, 2176, 128, 1024, 1024,
                          gate_small_bk=True) is None
    # Unaligned head_dim or sub-8 rows never tile.
    assert _pallas_blocks(4096, 4096, 64, 1024, 1024) is None
    assert _require_fit(8, 16) == 8
    with pytest.raises(ValueError, match="tiles sequence length"):
        _require_fit(1024, 1288)
    # Explicit (strict) blocks that don't divide take the fallback
    # instead of a refitted grid; defaults at the same shape refit.
    assert _pallas_blocks(768, 768, 128, 512, 512,
                          strict_q=True, strict_k=True) is None
    assert _pallas_blocks(768, 768, 128, 512, 512) == (384, 384)
    assert _pallas_blocks(1024, 768, 128, 512, 512,
                          strict_q=True, strict_k=False) == (512, 384)


# ------------------------------------------- prefix (chunked prefill)


@jax.jit
def _dense_chunk_attention(q, k, v, off):
    """``models/generate.py::_decode_chunk``'s dense body for a uniform
    chunk at ``off``: float32 scores over every cache slot, the
    position mask, softmax, values — the kernel's oracle."""
    b, t, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    qg = q.astype(jnp.float32).reshape(b, t, kv, h // kv, d)
    logits = jnp.einsum("btcgk,bsck->btcgs", qg, k.astype(jnp.float32))
    logits = logits / jnp.sqrt(jnp.float32(d))
    pos_ids = off + jnp.arange(t)
    mask = (jnp.arange(s)[None, :] <= pos_ids[:, None]
            )[None, :, None, None, :]
    probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
    return jnp.einsum("btcgs,bsck->btcgk", probs,
                      v.astype(jnp.float32)).reshape(b, t, h, d)


PREFIX_S = 64          # max_len of the stand-in cache


def _prefix_case(rng, t, h, kv, off, b=1, d=16):
    """A chunk ``[off, off + t)`` written into a cache whose slots past
    the chunk hold garbage (large, finite): what a reused lane holds."""
    q = rng.normal(size=(b, t, h, d))
    k = rng.normal(size=(b, PREFIX_S, kv, d))
    v = rng.normal(size=(b, PREFIX_S, kv, d))
    k[:, off + t:] = 3e4
    v[:, off + t:] = -3e4
    return tuple(jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))


@pytest.mark.parametrize("heads", [(4, 1), (4, 2)],
                         ids=["multi_query", "grouped"])
@pytest.mark.parametrize("t,bq", [(8, 8), (32, 16)],
                         ids=["narrow_bucket", "wide_bucket"])
@pytest.mark.parametrize("where", ["start", "unaligned", "end"])
def test_flash_prefix_matches_dense_body(rng, where, t, bq, heads):
    """The blocked kernel (interpreter) against ``_decode_chunk``'s
    dense body: at offset 0, at an offset no block boundary meets, and
    with the chunk ending at the cache's last slot; two chunk widths
    (stand-ins for the 64 and 512 buckets; the wide one spans two q
    blocks); multi-query and grouped heads; garbage past the chunk."""
    h, kv = heads
    off = {"start": 0, "unaligned": 13, "end": PREFIX_S - t}[where]
    q, k, v = _prefix_case(rng, t, h, kv, off, b=2 if kv == 2 else 1)
    out = flash_prefix_attention(q, k, v, jnp.int32(off), block_q=bq,
                                 block_k=16, interpret=True)
    assert out.shape == q.shape and out.dtype == q.dtype
    ref = _dense_chunk_attention(q, k, v, off)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=2e-2,
                               rtol=2e-2)


def test_flash_prefix_garbage_and_padded_tail_change_nothing(rng):
    """Bit for bit: slots past ``off + T`` are never read, whatever
    they hold, and a bucket-padded tail (the last rows of the chunk are
    padding: other queries, other K/V in their slots) leaves the real
    rows' results alone — each row sees only slots up to its own."""
    t, real, off = 16, 11, 21
    q, k, v = _prefix_case(rng, t, 4, 1, off)
    run = lambda q, k, v: flash_prefix_attention(
        q, k, v, jnp.int32(off), block_q=8, block_k=16, interpret=True)
    out = run(q, k, v)
    np.testing.assert_array_equal(
        out, run(q, k.at[:, off + t:].set(-7e3), v.at[:, off + t:].set(9e3)))
    pad = slice(off + real, off + t)
    padded = run(q.at[:, real:].set(1.5), k.at[:, pad].set(0.25),
                 v.at[:, pad].set(-2.0))
    np.testing.assert_array_equal(out[:, :real], padded[:, :real])


def test_flash_prefix_traced_offset_is_one_program(rng):
    """``off`` is a traced scalar: one compiled program serves every
    chunk offset (an admission bucket compiles once)."""
    q, k, v = _prefix_case(rng, 8, 4, 1, 0)
    fn = jax.jit(lambda q, k, v, off: flash_prefix_attention(
        q, k, v, off, block_q=8, block_k=16, interpret=True))
    for off in (0, 5, 40):
        np.testing.assert_allclose(
            fn(q, k, v, jnp.int32(off)).astype(jnp.float32),
            _dense_chunk_attention(q, k.at[:, off + 8:].set(0),
                                   v.at[:, off + 8:].set(0), off),
            atol=2e-2, rtol=2e-2)
    assert fn._cache_size() == 1


def test_prefix_blocks_tile_rule():
    """The serving cell's five admission widths (buckets 64-512 and the
    cap-wide 8192) tile against its 8192 slots; a head that is no lane
    multiple, a chunk that is no whole sublane tile and a cache that is
    no lane multiple do not."""
    bf16 = jnp.bfloat16
    for t, bq in ((64, 64), (128, 128), (256, 128), (512, 128),
                  (8192, 128)):
        assert prefix_blocks(t, 8192, 128, 16, bf16) == (bq, 1024)
    assert prefix_blocks(512, 4096, 128, 12, bf16) == (128, 1024)  # 24 / 2
    assert prefix_blocks(512, 1536, 128, 1, bf16) == (512, 768)
    assert prefix_blocks(24, 384, 128, 1, jnp.float32) == (24, 384)
    assert prefix_blocks(24, 384, 128, 1, bf16) is None           # 24 % 16
    assert prefix_blocks(8, 8192, 128, 16, bf16) is None
    assert prefix_blocks(512, 8192, 64, 16, bf16) is None
    assert prefix_blocks(512, 1000, 128, 16, bf16) is None


# ------------------------------------- decode (per-lane live prefix)


DECODE_S, DECODE_BK, DECODE_PLANE = 64, 16, 1
# A lane with nothing before it, with one slot, at a block's edge, on
# either side of one (a last block of one, three and four quarters),
# and at the cache's last slot.
DECODE_POS = (0, 1, 16, 17, 43, 63)


@jax.jit
def _dense_before(q, k_all, v_all, plane, pos0):
    """``_chunk_in_place``'s dense read of a plane, cut to the slots
    before the chunk: float32 scores over EVERY slot of every lane
    under the mask ``s < pos0[b]``, softmax, values, and the
    log-sum-exp the kernel hands to the merge — its oracle."""
    b, t, h, d = q.shape
    s, kv = k_all.shape[2], k_all.shape[3]
    ck, cv = (a[plane].astype(jnp.float32) for a in (k_all, v_all))
    qg = q.astype(jnp.float32).reshape(b, t, kv, h // kv, d)
    logits = jnp.einsum("btcgk,bsck->btcgs", qg, ck)
    logits = logits / jnp.sqrt(jnp.float32(d))
    before = (jnp.arange(s)[None, :] < pos0[:, None]
              )[:, None, None, None, :]
    logits = jnp.where(before, logits, -1e30)
    out = jnp.einsum("btcgs,bsck->btcgk", jax.nn.softmax(logits, axis=-1),
                     cv)
    return (out.reshape(b, t, h, d),
            jax.nn.logsumexp(logits, axis=-1).reshape(b, t, h))


def _decode_case(rng, t, h, kv, pos, planes=3, d=16, junk=True):
    """A slab of ``planes`` planes; with ``junk``, every slot at or
    past a lane's position, in every plane, and EVERY slot of every
    plane but ``DECODE_PLANE`` holds garbage (large, finite)."""
    b = len(pos)
    q = rng.normal(size=(b, t, h, d))
    k = rng.normal(size=(planes, b, DECODE_S, kv, d))
    v = rng.normal(size=(planes, b, DECODE_S, kv, d))
    if junk:
        dead = np.arange(DECODE_S)[None, :] >= np.asarray(pos)[:, None]
        k[:, dead], v[:, dead] = 3e4, -3e4
        others = np.arange(planes) != DECODE_PLANE
        k[others], v[others] = -2e4, 2e4
    return tuple(jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))


@pytest.mark.parametrize("heads", [(16, 1), (16, 16), (8, 2)],
                         ids=["groups16", "groups1", "grouped"])
@pytest.mark.parametrize("t", [1, 4], ids=["token", "chunk4"])
def test_flash_decode_matches_dense_body(rng, t, heads):
    """The per-lane bounded kernel (interpreter) against the dense
    body's read of the plane, lanes at 0, 1, a block edge, past one and
    ``S - 1`` in ONE call: the serving cells' two head layouts (16
    query heads on one K/V head; 16 K/V heads, groups of 1) and a
    grouped one; one token and a per-row chunk of four.  The lane at 0
    returns zeros and a log-sum-exp that weighs nothing."""
    h, kv = heads
    q, k, v = _decode_case(rng, t, h, kv, DECODE_POS)
    pos0 = jnp.asarray(DECODE_POS, jnp.int32)
    out, lse = flash_decode_attention(q, k, v, jnp.int32(DECODE_PLANE), pos0,
                                      block_k=DECODE_BK, interpret=True)
    assert out.shape == q.shape and lse.shape == q.shape[:3]
    assert out.dtype == lse.dtype == jnp.float32
    ref, ref_lse = _dense_before(q, k, v, DECODE_PLANE, pos0)
    np.testing.assert_allclose(out[1:], ref[1:], atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(lse[1:], ref_lse[1:], atol=2e-2, rtol=2e-2)
    np.testing.assert_array_equal(out[0], 0.0)
    np.testing.assert_array_equal(lse[0], np.float32(NEG_INF))


@pytest.mark.parametrize("heads", [(16, 1), (16, 16)],
                         ids=["groups16", "groups1"])
def test_flash_decode_garbage_changes_nothing(rng, heads):
    """Bit for bit: what the slots at and past a lane's position hold,
    in any plane, and what every other plane holds, never reaches the
    result — the plane index is honoured and dead blocks are not
    read."""
    h, kv = heads
    q, k, v = _decode_case(rng, 1, h, kv, DECODE_POS)
    _, k0, v0 = _decode_case(rng, 1, h, kv, DECODE_POS, junk=False)
    pos0 = jnp.asarray(DECODE_POS, jnp.int32)
    dead = (jnp.arange(DECODE_S)[None, :] >= pos0[:, None]
            )[:, :, None, None]
    clean = lambda a, a0: a0.at[DECODE_PLANE].set(
        jnp.where(dead, 0, a[DECODE_PLANE]))
    run = lambda k, v: flash_decode_attention(
        q, k, v, jnp.int32(DECODE_PLANE), pos0, block_k=DECODE_BK,
        interpret=True)
    for got, want in zip(run(k, v), run(clean(k, k0), clean(v, v0))):
        np.testing.assert_array_equal(got, want)


def test_flash_decode_traced_plane_and_positions_are_one_program(rng):
    """``plane`` and ``pos0`` are traced: one compiled program serves
    every layer of the scan and every state of the lanes."""
    pos = (5, 40, 16)
    q, k, v = _decode_case(rng, 1, 4, 1, pos, junk=False)
    fn = jax.jit(lambda q, k, v, plane, pos0: flash_decode_attention(
        q, k, v, plane, pos0, block_k=DECODE_BK, interpret=True))
    for plane, shift in ((0, 0), (2, 7), (1, 23)):
        pos0 = jnp.asarray(pos, jnp.int32) + shift
        out, lse = fn(q, k, v, jnp.int32(plane), pos0)
        ref, ref_lse = _dense_before(q, k, v, plane, pos0)
        np.testing.assert_allclose(out, ref, atol=2e-2, rtol=2e-2)
        np.testing.assert_allclose(lse, ref_lse, atol=2e-2, rtol=2e-2)
    assert fn._cache_size() == 1


def test_decode_block_tile_rule():
    """Both serving cells' decode steps tile (one K/V head of 128 over
    8192 slots; 16 K/V heads over 512), as does a verification chunk of
    five; K/V heads that fill no sublane tile, a head that is no lane
    multiple, too many query rows and a cache no lane multiple tiles do
    not."""
    bf16 = jnp.bfloat16
    assert decode_block(1, 8192, 128, 16, 1, bf16) == 2048
    assert decode_block(1, 512, 128, 16, 16, bf16) == 128
    assert decode_block(5, 8192, 128, 16, 1, bf16) == 2048
    assert decode_block(1, 1536, 128, 8, 8, jnp.float32) == 128
    assert decode_block(1, 24, 128, 16, 16, bf16) == 24      # 384 rows
    assert decode_block(1, 512, 128, 16, 8, bf16) is None     # 8 % 16
    assert decode_block(1, 512, 64, 16, 1, bf16) is None
    assert decode_block(9, 8192, 128, 16, 1, bf16) is None    # 144 rows
    assert decode_block(1, 1000, 128, 16, 1, bf16) is None


# ------------------------------------ segmented launch: dead tiles skipped
#
# With segment_ids the three training kernels get, as scalar prefetch,
# the first and last live tile of the streamed side for every resident
# tile (_tile_bounds) and neither fetch nor multiply a tile outside
# them.  A skipped tile contributed exactly nothing before, so at
# unchanged blocks the results are the masking launch's, bit for bit.

S_SEG = 512


def _packed_ids(*rows, s=S_SEG):
    """``[len(rows), s]`` int32: each row ``(splits, n_pad)`` — ids 1,
    2, ... changing at ``splits``, the last ``n_pad`` positions 0."""
    out = np.zeros((len(rows), s), np.int32)
    for r, (splits, n_pad) in enumerate(rows):
        edges = [0, *splits, s - n_pad]
        for n, (a, e) in enumerate(zip(edges[:-1], edges[1:])):
            out[r, a:e] = n + 1
    return jnp.asarray(out)


# Row 0 is the case's; row 1 another packing, so that bounds are read by
# row while the grid walks batch * head.
OTHER_ROW = ((40, 130, 200, 390), 0)
SEG_CASES = {
    "window": (((100, 180, 300), 0), 200),
    "no_window": (((100, 180, 300), 0), None),
    "padding_tail": (((128, 300), 62), None),
    "one_block_document": (((128, 256), 0), None),
    "spanning_document": (((), 0), 300),
}


def _seg_case(rng, name, b=2, h=2, d=128):
    row, window = SEG_CASES[name]
    seg = _packed_ids(row, OTHER_ROW)[:b]
    q, k, v = (jnp.asarray(rng.normal(size=(b, S_SEG, h, d))
                           .astype(np.float32)) for _ in range(3))
    return q, k, v, seg, window


def _local_launchers(window, block=128, **kw):
    from distkeras_tpu.ops.attention import (_flash_bwd_local,
                                             _flash_fwd_local)

    common = dict(causal=True, scale=128 ** -0.5, block_q=block,
                  block_k=block, interpret=True, window=window, **kw)
    return (jax.jit(functools.partial(_flash_fwd_local, with_lse=True,
                                      **common)),
            jax.jit(functools.partial(_flash_bwd_local, **common)))


@pytest.mark.parametrize("case", list(SEG_CASES))
def test_segmented_forward_skip_is_bit_identical(rng, case):
    q, k, v, seg, window = _seg_case(rng, case)
    fwd, _ = _local_launchers(window)
    masked, _ = _local_launchers(window, skip_dead=False)
    for got, want in zip(fwd(q, k, v, seg), masked(q, k, v, seg)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case", list(SEG_CASES))
def test_segmented_backward_skip_is_bit_identical(rng, case):
    q, k, v, seg, window = _seg_case(rng, case)
    fwd, bwd = _local_launchers(window)
    _, masked = _local_launchers(window, skip_dead=False)
    out, lse = fwd(q, k, v, seg)
    for got, want in zip(bwd(q, k, v, out, lse, 2 * out, seg),
                         masked(q, k, v, out, lse, 2 * out, seg)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _naive_out_and_grads(q, k, v, seg, window):
    ref = functools.partial(naive_attention, causal=True, window=window,
                            segment_ids=seg)
    grads = jax.grad(lambda q, k, v: (ref(q, k, v) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    return ref(q, k, v), grads


def _assert_matches_naive(fwd, bwd, q, k, v, seg, window):
    """The file's tolerances for the interpreted kernels (segments:
    tests/test_packing.py)."""
    ref, grads = _naive_out_and_grads(q, k, v, seg, window)
    out, lse = fwd(q, k, v, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
    for got, want in zip(bwd(q, k, v, out, lse, 2 * out, seg), grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-3, rtol=5e-3)


@pytest.mark.parametrize("tiles", [None, (128, 128)],
                         ids=["blocks", "tiles"])
def test_segment_ids_that_decrease_still_match_naive(rng, tiles):
    """Ids 1, 3, 2, 3: the range test only ever keeps too much (a tile
    between two live ones is computed and masked), never too little."""
    q, k, v, _, _ = _seg_case(rng, "no_window", b=1)
    seg = jnp.asarray(np.repeat([1, 3, 2, 3], S_SEG // 4)[None]
                      .astype(np.int32))
    block = 128 if tiles is None else 256
    fwd, bwd = _local_launchers(None, block=block, tiles=tiles)
    _assert_matches_naive(fwd, bwd, q, k, v, seg, None)


@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("tiles", [(128, 128), (256, 128), (128, 256)],
                         ids=lambda t: f"{t[0]}x{t[1]}")
def test_segmented_tiles_match_naive(rng, tiles, window):
    """Blocks of 256 walked as tiles (the segmented launch's
    granularity: SEGMENT_TILES inside SEGMENT_BLOCK_* on the chip; a k
    tile twice the q tile is the forward's), forward and gradients."""
    q, k, v, seg, _ = _seg_case(rng, "padding_tail")
    fwd, bwd = _local_launchers(window, block=256, tiles=tiles)
    _assert_matches_naive(fwd, bwd, q, k, v, seg, window)


def _tiles_with_a_pair(seg, tile_q, tile_k, window):
    """Brute force over the mask: ``[B, S/tile_q, S/tile_k]`` bool,
    tiles that hold an attended pair."""
    seg = np.asarray(seg)
    b, s = seg.shape
    pos = np.arange(s)
    keep = pos[:, None] >= pos[None, :]
    if window is not None:
        keep &= pos[:, None] - pos[None, :] < window
    mask = keep[None] & (seg[:, :, None] == seg[:, None, :])
    return mask.reshape(b, s // tile_q, tile_q, s // tile_k,
                        tile_k).any(axis=(2, 4))


@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("tiles", [(128, 128), (256, 128), (64, 256)],
                         ids=lambda t: f"{t[0]}x{t[1]}")
def test_live_tile_share_is_the_masks_count(tiles, window):
    from distkeras_tpu.ops.attention import live_tile_share

    seg = _packed_ids(((100, 180, 300), 0), ((128, 300), 62), OTHER_ROW,
                      ((), 0))
    live = _tiles_with_a_pair(seg, *tiles, window)
    band = _tiles_with_a_pair(np.ones_like(seg), *tiles, window)
    assert 0 < live.sum() < band.sum()
    assert live_tile_share(seg, *tiles, window) \
        == pytest.approx(live.sum() / band.sum(), abs=1e-12)
    assert live_tile_share(np.ones_like(seg), *tiles, window) == 1.0


@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dq",
                                    "flash_bwd_dkv"])
def test_kernels_compute_the_live_tiles_and_no_other(rng, kernel):
    """Which tiles a kernel really multiplies, found by poison: NaN in
    the streamed-or-resident operand of one tile column reaches the
    result rows of exactly the tiles computed against it (0 x NaN is
    NaN, so a computed but wholly masked tile shows too).  The count
    is ``live_tile_share``'s."""
    from distkeras_tpu.ops.attention import live_tile_share

    tile, window = 128, None
    q, k, v, seg, _ = _seg_case(rng, "padding_tail", b=1, h=1)
    fwd, bwd = _local_launchers(window, block=256, tiles=(tile, tile))
    out, lse = fwd(q, k, v, seg)
    n = S_SEG // tile
    poison = lambda a, t: a.at[:, t * tile:(t + 1) * tile].set(jnp.nan)
    by_tile = lambda a: np.isnan(np.asarray(a)).reshape(n, -1).any(axis=1)
    computed = np.zeros((n, n), bool)          # [q tile, k tile]
    for t in range(n):
        if kernel == "flash_fwd":              # V's k tile -> out's q tiles
            computed[:, t] = by_tile(fwd(q, k, poison(v, t), seg)[0])
        elif kernel == "flash_bwd_dq":         # K's k tile -> dq's q tiles
            computed[:, t] = by_tile(
                bwd(q, poison(k, t), v, out, lse, 2 * out, seg)[0])
        else:                                  # dO's q tile -> dv's k tiles
            computed[t, :] = by_tile(
                bwd(q, k, v, out, lse, poison(2 * out, t), seg)[2])
    want = _tiles_with_a_pair(seg, tile, tile, window)[0]
    np.testing.assert_array_equal(computed, want)
    band = _tiles_with_a_pair(np.ones_like(seg), tile, tile, window)[0]
    assert computed.sum() / band.sum() == pytest.approx(
        live_tile_share(seg, tile, tile, window))


# ------------------------------------------------ TPU lowering, no chip
#
# The Mosaic lowering runs under JAX_PLATFORMS=cpu, and it is where the
# TPU compiler's shape rules are enforced (the (8, 128) block rule, the
# refusal to partition a kernel automatically) — so a kernel the chip
# would refuse fails here, at kernel-legal shapes (D 128, blocks 128).

def _tpu_lower(fn, *avals):
    return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))


def _scalar_prefetch(fn, *avals):
    """Scalar-prefetch operands of every ``pallas_call`` ``fn`` traces
    to, in order."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["grid_mapping"].num_index_operands)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*avals).jaxpr)
    return found


def _kernel_avals(b=2, l=512, h=2, d=128, sharding=None, seg_sharding=None):
    x = jax.ShapeDtypeStruct((b, l, h, d), jnp.bfloat16, sharding=sharding)
    seg = jax.ShapeDtypeStruct((b, l), jnp.int32, sharding=seg_sharding)
    return x, seg


@pytest.mark.parametrize("with_lse", [False, True])
@pytest.mark.parametrize("segmented", [False, True])
@pytest.mark.parametrize("window", [None, 256])
def test_forward_kernel_lowers_for_tpu(window, segmented, with_lse):
    x, seg = _kernel_avals()

    def fwd(q, k, v, s):
        return _flash_pallas(q, k, v, True, 0.1, 128, 128,
                             with_lse=with_lse, window=window,
                             segment_ids=s if segmented else None)
    text = _tpu_lower(fwd, x, x, x, seg).as_text()
    assert text.count("tpu_custom_call") == 1
    # Segments bring the tile bounds (plain XLA, no kernel of their
    # own); without them the launch is the plain grid.
    assert _scalar_prefetch(fwd, x, x, x, seg) == [2 if segmented else 0]


@pytest.mark.parametrize("segmented", [False, True])
@pytest.mark.parametrize("window", [None, 256])
def test_backward_kernels_lower_for_tpu(window, segmented):
    from distkeras_tpu.ops.attention import _flash_pallas_bwd

    x, seg = _kernel_avals()
    lse = jax.ShapeDtypeStruct((2, 2, 512), jnp.float32)

    def bwd(q, k, v, out, lse, g, s):
        return _flash_pallas_bwd(q, k, v, out, lse, g, True, 0.1, 128, 128,
                                 window=window,
                                 segment_ids=s if segmented else None)
    text = _tpu_lower(bwd, x, x, x, x, lse, x, seg).as_text()
    assert text.count("tpu_custom_call") == 2   # dq, dkv
    assert _scalar_prefetch(bwd, x, x, x, x, lse, x, seg) \
        == [2 if segmented else 0] * 2


@pytest.mark.parametrize("window", [None, 2048])
def test_segmented_default_launch_lowers_for_tpu(monkeypatch, window):
    """``flash_attention`` with segments and defaulted blocks, forward
    and backward, at the segmented launch's own blocks and tiles over
    rows long enough to hold several: three Mosaic calls, each under
    the tile bounds; without segments the same call takes none."""
    from distkeras_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    x, seg = _kernel_avals(b=1, l=4096)
    assert all(tq < 4096 and tk < 4096 for tq, tk in
               attention.segment_tiles_for(4096).values())

    def loss(q, k, v, s=None):
        return flash_attention(q, k, v, True, window=window,
                               segment_ids=s).astype(jnp.float32).sum()
    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    assert _tpu_lower(grad, x, x, x, seg).as_text().count(
        "tpu_custom_call") == 3
    assert _scalar_prefetch(grad, x, x, x, seg) == [2, 2, 2]
    assert _scalar_prefetch(grad, x, x, x) == [0, 0, 0]


@pytest.mark.parametrize("spec", [("data", None, None, None),
                                  (None, None, "model", None),
                                  ("data", None, "model", None)])
def test_flash_attention_lowers_for_tpu_in_sharded_jit(devices, monkeypatch,
                                                       spec):
    """flash_attention inside a multi-device jit, batch- and
    head-sharded operands, forward and backward, segments included:
    the kernels must sit in a shard_map (read off the operands' mesh),
    or the Mosaic lowering raises "cannot be automatically
    partitioned"."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distkeras_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    mesh = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    x, seg = _kernel_avals(
        b=4, sharding=NamedSharding(mesh, P(*spec)),
        seg_sharding=NamedSharding(mesh, P(spec[0], None)))

    def loss(q, k, v, s):
        return flash_attention(q, k, v, True, window=256,
                               segment_ids=s).astype(jnp.float32).sum()
    text = _tpu_lower(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                      x, x, x, seg).as_text()
    assert text.count("tpu_custom_call") == 3


def test_per_shard_matches_unsharded(devices, rng):
    """_per_shard's layout logic, with a jnp function in the kernel's
    place: batch splits over data, heads over model, an axis that does
    not divide its dimension leaves it whole, and the result keeps the
    operands' placement (nothing gathered)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distkeras_tpu.ops.attention import _per_shard

    mesh = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    seg = np.repeat(np.arange(4, dtype=np.int32), 8)[None]

    def local(q, k, v, s):
        return (naive_attention(q, k, v, causal=True, segment_ids=s),)

    def run(q, k, v, s):
        return _per_shard(local, ["bqhd", "bkhd", "bkhd", "bq"], ["bqhd"],
                          q, k, v, s)[0]

    for b, want in ((4, P("data", None, "model", None)),
                    (2, P(None, None, "model", None))):   # 2 % 4 != 0
        q, k, v = qkv(rng, b=b, d=4)
        s = np.broadcast_to(seg, (b, 32))
        ref = naive_attention(q, k, v, causal=True, segment_ids=s)
        put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
        out = jax.jit(run)(*(put(a, P("data", None, "model", None))
                             if b == 4 else put(a, P(None, None, "model"))
                             for a in (q, k, v)), s)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
        assert out.sharding.is_equivalent_to(NamedSharding(mesh, want), 4)


@pytest.mark.parametrize("t,h,kv,s", [(64, 16, 1, 8192), (512, 16, 1, 8192),
                                      (8192, 16, 1, 8192),
                                      (512, 24, 2, 4096)])
def test_prefix_kernel_lowers_for_tpu(t, h, kv, s):
    """The chunked-prefill kernel at the serving cell's widths (16
    query heads on one K/V head of 128, 8192 slots; the narrowest, the
    chunk-wide and the cap-wide bucket) and at grouped heads, with the
    blocks its tile rule picks and a traced offset."""
    x = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = _tpu_lower(flash_prefix_attention, x(1, t, h, 128),
                      x(1, s, kv, 128), x(1, s, kv, 128),
                      jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert "flash_prefix_fwd" in text


@pytest.mark.parametrize("slab,h,t", [((24, 32, 8192, 1, 128), 16, 1),
                                      ((192, 11, 512, 16, 128), 16, 1),
                                      ((24, 32, 8192, 1, 128), 16, 5)],
                         ids=["sc1b", "ouro", "sc1b_verify5"])
def test_decode_kernel_lowers_for_tpu(slab, h, t):
    """The per-lane bounded decode kernel at both serving cells' slab
    shapes (and a verification chunk), taking the WHOLE slab, a traced
    plane and traced per-lane positions, with the block its tile rule
    picks."""
    x = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    text = _tpu_lower(flash_decode_attention, x(slab[1], t, h, 128),
                      x(*slab), x(*slab),
                      jax.ShapeDtypeStruct((), jnp.int32),
                      jax.ShapeDtypeStruct((slab[1],), jnp.int32)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert "flash_decode_fwd" in text
    # Nothing is cut out of the slab in front of the call.
    assert "dynamic_slice" not in text and "dynamic-slice" not in text


def test_head_major_kernels_and_the_grouped_product_lower_for_tpu(
        monkeypatch):
    """A typed stack's planes are K/V-head-major: the prefix kernel
    takes them as they lie and the decode kernel their free view of one
    K/V head a row, at 8 K/V heads of 128 under 64 query heads (no
    token-major tiling exists: ``decode_block`` says so); the held
    experts' grouped product at the benchmark's widths, a group's whole
    stack of 3 layers x 16 experts handed over."""
    from distkeras_tpu.ops import attention, grouped

    x = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    assert decode_block(1, 8192, 128, 64, 8, jnp.bfloat16) is None
    assert decode_block(1, 8192, 128, 8, 1, jnp.bfloat16) == 2048
    text = _tpu_lower(
        functools.partial(flash_prefix_attention, head_major=True),
        x(1, 512, 64, 128), x(1, 8, 8192, 128), x(1, 8, 8192, 128),
        i32()).as_text()
    assert text.count("tpu_custom_call") == 1 and "flash_prefix_fwd" in text
    text = _tpu_lower(flash_decode_attention, x(16 * 8, 1, 8, 128),
                      x(1, 16 * 8, 8192, 1, 128), x(1, 16 * 8, 8192, 1, 128),
                      i32(), i32(16 * 8)).as_text()
    assert text.count("tpu_custom_call") == 1 and "flash_decode_fwd" in text
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(grouped, "_on_tpu", lambda: True)
    assert grouped.grouped_tiles(6144, 4096, jnp.bfloat16) == (128, 2048,
                                                                1024)
    text = _tpu_lower(grouped.grouped_matmul, x(1520, 6144),
                      x(48, 6144, 4096), i32(48)).as_text()
    # The name the trace gives the kernel's calls (``mosaic:gmm``, read
    # by benchmarks/readers/moe_gmm_roofline.py) is that library's.
    assert text.count("tpu_custom_call") >= 1 and "gmm" in text
