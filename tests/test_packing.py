"""Packed-sequence training: segment-masked attention (all tiers),
loss masking, the packing utility, and LMTrainer integration."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distkeras_tpu as dk
from distkeras_tpu.data.packing import pack_documents, packing_efficiency
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.ops.attention import (
    blockwise_attention,
    flash_attention,
    naive_attention,
)
from helpers import jtfm, toy_params


# ---------------------------------------------------------------- packing

def test_pack_documents_layout():
    docs = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 11]]
    rows, segs = pack_documents(docs, seq_len=5)
    assert rows.shape == segs.shape and rows.shape[1] == 6
    # Within a row, segment ids are 1..k in order and padding is 0.
    for r, s in zip(rows, segs):
        nz = s[s != 0]
        changes = np.flatnonzero(np.diff(nz)) + 1
        assert (np.diff(nz) >= 0).all()  # non-decreasing
        assert set(np.unique(nz)) == set(range(1, nz.max() + 1))
        del changes
        assert (r[s == 0] == 0).all()
    # Every document's tokens appear, in order, under one segment (or a
    # row-boundary split into consecutive fresh segments).
    flat = [tok for r, s in zip(rows, segs) for tok in r[s != 0]]
    assert flat == [t for d in docs for t in d]


def test_pack_documents_long_doc_spans_rows():
    rows, segs = pack_documents([list(range(1, 15))], seq_len=5)
    assert rows.shape[0] >= 2
    # Continuations restart as fresh segments (context resets at the
    # row boundary) and every row starts with segment 1.
    assert all(s[0] == 1 for s in segs if s[0] != 0)


def test_pack_documents_drops_single_tokens():
    rows, segs = pack_documents([[7], [1, 2, 3]], seq_len=3)
    assert 7 not in rows[segs != 0]


def test_pack_documents_never_emits_single_token_segments():
    """A 1-token chunk is untrainable (boundary-masked target): the
    packer must start the document on a fresh row instead (regression:
    [[1,2,3,4],[5,6,7]] @ seq_len=5 used to strand token 5 alone)."""
    cases = [([[1, 2, 3, 4], [5, 6, 7]], 5),
             ([[1, 2], [3, 4, 5], [6, 7, 8, 9, 10, 11, 12]], 4),
             ([list(range(1, 40))], 6)]
    for docs, sl in cases:
        rows, segs = pack_documents(docs, seq_len=sl)
        for s in segs:
            ids, counts = np.unique(s[s != 0], return_counts=True)
            assert (counts >= 2).all(), (docs, sl, s)


def test_packing_efficiency():
    rows, segs = pack_documents([[1, 2, 3, 4]], seq_len=3)
    assert packing_efficiency(segs) == 1.0


def test_pack_validation():
    with pytest.raises(ValueError, match="seq_len"):
        pack_documents([[1, 2]], seq_len=0)
    with pytest.raises(ValueError, match="2 tokens"):
        pack_documents([[1]], seq_len=4)


# ------------------------------------------------- attention segment masking

def _qkv(rng, b=2, s=64, h=2, d=16):
    mk = lambda: jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
    return mk(), mk(), mk()


def _segs(b, s, splits=(20, 44)):
    seg = np.zeros((b, s), np.int32)
    bounds = (0,) + tuple(splits) + (s,)
    for i in range(len(bounds) - 1):
        seg[:, bounds[i]:bounds[i + 1]] = i + 1
    return jnp.asarray(seg)


def test_blockwise_segments_match_naive(rng):
    q, k, v = _qkv(rng)
    seg = _segs(2, 64)
    ref = naive_attention(q, k, v, causal=True, segment_ids=seg)
    out = blockwise_attention(q, k, v, causal=True, block_k=16,
                              segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_segments_equal_separate_documents(rng):
    """The semantic contract: a packed row attends exactly like its
    documents run alone (per-document slices match)."""
    q, k, v = _qkv(rng, b=1)
    seg = _segs(1, 64)
    packed = naive_attention(q, k, v, causal=True, segment_ids=seg)
    for lo, hi in ((0, 20), (20, 44), (44, 64)):
        alone = naive_attention(q[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
                                causal=True)
        np.testing.assert_allclose(np.asarray(packed[:, lo:hi]),
                                   np.asarray(alone), atol=2e-5, rtol=2e-5)


def test_flash_fallback_segments_grads_match_naive(rng):
    q, k, v = _qkv(rng)
    seg = _segs(2, 64)
    f = lambda fn: jax.grad(
        lambda q, k, v: (fn(q, k, v) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    got = f(lambda q, k, v: flash_attention(q, k, v, True, segment_ids=seg))
    ref = f(lambda q, k, v: naive_attention(q, k, v, causal=True,
                                            segment_ids=seg))
    for a, b in zip(got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_pallas_interpret_segments_fwd_bwd(rng):
    """The Pallas kernels under the TPU-semantics interpreter: segment
    masking in the forward and in both backward kernels, composed with
    the banded (windowed) grid."""
    from distkeras_tpu.ops.attention import _flash_pallas, _flash_pallas_bwd

    # Two rows with DIFFERENT packings and two heads: the segment tiles
    # are indexed by batch row while the grid walks batch*head.
    b, s, h, d = 2, 256, 2, 128
    q = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, d)).astype(np.float32))
    seg = jnp.concatenate([_segs(1, s, splits=(100, 180)),
                           _segs(1, s, splits=(40, 130, 200))])
    for window in (None, 96):
        ref = naive_attention(q, k, v, causal=True, window=window,
                              segment_ids=seg)
        g = jax.grad(lambda q, k, v: (naive_attention(
            q, k, v, causal=True, window=window, segment_ids=seg) ** 2
        ).sum(), argnums=(0, 1, 2))(q, k, v)
        out, lse = _flash_pallas(q, k, v, True, 1 / np.sqrt(d), 128, 128,
                                 interpret=True, window=window,
                                 segment_ids=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)
        dq, dk, dv = _flash_pallas_bwd(
            q, k, v, out, lse, 2 * out, True, 1 / np.sqrt(d), 128, 128,
            interpret=True, window=window, segment_ids=seg)
        for a, b_ in zip((dq, dk, dv), g):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-3, rtol=5e-3)


# --------------------------------------------------------- transformer + loss

CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=64, rope=True)


def test_packed_forward_equals_separate_docs(rng):
    """rope + segments: the packed logits for each document equal the
    document run alone (relative positions survive the shift)."""
    params = toy_params(CFG)
    d1 = rng.integers(1, 64, (1, 11)).astype(np.int32)
    d2 = rng.integers(1, 64, (1, 13)).astype(np.int32)
    row = np.concatenate([d1, d2], axis=1)
    seg = np.concatenate([np.full((1, 11), 1), np.full((1, 13), 2)],
                         axis=1).astype(np.int32)
    packed, _ = jtfm.apply(params, jnp.asarray(row), CFG,
                          segment_ids=jnp.asarray(seg))
    for doc, lo, hi in ((d1, 0, 11), (d2, 11, 24)):
        alone, _ = jtfm.apply(params, jnp.asarray(doc), CFG)
        np.testing.assert_allclose(np.asarray(packed[:, lo:hi]),
                                   np.asarray(alone), atol=2e-4, rtol=2e-4)


def test_packed_loss_equals_weighted_separate_losses(rng):
    """Masked packed NLL == target-count-weighted mean of per-document
    NLLs (boundary and pad targets excluded)."""
    params = toy_params(CFG)
    d1 = rng.integers(1, 64, (1, 11)).astype(np.int32)
    d2 = rng.integers(1, 64, (1, 9)).astype(np.int32)
    row = np.zeros((1, 25), np.int32)
    row[:, :11], row[:, 11:20] = d1, d2
    seg = np.zeros((1, 25), np.int32)
    seg[:, :11], seg[:, 11:20] = 1, 2
    packed = float(jtfm.lm_nll(params, jnp.asarray(row), CFG,
                              segment_ids=jnp.asarray(seg)))
    nll1 = float(jtfm.lm_nll(params, jnp.asarray(d1), CFG))
    nll2 = float(jtfm.lm_nll(params, jnp.asarray(d2), CFG))
    want = (10 * nll1 + 8 * nll2) / 18
    np.testing.assert_allclose(packed, want, rtol=1e-5)


def test_packed_loss_chunked_ce_matches_full(rng):
    cfg = dataclasses.replace(CFG, ce_chunks=4)
    params = toy_params(CFG, 1)
    row = rng.integers(1, 64, (2, 25)).astype(np.int32)
    seg = np.asarray(_segs(2, 25, splits=(9, 17)))
    full = float(jtfm.lm_nll(params, jnp.asarray(row), CFG,
                            segment_ids=jnp.asarray(seg)))
    chunked = float(jtfm.lm_nll(params, jnp.asarray(row), cfg,
                               segment_ids=jnp.asarray(seg)))
    np.testing.assert_allclose(chunked, full, rtol=1e-5)


def test_segments_with_custom_attention_fn_rejected(rng):
    params = toy_params(CFG)
    row = rng.integers(1, 64, (1, 8)).astype(np.int32)
    seg = np.ones((1, 8), np.int32)
    with pytest.raises(ValueError, match="custom attention_fn"):
        jtfm.apply(params, jnp.asarray(row), CFG,
                  attention_fn=lambda q, k, v: q,
                  segment_ids=jnp.asarray(seg))


# ----------------------------------------------------------- LMTrainer e2e

def test_lm_trainer_packed_end_to_end(rng):
    """pack_documents -> LMTrainer(train with segments) -> eval with
    segments: loss falls and the eval NLL is finite."""
    docs = [rng.integers(1, 64, (int(n),)).tolist()
            for n in rng.integers(3, 30, 40)]
    rows, segs = pack_documents(docs, seq_len=16)
    cfg = dataclasses.replace(CFG, max_len=17)
    n = (len(rows) // 8) * 8
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=8, num_epoch=3,
                      eval_every=2)
    tr.train(rows[:n], segments=segs[:n],
             eval_tokens=rows[:8], eval_segments=segs[:8])
    assert tr.history[-1] < tr.history[0]
    assert all(np.isfinite(v["loss"]) for _, v in tr.eval_history)


def test_packed_eval_weighted_by_valid_counts(rng):
    """Eval chunks with unequal valid-target counts must combine into
    the corpus mean (count-weighted), not a mean of chunk means."""
    cfg = dataclasses.replace(CFG, max_len=17)
    rows = rng.integers(1, 64, (16, 17)).astype(np.int32)
    segs = np.ones((16, 17), np.int32)
    # Second chunk: mostly padding -> few valid targets.
    rows[8:, 5:] = 0
    segs[8:, 5:] = 0
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=8, num_epoch=1)
    params = tr.train(rows[:8], segments=segs[:8],
                      eval_tokens=rows, eval_segments=segs)
    got = tr.eval_history[-1][1]["loss"]

    n1 = float(jtfm.lm_nll(params, jnp.asarray(rows[:8]), cfg,
                          segment_ids=jnp.asarray(segs[:8])))
    n2 = float(jtfm.lm_nll(params, jnp.asarray(rows[8:]), cfg,
                          segment_ids=jnp.asarray(segs[8:])))
    w1, w2 = 8 * 16, 8 * 4  # valid targets per chunk
    np.testing.assert_allclose(got, (w1 * n1 + w2 * n2) / (w1 + w2),
                               rtol=1e-6)


def test_ring_attention_segments_match_single(devices, rng):
    """Ring attention with rotating KV-side segment shards equals the
    single-device segmented attention exactly (the packed long-context
    combination)."""
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
    from distkeras_tpu.parallel.ring import make_ring_attention
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(MeshSpec(data=2, seq=4), devices=devices)
    q, k, v = _qkv(rng, b=2, s=64)
    seg = _segs(2, 64, splits=(13, 37))
    for window in (None, 24):
        ref = naive_attention(q, k, v, causal=True, window=window,
                              segment_ids=seg)
        ring = make_ring_attention(mesh, causal=True, window=window)
        out = jax.jit(lambda q, k, v, s: ring(q, k, v, segment_ids=s),
                      in_shardings=(None, None, None,
                                    NamedSharding(mesh, P("data", "seq"))
                                    ))(q, k, v, seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def test_packed_forward_ring_mesh_matches_default(devices, rng):
    """apply() with segments on a seq mesh (ring path) == the default
    flash path — one segment semantics across parallelism choices."""
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
    from distkeras_tpu.parallel.ring import make_ring_attention

    mesh = make_mesh(MeshSpec(data=2, seq=4), devices=devices)
    cfg = dataclasses.replace(CFG, max_len=33)
    params = toy_params(cfg, 2)
    row = rng.integers(1, 64, (2, 32)).astype(np.int32)
    seg = np.asarray(_segs(2, 32, splits=(11, 21)))
    ref, _ = jtfm.apply(params, jnp.asarray(row), cfg,
                       segment_ids=jnp.asarray(seg))
    ring = make_ring_attention(mesh, causal=True)
    out, _ = jtfm.apply(params, jnp.asarray(row), cfg, attention_fn=ring,
                       segment_ids=jnp.asarray(seg))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_lm_trainer_packed_ring_mesh(devices, rng):
    """Packed training runs on a seq (ring) mesh end to end."""
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh

    docs = [rng.integers(1, 64, (int(n),)).tolist()
            for n in rng.integers(5, 28, 48)]
    rows, segs = pack_documents(docs, seq_len=16)
    cfg = dataclasses.replace(CFG, max_len=17)
    n = (len(rows) // 8) * 8
    mesh = make_mesh(MeshSpec(data=2, seq=4), devices=devices)
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=8, num_epoch=2,
                      mesh=mesh)
    tr.train(rows[:n], segments=segs[:n])
    assert tr.history[-1] < tr.history[0]


@pytest.mark.parametrize("with_seq", [False, True], ids=["pp", "ppxsp"])
def test_packed_forward_pipeline_matches_default(devices, rng, with_seq):
    """apply_pipelined with segments == the default segmented apply —
    per-microbatch segment slices ride the pipeline (and shard over
    seq under PP x SP)."""
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh

    spec = (MeshSpec(data=2, pipeline=2, seq=2) if with_seq
            else MeshSpec(data=4, pipeline=2))
    mesh = make_mesh(spec, devices=devices)
    cfg = dataclasses.replace(CFG, max_len=33)
    params = toy_params(cfg, 3)
    rows = rng.integers(1, 64, (4, 32)).astype(np.int32)
    seg = np.asarray(_segs(4, 32, splits=(9, 23)))
    ref, _ = jtfm.apply(params, jnp.asarray(rows), cfg,
                       segment_ids=jnp.asarray(seg))
    out, _ = jax.jit(lambda p, t, s: tfm.apply_pipelined(
        p, t, cfg, mesh, microbatches=2,
        seq_axis="seq" if with_seq else None, segment_ids=s))(
        params, jnp.asarray(rows), jnp.asarray(seg))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_lm_trainer_packed_pipeline_mesh(devices, rng):
    """Packed training end to end on a PP x SP mesh."""
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh

    docs = [rng.integers(1, 64, (int(n),)).tolist()
            for n in rng.integers(5, 28, 48)]
    rows, segs = pack_documents(docs, seq_len=16)
    cfg = dataclasses.replace(CFG, max_len=17)
    n = (len(rows) // 8) * 8
    mesh = make_mesh(MeshSpec(data=2, pipeline=2, seq=2),
                     devices=devices)
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=8, num_epoch=2,
                      mesh=mesh)
    tr.train(rows[:n], segments=segs[:n])
    assert tr.history[-1] < tr.history[0]


def test_remat_composes_with_segments(rng):
    """remat=True with segment_ids: the attention lambda closes over
    the traced segments and still goes through jax.checkpoint's static
    attention_fn slot — loss and grads must match the no-remat run."""
    cfg = dataclasses.replace(CFG, max_len=33, remat=True)
    plain = dataclasses.replace(cfg, remat=False)
    params = toy_params(cfg, 4)
    rows = jnp.asarray(rng.integers(1, 64, (2, 20)), jnp.int32)
    seg = jnp.asarray(np.asarray(_segs(2, 20, splits=(7, 13))))
    ref = float(jtfm.lm_nll(params, rows, plain, segment_ids=seg))
    out = float(jax.jit(lambda p, t, s: jtfm.lm_nll(p, t, cfg,
                                                   segment_ids=s))(
        params, rows, seg))
    np.testing.assert_allclose(out, ref, rtol=1e-5)
    g = jax.jit(jax.grad(lambda p: jtfm.lm_nll(p, rows, cfg,
                                               segment_ids=seg)))(params)
    gr = jax.jit(jax.grad(lambda p: jtfm.lm_nll(p, rows, plain,
                                                segment_ids=seg)))(params)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_lm_trainer_packed_tp_fsdp_mesh(devices, rng):
    """Packed training composes with TP x FSDP sharding."""
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh

    docs = [rng.integers(1, 64, (int(n),)).tolist()
            for n in rng.integers(5, 28, 48)]
    rows, segs = pack_documents(docs, seq_len=16)
    cfg = dataclasses.replace(CFG, max_len=17)
    n = (len(rows) // 8) * 8
    mesh = make_mesh(MeshSpec(data=4, model=2), devices=devices)
    tr = dk.LMTrainer(cfg, learning_rate=1e-2, batch_size=8, num_epoch=2,
                      mesh=mesh, fsdp=True)
    tr.train(rows[:n], segments=segs[:n])
    assert tr.history[-1] < tr.history[0]
