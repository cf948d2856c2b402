"""chip_smoke.py — does the system still start on the chip?

Drives the main paths once through the entry points a user calls, at
full model width with random weights and data made from a seed, on
every chip JAX finds (one process, no children):

- kernels: ``flash_attention`` against ``naive_attention`` in float32,
  forward and gradient, causal / windowed / segmented / both;
  ``flash_prefix_attention`` (chunked prefill) against masked float32
  attention over the whole cache, at the serving cell's widths;
- paper: ``ADAG`` on the CIFAR CNN -> ``ModelPredictor`` ->
  ``AccuracyEvaluator``;
- lm: ``LMTrainer`` on the d1024 L8 long-context model at seq 4096,
  batch 8 per chip (plain + held-out evaluation, then packed rows);
- serve: ``ContinuousBatcher`` and ``PagedBatcher`` (the latter behind
  ``Router``) on the d1024 L8 serving model, requests admitted
  mid-flight, every generated token checked against a teacher-forced
  forward pass and compared with solo ``generate``; zero programs
  built in the serve phase.

On four chips it adds ``fsdp=True`` and ``data=2 x model=2`` training,
one ``serving_plan()`` engine over ``model=4``, a sharded kernel
check, and assertions that the work is spread.

Prints per leg: compile seconds, run seconds, programs built, cache
hits and every device's ``peak_bytes_in_use``.  Last stdout line:
``{"ok": true, "device": {...}}`` and exit code 0 — only when every
leg passed, and only on a TPU.  On any other backend it exits non-zero
at once.  It reports no rate: speed is the benchmark's business.

    python chip_smoke.py            # on the chip (see README, Running)
"""

from __future__ import annotations

import functools
import json
import math
import sys
import time
import traceback

# Sizes.  Full width; a CPU debug driver may shrink them (the script
# itself never runs its legs off the TPU).
LM_CFG = dict(vocab_size=32768, d_model=1024, n_heads=8, n_layers=8,
              d_ff=4096, max_len=4097, dtype="bfloat16", remat=True)
LM_SEQ = 4096
LM_BATCH_PER_CHIP = 8
LM_STEPS = 6
SERVE_CFG = dict(vocab_size=32768, d_model=1024, n_heads=8, n_layers=8,
                 d_ff=4096, max_len=1025, dtype="bfloat16", rope=True)
# The looped leg: the extended block (gated SiLU feed-forward, output
# norms, untied head) applied three times with the same weights, at a
# toy depth; 1024 slots so that its admissions take the prefix kernel.
SERVE_LOOPED_CFG = dict(vocab_size=32768, d_model=1024, n_heads=8,
                        n_layers=4, d_ff=2816, max_len=1024,
                        dtype="bfloat16", rope=True, rope_theta=1e6,
                        ffn_gated=True, tie_head=False, post_norms=True,
                        fused_qkv=True, n_passes=3)
SERVE_PROMPT_LENS = (20, 100, 300, 500)   # buckets 32 / 128 / 512
SERVE_NEW = 32
SERVE_LANES = 4                           # < requests: mid-flight admission
SERVE_PAD = 1024                          # teacher-forced reference length
KERNEL_SHAPE = (2, 4096, 8, 128)          # B, L, H, D
KERNEL_WINDOW = 1024
# Chunked prefill at the serving cells' widths: query heads, K/V heads,
# head, cache slots; (bucket, offset) pairs, the offsets on no block
# edge.  ``sc1b``: 16 query heads on one K/V head, 8192 slots;
# ``ouro``: 16 K/V heads (groups of 1), 512 slots.
PREFIX_SHAPES = {
    "sc1b": ((16, 1, 128, 8192),
             ((64, 4099), (512, 2283), (512, 8192 - 512))),
    "ouro": ((16, 16, 128, 512), ((64, 37), (128, 131), (256, 256))),
}
# The decode step's attention at the serving cells' slab shapes, cut in
# planes to what fits beside nothing else: slab [planes, lanes, slots,
# K/V heads, head], query heads, and the share of a lane's slots that
# the cell's traced runs found live.
DECODE_SHAPES = {
    "sc1b": ((4, 32, 8192, 1, 128), 16, 0.54),
    "ouro": ((16, 11, 512, 16, 128), 16, 0.43),
}
DECODE_CALLS = 96                         # timed calls a dispatch
CIFAR_ROWS_PER_CHIP = 4096

# A generated token must score within this of the best logit of the
# teacher-forced forward pass at its position.  Logits of the random
# model are ~N(0, 1) over 32768 entries (the best ~4), so a wrong token
# misses by ~4 while bf16 reordering noise stays well under 0.1.
LOGIT_TOL = 0.25
KERNEL_TOL = 2e-2                         # bf16 outputs vs the f32 oracle
PEAK_SPREAD = 1.5                         # max/min peak bytes across chips


class Meter:
    """Counts what JAX builds: every program requested from the
    backend (compiled, or fetched from the persistent cache), the
    seconds that took, and the cache hits."""

    def __init__(self):
        import jax.monitoring

        self.programs = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.programs, self.seconds, self.hits


def peak_bytes():
    import jax

    return [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]


def counting_rows(n_rows, width, vocab, seed):
    """Learnable toy language (examples/serving_engine.py): each row
    counts up from a random start, modulo the vocabulary."""
    import numpy as np

    start = np.random.default_rng(seed).integers(0, vocab, (n_rows, 1))
    return ((np.arange(width)[None] + start) % vocab).astype(np.int32)


# ------------------------------------------------------------------ kernels


def leg_kernels(mesh=None):
    """flash_attention == naive_attention (float32) on the device,
    forward and gradient.  With ``mesh`` the operands are sharded over
    batch (data) and heads (model) and the kernel runs per shard."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distkeras_tpu.ops.attention import flash_attention, naive_attention

    b, l = KERNEL_SHAPE[:2]
    rng = np.random.default_rng(0)
    q, k, v, w = (jnp.asarray(rng.normal(size=KERNEL_SHAPE), jnp.bfloat16)
                  for _ in range(4))
    # Packed rows: a few documents of uneven length per row.
    cuts = np.sort(rng.integers(1, l, (b, 5)), axis=1)
    seg = jnp.asarray(
        1 + (np.arange(l)[None, :, None] >= cuts[:, None, :]).sum(-1),
        jnp.int32)
    if mesh is not None:
        put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))
        q, k, v, w = (put(a, P("data", None, "model", None))
                      for a in (q, k, v, w))
        seg = put(seg, P("data", None))

    def both(fn):
        def run(q, k, v, seg):
            out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, seg), q, k, v)
            return (out, *vjp(w.astype(out.dtype)))
        return jax.jit(run)

    report = {}
    for name, window, segmented in (("causal", None, False),
                                    ("window", KERNEL_WINDOW, False),
                                    ("segments", None, True),
                                    ("window+segments", KERNEL_WINDOW, True)):
        flash = both(lambda q, k, v, s: flash_attention(
            q, k, v, True, window=window, segment_ids=s))
        naive = both(lambda q, k, v, s: naive_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True, window=window,
            segment_ids=s))
        s = seg if segmented else None
        assert "tpu_custom_call" in flash.lower(q, k, v, s).as_text(), (
            f"{name}: flash_attention took the blockwise path")
        got = flash(q, k, v, s)
        with jax.default_matmul_precision("float32"):  # a true f32 oracle
            want = naive(q, k, v, s)
        errs = []
        for g, r in zip(got, want):
            g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
            assert np.isfinite(g).all(), f"{name}: non-finite kernel output"
            errs.append(float(np.abs(g - r).max() / np.abs(r).max()))
        report[name] = [round(e, 5) for e in errs]
        assert max(errs) < KERNEL_TOL, (
            f"{name}: kernel vs float32 oracle, max error relative to the "
            f"largest entry (out, dq, dk, dv) = {errs}")
    return {"rel_err(out,dq,dk,dv)": report,
            "sharded": None if mesh is None else dict(mesh.shape)}


def leg_kernel_prefix():
    """flash_prefix_attention == float32 attention over every cache
    slot under the position mask, at each serving cell's widths
    (PREFIX_SHAPES), for chunks at offsets no block edge meets and at
    the cache's end; the slots past a chunk hold garbage, which must
    not reach the result."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.ops.attention import (flash_prefix_attention,
                                             naive_attention)

    rng = np.random.default_rng(0)
    kernel = jax.jit(flash_prefix_attention)
    report = {}
    for name, ((h, kv, d, s_len), chunks) in PREFIX_SHAPES.items():
        k, v = (jnp.asarray(rng.normal(size=(1, s_len, kv, d)),
                            jnp.bfloat16) for _ in range(2))

        def oracle(q, k, v, off, groups=h // kv):
            # Every cache slot, K/V heads repeated per query head, f32.
            wide = lambda a: jnp.repeat(a.astype(jnp.float32), groups,
                                        axis=2)
            return naive_attention(q.astype(jnp.float32), wide(k), wide(v),
                                   causal=True, q_offset=off)

        for t, off in chunks:
            q = jnp.asarray(rng.normal(size=(1, t, h, d)), jnp.bfloat16)
            off_ = jnp.int32(off)
            assert "tpu_custom_call" in kernel.lower(q, k, v, off_).as_text()
            junk_k = k.at[:, off + t:].set(3e4)
            junk_v = v.at[:, off + t:].set(-3e4)
            got = np.asarray(kernel(q, junk_k, junk_v, off_), np.float32)
            with jax.default_matmul_precision("float32"):  # a true f32 oracle
                want = np.asarray(jax.jit(oracle)(q, k, v, off_))
            assert np.isfinite(got).all(), (
                f"{name} {t}@{off}: non-finite kernel output")
            err = float(np.abs(got - want).max() / np.abs(want).max())
            report[f"{name}:{t}@{off}"] = round(err, 5)
            assert err < KERNEL_TOL, (
                f"{name}: {t} queries at {off}: kernel vs float32 oracle, "
                f"max error relative to the largest entry = {err}")
    return {"rel_err": report}


def leg_kernel_decode():
    """flash_decode_attention == float32 attention over every slot
    before each lane's position, on the whole slab at each serving
    cell's shape (DECODE_SHAPES): lanes filled as the cells fill them,
    one at 0, one parked at the last slot, garbage in every dead slot;
    one token a lane and a per-row chunk of four.  Then the kernel's
    time against the dense body's read of the plane (XLA's fusions
    over ALL slots), per call of a dependent chain over the planes,
    and the kernel's rate over the blocks it reads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.ops.attention import (DECODE_TAIL_PARTS, decode_block,
                                             flash_decode_attention)

    def dense(q, k_all, v_all, plane, pos0):
        # _chunk_in_place's dense read: every slot, masked.
        b, t, h, d = q.shape
        s_len, kv = k_all.shape[2], k_all.shape[3]
        ck, cv = (jax.lax.dynamic_index_in_dim(a, plane, 0, keepdims=False)
                  .astype(jnp.float32) for a in (k_all, v_all))
        qg = q.astype(jnp.float32).reshape(b, t, kv, h // kv, d)
        logits = jnp.einsum("btcgk,bsck->btcgs", qg, ck) / np.sqrt(d)
        before = (jnp.arange(s_len)[None, :] < pos0[:, None]
                  )[:, None, None, None, :]
        probs = jax.nn.softmax(jnp.where(before, logits, -1e30), axis=-1)
        return jnp.einsum("btcgs,bsck->btcgk", probs, cv).reshape(q.shape)

    def per_call_us(fn, q, k_all, v_all, pos0):
        @jax.jit
        def chain(q, k_all, v_all, pos0):
            def body(i, acc):
                return fn(q + (acc * 1e-3).astype(q.dtype), k_all, v_all,
                          i % k_all.shape[0], pos0).astype(jnp.float32)
            return jax.lax.fori_loop(0, DECODE_CALLS, body,
                                     jnp.zeros(q.shape, jnp.float32))
        chain(q, k_all, v_all, pos0).block_until_ready()
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            chain(q, k_all, v_all, pos0).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / DECODE_CALLS * 1e6

    rng = np.random.default_rng(0)
    kernel = lambda *a: flash_decode_attention(*a)[0]
    report = {}
    for name, (slab, h, fill) in DECODE_SHAPES.items():
        planes, b, s_len, kv, d = slab
        make = jax.jit(lambda key: jax.random.normal(key, slab, jnp.bfloat16))
        k_all, v_all = make(jax.random.key(1)), make(jax.random.key(2))
        pos = rng.uniform(0.15, 0.93, b)
        pos = np.clip((pos * fill / pos.mean() * s_len).astype(int), 1,
                      s_len - 1)
        pos[0], pos[-1] = 0, s_len - 1
        pos0 = jnp.asarray(pos, jnp.int32)
        dead = jnp.asarray(np.arange(s_len)[None, :] >= pos[:, None]
                           )[:, :, None, None]
        junk_k = k_all.at[1].set(jnp.where(dead, 3e4, k_all[1]))
        junk_v = v_all.at[1].set(jnp.where(dead, -3e4, v_all[1]))
        errs = {}
        for t in (1, 4):
            q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.bfloat16)
            run = jax.jit(flash_decode_attention)
            assert "tpu_custom_call" in run.lower(
                q, junk_k, junk_v, jnp.int32(1), pos0).as_text()
            got, lse = (np.asarray(a) for a in run(q, junk_k, junk_v,
                                                   jnp.int32(1), pos0))
            with jax.default_matmul_precision("float32"):  # a true oracle
                want = np.asarray(jax.jit(dense)(q, k_all[1:2], v_all[1:2],
                                                 jnp.int32(0), pos0))
            assert np.isfinite(got).all() and not got[0].any(), (
                f"{name} T={t}: the lane at 0 must read nothing")
            assert (lse[0] < -1e29).all() and np.isfinite(lse[1:]).all()
            errs[t] = float(np.abs(got[1:] - want[1:]).max()
                            / np.abs(want).max())
            assert errs[t] < KERNEL_TOL, (
                f"{name}: {t} token(s) a lane: kernel vs float32 oracle, "
                f"max error relative to the largest entry = {errs[t]}")
        q = jnp.asarray(rng.normal(size=(b, 1, h, d)), jnp.bfloat16)
        block = decode_block(1, s_len, d, h, kv, k_all.dtype)
        unit = block // DECODE_TAIL_PARTS       # what a read rounds up to
        live = int((-(-pos // unit) * unit).sum()) * kv * d * 2 * 2
        kernel_us = per_call_us(kernel, q, k_all, v_all, pos0)
        dense_us = per_call_us(dense, q, k_all, v_all, pos0)
        report[name] = {
            "rel_err": {f"T={t}": round(e, 5) for t, e in errs.items()},
            "block": block, "fill": round(float(pos.mean()) / s_len, 3),
            "kernel_us": round(kernel_us, 1), "dense_us": round(dense_us, 1),
            "kernel_gb_s_live": round(live / kernel_us / 1e3, 1),
            "dense_gb_s_all": round(b * s_len * kv * d * 4 / dense_us / 1e3,
                                    1)}
        del k_all, v_all, junk_k, junk_v
    return report


# -------------------------------------------------------------------- paper


def leg_paper():
    """The paper's workflow: ADAG -> ModelPredictor -> AccuracyEvaluator
    on the CIFAR CNN over every chip, separable synthetic images."""
    import jax
    import keras
    import numpy as np

    import distkeras_tpu as dk
    from distkeras_tpu.models.zoo import cifar_cnn

    n = CIFAR_ROWS_PER_CHIP * jax.device_count()
    rng = np.random.default_rng(0)
    protos = rng.normal(0, 1.0, (10, 32, 32, 3))
    y = rng.integers(0, 10, n)
    x = (protos[y] + rng.normal(0, 1.0, (n, 32, 32, 3))).astype(np.float32)
    ds = dk.Dataset.from_arrays(x, y.astype(np.int64))

    keras.mixed_precision.set_global_policy("mixed_bfloat16")
    try:
        trainer = dk.ADAG(cifar_cnn(seed=0),
                          loss="sparse_categorical_crossentropy",
                          worker_optimizer="adam", learning_rate=1e-3,
                          batch_size=64, communication_window=4,
                          num_epoch=2)
        trained = trainer.train(ds)
        scored = dk.LabelIndexTransformer(input_col="prediction").transform(
            dk.ModelPredictor(trained, output_col="prediction").predict(ds))
        acc = dk.AccuracyEvaluator(
            prediction_col="prediction_index").evaluate(scored)
    finally:
        keras.mixed_precision.set_global_policy("float32")
    hist = [float(v) for v in trainer.history]
    assert all(math.isfinite(v) for v in hist), hist
    assert hist[-1] < hist[0], f"ADAG loss did not fall: {hist[0]} -> {hist[-1]}"
    assert acc > 0.9, f"accuracy {acc} <= 0.9"
    return {"windows": len(hist), "loss": [round(hist[0], 4),
                                           round(hist[-1], 4)],
            "accuracy": round(float(acc), 4), "workers": trainer.num_workers}


# ----------------------------------------------------------------------- lm


def _assert_kernel_in_step(trainer, segments):
    """The program ``train`` runs holds the Mosaic kernel, not the
    blockwise path (the dispatch is made when the step is traced)."""
    import jax
    import jax.numpy as jnp

    params, opt_state, _, _, step, step_sh, _ = (
        trainer._build_carry_and_step(trainer.init_params()))
    tok = jax.ShapeDtypeStruct((trainer.batch_size, LM_SEQ + 1), jnp.int32,
                               sharding=step_sh)
    text = step.lower((params, opt_state), tok, None,
                      tok if segments else None).as_text()
    assert "tpu_custom_call" in text, (
        "LMTrainer's step took the blockwise path, not the Pallas kernel")
    return text.count("tpu_custom_call")


def _train_lm(mesh_spec=None, packed=False, steps=LM_STEPS, **kw):
    import jax
    import numpy as np

    import distkeras_tpu as dk
    from distkeras_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(**LM_CFG)
    mesh = None if mesh_spec is None else dk.make_mesh(mesh_spec)
    n_data = (jax.device_count() if mesh is None
              else int(mesh.shape["data"]))
    batch = LM_BATCH_PER_CHIP * n_data
    trainer = dk.LMTrainer(cfg, learning_rate=1e-3, batch_size=batch,
                           num_epoch=1, mesh=mesh, **kw)
    n_rows = batch * (steps + 1)
    if packed:
        # Counting documents of uneven length, packed into full rows.
        rng = np.random.default_rng(1)
        lens = rng.integers(LM_SEQ // 8, LM_SEQ, 2 * n_rows)
        docs = [row[:k].tolist() for row, k in zip(
            counting_rows(len(lens), LM_SEQ, cfg.vocab_size, 2), lens)]
        rows, segs = dk.pack_documents(docs, seq_len=LM_SEQ)
        assert len(rows) >= n_rows, (len(rows), n_rows)
        rows, segs = rows[:n_rows], segs[:n_rows]
        trainer.train(rows[batch:], segments=segs[batch:],
                      eval_tokens=rows[:batch], eval_segments=segs[:batch])
        fill = float(dk.packing_efficiency(segs))
    else:
        rows = counting_rows(n_rows, LM_SEQ + 1, cfg.vocab_size, 0)
        trainer.train(rows[batch:], eval_tokens=rows[:batch])
        fill = None
    hist = [float(v) for v in trainer.history]
    assert len(hist) == steps, (len(hist), steps)
    assert all(math.isfinite(v) for v in hist), hist
    assert hist[-1] < hist[0], f"LM loss did not fall: {hist}"
    ev = trainer.eval_history[-1][1]["loss"]
    assert math.isfinite(ev) and ev < math.log(cfg.vocab_size) + 1, ev
    return trainer, {
        "mesh": {a: s for a, s in trainer.mesh.shape.items() if s > 1},
        "batch": batch, "loss": [round(v, 3) for v in hist],
        "eval_loss": round(ev, 3), "packing_fill": fill,
        "mosaic_calls_in_step": _assert_kernel_in_step(trainer, packed)}


def leg_lm():
    """LMTrainer over every chip (replicated data parallelism): a
    handful of steps and one held-out evaluation."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    trainer, report = _train_lm()
    n, batch = jax.device_count(), trainer.batch_size
    tok = trainer._global_batch(
        np.zeros((batch, LM_SEQ + 1), np.int32),
        NamedSharding(trainer.mesh, P("data", None)))
    shards = tok.addressable_shards
    assert len({s.device for s in shards}) == n and all(
        s.data.shape == (batch // n, LM_SEQ + 1) for s in shards), (
        "a batch-sharded array does not have one shard per chip: "
        f"{[(s.device, s.data.shape) for s in shards]}")
    report["batch_shards"] = len(shards)
    if n > 1:
        # First leg to run, so the peaks are this leg's: every chip
        # must have carried its share of the step.
        peaks = peak_bytes()
        assert min(peaks) > 2**30 and max(peaks) < PEAK_SPREAD * min(peaks), (
            f"peak bytes are not spread over the chips (factor "
            f"{PEAK_SPREAD}): {peaks}")
    return report


def leg_lm_packed():
    """Packed rows: ``pack_documents`` + ``train(rows, segments=...)``
    with a packed evaluation — the segment-id kernels."""
    return _train_lm(packed=True, steps=4)[1]


def leg_lm_fsdp():
    import jax

    trainer, report = _train_lm(fsdp=True, steps=3)
    emb = trainer.init_params()["tok_emb"]
    assert "data" in tuple(emb.sharding.spec), emb.sharding
    assert len(emb.addressable_shards) == jax.device_count()
    report["emb_shard"] = list(emb.addressable_shards[0].data.shape)
    return report


def leg_lm_dp_tp():
    import distkeras_tpu as dk

    return _train_lm(dk.MeshSpec(data=2, model=2), steps=3)[1]


# -------------------------------------------------------------------- serve


class Serving:
    """The serving model, its requests and its references, built once
    and shared by the engine legs."""

    def __init__(self, cfg_kwargs=None):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from distkeras_tpu.models import transformer as tfm
        from distkeras_tpu.models.generate import generate

        self.cfg = cfg = tfm.TransformerConfig(**(cfg_kwargs or SERVE_CFG))
        self.params = tfm.init_params(jax.random.key(0), cfg)
        rng = np.random.default_rng(0)
        # Two requests per prompt length, interleaved so that lengths
        # (and admission buckets) mix in flight.
        self.prompts = [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
                        for _ in range(2) for p in SERVE_PROMPT_LENS]
        # Solo generate: the repo's own reference for the engines.
        solo = {}
        for p in SERVE_PROMPT_LENS:
            idx = [i for i, r in enumerate(self.prompts) if len(r) == p]
            out = np.asarray(jax.jit(
                lambda w, t: generate(w, t, cfg, SERVE_NEW))(
                    self.params, np.stack([self.prompts[i] for i in idx])))
            solo.update(zip(idx, out))
        self.solo = [solo[i] for i in range(len(self.prompts))]
        for r, s in zip(self.prompts, self.solo):
            assert s.shape == (len(r) + SERVE_NEW,) and (
                s[:len(r)] == r).all()

        def forward(w, t):
            logits, _ = tfm.apply(w, t, cfg)
            return logits.astype(jnp.float32)
        self._forward = jax.jit(forward)

    def check(self, outputs):
        """Every generated token of every request is (within LOGIT_TOL)
        the best-scoring token of a teacher-forced forward pass over
        the request's OWN output; returns the agreement report."""
        import numpy as np

        rows = np.zeros((len(outputs), SERVE_PAD), np.int32)
        for i, (r, out) in enumerate(zip(self.prompts, outputs)):
            out = np.asarray(out)
            assert out.shape == (len(r) + SERVE_NEW,), (
                f"request {i}: {out.shape[0] - len(r)} new tokens of "
                f"{SERVE_NEW}")
            assert (out[:len(r)] == r).all(), f"request {i}: prompt changed"
            rows[i, :len(out)] = out
        logits = np.asarray(self._forward(self.params, rows))
        worst, argmax_hits, total = 0.0, 0, 0
        first_equal = full_equal = 0
        divergences = []
        for i, (r, out) in enumerate(zip(self.prompts, outputs)):
            out, p = np.asarray(out), len(r)
            at = logits[i, p - 1:p - 1 + SERVE_NEW]     # scores of out[p:]
            assert np.isfinite(at).all(), f"request {i}: non-finite logits"
            gap = at.max(-1) - at[np.arange(SERVE_NEW), out[p:]]
            worst = max(worst, float(gap.max()))
            argmax_hits += int((gap == 0).sum())
            total += SERVE_NEW
            assert gap.max() < LOGIT_TOL, (
                f"request {i}: generated token {int(gap.argmax())} scores "
                f"{gap.max():.3f} under the best logit of the reference "
                f"forward pass (tolerance {LOGIT_TOL})")
            solo = self.solo[i]
            first_equal += int(out[p] == solo[p])
            diff = np.nonzero(out != solo)[0]
            full_equal += int(diff.size == 0)
            if diff.size:
                t = int(diff[0])
                # Shared prefix up to t: the reference scores both
                # candidates at the same position.
                row = logits[i, t - 1]
                divergences.append({
                    "request": i, "new_token": t - p,
                    "logit_gap": round(float(abs(row[solo[t]]
                                                 - row[out[t]])), 4)})
        n = len(outputs)
        return {"requests": n, "new_tokens": SERVE_NEW,
                "argmax_of_reference": f"{argmax_hits}/{total}",
                "worst_gap_to_best_logit": round(worst, 4),
                "first_token_equals_solo_generate": f"{first_equal}/{n}",
                "sequence_equals_solo_generate": f"{full_equal}/{n}",
                "first_divergences": divergences}


def _serve(front, prompts, step):
    """Enqueue half the requests, decode a few steps, enqueue the rest
    (they wait for lanes and join mid-flight); returns the outputs."""
    half = len(prompts) // 2
    rids = [front.enqueue(p, SERVE_NEW) for p in prompts[:half]]
    for _ in range(5):
        step()
    rids += [front.enqueue(p, SERVE_NEW) for p in prompts[half:]]
    for _ in range(100 * SERVE_NEW):
        if all(front.poll(r) is not None for r in rids):
            break
        step()
    results = [front.take(r) for r in rids]
    assert all(r is not None and r.ok for r in results), [
        None if r is None else r.status for r in results]
    return [r.tokens for r in results]


def _devices_of(engine):
    import jax

    return {d for leaf in jax.tree.leaves((engine.params, engine.cache))
            for d in leaf.devices()}


def _leg_engine(serving, meter, build, router=False):
    """Build, warm every program the requests touch, then serve them
    under the meter: the serve phase may build no program."""
    from distkeras_tpu.serving import InProcessReplica, Router

    engine = build()
    if router:
        front = Router([InProcessReplica("r0", engine)])
        step = front.step
    else:
        front, step = engine, engine.step
    # Warm-up pass: one request per admission bucket, decoded to the
    # end (a plain ContinuousBatcher compiles lazily).
    _serve(front, serving.prompts[:len(SERVE_PROMPT_LENS)], step)
    built = meter.programs
    outputs = _serve(front, serving.prompts, step)
    built = meter.programs - built
    assert built == 0, f"the serve phase built {built} program(s)"
    report = serving.check(outputs)
    report["serve_phase_programs"] = built
    return engine, report


def leg_serve_continuous(serving, meter):
    from distkeras_tpu.serving import ContinuousBatcher

    engine, report = _leg_engine(serving, meter, lambda: ContinuousBatcher(
        serving.params, serving.cfg, lanes=SERVE_LANES,
        max_queue=len(serving.prompts)))
    devices = _devices_of(engine)
    # An engine without mesh= lives on ONE chip (the first): a fleet of
    # one-chip replicas needs each placed on its own (ROADMAP R6).
    assert len(devices) == 1, f"unsharded engine spans {devices}"
    report["engine_devices"] = sorted(str(d) for d in devices)
    return report


def leg_serve_looped(serving, meter):
    """A looped stack (SERVE_LOOPED_CFG) through the engine the looped
    benchmark cell uses — hot-swap, chunked prefill, every program
    warmed on the live slab — against solo ``generate`` and the full
    forward, gated on logit distance like the other engine legs."""
    from distkeras_tpu.serving import ContinuousBatcher

    engine, report = _leg_engine(serving, meter, lambda: ContinuousBatcher(
        serving.params, serving.cfg, lanes=SERVE_LANES, hot_swap=True,
        prefill_chunk=256, prompt_buckets=(32, 128, 256),
        max_queue=len(serving.prompts)))
    report["kv_planes"] = int(engine.cache["k"].shape[0])
    assert report["kv_planes"] == serving.cfg.n_passes * serving.cfg.n_layers
    return report


def leg_serve_paged_router(serving, meter):
    from distkeras_tpu.serving import PagedBatcher

    max_len = serving.cfg.max_len
    block = next(b for b in range(max_len // 8, 0, -1) if max_len % b == 0)
    _, report = _leg_engine(serving, meter, lambda: PagedBatcher(
        serving.params, serving.cfg, lanes=SERVE_LANES, block=block,
        max_queue=len(serving.prompts)), router=True)
    report["block"] = block
    return report


def leg_serve_sharded(serving, meter):
    """One engine over every chip: ``serving_plan()`` on ``model=N``."""
    import jax

    import distkeras_tpu as dk
    from distkeras_tpu.parallel.sharding import serving_plan
    from distkeras_tpu.serving import ContinuousBatcher

    n = jax.device_count()
    mesh = dk.make_mesh(dk.MeshSpec(data=1, model=n))
    engine, report = _leg_engine(serving, meter, lambda: ContinuousBatcher(
        serving.params, serving.cfg, lanes=SERVE_LANES,
        max_queue=len(serving.prompts), plan=serving_plan(), mesh=mesh))
    devices = _devices_of(engine)
    assert len(devices) == n, f"sharded engine on {devices}"
    wq = engine.params["layers"]["attn"]["wq"]
    assert wq.addressable_shards[0].data.size * n == wq.size, wq.sharding
    report["engine_devices"] = len(devices)
    return report


# --------------------------------------------------------------------- main


def main():
    from distkeras_tpu.utils.misc import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke.py runs on the TPU only; this backend is "
                 f"{backend!r}")
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}
    n = jax.device_count()
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"device_count={n} bytes_limit="
          f"{jax.devices()[0].memory_stats()['bytes_limit']}")
    print(f"compile_cache_dir={cache_dir}")

    import distkeras_tpu as dk
    from distkeras_tpu import native

    print("data_plane=" + ("native" if native.available() else "numpy"))
    meter = Meter()
    serving = functools.cache(Serving)  # built by the first leg that serves
    looped = functools.cache(lambda: Serving(SERVE_LOOPED_CFG))
    # lm first: its spread check reads peaks that never reset.
    legs = [("lm", leg_lm), ("lm_packed", leg_lm_packed),
            ("kernels", leg_kernels), ("kernel_prefix", leg_kernel_prefix),
            ("kernel_decode", leg_kernel_decode),
            ("paper", leg_paper),
            ("serve_continuous",
             lambda: leg_serve_continuous(serving(), meter)),
            ("serve_paged_router",
             lambda: leg_serve_paged_router(serving(), meter)),
            ("serve_looped", lambda: leg_serve_looped(looped(), meter))]
    if n >= 4:
        legs += [("kernels_sharded", lambda: leg_kernels(
                     dk.make_mesh(dk.MeshSpec(data=n // 2, model=2)))),
                 ("lm_fsdp", leg_lm_fsdp), ("lm_dp_tp", leg_lm_dp_tp),
                 ("serve_sharded",
                  lambda: leg_serve_sharded(serving(), meter))]

    failed = []
    for name, leg in legs:
        programs, seconds, hits = meter.snapshot()
        t0 = time.perf_counter()
        try:
            report = leg()
        except Exception:  # a failed leg is reported, loudly; the rest run
            traceback.print_exc()
            report = None
            failed.append(name)
        wall = time.perf_counter() - t0
        p1, s1, h1 = meter.snapshot()
        print(json.dumps({
            "leg": name, "pass": report is not None,
            "compile_s": round(s1 - seconds, 1),
            "run_s": round(wall - (s1 - seconds), 1),
            "programs": p1 - programs, "cache_hits": h1 - hits,
            "peak_bytes_in_use": peak_bytes(), "report": report}),
            flush=True)

    if failed:
        print(json.dumps({"ok": False, "failed": failed, "device": device}))
        sys.exit(1)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
