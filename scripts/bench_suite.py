"""Full benchmark suite on the TPU (one JSON line per config; bench.py
stays the single-headline driver).  A backend other than TPU, or a row
that raises, ends the run with a non-zero exit code; every line names
the platform, device kind and device count it was measured on.

Methodology (same as bench.py): bf16 compute policy, jitted train step
with donated state, device-resident synthetic data, warmup, then a
timed run whose barrier is a device->host float() through the step
dependency chain (chosen when block_until_ready was seen to return
early; not re-examined on a directly attached chip).

Every line reports ``mfu``: flops from the compiled program's own
cost_analysis (not an analytic estimate) against the chip's bf16 peak;
scan-path configs take FLOPs from the single-step program because
cost_analysis counts a lax.scan body once, not times the trip count.

Two configs exercise the input pipeline end-to-end instead of
device-resident synthetic data:
``cifar_cnn_hostdata`` streams host uint8 windows through the native
row gather + DeviceFeed + multi-step scan with on-device normalization;
``cifar_cnn_resident`` stages the uint8 dataset in HBM once and gathers
minibatches on device from host-sent index blocks.

Usage: python scripts/bench_suite.py [config ...]
Configs: see BENCHES at the bottom of this file (python
scripts/bench_suite.py bogus lists them) — training configs for every
zoo model + the transformer at short/long/windowed/chunked-CE/remat
variants, decode throughput (prefill + int8), and the end-to-end input
pipeline pair.
"""

import json
import os
import sys
import time

os.environ.setdefault("KERAS_BACKEND", "jax")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Peak bf16 TFLOP/s per chip, keyed on jax device_kind.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
}


def peak_flops():
    import jax

    return PEAK_FLOPS.get(jax.devices()[0].device_kind)


def tpu_device_fields() -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` for every JSON
    line — and the gate: a benchmark measures the TPU, so any other
    backend ends the run here with a non-zero exit code (a CPU wall
    clock is not a device metric).  Shared by bench.py and
    bench_serving.py."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmarks run on the TPU only; this backend is "
                 f"{devices[0].platform!r}")
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def compiled_flops(jitted, *args) -> float:
    """FLOPs of one call, from the compiled executable's cost model."""
    try:
        return float(jitted.lower(*args).compile()
                     .cost_analysis().get("flops", 0.0))
    except Exception:
        return 0.0


def measure_keras(build, shape, classes, batch, iters, warmup=10,
                  int_input=False, vocab=None, scan_steps=1):
    """``scan_steps`` > 1 uses the multi-step scan path
    (SingleTrainer(steps_per_call=...)): several optimizer updates per
    XLA call, amortizing host dispatch for small models."""
    import jax
    import numpy as np
    from distkeras_tpu.models.adapter import ModelAdapter

    model = build()
    adapter = ModelAdapter(model, loss=(
        "binary_crossentropy" if classes == 1
        else "sparse_categorical_crossentropy"),
        optimizer="sgd", learning_rate=0.01)
    state = adapter.init_state()
    if scan_steps > 1:
        step = jax.jit(adapter.make_multi_train_step(scan_steps),
                       donate_argnums=0)
        lead = (scan_steps, batch)
    else:
        step = jax.jit(adapter.make_train_step(), donate_argnums=0)
        lead = (batch,)

    rng = np.random.default_rng(0)
    if int_input:
        x = jax.device_put(rng.integers(0, vocab, (*lead, *shape))
                           .astype(np.int32))
    else:
        x = jax.device_put(rng.normal(size=(*lead, *shape))
                           .astype(np.float32))
    y = jax.device_put(rng.integers(0, max(classes, 2), lead)
                       .astype(np.float32 if classes == 1 else np.int64))

    # FLOPs from the *single-step* program: XLA's cost_analysis counts a
    # lax.scan body once, not times the trip count, so analyzing the
    # scanned program and dividing by scan_steps would undercount ~8x.
    if scan_steps > 1:
        one = jax.jit(adapter.make_train_step())
        step_flops = compiled_flops(one, state, x[0], y[0])
    else:
        step_flops = compiled_flops(step, state, x, y)
    for _ in range(warmup):
        state, loss = step(state, x, y)
    float(np.asarray(loss).ravel()[-1])  # device->host: the true barrier
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step(state, x, y)
    float(np.asarray(loss).ravel()[-1])
    dt = time.perf_counter() - t0
    steps = iters * scan_steps
    return batch * steps / dt, dt / steps, step_flops


def bench_mnist_mlp():
    import keras
    from distkeras_tpu.models.zoo import mnist_mlp

    keras.mixed_precision.set_global_policy("mixed_bfloat16")
    return measure_keras(lambda: mnist_mlp(seed=0), (784,), 10,
                         batch=4096, iters=60, scan_steps=8)


def bench_cifar_cnn():
    import keras
    from distkeras_tpu.models.zoo import cifar_cnn

    keras.mixed_precision.set_global_policy("mixed_bfloat16")
    return measure_keras(lambda: cifar_cnn(seed=0), (32, 32, 3), 10,
                         batch=1024, iters=300)


def bench_higgs_mlp():
    import keras
    from distkeras_tpu.models.zoo import higgs_mlp

    keras.mixed_precision.set_global_policy("mixed_bfloat16")
    return measure_keras(lambda: higgs_mlp(seed=0), (28,), 2,
                         batch=4096, iters=60, scan_steps=8)


def bench_imdb_lstm():
    """FusedLSTM path (models/rnn.py): input projection hoisted out of
    the recurrence into one MXU matmul."""
    import keras
    from distkeras_tpu.models.zoo import imdb_lstm

    keras.mixed_precision.set_global_policy("mixed_bfloat16")
    return measure_keras(
        lambda: imdb_lstm(vocab_size=20000, maxlen=128, seed=0), (128,), 1,
        batch=512, iters=100, int_input=True, vocab=20000)


def bench_imdb_lstm_keras():
    """Ablation baseline: the stock keras.layers.LSTM recurrence."""
    import keras
    from distkeras_tpu.models.zoo import imdb_lstm

    keras.mixed_precision.set_global_policy("mixed_bfloat16")
    return measure_keras(
        lambda: imdb_lstm(vocab_size=20000, maxlen=128, seed=0,
                          fused=False), (128,), 1,
        batch=512, iters=100, int_input=True, vocab=20000)


def bench_resnet50():
    import keras
    from distkeras_tpu.models.zoo import resnet50

    keras.mixed_precision.set_global_policy("mixed_bfloat16")
    return measure_keras(lambda: resnet50(seed=0), (224, 224, 3), 1000,
                         batch=128, iters=50, warmup=5)


def _measure_lm(cfg, batch, seq, iters, warmup=5, attention_fn=None,
                flops_cfg=None):
    """``flops_cfg``: config whose compiled program supplies the FLOPs
    count — a ce_chunks config hides the head matmuls inside a lax.scan
    whose body cost_analysis counts once, so its MFU must come from the
    numerically-identical unchunked program."""
    import jax
    import numpy as np
    import optax
    from distkeras_tpu.models import transformer as tfm

    params = tfm.init_params(jax.random.key(0), cfg)
    opt = optax.adamw(3e-4)
    step = jax.jit(tfm.make_train_step(cfg, opt, attention_fn=attention_fn),
                   donate_argnums=0)
    carry = (params, opt.init(params))

    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32))
    if flops_cfg is not None:
        fstep = jax.jit(tfm.make_train_step(flops_cfg, opt,
                                            attention_fn=attention_fn))
        step_flops = compiled_flops(fstep, carry, tokens)
    else:
        step_flops = compiled_flops(step, carry, tokens)
    for _ in range(warmup):
        carry, loss = step(carry, tokens)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        carry, loss = step(carry, tokens)
    float(loss)
    dt = time.perf_counter() - t0
    return batch * seq * iters / dt, dt / iters, step_flops


def bench_transformer():
    """Flagship LM, short-sequence config (head-dominated at seq 1024)."""
    from distkeras_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=32768, d_model=512, n_heads=4, n_layers=4, d_ff=2048,
        max_len=1025, dtype="bfloat16")
    return _measure_lm(cfg, batch=8, seq=1024, iters=50)


def bench_transformer_fusedce():
    """Same head-dominated config with the chunked vocab-head CE
    (ce_chunks=8): the [8, 1024, 32k] f32 logits (~1 GB) never
    materialize — the delta vs ``transformer`` is pure head HBM
    traffic."""
    from distkeras_tpu.models import transformer as tfm

    import dataclasses

    cfg = tfm.TransformerConfig(
        vocab_size=32768, d_model=512, n_heads=4, n_layers=4, d_ff=2048,
        max_len=1025, dtype="bfloat16", ce_chunks=8)
    return _measure_lm(cfg, batch=8, seq=1024, iters=50,
                       flops_cfg=dataclasses.replace(cfg, ce_chunks=0))


def _d1024_cfg(**kw):
    from distkeras_tpu.models import transformer as tfm

    # Dense d1024 L8 at seq 1024: the direct comparison row for the MoE
    # and LoRA configs below (same trunk; transformer_long differs in
    # seq length and remat, so it can't serve as their baseline).
    return tfm.TransformerConfig(
        vocab_size=32768, d_model=1024, n_heads=8, n_layers=8, d_ff=4096,
        max_len=1025, dtype="bfloat16", **kw)


def bench_transformer_d1024(batch=8, seq=1024, iters=30):
    """Dense-FFN baseline row for the MoE/LoRA family (d1024 L8 s1024).
    (batch/seq/iters overridable so CPU smoke tests can shrink them.)"""
    return _measure_lm(_d1024_cfg(), batch=batch, seq=seq, iters=iters)


def bench_transformer_moe(top_k):
    """Mixture-of-experts training: 8 experts over the d1024 L8 trunk,
    capacity_factor 1.25 (Switch top-1 / renormalized top-2).  The
    capacity einsum dispatch is all-to-all-shaped even on one chip, so
    step time vs the dense row IS the routing+dispatch overhead; MFU
    comes from the compiled program's own cost_analysis (it counts the
    dispatch/combine einsums — hardware MFU, not active-param MFU)."""
    def run(batch=8, seq=1024, iters=30):
        cfg = _d1024_cfg(num_experts=8, moe_top_k=top_k,
                         capacity_factor=1.25)
        rate, step_s, flops = _measure_lm(cfg, batch=batch, seq=seq,
                                          iters=iters)
        return rate, step_s, flops, {
            "num_experts": 8, "moe_top_k": top_k,
            "capacity_factor": 1.25,
            "dense_baseline": "transformer_d1024"}
    return run


def bench_lora_finetune(batch=8, seq=1024, iters=30):
    """LoRA fine-tune step throughput on the d1024 L8 row (rank 8,
    wq/wv): the forward is byte-identical to full fine-tuning (merge
    inside the step), so the delta vs ``transformer_d1024`` isolates
    what LoRA saves — backward skips the base's gradient paths and the
    optimizer touches ~1000x fewer moments."""
    import jax
    import numpy as np
    import optax
    from distkeras_tpu.models import transformer as tfm
    from distkeras_tpu.models.lora import (LoRAConfig, lora_init,
                                           lora_mask, make_lora_loss)

    cfg = _d1024_cfg()
    lcfg = LoRAConfig(rank=8, alpha=16.0, targets=("wq", "wv"))
    base = tfm.init_params(jax.random.key(0), cfg)
    adapters = lora_init(jax.random.key(1), cfg, lcfg)
    opt = optax.masked(optax.adamw(3e-4), lora_mask)
    step = jax.jit(
        tfm.make_train_step(cfg, opt, loss_fn=make_lora_loss(cfg, lcfg)),
        donate_argnums=0)
    packed = (adapters, base)
    carry = (packed, opt.init(packed))
    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32))
    step_flops = compiled_flops(step, carry, tokens)
    for _ in range(5):
        carry, loss = step(carry, tokens)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        carry, loss = step(carry, tokens)
    float(loss)
    dt = time.perf_counter() - t0
    n_adapter = sum(int(np.prod(np.shape(a))) for a in
                    jax.tree.leaves(adapters))
    return batch * seq * iters / dt, dt / iters, step_flops, {
        "lora_rank": 8, "lora_targets": "wq,wv",
        "adapter_params": n_adapter,
        "dense_baseline": "transformer_d1024"}


def _long_cfg():
    from distkeras_tpu.models import transformer as tfm

    # Attention-dominated: at seq 4096 / d_model 1024 the S^2 term is
    # ~2x the matmul term per layer, and the 32k-vocab head is <10% of
    # the step.  remat keeps activations in budget at this depth.
    return tfm.TransformerConfig(
        vocab_size=32768, d_model=1024, n_heads=8, n_layers=8, d_ff=4096,
        max_len=4097, dtype="bfloat16", remat=True)


def bench_transformer_long():
    """Long-context LM on the Pallas flash-attention path."""
    return _measure_lm(_long_cfg(), batch=8, seq=4096, iters=20)


def bench_transformer_long_rope():
    """Long config with rotary positions + grouped-query attention (the
    modern long-context layout; rope/GQA cost vs the learned-table MHA
    baseline is the interesting delta)."""
    import dataclasses

    return _measure_lm(
        dataclasses.replace(_long_cfg(), rope=True, n_kv_heads=2),
        batch=8, seq=4096, iters=20)


def bench_transformer_long_window():
    """Long config with sliding-window attention (window 1024 at seq
    4096): the kernels skip blocks beyond the lookback, so the S^2
    attention term drops ~4x."""
    import dataclasses

    return _measure_lm(
        dataclasses.replace(_long_cfg(), attention_window=1024),
        batch=8, seq=4096, iters=20)


def bench_transformer_long_rematdots():
    """Long config with selective remat (policy='dots': matmul outputs
    saved, elementwise recomputed) — the middle point between full
    remat and no remat."""
    import dataclasses

    return _measure_lm(
        dataclasses.replace(_long_cfg(), remat_policy="dots"),
        batch=8, seq=4096, iters=20)


def bench_transformer_long_noremat():
    """Same config without per-block rematerialization (fits at this
    size; remat trades ~13% step time for O(1)-block activations)."""
    import dataclasses

    return _measure_lm(dataclasses.replace(_long_cfg(), remat=False),
                       batch=8, seq=4096, iters=20)


def bench_transformer_long_xla():
    """Same config on the blockwise-jnp XLA fallback (no Pallas).

    batch 4: the fallback's backward (re-run forward under jax.vjp)
    fails to compile at batch 8 on a 16 GB chip — itself part of the
    comparison; tokens/sec is batch-normalized.
    """
    from distkeras_tpu.ops.attention import blockwise_attention

    return _measure_lm(
        _long_cfg(), batch=4, seq=4096, iters=20,
        attention_fn=lambda q, k, v: blockwise_attention(q, k, v, causal=True))


def bench_generate_decode():
    """KV-cached greedy decode on the flagship config: sustained decode
    tokens/s (batch x new tokens / wall), plus the prefill win — wall
    time of the one-forward prompt fill vs teacher-forcing the prompt
    through the cached step (``prefill_speedup`` in the extras)."""
    import jax
    import numpy as np
    from distkeras_tpu.models import transformer as tfm
    from distkeras_tpu.models.generate import generate

    cfg = tfm.TransformerConfig(
        vocab_size=32768, d_model=512, n_heads=4, n_layers=4, d_ff=2048,
        max_len=1025, dtype="bfloat16")
    params = tfm.init_params(jax.random.key(0), cfg)
    batch, p_len, new = 8, 512, 512
    prompt = jax.device_put(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, p_len)).astype(np.int32))

    gen = jax.jit(lambda pp, pr: generate(pp, pr, cfg, new))
    seq = jax.jit(lambda pp, pr: generate(pp, pr, cfg, new,
                                          use_prefill=False))
    int(np.asarray(gen(params, prompt))[0, -1])  # compile + barrier
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        out = gen(params, prompt)
    int(np.asarray(out)[0, -1])
    dt_pre = (time.perf_counter() - t0) / iters

    int(np.asarray(seq(params, prompt))[0, -1])
    t0 = time.perf_counter()
    out = seq(params, prompt)
    int(np.asarray(out)[0, -1])
    dt_seq = time.perf_counter() - t0

    # Decode rate from the prefill path; per-token step time likewise.
    rate = batch * new / dt_pre
    extras = {"prefill_speedup": round(dt_seq / dt_pre, 2),
              "prompt_len": p_len, "new_tokens": new}
    return rate, dt_pre / new, 0.0, extras


def bench_generate_decode_int8():
    """Same decode workload with int8-quantized weights (models/quant):
    the sequential loop is weight-bandwidth-bound, so halving the
    weight bytes vs bf16 is the lever.  Short prompt (the int8 path is
    sequential-only; its regime is generation-heavy serving)."""
    import jax
    import numpy as np
    from distkeras_tpu.models import transformer as tfm
    from distkeras_tpu.models.generate import generate
    from distkeras_tpu.models.quant import quantize_params

    cfg = tfm.TransformerConfig(
        vocab_size=32768, d_model=512, n_heads=4, n_layers=4, d_ff=2048,
        max_len=1025, dtype="bfloat16")
    qparams = quantize_params(tfm.init_params(jax.random.key(0), cfg))
    batch, p_len, new = 8, 16, 512
    prompt = jax.device_put(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, p_len)).astype(np.int32))

    gen = jax.jit(lambda pp, pr: generate(pp, pr, cfg, new))
    int(np.asarray(gen(qparams, prompt))[0, -1])
    iters = 3
    t0 = time.perf_counter()
    for _ in range(iters):
        out = gen(qparams, prompt)
    int(np.asarray(out)[0, -1])
    dt = (time.perf_counter() - t0) / iters
    return batch * new / dt, dt / new, 0.0, {"prompt_len": p_len,
                                             "new_tokens": new}


def bench_cifar_cnn_hostdata():
    """End-to-end input pipeline: host uint8 rows -> native gather ->
    DeviceFeed (async h2d, uint8 on the wire) -> multi-step scan with
    on-device normalization.

    The honest counterpart of ``cifar_cnn`` (device-resident synthetic
    data): same model and batch, but every batch starts as host uint8
    rows the way training data does (SURVEY.md §7.3 #4).  Three design
    rules keep the link, not the software, as the only limit:
    uint8 on the wire (4x fewer bytes; ModelAdapter ``preprocess``
    normalizes on device), windows of ``scan`` steps per XLA call
    (execution/transfer interleaving carries a fixed per-dispatch
    cost), and DeviceFeed lookahead so the next
    window streams under the current scan.  The JSON line reports
    ``h2d_mbytes_per_s`` (achieved wire rate) next to ``mfu`` — when the
    achieved rate saturates the measured link bandwidth, the gap to the
    synthetic number is transport physics, not pipeline overhead.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import keras
    from distkeras_tpu import native
    from distkeras_tpu.data.prefetch import DeviceFeed
    from distkeras_tpu.models.adapter import ModelAdapter
    from distkeras_tpu.models.zoo import cifar_cnn

    keras.mixed_precision.set_global_policy("mixed_bfloat16")
    batch, scan, windows, warmup = 1024, 8, 24, 3
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (50_000, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, 50_000).astype(np.int32)

    adapter = ModelAdapter(
        cifar_cnn(seed=0), loss="sparse_categorical_crossentropy",
        optimizer="sgd", learning_rate=0.01,
        preprocess=lambda x: x.astype(jnp.bfloat16) * (1 / 255.0))
    state = adapter.init_state()
    step = jax.jit(adapter.make_multi_train_step(scan), donate_argnums=0)

    def window_batches(n):
        order, i = rng.permutation(len(images)), 0
        rows = scan * batch
        for _ in range(n):
            if i + rows > len(order):
                order, i = rng.permutation(len(images)), 0
            idx = order[i:i + rows]
            i += rows
            x = native.gather_rows(images, idx).reshape(
                scan, batch, *images.shape[1:])
            y = native.gather_rows(labels, idx).reshape(scan, batch)
            yield x, y

    x0, y0 = next(iter(window_batches(1)))
    wire_bytes = x0.nbytes + y0.nbytes
    x0d, y0d = jax.device_put((x0, y0))
    # Single-step program for FLOPs (scan bodies are counted once by
    # cost_analysis, see measure_keras).
    one = jax.jit(adapter.make_train_step())
    step_flops = compiled_flops(one, state, x0d[0], y0d[0])
    for x, y in DeviceFeed(window_batches(warmup), depth=2):
        state, loss = step(state, x, y)
    float(np.asarray(loss).ravel()[-1])
    t0 = time.perf_counter()
    for x, y in DeviceFeed(window_batches(windows), depth=2):
        state, loss = step(state, x, y)
    float(np.asarray(loss).ravel()[-1])
    dt = time.perf_counter() - t0
    steps = windows * scan
    extra = {"h2d_mbytes_per_s": round(wire_bytes * windows / dt / 1e6, 1)}
    return batch * steps / dt, dt / steps, step_flops, extra


def bench_cifar_cnn_resident():
    """End-to-end with a device-resident dataset: the uint8 training set
    is staged in HBM once, and each multi-step call gathers its
    minibatches on device from a host-sent int32 index block
    (SingleTrainer(device_data=True) path).

    This is the TPU-native answer for any dataset that fits HBM: after
    staging, ~4 bytes/sample/epoch cross the host link, so throughput
    tracks the synthetic number regardless of link quality — compare
    ``cifar_cnn_hostdata``, which streams every pixel and is bounded by
    the link.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import keras
    from distkeras_tpu.models.adapter import ModelAdapter
    from distkeras_tpu.models.zoo import cifar_cnn

    keras.mixed_precision.set_global_policy("mixed_bfloat16")
    batch, scan, windows, warmup = 1024, 8, 24, 3
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (50_000, 32, 32, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, 50_000).astype(np.int32)

    adapter = ModelAdapter(
        cifar_cnn(seed=0), loss="sparse_categorical_crossentropy",
        optimizer="sgd", learning_rate=0.01,
        preprocess=lambda x: x.astype(jnp.bfloat16) * (1 / 255.0))
    state = adapter.init_state()
    step = jax.jit(adapter.make_indexed_train_step(scan), donate_argnums=0)
    X, Y = jax.device_put((images, labels))

    def idx_blocks(n):
        order, i = rng.permutation(len(images)), 0
        rows = scan * batch
        for _ in range(n):
            if i + rows > len(order):
                order, i = rng.permutation(len(images)), 0
            block = order[i:i + rows].astype(np.int32).reshape(scan, batch)
            i += rows
            yield block

    i0 = next(iter(idx_blocks(1)))
    one = jax.jit(adapter.make_train_step())
    step_flops = compiled_flops(
        one, state, jnp.take(X, i0[0], axis=0), jnp.take(Y, i0[0], axis=0))
    for idx in idx_blocks(warmup):
        state, loss = step(state, X, Y, idx)
    float(np.asarray(loss).ravel()[-1])
    t0 = time.perf_counter()
    for idx in idx_blocks(windows):
        state, loss = step(state, X, Y, idx)
    float(np.asarray(loss).ravel()[-1])
    dt = time.perf_counter() - t0
    steps = windows * scan
    return batch * steps / dt, dt / steps, step_flops


def bench_zero1_update(batch_unused=None, iters=30):
    """The weight-update phase in isolation: replicated update vs the
    ZeRO-1 sharded update (docs/zero1.md), over a data axis spanning
    every visible device.

    Training-step benchmarks hide the update behind the forward/backward;
    this one feeds a fixed synthetic gradient of the flagship short
    transformer config to adamw directly, so the measured wall is
    exactly exchange + update math — the thing ZeRO-1 shards.  Reports
    per-device optimizer-state bytes for both layouts (from the sharded
    state's addressable shards — the ~num_workers x memory claim as a
    measured number) and the update-time pair.  On a single-device
    backend the two paths coincide (ratio ~1): the win needs a real
    data axis.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from distkeras_tpu.models import transformer as tfm
    from distkeras_tpu.parallel.collectives import (zero1_optimizer,
                                                    zero1_state_shardings)
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = len(jax.devices())
    mesh = make_mesh(MeshSpec(data=n_dev))
    cfg = tfm.TransformerConfig(
        vocab_size=32768, d_model=512, n_heads=4, n_layers=4, d_ff=2048,
        max_len=1025, dtype="bfloat16")
    params = tfm.init_params(jax.random.key(0), cfg)
    grads = jax.tree.map(lambda p: p * 1e-3, params)
    opt = optax.adamw(3e-4)
    rep = NamedSharding(mesh, P())
    params = jax.device_put(params, jax.tree.map(lambda _: rep, params))
    grads = jax.device_put(grads, jax.tree.map(lambda _: rep, grads))

    def bytes_per_device(state):
        return sum(l.addressable_shards[0].data.nbytes
                   for l in jax.tree.leaves(state)
                   if hasattr(l, "addressable_shards"))

    def measure(optimizer, state_shardings):
        state = jax.jit(optimizer.init,
                        out_shardings=state_shardings)(params)
        per_dev = bytes_per_device(state)

        def upd(g, s, p):
            u, s2 = optimizer.update(g, s, p)
            return optax.apply_updates(p, u), s2

        psh = jax.tree.map(lambda _: rep, params)
        step = jax.jit(upd, donate_argnums=(1, 2),
                       in_shardings=(psh, state_shardings, psh),
                       out_shardings=(psh, state_shardings))
        # The step donates its params operand; work on a copy so the
        # shared tree survives for the other layout's measurement.
        p = jax.tree.map(jnp.copy, params)
        for _ in range(3):
            p, state = step(grads, state, p)
        jax.block_until_ready(p)
        t0 = time.perf_counter()
        for _ in range(iters):
            p, state = step(grads, state, p)
        jax.block_until_ready(p)
        return (time.perf_counter() - t0) / iters, per_dev

    # Replicated baseline: every state leaf whole on every device.
    opt_shapes = jax.eval_shape(opt.init, params)
    rep_sh = jax.tree.map(lambda _: rep, opt_shapes)
    rep_s, rep_bytes = measure(opt, rep_sh)

    z = zero1_optimizer(opt, mesh)
    z_sh = zero1_state_shardings(params, jax.eval_shape(z.init, params),
                                 mesh)
    z_s, z_bytes = measure(z, z_sh)

    n_params = sum(int(np.prod(np.shape(l)))
                   for l in jax.tree.leaves(params))
    return 1.0 / z_s, z_s, 0.0, {
        "n_devices": n_dev, "n_params": n_params,
        "update_ms_replicated": round(rep_s * 1e3, 3),
        "update_ms_zero1": round(z_s * 1e3, 3),
        "update_speedup": round(rep_s / z_s, 3),
        "opt_bytes_per_device_replicated": rep_bytes,
        "opt_bytes_per_device_zero1": z_bytes,
        "opt_memory_ratio": round(rep_bytes / max(z_bytes, 1), 2),
    }


def bench_zero_stages(iters=10, batch=8, seq=256, d_model=256,
                      n_layers=4, vocab=8192):
    """The ZeRO stage ladder, side by side (docs/zero1.md): for
    replicated DP and stages 1/2/3, ONE real ``LMTrainer`` train-step
    program (built through the trainer's own ``_build_carry_and_step``,
    so the measured program is exactly what users train) on a data
    axis spanning every visible device.  Reports per stage:

    * ``step_ms_*`` — steady-state wall of the full train step (the
      stage-3 row is where the gather-on-use overhead shows: the
      per-use parameter all-gathers ride inside the step);
    * ``state_bytes_per_device_*`` — persistent params+optimizer bytes
      per device from ADDRESSABLE SHARDS (the acceptance's ~n x memory
      claim as a measured number: stage 1 shards the moments, stage 3
      params+moments both).

    Model dims overridable so CPU smoke tests can shrink them; the
    default is a flagship-short config sized to make the update and
    gather phases visible.  On a single-device backend every stage
    coincides (ratio ~1): the ladder needs a real data axis."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distkeras_tpu.models import transformer as tfm
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
    from distkeras_tpu.trainers.lm import LMTrainer

    n_dev = len(jax.devices())
    mesh = make_mesh(MeshSpec(data=n_dev))
    cfg = tfm.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=4,
        n_layers=n_layers, d_ff=4 * d_model, max_len=seq + 1)
    rows = np.random.default_rng(0).integers(
        0, vocab, (batch, seq + 1)).astype(np.int32)
    extras = {"n_devices": n_dev}
    walls = {}
    for stage in (0, 1, 2, 3):
        t = LMTrainer(cfg, learning_rate=3e-4, batch_size=batch,
                      mesh=mesh, **({"zero": stage} if stage else {}))
        params = t.init_params()
        (carry_p, opt_state, _psh, _osh, step, step_sh,
         _tok) = t._build_carry_and_step(params)
        carry = (carry_p, opt_state)
        tok = jax.device_put(rows, step_sh)
        per_dev = sum(l.addressable_shards[0].data.nbytes
                      for l in jax.tree.leaves(carry)
                      if hasattr(l, "addressable_shards"))
        for _ in range(2):
            carry, loss = step(carry, tok, None, None)
        jax.block_until_ready(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            carry, loss = step(carry, tok, None, None)
        jax.block_until_ready(loss)
        wall = (time.perf_counter() - t0) / iters
        key = f"stage{stage}" if stage else "dp"
        walls[key] = wall
        extras[f"step_ms_{key}"] = round(wall * 1e3, 3)
        extras[f"state_bytes_per_device_{key}"] = per_dev
    extras["state_memory_ratio_stage3"] = round(
        extras["state_bytes_per_device_dp"]
        / max(extras["state_bytes_per_device_stage3"], 1), 2)
    tokens_per_step = batch * seq
    return (tokens_per_step / walls["stage3"] / n_dev,
            walls["stage3"], 0.0, extras)


def bench_lowcomm_convergence(**opts):
    """Convergence-vs-baseline row for one gradient-exchange variant
    (docs/lowcomm.md): train the toy LM twice on the same seeded rows —
    replicated-DP baseline, then the variant — and report both final
    losses against the DECLARED tolerance (the same bound
    tests/test_exchange.py::TOL_LOSS enforces; the row makes the margin
    visible, the test makes it binding).  Wire-bytes/collective-count
    claims live in the compiled census (scripts/comm_budget.json), not
    here — this row is the convergence half of the lowcomm contract.
    """
    def run(batch=16, seq=16, n_rows=128, epochs=2, tol=0.05):
        import jax
        import numpy as np
        from distkeras_tpu.models import transformer as tfm
        from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
        from distkeras_tpu.trainers.lm import LMTrainer

        cfg = tfm.TransformerConfig(vocab_size=64, d_model=32,
                                    n_heads=2, n_layers=2, d_ff=64,
                                    max_len=seq + 1)
        rows = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (n_rows, seq + 1)).astype(np.int32)
        mesh = make_mesh(MeshSpec(data=len(jax.devices())))

        def train(**kw):
            t = LMTrainer(cfg, learning_rate=1e-2, batch_size=batch,
                          num_epoch=epochs, mesh=mesh, **kw)
            t0 = time.perf_counter()
            t.train(rows)
            return t, time.perf_counter() - t0

        base, _ = train()
        t, wall = train(**opts)
        steps = len(t.history)
        delta = abs(t.history[-1] - base.history[-1])
        # One row == one sync round; under local-SGD a round carries
        # sync_every optimizer steps' worth of tokens.
        tokens = n_rows * seq * epochs
        return tokens / wall, wall / steps, 0.0, {
            **opts,
            "final_loss": round(t.history[-1], 5),
            "baseline_loss": round(base.history[-1], 5),
            "loss_delta": round(delta, 5),
            "tolerance": tol,
            "within_tolerance": bool(delta <= tol),
            "rounds": steps, "baseline_rounds": len(base.history)}
    return run


def bench_lowcomm_update(iters=10, d_model=512, n_layers=4,
                         vocab=32768):
    """The gradient-exchange + update path in isolation, per variant
    (docs/lowcomm.md): feed a fixed synthetic STACKED per-replica
    gradient of the flagship short transformer config through
    ``exchange_optimizer`` for each merge rule / codec, so the measured
    wall is exactly merge collectives + inner update — the thing the
    exchange layer changes.  Reports per-variant update time and the
    analytic per-step gradient wire bytes (``exchange.wire_bytes`` —
    the same formula the obs gauges carry; the compiled census pins the
    claim), so the ~4x int8-EF byte reduction and its CPU-mesh cost
    show up side by side.  (Model dims overridable so CPU smoke tests
    can shrink them; the flagship default is chip-sized.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from distkeras_tpu.models import transformer as tfm
    from distkeras_tpu.parallel import exchange as ex
    from distkeras_tpu.parallel.collectives import Zero1Layout
    from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = len(jax.devices())
    mesh = make_mesh(MeshSpec(data=n_dev))
    cfg = tfm.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=4,
        n_layers=n_layers, d_ff=4 * d_model, max_len=1025,
        dtype="bfloat16")
    params = tfm.init_params(jax.random.key(0), cfg)
    rep = NamedSharding(mesh, P())
    stk = NamedSharding(mesh, P("data"))
    params = jax.device_put(params, jax.tree.map(lambda _: rep, params))
    # Per-replica contributions: the mean over the leading axis equals
    # the replicated-baseline gradient, so every variant does real work.
    stacked = jax.device_put(
        jax.tree.map(lambda p: jnp.broadcast_to(
            (p * 1e-3)[None], (n_dev,) + p.shape), params),
        jax.tree.map(lambda _: stk, params))
    layout = Zero1Layout.for_tree(
        jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype),
                     params), n_dev, ex.ExchangeConfig().bucket_mb)

    def measure(config, zero1=False):
        opt = ex.exchange_optimizer(optax.adamw(3e-4), mesh, config,
                                    zero1=zero1)
        osh = ex.exchange_state_shardings(
            params, jax.eval_shape(opt.init, params), mesh, zero1=zero1)
        state = jax.jit(opt.init, out_shardings=osh)(params)

        def upd(g, s, p):
            u, s2 = opt.update(g, s, p)
            return optax.apply_updates(p, u), s2

        psh = jax.tree.map(lambda _: rep, params)
        gsh = jax.tree.map(lambda _: stk, params)
        step = jax.jit(upd, donate_argnums=(1, 2),
                       in_shardings=(gsh, osh, psh),
                       out_shardings=(psh, osh))
        p = jax.tree.map(jnp.copy, params)
        for _ in range(3):
            p, state = step(stacked, state, p)
        jax.block_until_ready(p)
        t0 = time.perf_counter()
        for _ in range(iters):
            p, state = step(stacked, state, p)
        jax.block_until_ready(p)
        return (time.perf_counter() - t0) / iters

    variants = {
        "mean": (ex.ExchangeConfig(), False),
        "adasum": (ex.ExchangeConfig(merge_rule="adasum"), False),
        "int8ef": (ex.ExchangeConfig(compress="int8"), False),
        "topk": (ex.ExchangeConfig(compress="topk", topk_frac=0.01),
                 False),
        "zero1_int8ef": (ex.ExchangeConfig(compress="int8"), True),
    }
    extras = {"n_devices": n_dev}
    walls = {}
    for name, (config, zero1) in variants.items():
        walls[name] = measure(config, zero1)
        f32_b, wire_b = ex.wire_bytes(layout, config, zero1)
        extras[f"update_ms_{name}"] = round(walls[name] * 1e3, 3)
        extras[f"grad_wire_bytes_{name}"] = wire_b
        if name == "mean":
            extras["grad_f32_bytes"] = f32_b
        else:
            extras[f"compression_{name}"] = round(f32_b / max(wire_b, 1),
                                                  2)
    return 1.0 / walls["int8ef"], walls["int8ef"], 0.0, extras


def bench_async_convergence(**opts):
    """Convergence-vs-ADAG row for the bounded-staleness async tier
    (docs/async.md): train the same seeded MLP on the same blob rows
    twice — synchronous ADAG baseline, then ``AsyncDP`` under the
    given staleness/merge config with a deterministic virtual-time
    schedule — and report both final losses against the DECLARED
    tolerance (the bound tests/test_async_tier.py::
    test_converges_within_tol_of_adag enforces; the row makes the
    margin visible, the test makes it binding).  The int8 cross-host
    wire claim lives in the compiled census (asyncdp_wire/* in
    scripts/comm_budget.json), not here — this row is the convergence
    half of the async contract."""
    def run(n_rows=256, epochs=2, tol=0.05):
        import keras
        import numpy as np

        import distkeras_tpu as dk
        from distkeras_tpu.parallel.async_tier import AsyncSchedule

        rng = np.random.default_rng(0)
        centers = rng.normal(0, 4.0, (4, 16))
        labels = rng.integers(0, 4, n_rows)
        feats = (centers[labels]
                 + rng.normal(0, 0.5, (n_rows, 16))).astype(np.float32)
        ds = dk.Dataset({"features": feats,
                         "label": labels.astype(np.int64)})

        def mlp():
            keras.utils.set_random_seed(0)
            return keras.Sequential([
                keras.Input((16,)),
                keras.layers.Dense(32, activation="relu"),
                keras.layers.Dense(4)])

        kw = dict(loss="sparse_categorical_crossentropy",
                  worker_optimizer="sgd", learning_rate=0.05,
                  batch_size=2, num_epoch=epochs,
                  communication_window=2, seed=11)
        base = dk.ADAG(mlp(), **kw)
        base.train(ds)
        t = dk.AsyncDP(mlp(), hosts=2, beat_window=1.5,
                       schedule=AsyncSchedule(seed=3), **kw, **opts)
        t0 = time.perf_counter()
        t.train(ds)
        wall = time.perf_counter() - t0
        rounds = len(t.history)
        delta = abs(t.history[-1] - base.history[-1])
        rep = t.async_report
        return n_rows * epochs / wall, wall / rounds, 0.0, {
            **opts,
            "final_loss": round(t.history[-1], 5),
            "baseline_loss": round(base.history[-1], 5),
            "loss_delta": round(delta, 5),
            "tolerance": tol,
            "within_tolerance": bool(delta <= tol),
            "rounds": rounds, "baseline_rounds": len(base.history),
            "hard_syncs": rep["hard_syncs"],
            "wire_bytes": rep["wire_bytes"]}
    return run


def bench_lm_e2e(device_data):
    """End-to-end ``LMTrainer.train()`` throughput over real host rows,
    streaming vs ``device_data=True`` — the LM flagship's input-plane
    delta.  The per-step
    ``transformer_*`` rows feed ONE pre-staged device batch and so
    cannot see the host link at all; this pair trains on a real row
    set through the public trainer API.

    Timing is a DELTA of two train() calls (``steps`` vs
    ``warm_steps`` rows, same shapes), after one DISCARDED warmup
    call: the warmup absorbs process-level one-time costs (backend
    init, first-compile cache seeding), and whatever per-call cost
    remains — train() builds its jitted step from fresh closures, so
    the compile is re-resolved per call, cached or not — lands
    equally on both measured calls and cancels in the subtraction,
    leaving steady-state step time + the per-row input plane (for
    device_data that includes its share of the bulk staging transfer,
    which is the thing being measured)."""
    def run(batch=8, seq=1024, steps=64, warm_steps=4, cfg=None):
        import numpy as np
        from distkeras_tpu.trainers.lm import LMTrainer

        cfg = cfg or _d1024_cfg()
        rng = np.random.default_rng(0)
        rows = rng.integers(0, cfg.vocab_size,
                            (batch * steps, seq + 1)).astype(np.int32)

        def train_once(n):
            t = LMTrainer(cfg, learning_rate=3e-4, batch_size=batch,
                          num_epoch=1, device_data=device_data)
            t.train(rows[:batch * n])
            return t.training_time

        if steps <= warm_steps:
            raise ValueError(
                f"steps ({steps}) must exceed warm_steps ({warm_steps}) "
                "— the delta IS the measurement")
        train_once(warm_steps)            # discarded: one-time costs
        wall_short = train_once(warm_steps)
        wall_long = train_once(steps)
        d_steps = steps - warm_steps
        wall = wall_long - wall_short
        if wall <= 0:
            raise RuntimeError(
                f"non-positive delta wall ({wall_long:.3f}s - "
                f"{wall_short:.3f}s): per-call compile variance exceeds "
                f"the {d_steps}-step term at these dims — raise steps "
                "(chip dims resolve; toy CPU dims often cannot)")
        return batch * d_steps * seq / wall, wall / d_steps, 0.0, {
            "device_data": device_data, "steps_delta": d_steps,
            "batch": batch, "seq": seq,
            "e2e_wall_long_s": round(wall_long, 3),
            "e2e_wall_short_s": round(wall_short, 3)}
    return run


BENCHES = {
    "mnist_mlp": (bench_mnist_mlp, "samples/sec/chip"),
    "cifar_cnn": (bench_cifar_cnn, "samples/sec/chip"),
    "cifar_cnn_hostdata": (bench_cifar_cnn_hostdata, "samples/sec/chip"),
    "cifar_cnn_resident": (bench_cifar_cnn_resident, "samples/sec/chip"),
    "higgs_mlp": (bench_higgs_mlp, "samples/sec/chip"),
    "imdb_lstm": (bench_imdb_lstm, "samples/sec/chip"),
    "imdb_lstm_keras": (bench_imdb_lstm_keras, "samples/sec/chip"),
    "resnet50": (bench_resnet50, "samples/sec/chip"),
    "transformer": (bench_transformer, "tokens/sec/chip"),
    "transformer_fusedce": (bench_transformer_fusedce, "tokens/sec/chip"),
    "generate_decode": (bench_generate_decode, "tokens/sec/chip"),
    "generate_decode_int8": (bench_generate_decode_int8, "tokens/sec/chip"),
    "transformer_long": (bench_transformer_long, "tokens/sec/chip"),
    "transformer_long_rope": (bench_transformer_long_rope, "tokens/sec/chip"),
    "transformer_long_window": (bench_transformer_long_window,
                                "tokens/sec/chip"),
    "transformer_long_rematdots": (bench_transformer_long_rematdots,
                                   "tokens/sec/chip"),
    "transformer_long_noremat": (bench_transformer_long_noremat,
                                 "tokens/sec/chip"),
    "transformer_long_xla": (bench_transformer_long_xla, "tokens/sec/chip"),
    "transformer_d1024": (bench_transformer_d1024, "tokens/sec/chip"),
    "transformer_moe_top1": (bench_transformer_moe(1), "tokens/sec/chip"),
    "transformer_moe_top2": (bench_transformer_moe(2), "tokens/sec/chip"),
    "lora_finetune": (bench_lora_finetune, "tokens/sec/chip"),
    "lm_e2e_stream": (bench_lm_e2e(False), "tokens/sec/chip"),
    "lm_e2e_device_data": (bench_lm_e2e(True), "tokens/sec/chip"),
    "zero1_update": (bench_zero1_update, "updates/sec"),
    "zero_stages": (bench_zero_stages, "tokens/sec/chip"),
    "lowcomm_adasum": (bench_lowcomm_convergence(merge_rule="adasum"),
                       "tokens/sec/chip"),
    "lowcomm_localsgd4": (bench_lowcomm_convergence(sync_every=4),
                          "tokens/sec/chip"),
    "lowcomm_int8ef": (bench_lowcomm_convergence(compress="int8"),
                       "tokens/sec/chip"),
    "lowcomm_zero1_int8ef": (
        bench_lowcomm_convergence(zero1=True, compress="int8"),
        "tokens/sec/chip"),
    "lowcomm_update": (bench_lowcomm_update, "updates/sec"),
    "async_tau1": (bench_async_convergence(tau=1, async_merge="sum"),
                   "samples/sec"),
    "async_tau4": (bench_async_convergence(tau=4, async_merge="sum"),
                   "samples/sec"),
    "async_adasum": (bench_async_convergence(tau=4, async_merge="adasum",
                                             async_compress="int8"),
                     "samples/sec"),
}


def run_rows(benches, names, line_of):
    """The main loop of both bench scripts (this one and
    bench_serving.py): gate on the TPU, run each requested row under
    its own obs session, print one JSON line per row —
    ``line_of(out, unit)`` plus the device fields — and exit non-zero
    if any row raised (after the other rows ran)."""
    import traceback

    from distkeras_tpu import obs
    from distkeras_tpu.utils.misc import configure_compile_cache

    configure_compile_cache()
    unknown = set(names) - set(benches)
    if unknown:
        sys.exit(f"unknown config(s) {sorted(unknown)}; "
                 f"choose from {sorted(benches)}")
    device = tpu_device_fields()
    failed = []
    for name in names or benches:
        fn, unit = benches[name]
        # Each config runs under its own obs session (metrics only, no
        # trace file) so the result line carries its telemetry — h2d
        # bytes, prefetch occupancy, zero1 bucket geometry, serving
        # counters — and a perf regression ships its own evidence.
        sess = obs.enable()
        try:
            line = line_of(fn(), unit)
        except Exception as e:  # finish the other rows, then exit non-zero
            traceback.print_exc()
            failed.append(name)
            line = {"error": repr(e)[:200]}
        finally:
            snapshot = sess.registry.compact()
            obs.disable()
        if snapshot and "error" not in line:
            line["obs"] = snapshot
        print(json.dumps({"metric": name, **line, **device}))
    if failed:
        sys.exit(f"{len(failed)} row(s) raised: {failed}")


def main(names):
    def line_of(out, unit):
        rate, step_s, step_flops = out[:3]
        line = {"value": round(rate, 1), "unit": unit,
                "step_ms": round(step_s * 1e3, 2),
                "gflops_per_step": round(step_flops / 1e9, 1),
                **(out[3] if len(out) > 3 else {})}
        peak = peak_flops()
        if peak and step_flops:
            line["mfu"] = round(step_flops / step_s / peak, 4)
        return line

    run_rows(BENCHES, names, line_of)


if __name__ == "__main__":
    main(sys.argv[1:])
