"""Sweep Pallas flash-attention block sizes on the long-context config.

The three kernels (fwd, dq, dkv) share one (block_q, block_k) pair via
``flash_attention``'s custom_vjp.  This sweeps the pair over the
training step of the benchmark long config (seq 4096, d1024, L8, bf16,
remat) and prints one JSON line per point; the kernel's defaults
(ops/attention.py::DEFAULT_BLOCK_Q/K) came from such a sweep.  A grid
point whose kernel fails to compile ends the sweep with its error.

Also sweeps the forward-only (inference) kernel separately, since the
optimum can differ when no lse is written and no backward runs.

Usage: python scripts/sweep_attention_blocks.py [--quick]
(--quick: 3 iters instead of 10 — a coarse first pass).
"""

import itertools
import json
import os
import sys
import time

os.environ.setdefault("KERAS_BACKEND", "jax")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


BLOCKS_Q = (128, 256, 512, 1024)
BLOCKS_K = (128, 256, 512, 1024)


def _long_cfg():
    from distkeras_tpu.models import transformer as tfm

    return tfm.TransformerConfig(
        vocab_size=32768, d_model=1024, n_heads=8, n_layers=8, d_ff=4096,
        max_len=4097, dtype="bfloat16", remat=True)


def sweep_train(iters):
    import jax
    import numpy as np
    import optax
    from distkeras_tpu.models import transformer as tfm
    from distkeras_tpu.ops.attention import flash_attention

    cfg = _long_cfg()
    params = tfm.init_params(jax.random.key(0), cfg)
    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)
    tokens = jax.device_put(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 4097)).astype(np.int32))

    for bq, bk in itertools.product(BLOCKS_Q, BLOCKS_K):
        attn = lambda q, k, v, bq=bq, bk=bk: flash_attention(
            q, k, v, True, block_q=bq, block_k=bk)
        step = jax.jit(tfm.make_train_step(cfg, opt, attention_fn=attn))
        carry = (params, opt_state)
        for _ in range(3):
            carry, loss = step(carry, tokens)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            carry, loss = step(carry, tokens)
        float(loss)
        dt = (time.perf_counter() - t0) / iters
        print(json.dumps({"mode": "train", "block_q": bq, "block_k": bk,
                          "step_ms": round(dt * 1e3, 2),
                          "tokens_per_s": round(8 * 4096 / dt, 1)}))


def sweep_fwd(iters):
    import jax
    import numpy as np
    from distkeras_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(0)
    b, s, h, d = 8, 4096, 8, 128
    q = jax.device_put(rng.normal(size=(b, s, h, d)).astype(np.float32)
                       ).astype("bfloat16")
    k = jax.device_put(rng.normal(size=(b, s, h, d)).astype(np.float32)
                       ).astype("bfloat16")
    v = jax.device_put(rng.normal(size=(b, s, h, d)).astype(np.float32)
                       ).astype("bfloat16")
    for bq, bk in itertools.product(BLOCKS_Q, BLOCKS_K):
        fn = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
            q, k, v, True, block_q=bq, block_k=bk))
        fn(q, k, v).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, k, v)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        print(json.dumps({"mode": "fwd", "block_q": bq, "block_k": bk,
                          "ms": round(dt * 1e3, 3)}))


if __name__ == "__main__":
    iters = 3 if "--quick" in sys.argv else 10
    sweep_fwd(iters)
    sweep_train(iters)
