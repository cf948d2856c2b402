"""Flagship-MFU ablation: where do the missing percent go?

The long-config train step's residual (~21% of step time; measured
2026-07-31 on one v5e, not re-measured since) was attributed to
unfused elementwise/optimizer/CE-head bandwidth without per-component
numbers.  This script measures each candidate in isolation on the
current accelerator so the next optimization lands on evidence, not
attribution folklore:

- ``optimizer``: adamw update alone on the flagship param tree (m/v
  read-modify-write is pure HBM traffic; its share of the step bounds
  what any optimizer fusion could win).
- ``qkv``: the 3-einsum split QKV projection vs ONE fused
  ``[D, (H+2*KV)*K]`` einsum over the same weights (x is read once
  instead of three times; one MXU launch instead of three).  Forward
  and forward+backward.
- ``ce_head``: the vocab head fwd+bwd at the long-config shapes,
  unchunked vs ce_chunks=8 (the chunked scan trades logits
  materialization for serialization; the crossover is shape-dependent).
- ``trunk_vs_full``: full train step vs the same step with the CE head
  replaced by a mean over hidden states — the head's true share of the
  step, measured rather than modeled.

Prints one JSON line per measurement.

Usage: python scripts/ablate_flagship.py [name ...]
"""

import json
import os
import sys
import time

os.environ.setdefault("KERAS_BACKEND", "jax")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _long_cfg():
    from distkeras_tpu.models import transformer as tfm

    return tfm.TransformerConfig(
        vocab_size=32768, d_model=1024, n_heads=8, n_layers=8, d_ff=4096,
        max_len=4097, dtype="bfloat16", remat=True)


def _time(fn, *args, iters=20, warmup=3):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def ablate_optimizer(iters=20):
    import jax
    import optax
    from distkeras_tpu.models import transformer as tfm

    cfg = _long_cfg()
    params = tfm.init_params(jax.random.key(0), cfg)
    opt = optax.adamw(3e-4)
    opt_state = opt.init(params)
    grads = jax.tree.map(lambda p: p.astype(p.dtype), params)  # stand-in

    @jax.jit
    def apply(params, opt_state, grads):
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    dt = _time(apply, params, opt_state, grads, iters=iters)
    n = sum(int(x.size) for x in jax.tree.leaves(params))
    return {"metric": "ablate_optimizer_only", "value": round(dt * 1e3, 3),
            "unit": "ms", "params": n}


def ablate_qkv(b=8, s=4096, iters=20):
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = _long_cfg()
    d = cfg.d_model
    h, kv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    rng = np.random.default_rng(0)
    x = jax.device_put(rng.normal(0, 1, (b, s, d)).astype(np.float32)
                       .astype(jnp.bfloat16))
    wq = jax.device_put(rng.normal(0, 0.02, (d, h, hd))
                        .astype(np.float32).astype(jnp.bfloat16))
    wk = jax.device_put(rng.normal(0, 0.02, (d, kv, hd))
                        .astype(np.float32).astype(jnp.bfloat16))
    wv = jax.device_put(rng.normal(0, 0.02, (d, kv, hd))
                        .astype(np.float32).astype(jnp.bfloat16))
    # Pre-fused layout (what a fused_qkv param layout would store).
    wf = jax.device_put(np.concatenate(
        [np.asarray(wq.reshape(d, -1), np.float32),
         np.asarray(wk.reshape(d, -1), np.float32),
         np.asarray(wv.reshape(d, -1), np.float32)], axis=1)
        .astype(jnp.bfloat16))

    def split(x, wq, wk, wv):
        q = jnp.einsum("bsd,dhk->bshk", x, wq)
        k = jnp.einsum("bsd,dhk->bshk", x, wk)
        v = jnp.einsum("bsd,dhk->bshk", x, wv)
        return q.sum() + k.sum() + v.sum()

    def fused(x, wf):
        qkv = jnp.einsum("bsd,de->bse", x, wf)
        q = qkv[..., :h * hd].reshape(b, s, h, hd)
        k = qkv[..., h * hd:(h + kv) * hd].reshape(b, s, kv, hd)
        v = qkv[..., (h + kv) * hd:].reshape(b, s, kv, hd)
        return q.sum() + k.sum() + v.sum()

    out = {"metric": "ablate_qkv_projection", "unit": "ms",
           "shape": f"b{b} s{s} d{d} h{h} kv{kv}"}
    out["split_fwd"] = round(_time(jax.jit(split), x, wq, wk, wv, iters=iters) * 1e3, 3)
    out["fused_fwd"] = round(_time(jax.jit(fused), x, wf, iters=iters) * 1e3, 3)
    out["split_fwdbwd"] = round(_time(
        jax.jit(jax.grad(split, argnums=(1, 2, 3))), x, wq, wk, wv,
        iters=iters) * 1e3, 3)
    out["fused_fwdbwd"] = round(_time(
        jax.jit(jax.grad(fused, argnums=1)), x, wf, iters=iters)
        * 1e3, 3)
    out["value"] = round(out["split_fwdbwd"] / out["fused_fwdbwd"], 3)
    return out


def ablate_ce_head(b=8, s=4096, iters=20):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from distkeras_tpu.models import transformer as tfm

    cfg = _long_cfg()
    rng = np.random.default_rng(0)
    hidden = jax.device_put(rng.normal(0, 1, (b, s, cfg.d_model))
                            .astype(np.float32).astype(jnp.bfloat16))
    emb = jax.device_put(rng.normal(0, 0.02, (cfg.vocab_size, cfg.d_model))
                         .astype(np.float32).astype(jnp.bfloat16))
    targets = jax.device_put(rng.integers(
        0, cfg.vocab_size, (b, s)).astype(np.int32))

    def head_loss(emb, hidden, chunks):
        if chunks > 1:
            nll, _ = tfm.chunked_softmax_xent(hidden, emb, targets,
                                              chunks)
            return nll
        logits = jnp.einsum("bsd,vd->bsv", hidden, emb).astype(
            jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, targets[..., None], axis=-1).mean()

    out = {"metric": "ablate_ce_head", "unit": "ms",
           "shape": f"b{b} s{s} v{cfg.vocab_size}"}
    for chunks in (0, 4, 8, 16):
        f = jax.jit(jax.grad(
            lambda e, h, c=chunks: head_loss(e, h, c)))
        out[f"chunks{chunks}_fwdbwd"] = round(
            _time(f, emb, hidden, iters=iters) * 1e3, 3)
    out["value"] = out["chunks8_fwdbwd"]
    return out


def ablate_trunk_vs_full(b=8, s=4096, iters=10):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from distkeras_tpu.models import transformer as tfm

    cfg = _long_cfg()
    params = tfm.init_params(jax.random.key(0), cfg)
    opt = optax.adamw(3e-4)
    rng = np.random.default_rng(0)
    tokens = jax.device_put(rng.integers(
        0, cfg.vocab_size, (b, s + 1)).astype(np.int32))

    full = jax.jit(tfm.make_train_step(cfg, opt), donate_argnums=0)

    def trunk_loss(params, toks, cfg_, attention_fn=None, apply_fn=None,
                   dropout_rng=None, hidden_fn=None, segment_ids=None):
        hid, aux = tfm.apply_hidden(params, toks[:, :-1], cfg_,
                                    attention_fn)
        return jnp.mean(hid.astype(jnp.float32) ** 2) + aux

    trunk = jax.jit(tfm.make_train_step(cfg, opt, loss_fn=trunk_loss),
                    donate_argnums=0)

    def run(step):
        carry = (tfm.init_params(jax.random.key(0), cfg),)
        carry = (carry[0], opt.init(carry[0]))
        for _ in range(3):
            carry, loss = step(carry, tokens)
        float(loss)
        t0 = time.perf_counter()
        n = iters
        for _ in range(n):
            carry, loss = step(carry, tokens)
        float(loss)
        return (time.perf_counter() - t0) / n

    t_full, t_trunk = run(full), run(trunk)
    return {"metric": "ablate_trunk_vs_full", "unit": "ms",
            "full_ms": round(t_full * 1e3, 2),
            "trunk_only_ms": round(t_trunk * 1e3, 2),
            "head_share": round(1 - t_trunk / t_full, 4),
            "value": round(t_full * 1e3, 2)}


ABLATIONS = {
    "optimizer": ablate_optimizer,
    "qkv": ablate_qkv,
    "ce_head": ablate_ce_head,
    "trunk_vs_full": ablate_trunk_vs_full,
}


def main(names):
    import jax

    unknown = set(names) - set(ABLATIONS)
    if unknown:
        sys.exit(f"unknown ablation(s) {sorted(unknown)}; "
                 f"choose from {sorted(ABLATIONS)}")
    print(f"# backend={jax.default_backend()} device={jax.devices()[0]}",
          file=sys.stderr)
    for name in names or ABLATIONS:
        print(json.dumps(ABLATIONS[name]()))


if __name__ == "__main__":
    main(sys.argv[1:])
