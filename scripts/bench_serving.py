"""Serving benchmark: KV-cached decode through the bandwidth lens.

Decode is HBM-bandwidth-bound, not FLOPs-bound: each generated token
re-reads every matmul weight plus the KV cache at batch sizes far too
small to amortize them, so the right utilization metric is **achieved
bytes/s against the chip's HBM bandwidth**, not MFU (the roofline
argument of docs/perf_resnet50.md applied to inference — decode lives
on the bandwidth-bound side of the ridge).

Per config this prints one JSON line with:

- ``tokens_per_s`` (batch x new_tokens / wall) and ``ms_per_token``
  (per decode step — the user-visible latency between tokens),
- ``bw_util``: modeled HBM traffic per step / (step time x peak HBM
  bandwidth).  Traffic model, intentionally minimal: weight bytes are
  read once per step (batch shares them — that IS batching's win) and
  each batch row reads its cache slots once; activations are noise at
  decode shapes.  ``bw_util`` near 1.0 = the decode loop is running at
  the hardware's bandwidth roofline; the headroom 1 - bw_util is what
  software (fusion, layout, quantization) can still claim.

Workloads: greedy and sampled (top-k=50, temperature 0.8) at batch
1/8/64, bf16 vs int8 weights, rolling-window cache, and beam width 4 —
every serving surface models/generate.py offers.

Usage: python scripts/bench_serving.py [config ...]
(no args = all; unknown name lists the choices).  A backend other
than TPU, or a row that raises, exits non-zero; every line names the
platform, device kind and device count.
"""

import json
import os
import sys
import time

os.environ.setdefault("KERAS_BACKEND", "jax")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Peak HBM GB/s per chip, keyed on jax device_kind (public spec sheets:
# v5e 819, v4 1228, v5p 2765).
PEAK_HBM = {
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v4": 1228e9,
    "TPU v5p": 2765e9,
}


def _cfg(window=None):
    from distkeras_tpu.models import transformer as tfm

    # The flagship serving config (>= d1024 L8 per the round-2 review):
    # 32k vocab, 8 layers, d_model 1024 — ~152M weight params, the tied
    # embedding table is ~22% of weight bytes.
    return tfm.TransformerConfig(
        vocab_size=32768, d_model=1024, n_heads=8, n_layers=8, d_ff=4096,
        max_len=1025, dtype="bfloat16", rope=True,
        attention_window=window)


def weight_bytes(cfg, bytes_per_el=2):
    """Matmul-weight bytes one decode step reads (norm scales ignored:
    <0.01%).  Tied embedding counts once (embed gather touches B rows,
    the unembed reads the full [V, D] table)."""
    d, f, l, v = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab_size
    attn = 4 * d * d          # wq wk wv wo
    ffn = 2 * d * f
    return (l * (attn + ffn) + v * d) * bytes_per_el


def cache_bytes_per_row(cfg, filled, bytes_per_el=2):
    """KV bytes one decode step reads per batch row.

    Static shapes: the masked attention reads all ``cfg.max_len`` slots
    regardless of how many are filled — that is the real traffic, and
    exactly why the rolling-window config (small max_len ring buffer)
    wins on long generations.  ``filled`` is kept for reporting only.
    """
    del filled
    return 2 * cfg.n_layers * cfg.max_len * cfg.kv_heads * cfg.head_dim \
        * bytes_per_el


def compiled_step_bytes(cfg, params, batch, kv_int8=False, pos=512):
    """``bytes accessed`` of ONE compiled decode step, from the
    executable's own cost model — the self-auditing counterpart to the
    hand-built traffic model (round-3 verdict: bw_util was self-graded;
    this makes the roofline claim checkable against the compiler).
    Abstract lowering only — nothing is allocated."""
    import jax
    import jax.numpy as jnp
    from distkeras_tpu.models.generate import _decode_step, init_cache

    try:
        cache = jax.eval_shape(
            lambda: init_cache(cfg, batch, kv_int8=kv_int8))
        p_sh = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                           jnp.asarray(a).dtype), params)
        toks = jax.ShapeDtypeStruct((batch,), jnp.int32)
        comp = jax.jit(
            lambda p, c, t: _decode_step(p, c, t, pos, cfg)
        ).lower(p_sh, cache, toks).compile()
        ca = comp.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return float(ca.get("bytes accessed", 0.0))
    except Exception as e:
        # Degrade loudly: without this number the roofline claim is
        # back to self-graded (the round-3 weakness), so a broken
        # self-audit must be visible, not silent.
        print(f"# compiled_step_bytes unavailable: {e!r}",
              file=sys.stderr)
        return 0.0


def _measure_decode(cfg, params, batch, new, p_len=64, iters=3,
                    w_bytes=None, seq_steps=None, c_bytes=None,
                    **gen_kw):
    """``seq_steps``: actual decode-step count of the compiled scan.
    Defaults to ``new`` (the prefill path); the quantized tree forces
    the sequential path, which teacher-forces p_len - 1 extra steps —
    callers on that path must pass ``p_len - 1 + new`` or ms_per_token
    and bw_util are biased against it."""
    import jax
    import numpy as np
    from distkeras_tpu.models.generate import generate

    prompt = jax.device_put(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, p_len)).astype(np.int32))
    gen = jax.jit(lambda pp, pr: generate(pp, pr, cfg, new, **gen_kw))
    int(np.asarray(gen(params, prompt))[0, -1])  # compile + barrier
    t0 = time.perf_counter()
    for _ in range(iters):
        out = gen(params, prompt)
    int(np.asarray(out)[0, -1])
    dt = (time.perf_counter() - t0) / iters

    step_s = dt / (seq_steps if seq_steps is not None else new)
    w_bytes = w_bytes if w_bytes is not None else weight_bytes(cfg)
    c_bytes = (c_bytes if c_bytes is not None
               else cache_bytes_per_row(cfg, p_len + new))
    step_bytes = w_bytes + batch * c_bytes
    extras = {"batch": batch, "prompt_len": p_len, "new_tokens": new,
              "step_bytes_mb": round(step_bytes / 1e6, 1)}
    import jax as _j

    peak = PEAK_HBM.get(_j.devices()[0].device_kind)
    if peak:
        extras["bw_util"] = round(step_bytes / step_s / peak, 4)
        meas = compiled_step_bytes(cfg, params, batch,
                                   kv_int8=gen_kw.get("kv_int8", False))
        if meas:
            extras["step_bytes_measured_mb"] = round(meas / 1e6, 1)
            extras["bw_util_measured"] = round(meas / step_s / peak, 4)
    return batch * new / dt, step_s, 0.0, extras


def _params(quant=False, cfg=None):
    import jax
    from distkeras_tpu.models import transformer as tfm
    from distkeras_tpu.models.quant import quantize_params

    p = tfm.init_params(jax.random.key(0), cfg or _cfg())
    return quantize_params(p) if quant else p


def kv_int8_cache_bytes(cfg):
    """Modeled per-row cache traffic of the int8 KV cache: data bytes
    halve (bytes_per_el=1) and the per-token per-kv-head f32 scales add
    a head_dim/4 x smaller term.  ONE definition for every kv_int8
    bench row."""
    return (cache_bytes_per_row(cfg, None, bytes_per_el=1)
            + 2 * cfg.n_layers * cfg.max_len * cfg.kv_heads * 4)


def bench_kv_int8(batch):
    def run():
        cfg = _cfg()
        return _measure_decode(cfg, _params(), batch, new=512,
                               kv_int8=True,
                               c_bytes=kv_int8_cache_bytes(cfg))
    return run


def bench_gqa4(batch):
    # GQA 4:1 (kv_heads 2 of 8): the cache-byte term drops 4x by
    # architecture. wk/wv shrink too (project to kv_heads only).
    def run():
        import dataclasses

        cfg = dataclasses.replace(_cfg(), n_kv_heads=2)
        d = cfg.d_model
        w_b = weight_bytes(cfg) - 2 * cfg.n_layers * d * (
            d - cfg.kv_heads * cfg.head_dim) * 2
        return _measure_decode(cfg, _params(cfg=cfg), batch, new=512,
                               w_bytes=w_b)
    return run


def bench_greedy(batch):
    def run():
        return _measure_decode(_cfg(), _params(), batch, new=512)
    return run


def bench_sampled(batch):
    def run():
        import jax

        return _measure_decode(_cfg(), _params(), batch, new=512,
                               temperature=0.8, top_k=50,
                               key=jax.random.key(0))
    return run


def bench_int8(batch):
    def run():
        # int8 params force the sequential path (no prefill): short
        # prompt keeps the measured region decode-dominated, and
        # seq_steps counts the p_len-1 teacher-forcing steps the scan
        # really runs so per-step numbers compare fairly vs bf16.
        return _measure_decode(_cfg(), _params(quant=True), batch,
                               new=512, p_len=16, seq_steps=15 + 512,
                               w_bytes=weight_bytes(_cfg(), bytes_per_el=1))
    return run


def bench_rolling_window():
    """Sliding-window serving: window 256 on a 256-slot ring-buffer
    cache, generating PAST the cache size (the rolling-decode path).
    Cache traffic/row drops ~4x vs the full-1025-slot config."""
    import dataclasses

    def run():
        import jax
        from distkeras_tpu.models import transformer as tfm

        cfg = dataclasses.replace(_cfg(window=256), max_len=256)
        params = tfm.init_params(jax.random.key(0), cfg)
        return _measure_decode(cfg, params, batch=8, new=512, p_len=64)
    return run


def bench_rolling_window_kvint8():
    """Rolling ring decode x int8 KV cache (round-5: the composition
    the engine refused through round 4).  Window 256 ring + int8 K/V:
    the cache term drops ~8x vs the full-1025-slot bf16 config (4x
    ring, 2x int8, minus the f32 scale rows)."""
    import dataclasses

    def run():
        import jax
        from distkeras_tpu.models import transformer as tfm

        cfg = dataclasses.replace(_cfg(window=256), max_len=256)
        params = tfm.init_params(jax.random.key(0), cfg)
        return _measure_decode(cfg, params, batch=8, new=512, p_len=64,
                               kv_int8=True,
                               c_bytes=kv_int8_cache_bytes(cfg))
    return run


def bench_beam4(window=None, beam_impl="auto"):
    """Beam-4 decode; ``window`` runs the ring-buffer config (the
    round-4 ancestry extension — compare beam4_windowed vs
    beam4_windowed_physical for what dropping the per-step cache
    gather is worth on a windowed cache)."""
    def run():
        import dataclasses

        import jax
        import numpy as np
        from distkeras_tpu.models.generate import beam_search

        if window is None:
            cfg = _cfg()
            params = _params()
        else:
            # Ring cache sized to the workload (prompt 64 + 256 new =
            # 320 <= 384 slots; beam search never rolls past max_len),
            # so the cache-traffic term shrinks with the ring, not the
            # full 1025-slot table.
            cfg = dataclasses.replace(_cfg(window=window), max_len=384)
            params = _params(cfg=cfg)
        batch, p_len, new, width = 8, 64, 256, 4
        prompt = jax.device_put(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (batch, p_len)).astype(np.int32))
        bs = jax.jit(lambda pp, pr: beam_search(
            pp, pr, cfg, new, beam_width=width,
            beam_impl=beam_impl)[0])
        int(np.asarray(bs(params, prompt))[0, 0, -1])
        iters = 3
        t0 = time.perf_counter()
        for _ in range(iters):
            out = bs(params, prompt)
        int(np.asarray(out)[0, 0, -1])
        dt = (time.perf_counter() - t0) / iters
        step_s = dt / new
        # Beam traffic: weights once, cache read per beam row (B x W
        # rows).  The physical impl ADDITIONALLY gathers the whole
        # beam cache through the parent permutation every step — a
        # full read + write on top of the attention read (the cost
        # ancestry attention removes; modeling it is the point of the
        # windowed ancestry-vs-physical pair).
        cache_rows = batch * width * cache_bytes_per_row(cfg, 0)
        step_bytes = weight_bytes(cfg) + cache_rows
        if beam_impl == "physical":
            step_bytes += 2 * cache_rows
        extras = {"batch": batch, "beam_width": width, "prompt_len": p_len,
                  "new_tokens": new,
                  "step_bytes_mb": round(step_bytes / 1e6, 1)}
        if window is not None:
            extras["attention_window"] = window
            extras["ring_slots"] = cfg.max_len
        if beam_impl != "auto":
            extras["beam_impl"] = beam_impl
        peak = PEAK_HBM.get(jax.devices()[0].device_kind)
        if peak:
            extras["bw_util"] = round(step_bytes / step_s / peak, 4)
        # tokens/s counts kept tokens (batch x new), not beam work.
        return batch * new / dt, step_s, 0.0, extras
    return run


def bench_speculative_int8draft():
    """Self-speculative decode: the int8-quantized tree drafts for its
    own f32 parent.  Quantization preserves ~97% of greedy argmax
    choices, so acceptance is high by construction, draft steps read
    half the weight bytes, and the target pass amortizes its reads
    over n_draft+1 positions — a serving configuration that needs no
    second trained model.  Reports acceptance_rate next to tokens/s;
    compare against decode_greedy_b8 for the speedup."""
    def run():
        import jax
        import numpy as np
        from distkeras_tpu.models.quant import quantize_params
        from distkeras_tpu.models.speculative import speculative_generate

        cfg = _cfg()
        params = _params()
        draft = quantize_params(params)
        batch, p_len, new, k = 8, 64, 512, 3
        prompt = jax.device_put(np.random.default_rng(0).integers(
            0, cfg.vocab_size, (batch, p_len)).astype(np.int32))
        fn = jax.jit(lambda tp, dp, pr: speculative_generate(
            tp, dp, pr, cfg, cfg, new, n_draft=k))
        out, stats = fn(params, draft, prompt)
        int(np.asarray(out)[0, -1])
        iters = 3
        t0 = time.perf_counter()
        for _ in range(iters):
            out, stats = fn(params, draft, prompt)
        int(np.asarray(out)[0, -1])
        dt = (time.perf_counter() - t0) / iters
        extras = {"batch": batch, "prompt_len": p_len, "new_tokens": new,
                  "n_draft": k,
                  "acceptance_rate": round(float(stats["acceptance_rate"]),
                                           4),
                  "target_passes": int(stats["iterations"])}
        return batch * new / dt, dt / new, 0.0, extras
    return run


def bench_moe(batch, top_k=1):
    """MoE decode (8 experts over the flagship trunk, dense-routing
    T=1 path: each row gathers its top-k experts' slabs).  The traffic
    model makes the MoE decode cost structure explicit: expert mats are
    PER-ROW reads (a row's selected expert isn't shared the way the
    dense FFN is), so the per-step bytes are
    ``shared(attn+embed+router) + batch x (cache + k expert slabs)`` —
    the architectural reason MoE decode falls off the dense-FFN
    roofline as batch grows.  Compare against decode_greedy_b{batch}."""
    def run(new=512, p_len=64):
        import dataclasses

        cfg = dataclasses.replace(_cfg(), num_experts=8,
                                  moe_top_k=top_k)
        d, f, l = cfg.d_model, cfg.d_ff, cfg.n_layers
        # Shared per step: attention mats + tied embedding + router.
        w_b = (weight_bytes(cfg) - l * 2 * d * f * 2
               + l * d * cfg.num_experts * 2)
        # Per row per step: selected experts' w1+w2 slabs.
        c_b = (cache_bytes_per_row(cfg, None)
               + l * top_k * 2 * d * f * 2)
        out = _measure_decode(cfg, _params(cfg=cfg), batch, new=new,
                              p_len=p_len, w_bytes=w_b, c_bytes=c_b)
        out[3].update(num_experts=8, moe_top_k=top_k,
                      dense_baseline=f"decode_greedy_b{batch}")
        return out
    return run


def bench_lora_merged_serve():
    """LoRA serving: merge rank-8 wq/wv adapters into the base once
    (lora_merge), then decode the merged tree — the framework's LoRA
    deployment story.  The value is merged-tree decode tokens/s, which
    must sit on the dense row (merging leaves the forward
    byte-identical); ``merge_ms`` is the one-time cost of producing
    the servable tree."""
    def run(new=512):
        import jax
        import numpy as np
        from distkeras_tpu.models.lora import (LoRAConfig, lora_init,
                                               lora_merge)

        cfg = _cfg()
        base = _params()
        lcfg = LoRAConfig(rank=8, alpha=16.0, targets=("wq", "wv"))
        adapters = lora_init(jax.random.key(1), cfg, lcfg)
        # Trained-like adapters: B is zero at init (delta == 0); fill it
        # so the merge adds a real delta (same FLOPs either way, but a
        # zero delta would invite "it benched a no-op" skepticism).
        adapters = jax.tree.map(
            lambda a: a + 0.01 * jax.random.normal(
                jax.random.key(2), a.shape, a.dtype), adapters)
        merge = jax.jit(lambda p, ad: lora_merge(p, ad, cfg, lcfg))
        merged = merge(base, adapters)
        jax.block_until_ready(merged)
        iters = 10
        t0 = time.perf_counter()
        for _ in range(iters):
            merged = merge(base, adapters)
        jax.block_until_ready(merged)
        merge_s = (time.perf_counter() - t0) / iters
        rate, step_s, z, extras = _measure_decode(cfg, merged, 8,
                                                  new=new)
        extras.update(merge_ms=round(merge_s * 1e3, 2), lora_rank=8,
                      lora_targets="wq,wv",
                      dense_baseline="decode_greedy_b8")
        return rate, step_s, z, extras
    return run


def bench_prefix_ttft():
    # Time-to-first-token with a reused 512-token prefix vs prefilling
    # prefix+tail from scratch: the system-prompt serving pattern.
    # Reported value = scratch_ttft / cached_ttft (the reuse speedup);
    # extras carry both absolute latencies.
    def run():
        import jax
        import numpy as np
        from distkeras_tpu.models.generate import generate, prefill

        cfg = _cfg()
        params = _params()
        rng = np.random.default_rng(0)
        prefix = jax.device_put(rng.integers(
            0, cfg.vocab_size, (8, 512)).astype(np.int32))
        tail = jax.device_put(rng.integers(
            0, cfg.vocab_size, (8, 32)).astype(np.int32))
        full = jax.numpy.concatenate([prefix, tail], axis=1)
        cache, _ = jax.jit(
            lambda pp, pr: prefill(pp, pr, cfg, last_logits=False)
        )(params, prefix)
        g_scratch = jax.jit(lambda pp, pr: generate(pp, pr, cfg, 1))
        g_cached = jax.jit(lambda pp, pr, c: generate(
            pp, pr, cfg, 1, prompt_cache=(c, 512)))
        int(np.asarray(g_scratch(params, full))[0, -1])
        int(np.asarray(g_cached(params, tail, cache))[0, -1])
        iters = 10
        t0 = time.perf_counter()
        for _ in range(iters):
            out = g_scratch(params, full)
        int(np.asarray(out)[0, -1])
        scratch = (time.perf_counter() - t0) / iters
        t0 = time.perf_counter()
        for _ in range(iters):
            out = g_cached(params, tail, cache)
        int(np.asarray(out)[0, -1])
        cached = (time.perf_counter() - t0) / iters
        return scratch / cached, cached, 0.0, {
            "scratch_ttft_ms": round(scratch * 1e3, 2),
            "cached_ttft_ms": round(cached * 1e3, 2),
            "prefix_len": 512, "tail_len": 32}
    return run


def bench_engine(kv_int8=False):
    # Continuous-batching engine overhead vs raw generate: 8 full lanes
    # decoding 256 tokens in step(8) windows (one host round-trip per 8
    # tokens/lane).  The value is engine tokens/s; ``raw_tok_s`` in the
    # extras is the same workload through plain generate for the
    # overhead ratio.  ``kv_int8``: int8 KV cache on both sides (the
    # engine regime where cache bytes dominate).
    def run():
        import jax
        import numpy as np
        from distkeras_tpu.models.generate import generate
        from distkeras_tpu.serving import ContinuousBatcher

        cfg = _cfg()
        params = _params()
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size, (8, 64)).astype(np.int32)
        new = 256

        g = jax.jit(lambda pp, pr: generate(pp, pr, cfg, new,
                                            kv_int8=kv_int8))
        int(np.asarray(g(params, prompts))[0, -1])
        t0 = time.perf_counter()
        out = g(params, prompts)
        int(np.asarray(out)[0, -1])
        raw = 8 * new / (time.perf_counter() - t0)

        eng = ContinuousBatcher(params, cfg, lanes=8,
                                kv_int8=kv_int8)
        lanes = [eng.submit(prompts[i], new) for i in range(8)]
        while eng.running():     # warm compile of admit + step(8)
            eng.step(8)
        for lane in lanes:
            eng.drain(lane)
        t0 = time.perf_counter()
        lanes = [eng.submit(prompts[i], new) for i in range(8)]
        while eng.running():
            eng.step(8)
        dt = time.perf_counter() - t0
        for lane in lanes:
            eng.drain(lane)
        tok_s = 8 * new / dt
        return tok_s, dt / new, 0.0, {
            "raw_tok_s": round(raw, 1),
            "engine_overhead": round(raw / tok_s, 3),
            "lanes": 8, "step_window": 8, "new_tokens": new,
            **({"kv_int8": True} if kv_int8 else {})}
    return run


def bench_engine_speculative():
    """SpeculativeBatcher vs ContinuousBatcher on the same greedy
    workload (8 lanes x 256 tokens, d1024 target): each speculative
    round is n_draft cheap draft passes + ONE target chunk, so the win
    is acceptance_rate * n_draft amortized target-weight reads per
    round — the serving regime where plain decode is weight-bound.
    Extras carry the plain-engine rate for the ratio and the measured
    rounds/tokens.  Draft = the int8-quantized target (same trick as
    decode_speculative_int8draft: a REAL high-acceptance draft —
    ~0.93 measured solo — without a second pretrained tree; a random
    small model would have ~zero argmax agreement and measure
    nothing)."""
    def run(n_draft=3, new=256, p_len=64):
        import numpy as np
        from distkeras_tpu.models.quant import quantize_params
        from distkeras_tpu.serving import ContinuousBatcher, \
            SpeculativeBatcher

        cfg = _cfg()
        params = _params()
        dcfg = cfg
        draft = quantize_params(params)
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size,
                               (8, p_len)).astype(np.int32)

        def drive(eng, step_args):
            # Warm-up run on the SAME instance (fresh engines would
            # recompile inside the timed region), then the timed run
            # over reused lanes.
            lanes = [eng.submit(prompts[i], new) for i in range(8)]
            while eng.running():
                eng.step(*step_args)
            for ln in lanes:
                eng.drain(ln)
            t0 = time.perf_counter()
            lanes = [eng.submit(prompts[i], new) for i in range(8)]
            rounds = 0
            while eng.running():
                eng.step(*step_args)
                rounds += 1
            dt = time.perf_counter() - t0
            for ln in lanes:
                eng.drain(ln)
            return 8 * new / dt, rounds, dt

        # Plain baseline at step(n_draft + 1): the same tokens-per-
        # host-round-trip budget as a speculative round, so the ratio
        # isolates speculation from dispatch amortization.
        plain_tok_s, plain_rounds, _ = drive(
            ContinuousBatcher(params, cfg, lanes=8), (n_draft + 1,))
        spec_tok_s, spec_rounds, spec_dt = drive(
            SpeculativeBatcher(params, draft, cfg, dcfg, lanes=8,
                               n_draft=n_draft), ())
        # Second element = per decode-POSITION time (dt / new), the
        # same convention as bench_engine's ms_per_token.
        return spec_tok_s, spec_dt / new, 0.0, {
            "plain_tok_s": round(plain_tok_s, 1),
            "speedup": round(spec_tok_s / plain_tok_s, 3),
            "n_draft": n_draft, "new_tokens": new, "lanes": 8,
            "spec_rounds": spec_rounds, "plain_rounds": plain_rounds}
    return run


def bench_engine_load(lanes, offered_rps):
    """Open-loop Poisson load test of the continuous-batching engine:
    requests arrive at ``offered_rps`` (seeded exponential
    interarrivals), are admitted when a lane frees, and decode in
    step(4) windows.  Reports the latency distribution serving engines
    live by: TTFT (arrival -> first emitted token, queueing included)
    and TPOT (per-token interval after the first) at p50/p99, plus
    achieved token throughput over the makespan.  The value is
    achieved tokens/s; compare TTFT across offered loads and lane
    counts for the saturation curve."""
    def run(n_req=48, p_len=64, new=128, window=4):
        import numpy as np
        from distkeras_tpu.serving import ContinuousBatcher

        cfg = _cfg()
        params = _params()
        rng = np.random.default_rng(0)
        arrivals = np.cumsum(rng.exponential(1.0 / offered_rps, n_req))
        prompts = rng.integers(0, cfg.vocab_size,
                               (n_req, p_len)).astype(np.int32)

        eng = ContinuousBatcher(params, cfg, lanes=lanes)
        # Compile admission (the p_len-1 bucket) and the step window
        # BEFORE the clock starts: first-call XLA compiles are not
        # serving latency.
        warm = eng.submit(prompts[0], new)
        while warm in eng.running():
            eng.step(window)
        eng.drain(warm)

        lane_req: dict[int, int] = {}
        first_t = np.full(n_req, np.nan)
        done_t = np.full(n_req, np.nan)
        tokens_of = np.zeros(n_req, np.int64)
        next_rid = 0
        t0 = time.perf_counter()
        while np.isnan(done_t).any():
            now = time.perf_counter() - t0
            # Admit every request that has arrived, while lanes free.
            while (next_rid < n_req and arrivals[next_rid] <= now
                   and eng.free_lanes()):
                lane = eng.submit(prompts[next_rid], new)
                lane_req[lane] = next_rid
                next_rid += 1
            if not eng.running():
                if next_rid < n_req:
                    # Idle until the next arrival (open-loop clock).
                    time.sleep(max(0.0, arrivals[next_rid]
                                   - (time.perf_counter() - t0)))
                continue
            out = eng.step(window)
            now = time.perf_counter() - t0
            for lane, toks in out.items():
                rid = lane_req[lane]
                if toks and np.isnan(first_t[rid]):
                    first_t[rid] = now
                tokens_of[rid] += len(toks)
            for lane, rid in list(lane_req.items()):
                if lane not in eng.running() and np.isnan(done_t[rid]):
                    done_t[rid] = now
                    eng.drain(lane)
                    del lane_req[lane]
        makespan = float(np.nanmax(done_t))
        total_tokens = int(tokens_of.sum())
        ttft = first_t - arrivals
        tpot = (done_t - first_t) / np.maximum(tokens_of - 1, 1)
        pct = lambda a, q: round(float(np.percentile(a, q)) * 1e3, 1)
        extras = {
            "lanes": lanes, "offered_rps": offered_rps,
            "n_requests": n_req, "prompt_len": p_len,
            "new_tokens": new, "step_window": window,
            "achieved_rps": round(n_req / makespan, 2),
            # Per-request makespan under its own key: NOT a per-token
            # rate (makespan/n_req spans queueing + all decode rounds).
            "ms_per_request": round(makespan / n_req * 1e3, 1),
            "ttft_p50_ms": pct(ttft, 50), "ttft_p99_ms": pct(ttft, 99),
            "tpot_p50_ms": pct(tpot, 50), "tpot_p99_ms": pct(tpot, 99),
            # TTFT/TPOT are observed at step(window) boundaries, so the
            # percentiles are quantized to ~window tokens of decode
            # time; this is the quantum in ms (window x median TPOT).
            "ttft_granularity_ms": round(
                float(np.percentile(tpot, 50)) * 1e3 * window, 1),
        }
        # Second element feeds main()'s ms_per_token: aggregate
        # per-token wall time (1/value), a real per-token rate.
        return total_tokens / makespan, makespan / total_tokens, 0.0, \
            extras
    return run


def bench_engine_load_elastic(tiers, offered_rps):
    """Open-loop Poisson load against an ELASTIC engine (the PR-5
    follow-up): requests go through enqueue/poll (lane ids are
    unstable across tier resizes), QueueFull is retried at the next
    loop tick (the shed-or-retry contract), and the row reports
    achieved throughput + request-latency percentiles plus the tier
    trajectory (the obs snapshot on the row carries
    serving.lanes_tier / serving.resizes — main() attaches it)."""
    def run(n_req=48, p_len=64, new=128, window=4):
        import numpy as np
        from distkeras_tpu.serving import ContinuousBatcher, QueueFull

        cfg = _cfg()
        params = _params()
        rng = np.random.default_rng(0)
        arrivals = np.cumsum(rng.exponential(1.0 / offered_rps, n_req))
        prompts = rng.integers(0, cfg.vocab_size,
                               (n_req, p_len)).astype(np.int32)
        eng = ContinuousBatcher(params, cfg, lane_tiers=tiers,
                                max_queue=4, scale_up_after=2,
                                scale_down_after=8,
                                step_windows=(1, window))
        done_t = np.full(n_req, np.nan)
        rid_of = {}
        next_req = 0
        t0 = time.perf_counter()
        while np.isnan(done_t).any():
            now = time.perf_counter() - t0
            while next_req < n_req and arrivals[next_req] <= now:
                try:
                    rid_of[next_req] = eng.enqueue(prompts[next_req],
                                                   new)
                except QueueFull:
                    break                  # retry at the next tick
                next_req += 1
            if not eng.running() and not eng.queued:
                if next_req < n_req:
                    time.sleep(max(0.0, arrivals[next_req]
                                   - (time.perf_counter() - t0)))
                continue
            eng.step(window)
            now = time.perf_counter() - t0
            for req, rid in rid_of.items():
                if np.isnan(done_t[req]) and eng.poll(rid) is not None:
                    done_t[req] = now
        results = eng.results()
        ok = sum(r.ok for r in results.values())
        makespan = float(np.nanmax(done_t))
        total_tokens = sum(len(r.generated) for r in results.values())
        lat = done_t - arrivals
        pct = lambda a, q: round(float(np.percentile(a, q)) * 1e3, 1)
        extras = {
            "lane_tiers": list(tiers), "offered_rps": offered_rps,
            "n_requests": n_req, "ok": ok, "new_tokens": new,
            "step_window": window, "final_lanes": eng.lanes,
            "tier_epoch": eng.tier_epoch,
            "achieved_rps": round(n_req / makespan, 2),
            "request_p50_ms": pct(lat, 50),
            "request_p99_ms": pct(lat, 99),
        }
        return total_tokens / makespan, makespan / max(total_tokens,
                                                       1), 0.0, extras
    return run


def bench_engine_load_spec(lanes, offered_rps):
    """Open-loop Poisson load against the SpeculativeBatcher (the
    PR-5 follow-up): same arrival process as engine_load_*, draft =
    the int8-quantized target (the high-acceptance self-draft), TTFT/
    TPOT percentiles per offered load.  Each step advances a lane up
    to n_draft + 1 tokens, so TPOT granularity is a speculative
    round, not a token."""
    def run(n_req=48, p_len=64, new=128, n_draft=3):
        import numpy as np
        from distkeras_tpu.models.quant import quantize_params
        from distkeras_tpu.serving import SpeculativeBatcher

        cfg = _cfg()
        params = _params()
        draft = quantize_params(params)
        rng = np.random.default_rng(0)
        arrivals = np.cumsum(rng.exponential(1.0 / offered_rps, n_req))
        prompts = rng.integers(0, cfg.vocab_size,
                               (n_req, p_len)).astype(np.int32)
        eng = SpeculativeBatcher(params, draft, cfg, cfg, lanes=lanes,
                                 n_draft=n_draft)
        warm = eng.submit(prompts[0], new)
        while warm in eng.running():
            eng.step()
        eng.drain(warm)

        lane_req: dict[int, int] = {}
        first_t = np.full(n_req, np.nan)
        done_t = np.full(n_req, np.nan)
        tokens_of = np.zeros(n_req, np.int64)
        next_rid = 0
        t0 = time.perf_counter()
        while np.isnan(done_t).any():
            now = time.perf_counter() - t0
            while (next_rid < n_req and arrivals[next_rid] <= now
                   and eng.free_lanes()):
                lane = eng.submit(prompts[next_rid], new)
                lane_req[lane] = next_rid
                next_rid += 1
            if not eng.running():
                if next_rid < n_req:
                    time.sleep(max(0.0, arrivals[next_rid]
                                   - (time.perf_counter() - t0)))
                continue
            out = eng.step()
            now = time.perf_counter() - t0
            for lane, toks in out.items():
                rid = lane_req[lane]
                if toks and np.isnan(first_t[rid]):
                    first_t[rid] = now
                tokens_of[rid] += len(toks)
            for lane, rid in list(lane_req.items()):
                if lane not in eng.running() and np.isnan(done_t[rid]):
                    done_t[rid] = now
                    eng.drain(lane)
                    del lane_req[lane]
        makespan = float(np.nanmax(done_t))
        total_tokens = int(tokens_of.sum())
        ttft = first_t - arrivals
        tpot = (done_t - first_t) / np.maximum(tokens_of - 1, 1)
        pct = lambda a, q: round(float(np.percentile(a, q)) * 1e3, 1)
        extras = {
            "lanes": lanes, "offered_rps": offered_rps,
            "n_requests": n_req, "prompt_len": p_len,
            "new_tokens": new, "n_draft": n_draft,
            "achieved_rps": round(n_req / makespan, 2),
            "ttft_p50_ms": pct(ttft, 50), "ttft_p99_ms": pct(ttft, 99),
            "tpot_p50_ms": pct(tpot, 50), "tpot_p99_ms": pct(tpot, 99),
            "degraded": eng.degraded,
        }
        return total_tokens / makespan, makespan / total_tokens, 0.0, \
            extras
    return run


def bench_longprompt(prefill_chunk):
    """The chunked-prefill claim, measured: 7 lanes decode steadily
    while ONE long prompt (1024 warm tokens) is admitted mid-flight.
    Reports the decoding lanes' inter-token step gap p50/p99 over the
    run and the gap of the single worst step (monolithic admission:
    the whole 1024-token prefill lands between two steps; chunked:
    bounded by one chunk).  Value = aggregate tokens/s (the chunked
    row pays the same total prefill compute, spread out)."""
    def run(p_short=64, p_long=1017, new=160, long_new=8):
        import numpy as np
        from distkeras_tpu.serving import ContinuousBatcher

        cfg = _cfg()
        params = _params()
        if p_long + long_new > cfg.max_len:
            p_long = cfg.max_len - long_new
        # Self-scale to the config (the bench-contract tests drive
        # this through a tiny model): the chunk is ~1/8 of the cache,
        # capped at the requested width.
        chunk = (None if prefill_chunk is None
                 else min(prefill_chunk, max(1, cfg.max_len // 8)))
        rng = np.random.default_rng(0)
        shorts = rng.integers(0, cfg.vocab_size,
                              (7, p_short)).astype(np.int32)
        long_p = rng.integers(0, cfg.vocab_size,
                              (p_long,)).astype(np.int32)
        eng = ContinuousBatcher(
            params, cfg, lanes=8,
            prompt_buckets=(p_short, chunk or 128, p_long),
            prefill_chunk=chunk)
        lanes = [eng.submit(s, new) for s in shorts]
        for _ in range(4):                    # warm the step program
            eng.step()
        gaps = []
        t0 = time.perf_counter()
        injected = None
        steps = 0
        while any(l in eng.running() for l in lanes):
            if steps == 2:
                injected = eng.submit(long_p, long_new)
            t1 = time.perf_counter()
            eng.step()
            gaps.append(time.perf_counter() - t1)
            steps += 1
        dt = time.perf_counter() - t0
        for lane in lanes:
            eng.drain(lane)
        if injected is not None:
            while injected in eng.running():
                eng.step()
            eng.drain(injected)
        gaps = np.asarray(gaps)
        pct = lambda q: round(float(np.percentile(gaps, q)) * 1e3, 2)
        total = 7 * new
        extras = {
            "lanes": 8, "prompt_len_long": int(p_long),
            "prefill_chunk": chunk, "new_tokens": new,
            "step_gap_p50_ms": pct(50), "step_gap_p99_ms": pct(99),
            "step_gap_max_ms": round(float(gaps.max()) * 1e3, 2),
        }
        return total / dt, dt / total, 0.0, extras
    return run


def bench_prefix_reuse(n_prefixes):
    """The multi-prefix KV pool, measured: ``n_prefixes`` distinct
    512-token prefixes pooled device-side, 32 requests with 32-token
    tails round-robin across them.  Value = pooled tokens/s over the
    full serve; ``noreuse_tok_s`` re-runs the same workload with the
    full prefix+tail prompt re-prefilled per request (the v1
    behavior), so the ratio is what the pool is worth at this prefix
    length.  1/4/16 prefixes sweep the pool-size axis."""
    def run(prefix_len=512, tail_len=32, n_req=32, new=32):
        import jax as _jax
        import numpy as np
        from distkeras_tpu.models.generate import prefill
        from distkeras_tpu.serving import ContinuousBatcher, PrefixPool

        cfg = _cfg()
        params = _params()
        rng = np.random.default_rng(0)
        prefixes = rng.integers(0, cfg.vocab_size,
                                (n_prefixes, prefix_len)
                                ).astype(np.int32)
        tails = rng.integers(0, cfg.vocab_size,
                             (n_req, tail_len)).astype(np.int32)
        pool = PrefixPool(cfg, slots=n_prefixes)
        pf = _jax.jit(lambda pp, pr: prefill(pp, pr, cfg,
                                             last_logits=False)[0])
        pids = []
        for i in range(n_prefixes):
            pids.append(pool.put(pf(params, prefixes[i][None]),
                                 prefix_len))

        def serve(eng, use_pool):
            order = []
            t0 = time.perf_counter()
            done = 0
            nxt = 0
            lane_req = {}
            while done < n_req:
                while nxt < n_req and eng.free_lanes():
                    if use_pool:
                        lane = eng.submit(tails[nxt], new,
                                          prefix_id=pids[nxt
                                                         % n_prefixes])
                    else:
                        full = np.concatenate(
                            [prefixes[nxt % n_prefixes], tails[nxt]])
                        lane = eng.submit(full, new)
                    lane_req[lane] = nxt
                    nxt += 1
                eng.step(4)
                for lane in [l for l in lane_req
                             if l not in eng.running()]:
                    eng.drain(lane)
                    del lane_req[lane]
                    done += 1
            return time.perf_counter() - t0

        pooled_eng = ContinuousBatcher(params, cfg, lanes=8,
                                       prompt_buckets=(tail_len,),
                                       prefix_pool=pool,
                                       step_windows=(1, 4))
        serve(pooled_eng, True)               # warm
        dt_pool = serve(pooled_eng, True)
        plain_eng = ContinuousBatcher(
            params, cfg, lanes=8,
            prompt_buckets=(tail_len, prefix_len + tail_len))
        serve(plain_eng, False)               # warm
        dt_plain = serve(plain_eng, False)
        total = n_req * new
        extras = {
            "n_prefixes": n_prefixes, "prefix_len": prefix_len,
            "tail_len": tail_len, "n_requests": n_req,
            "new_tokens": new,
            "noreuse_tok_s": round(total / dt_plain, 1),
            "reuse_speedup": round(dt_plain / dt_pool, 3),
        }
        return total / dt_pool, dt_pool / total, 0.0, extras
    return run


def _paged_block(max_len, target=None):
    """Largest divisor of ``max_len`` at or under ~max_len/8 — the
    paged rows must self-scale to the config (block must divide
    max_len; the flagship's 1025 has awkward divisors)."""
    cap = target if target is not None else max(1, max_len // 8)
    return next(b for b in range(min(cap, max_len), 0, -1)
                if max_len % b == 0)


def bench_paged_lanes(lane_mult):
    """The lane-count-at-fixed-HBM claim, measured: a monolithic
    engine at ``mono_lanes`` full-``max_len`` rows vs a PagedBatcher
    whose slab holds the SAME block count (same resident KV bytes)
    serving ``lane_mult`` x the lanes — possible because each request
    only touches ~1/lane_mult of max_len, so blocks cover actual
    tokens, not rows.  Both serve the identical request set; value =
    paged tokens/s, extras carry the monolithic rate, both lane
    counts, and the slab geometry.  ``lanes_ratio`` is the headline:
    >= 2 at fixed slab bytes is the acceptance bar."""
    def run(mono_lanes=4, p_len=32, new=None):
        import numpy as np
        from distkeras_tpu.serving import ContinuousBatcher, PagedBatcher

        cfg = _cfg()
        params = _params()
        block = _paged_block(cfg.max_len)
        mb = cfg.max_len // block
        paged_lanes = mono_lanes * lane_mult
        # Each request's whole budget fits 1/lane_mult of a lane row
        # (prompt + generation), so paged_lanes of them fit the slab.
        budget = cfg.max_len // lane_mult
        p_len = min(p_len, max(2, budget // 2))
        if new is None:
            # Slack of one block for roundup, floor of 1 token.
            new = max(1, budget - p_len - block)
        n_req = paged_lanes
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size,
                               (n_req, p_len)).astype(np.int32)

        def serve(eng):
            # ``peak`` is MEASURED concurrency (max simultaneously
            # decoding lanes), not the configured lane count — the
            # >=2x-at-fixed-slab acceptance claim must be falsifiable
            # (a regression that serializes paged admissions shows up
            # here, not hidden behind a constant).
            done, nxt, lane_req, peak = 0, 0, {}, 0
            t0 = time.perf_counter()
            while done < n_req:
                while nxt < n_req and eng.free_lanes():
                    lane = eng.submit(prompts[nxt], new)
                    if lane is None:
                        break
                    lane_req[lane] = nxt
                    nxt += 1
                peak = max(peak, len(eng.running()))
                eng.step()
                for lane in [l for l in lane_req
                             if l not in eng.running()]:
                    eng.drain(lane)
                    del lane_req[lane]
                    done += 1
            return time.perf_counter() - t0, peak

        slab_blocks = mono_lanes * mb   # the fixed HBM budget
        paged = PagedBatcher(params, cfg, lanes=paged_lanes,
                             block=block, n_blocks=slab_blocks + 1,
                             prompt_buckets=(p_len - 1,))
        serve(paged)                    # warm
        dt_paged, peak_paged = serve(paged)
        mono = ContinuousBatcher(params, cfg, lanes=mono_lanes,
                                 prompt_buckets=(p_len - 1,))
        serve(mono)                     # warm
        dt_mono, peak_mono = serve(mono)
        total = n_req * new
        bytes_per_block = (2 * cfg.n_layers * block * cfg.kv_heads
                           * cfg.head_dim * 2)
        extras = {
            "mono_lanes": mono_lanes, "paged_lanes": paged_lanes,
            "peak_lanes_paged": peak_paged,
            "peak_lanes_mono": peak_mono,
            "lanes_ratio": round(peak_paged / max(peak_mono, 1), 2),
            "block": block, "slab_blocks": slab_blocks,
            "slab_mb": round(slab_blocks * bytes_per_block / 1e6, 1),
            "prompt_len": p_len, "new_tokens": new,
            "mono_tok_s": round(total / dt_mono, 1),
            "paged_speedup": round(dt_mono / dt_paged, 3),
        }
        return total / dt_paged, dt_paged / total, 0.0, extras
    return run


def bench_paged_shared_stem(n_req):
    """Cross-request stem sharing, measured: ``n_req`` requests whose
    prompts share one long stem (block-aligned) with distinct tails,
    served on a PagedBatcher — every request past the first hash-hits
    the stem blocks and prefills only its tail.  ``noshare_tok_s``
    re-runs the same shapes with fully DISTINCT stems (every request
    pays the whole prefill); ``blocks_saved`` counts the refcounted
    block hits.  Value = shared-stem tokens/s."""
    def run(stem_len=None, tail_len=16, new=32, lanes=8):
        import numpy as np
        from distkeras_tpu.serving import PagedBatcher

        cfg = _cfg()
        params = _params()
        block = _paged_block(cfg.max_len)
        if stem_len is None:
            stem_len = (cfg.max_len // 2 // block) * block
        stem_len = max(block, (stem_len // block) * block)
        rng = np.random.default_rng(0)
        stem = rng.integers(0, cfg.vocab_size,
                            (stem_len,)).astype(np.int32)
        tails = rng.integers(0, cfg.vocab_size,
                             (n_req, tail_len)).astype(np.int32)
        alt_stems = rng.integers(0, cfg.vocab_size,
                                 (n_req, stem_len)).astype(np.int32)

        def serve(eng, prompts):
            done, nxt, lane_req = 0, 0, {}
            t0 = time.perf_counter()
            while done < n_req:
                while nxt < n_req and eng.free_lanes():
                    lane = eng.submit(prompts[nxt], new)
                    if lane is None:
                        break
                    lane_req[lane] = nxt
                    nxt += 1
                eng.step()
                for lane in [l for l in lane_req
                             if l not in eng.running()]:
                    eng.drain(lane)
                    del lane_req[lane]
                    done += 1
            return time.perf_counter() - t0

        shared_prompts = [np.concatenate([stem, t]) for t in tails]
        distinct_prompts = [np.concatenate([alt_stems[i], tails[i]])
                            for i in range(n_req)]
        mb = cfg.max_len // block
        eng = PagedBatcher(params, cfg, lanes=lanes, block=block,
                           n_blocks=lanes * mb + 1,
                           prompt_buckets=(tail_len, stem_len + tail_len))
        serve(eng, shared_prompts)              # warm
        hits0 = eng.stem_hit_blocks
        dt_shared = serve(eng, shared_prompts)
        hits = eng.stem_hit_blocks - hits0
        dt_plain = serve(eng, distinct_prompts)
        total = n_req * new
        extras = {
            "n_requests": n_req, "stem_len": int(stem_len),
            "tail_len": tail_len, "new_tokens": new, "block": block,
            "blocks_saved": int(hits),
            "noshare_tok_s": round(total / dt_plain, 1),
            "share_speedup": round(dt_plain / dt_shared, 3),
        }
        return total / dt_shared, dt_shared / total, 0.0, extras
    return run


def bench_paged_cow_fork():
    """CoW fork cost vs cache copy, measured: fork a mid-decode lane
    ``iters`` times (page-table share + ONE block copy) and time it
    against the monolithic alternative — copying the lane's whole
    ``max_len`` cache row (the physical beam/spec fork).  Value = the
    copy/fork speedup; extras carry both absolute latencies and the
    byte ratio (block vs max_len row)."""
    def run(p_len=64, warm_steps=4, iters=16):
        import jax as _jax
        import jax.numpy as _jnp
        import numpy as np
        from distkeras_tpu.serving import ContinuousBatcher, PagedBatcher

        cfg = _cfg()
        params = _params()
        block = _paged_block(cfg.max_len)
        mb = cfg.max_len // block
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, cfg.vocab_size,
                              (p_len,)).astype(np.int32)
        eng = PagedBatcher(params, cfg, lanes=2, block=block,
                           n_blocks=2 * mb + 2,
                           prompt_buckets=(p_len - 1,))
        src = eng.submit(prompt, warm_steps + 2)
        for _ in range(warm_steps):
            eng.step()
        alt = int(eng._lane_state[src].tokens[-1])
        f = eng.fork(src, token=alt)            # warm the fork path
        _jax.block_until_ready(eng.cache["k"])
        eng._finish(eng._lane_state[f].request_id, [], "cancelled", 1)
        eng._vacate(f)
        t0 = time.perf_counter()
        for _ in range(iters):
            f = eng.fork(src, token=alt)
            _jax.block_until_ready(eng.cache["k"])
            eng._finish(eng._lane_state[f].request_id, [], "cancelled",
                        1)
            eng._vacate(f)
        fork_s = (time.perf_counter() - t0) / iters

        # The monolithic alternative: physically copy the source
        # lane's whole cache row into the destination lane.
        mono = ContinuousBatcher(params, cfg, lanes=2,
                                 prompt_buckets=(p_len - 1,))
        lane = mono.submit(prompt, warm_steps + 2)
        for _ in range(warm_steps):
            mono.step()

        def copy_lane(cache, src_lane, dst_lane):
            row = _jax.tree.map(
                lambda a: _jax.lax.dynamic_slice_in_dim(
                    a, src_lane, 1, axis=1), cache)
            return _jax.tree.map(
                lambda a, r: _jax.lax.dynamic_update_slice_in_dim(
                    a, r, dst_lane, axis=1), cache, row)
        cp = _jax.jit(copy_lane, donate_argnums=0)
        mono.cache = cp(mono.cache, _jnp.int32(lane), _jnp.int32(1))
        _jax.block_until_ready(mono.cache["k"])
        t0 = time.perf_counter()
        for _ in range(iters):
            mono.cache = cp(mono.cache, _jnp.int32(lane),
                            _jnp.int32(1))
        _jax.block_until_ready(mono.cache["k"])
        copy_s = (time.perf_counter() - t0) / iters
        row_bytes = (2 * cfg.n_layers * cfg.max_len * cfg.kv_heads
                     * cfg.head_dim * 2)
        extras = {
            "fork_ms": round(fork_s * 1e3, 3),
            "cache_copy_ms": round(copy_s * 1e3, 3),
            "block": block,
            "bytes_ratio": round(cfg.max_len / block, 1),
            "lane_cache_mb": round(row_bytes / 1e6, 2),
        }
        return copy_s / fork_s, fork_s, 0.0, extras
    return run


def bench_router_scale(n_replicas):
    """Fleet throughput vs replica count (round 13): ``n_replicas``
    in-process engine replicas at EQUAL per-replica config, each
    stepping on its own driver thread (the fleet shape — XLA releases
    the GIL during execution, so replicas decode concurrently), behind
    the Router's enqueue/poll flow under open-loop Poisson load that
    scales with the replica count.  Value = aggregate tokens/s;
    extras carry achieved rps and TTFT/TPOT p50/p99 read from the obs
    ``serving.ttft_s``/``serving.tpot_s`` histograms (bucket-
    interpolated; the row needs an active obs session for them, which
    main() provides).  Compare router_scale_{1,2,4}: the ≥3x-at-4
    claim is the acceptance bar on hardware where replicas own their
    compute (separate chips/hosts); one shared CPU undercounts it by
    whatever the replicas contend for."""
    def run(n_req=48, p_len=64, new=128, lanes=4,
            per_replica_rps=8.0):
        import numpy as np

        from distkeras_tpu import obs
        from distkeras_tpu.obs.metrics import percentile_from_buckets
        from distkeras_tpu.serving import (ContinuousBatcher,
                                           InProcessReplica, QueueFull,
                                           Router)

        cfg = _cfg()
        params = _params()
        rng = np.random.default_rng(0)
        offered = per_replica_rps * n_replicas
        arrivals = np.cumsum(rng.exponential(1.0 / offered, n_req))
        prompts = rng.integers(0, cfg.vocab_size,
                               (n_req, p_len)).astype(np.int32)
        engines = [ContinuousBatcher(params, cfg, lanes=lanes,
                                     max_queue=n_req,
                                     prompt_buckets=(p_len - 1,))
                   for _ in range(n_replicas)]
        replicas = [InProcessReplica(f"r{i}", e)
                    for i, e in enumerate(engines)]
        # round_robin: the scale row measures capacity, not locality —
        # uniform spread isolates the replica-count axis.
        router = Router(replicas, policy="round_robin")
        for r in replicas:
            r.start()
        try:
            # Warm every replica's programs outside the timed region.
            warm = [router.enqueue(prompts[i % n_req], new)
                    for i in range(n_replicas)]
            while any(router.poll(w) is None for w in warm):
                router.pump()
                time.sleep(0.002)
            for w in warm:
                router.take(w)
            done_t = np.full(n_req, np.nan)
            rid_of: dict[int, int] = {}
            next_req = 0
            t0 = time.perf_counter()
            while np.isnan(done_t).any():
                now = time.perf_counter() - t0
                while next_req < n_req and arrivals[next_req] <= now:
                    try:
                        rid_of[next_req] = router.enqueue(
                            prompts[next_req], new)
                    except QueueFull:
                        break              # retry at the next tick
                    next_req += 1
                router.pump()
                now = time.perf_counter() - t0
                for req, rid in rid_of.items():
                    if np.isnan(done_t[req]) \
                            and router.poll(rid) is not None:
                        done_t[req] = now
                time.sleep(0.0005)
            results = router.results()
        finally:
            for r in replicas:
                r.stop()
        ok = sum(r.ok for r in results.values())
        makespan = float(np.nanmax(done_t))
        total_tokens = sum(len(r.generated)
                           for r in results.values())
        extras = {
            "replicas": n_replicas, "lanes_per_replica": lanes,
            "offered_rps": offered, "n_requests": n_req,
            "prompt_len": p_len, "new_tokens": new, "ok": ok,
            "achieved_rps": round(n_req / makespan, 2),
        }
        sess = obs.active()
        if sess is not None:
            snap = sess.registry.snapshot()
            for name, key in (("serving.ttft_s", "ttft"),
                              ("serving.tpot_s", "tpot")):
                series = [s for s in snap.get(name, {}).get(
                    "series", []) if s.get("count")]
                if series:
                    s = series[0]
                    extras[f"{key}_p50_ms"] = round(
                        percentile_from_buckets(s, 0.50) * 1e3, 1)
                    extras[f"{key}_p99_ms"] = round(
                        percentile_from_buckets(s, 0.99) * 1e3, 1)
        return total_tokens / makespan, makespan / max(total_tokens,
                                                       1), 0.0, extras
    return run


def bench_engine_sharded(tp):
    """Pod-sharded serving (round 14): ONE ContinuousBatcher replica
    spans a ``model=tp`` mesh over the host's devices under
    ``serving_plan()`` — params TP-sharded, KV heads sharded, GSPMD
    per-token collectives compiled in.  The row reports what the
    sharding BUYS and COSTS: per-device param+KV bytes vs the solo
    engine (read from addressable shards — the ~tp× memory claim) and
    TTFT/TPOT vs the solo engine on the identical workload (the
    per-token collective cost; on one CPU host the collectives are
    memcpys, so the latency column is declared-level until a hardware
    session — ROADMAP item 5).  Value = sharded tokens/s."""
    def run(n_req=8, p_len=64, new=64, lanes=4):
        import jax
        import numpy as np

        from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
        from distkeras_tpu.parallel.sharding import serving_plan
        from distkeras_tpu.serving import ContinuousBatcher

        cfg = _cfg()
        params = _params()
        n_dev = len(jax.devices())
        if n_dev % tp:
            raise RuntimeError(
                f"engine_sharded_tp{tp} needs a device count "
                f"divisible by {tp}, have {n_dev}")
        mesh = make_mesh(MeshSpec(data=n_dev // tp, model=tp))
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size,
                               (n_req, p_len)).astype(np.int32)

        def serve(eng):
            """Serve the full request set; returns (wall, ttft list,
            tpot list) measured at step boundaries."""
            done, nxt, lane_req = 0, 0, {}
            sub_t = {}
            first_t = np.full(n_req, np.nan)
            done_t = np.full(n_req, np.nan)
            toks = np.zeros(n_req, np.int64)
            t0 = time.perf_counter()
            while done < n_req:
                while nxt < n_req and eng.free_lanes():
                    lane = eng.submit(prompts[nxt], new)
                    if lane is None:
                        break
                    lane_req[lane] = nxt
                    sub_t[nxt] = time.perf_counter() - t0
                    nxt += 1
                out = eng.step()
                now = time.perf_counter() - t0
                for lane, emitted in out.items():
                    r = lane_req[lane]
                    if emitted and np.isnan(first_t[r]):
                        first_t[r] = now
                    toks[r] += len(emitted)
                for lane in [l for l in lane_req
                             if l not in eng.running()]:
                    r = lane_req.pop(lane)
                    eng.drain(lane)
                    done_t[r] = now
                    done += 1
            sub = np.asarray([sub_t[i] for i in range(n_req)])
            ttft = first_t - sub
            tpot = (done_t - first_t) / np.maximum(toks - 1, 1)
            return time.perf_counter() - t0, ttft, tpot

        kw = dict(lanes=lanes, prompt_buckets=(p_len - 1,))
        sharded = ContinuousBatcher(params, cfg, plan=serving_plan(),
                                    mesh=mesh, **kw)
        serve(sharded)                         # warm
        dt_sh, ttft_sh, tpot_sh = serve(sharded)
        fp_sh = sharded.memory_footprint()
        solo = ContinuousBatcher(params, cfg, **kw)
        serve(solo)                            # warm
        dt_solo, ttft_solo, tpot_solo = serve(solo)
        fp_solo = solo.memory_footprint()
        total = n_req * new
        # 4 decimals: the contract tests drive this through tiny
        # configs whose per-device KV is ~0.006 MB — 2 decimals would
        # round the tp× ratio away.
        mb = lambda b: round(b / 1e6, 4)
        pct = lambda a, q: round(float(np.percentile(a, q)) * 1e3, 1)
        extras = {
            "tp": tp, "lanes": lanes, "n_requests": n_req,
            "prompt_len": p_len, "new_tokens": new,
            "param_mb_per_device": mb(fp_sh["param_bytes_per_device"]),
            "kv_mb_per_device": mb(fp_sh["kv_bytes_per_device"]),
            "solo_param_mb_per_device":
                mb(fp_solo["param_bytes_per_device"]),
            "solo_kv_mb_per_device":
                mb(fp_solo["kv_bytes_per_device"]),
            "bytes_reduction": round(
                (fp_solo["param_bytes_per_device"]
                 + fp_solo["kv_bytes_per_device"])
                / max(fp_sh["param_bytes_per_device"]
                      + fp_sh["kv_bytes_per_device"], 1), 2),
            "ttft_p50_ms": pct(ttft_sh, 50),
            "tpot_p50_ms": pct(tpot_sh, 50),
            "solo_ttft_p50_ms": pct(ttft_solo, 50),
            "solo_tpot_p50_ms": pct(tpot_solo, 50),
            "solo_tok_s": round(total / dt_solo, 1),
        }
        return total / dt_sh, dt_sh / total, 0.0, extras
    return run


def bench_router_affinity():
    """Cache-aware routing vs round-robin on the SAME trace (round
    13): 2 paged replicas, requests drawn from a handful of shared
    stems in shuffled order.  The affinity policy sends every
    same-stem request to the replica whose blocks are already
    resident (stem_hit_blocks counts the re-prefill work avoided);
    round-robin scatters them, so each replica pays its own prefill.
    Value = affinity-policy tokens/s; extras carry both policies'
    stem-hit totals and throughput — the routing-policy win isolated
    from everything else (same engines-per-run, same request order,
    single-threaded stepping so hits are deterministic)."""
    def run(n_stems=4, reqs_per_stem=8, tail_len=16, new=32, lanes=4,
            n_replicas=2):
        import numpy as np

        from distkeras_tpu.serving import (InProcessReplica,
                                           PagedBatcher, Router)

        cfg = _cfg()
        params = _params()
        block = _paged_block(cfg.max_len)
        mb = cfg.max_len // block
        stem_len = max(block, (cfg.max_len // 2 // block) * block)
        n_req = n_stems * reqs_per_stem
        rng = np.random.default_rng(0)
        stems = rng.integers(0, cfg.vocab_size,
                             (n_stems, stem_len)).astype(np.int32)
        tails = rng.integers(0, cfg.vocab_size,
                             (n_req, tail_len)).astype(np.int32)
        order = rng.permutation(n_req)
        prompts = [np.concatenate([stems[i % n_stems], tails[i]])
                   for i in order]

        def serve(policy):
            engines = [PagedBatcher(
                params, cfg, lanes=lanes, block=block,
                n_blocks=lanes * mb + 1, max_queue=n_req,
                prompt_buckets=(tail_len, stem_len + tail_len))
                for _ in range(n_replicas)]
            router = Router([InProcessReplica(f"r{i}", e)
                             for i, e in enumerate(engines)],
                            policy=policy)
            warm = router.enqueue(prompts[0], new)
            while router.poll(warm) is None:
                router.step()
            router.take(warm)
            hits0 = sum(e.stem_hit_blocks for e in engines)
            t0 = time.perf_counter()
            rids = [router.enqueue(p, new) for p in prompts]
            while any(router.poll(r) is None for r in rids):
                router.step()
            dt = time.perf_counter() - t0
            assert all(router.take(r).ok for r in rids)
            hits = sum(e.stem_hit_blocks for e in engines) - hits0
            return dt, hits

        dt_aff, hits_aff = serve("affinity")
        dt_rr, hits_rr = serve("round_robin")
        total = n_req * new
        extras = {
            "replicas": n_replicas, "n_stems": n_stems,
            "n_requests": n_req, "stem_len": int(stem_len),
            "tail_len": tail_len, "new_tokens": new, "block": block,
            "affinity_hit_blocks": int(hits_aff),
            "round_robin_hit_blocks": int(hits_rr),
            "round_robin_tok_s": round(total / dt_rr, 1),
            "affinity_speedup": round(dt_rr / dt_aff, 3),
        }
        return total / dt_aff, dt_aff / total, 0.0, extras
    return run


def bench_router_disagg():
    """Disaggregated prefill/decode fleet vs the co-resident baseline
    (round 17): the SAME 2-replica paged fleet serves the SAME trace —
    a storm of multi-block-prompt, short-decode requests pounding the
    fleet while a handful of long-decode "victim" requests stream
    tokens through ``Router.stream()`` — once with role labels
    (``prefill``-specialized replica builds each storm prompt's KV
    blocks, ships them, the ``decode`` replica adopts by page-table
    splice) and once role-less (every replica pays its own prefills
    between its own decode steps).  The claim under test: moving
    prefill compute OFF the decode replica keeps the victims' decode
    TPOT flat through the storm.  TPOT here is the ROUTER-LEVEL
    inter-token gap observed by the streaming caller (the user-visible
    latency), p50/p99 pooled across every victim gap; value = the
    baseline-over-disagg p99 ratio (the immunity).  Storm prompts
    share a first block across ``n_stems`` stems with a unique second
    block, so every request takes the ship->adopt hop (the unique
    block defeats the warm-skip residency gate) while repeated stems
    hash-hit on adoption — extras carry the transfer bytes and the
    adoption-hit rate read from the obs counters (needs the active
    obs session main() provides)."""
    def run(n_storm=1000, n_victims=8, storm_new=2, victim_new=96,
            lanes=4, n_stems=None, window=8):
        import threading

        import numpy as np

        from distkeras_tpu import obs
        from distkeras_tpu.serving import (InProcessReplica,
                                           PagedBatcher, QueueFull,
                                           Router)

        cfg = _cfg()
        params = _params()
        block = _paged_block(cfg.max_len)
        mb = cfg.max_len // block
        rng = np.random.default_rng(0)
        if n_stems is None:
            n_stems = max(1, n_storm // 8)
        # stem + unique block + a ONE-TOKEN tail: the disagg planner
        # gates on the full-block stems of prompt[:-1], so the tail
        # makes the unique block count as a stem — every request
        # takes the hop (never warm-skipped), while the shared first
        # block hash-hits on adoption once its stem shipped before.
        stems = rng.integers(0, cfg.vocab_size,
                             (n_stems, block)).astype(np.int32)
        uniq = rng.integers(0, cfg.vocab_size,
                            (n_storm, block + 1)).astype(np.int32)
        storm = [np.concatenate([stems[i % n_stems], uniq[i]])
                 for i in range(n_storm)]
        v_len = block - 1        # sub-block: victims never take the hop
        vics = rng.integers(0, cfg.vocab_size,
                            (n_victims, v_len)).astype(np.int32)
        warm_storm = rng.integers(0, cfg.vocab_size,
                                  (2 * block + 1,)).astype(np.int32)

        def counters():
            sess = obs.active()
            if sess is None:
                return None
            snap = sess.registry.snapshot()

            def val(name):
                return sum(s.get("value", 0) or 0
                           for s in snap.get(name, {}).get("series", []))
            return {n: val(n) for n in (
                "router.transfer_bytes", "router.disagg_requests",
                "router.disagg_warm_skips", "router.disagg_fallbacks",
                "serving.disagg.blocks_in", "serving.disagg.adopt_hits")}

        def serve(disagg):
            roles = ("prefill", "decode") if disagg else (None, None)
            engines = [PagedBatcher(
                params, cfg, lanes=lanes, block=block,
                n_blocks=4 * lanes * mb + 2 * n_stems + 4,
                max_queue=n_storm + n_victims,
                prompt_buckets=(v_len, 2 * block + 1)) for _ in roles]
            # Warm every engine's admission/decode programs and the
            # export/import hop OUTSIDE the timed region (non-elastic
            # paged engines compile lazily).
            for e in engines:
                for p, new in ((warm_storm, storm_new),
                               (vics[0], victim_new)):
                    rid = e.enqueue(p, new)
                    while e.poll(rid) is None:
                        e.step()
                    e.take(rid)
            if disagg:
                ship = engines[0].export_blocks(warm_storm)
                imported = engines[1].import_blocks(ship)
                rid = engines[1].enqueue(warm_storm, storm_new)
                while engines[1].poll(rid) is None:
                    engines[1].step()
                engines[1].take(rid)
                engines[1].unpin_prefix(imported["prefix_id"])
            replicas = [InProcessReplica(f"{r or 'gen'}{i}", e, role=r)
                        for i, (r, e) in enumerate(zip(roles, engines))]
            router = Router(replicas, policy="affinity",
                            residency_interval=0.05)
            for r in replicas:
                r.start()
            try:
                router.pump()   # residency refresh: tables learn the
                # block geometry the disagg planner keys on.
                gaps: list[float] = []
                firsts: list[float] = []

                def stream_victim(i):
                    t0 = time.perf_counter()
                    rid = router.enqueue(vics[i], victim_new)
                    last = None
                    mine = []
                    for _tok in router.stream(rid):
                        now = time.perf_counter()
                        if last is None:
                            firsts.append(now - t0)
                        else:
                            mine.append(now - last)
                        last = now
                    gaps.extend(mine)

                threads = [threading.Thread(target=stream_victim,
                                            args=(i,), daemon=True)
                           for i in range(n_victims)]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                # The storm: open loop with a bounded in-flight window
                # (shipped blocks stay pinned until their request
                # decodes — an unbounded burst would just trade hop
                # fallbacks for allocator backpressure).
                rids: dict[int, int] = {}
                inflight: set[int] = set()
                nxt = done = 0
                while done < n_storm:
                    while nxt < n_storm and len(inflight) < window:
                        try:
                            rids[nxt] = router.enqueue(storm[nxt],
                                                       storm_new)
                        except QueueFull:
                            break
                        inflight.add(nxt)
                        nxt += 1
                    router.pump()
                    for i in list(inflight):
                        if router.poll(rids[i]) is not None:
                            inflight.discard(i)
                            done += 1
                    time.sleep(0.0005)
                dt = time.perf_counter() - t0
                for t in threads:
                    t.join()
                ok = sum(router.take(r).ok for r in rids.values())
            finally:
                for r in replicas:
                    r.stop()
            return gaps, firsts, dt, ok

        c0 = counters()
        gaps_d, firsts_d, dt_d, ok_d = serve(True)
        c1 = counters()
        gaps_b, firsts_b, dt_b, ok_b = serve(False)

        pct = lambda a, q: round(
            float(np.percentile(a or [0.0], q)) * 1e3, 2)
        extras = {
            "n_storm": n_storm, "n_victims": n_victims,
            "storm_new": storm_new, "victim_new": victim_new,
            "lanes": lanes, "block": block, "n_stems": n_stems,
            "storm_ok": ok_d, "baseline_storm_ok": ok_b,
            "storm_rps": round(n_storm / dt_d, 1),
            "baseline_storm_rps": round(n_storm / dt_b, 1),
            "tpot_p50_ms": pct(gaps_d, 50),
            "tpot_p99_ms": pct(gaps_d, 99),
            "baseline_tpot_p50_ms": pct(gaps_b, 50),
            "baseline_tpot_p99_ms": pct(gaps_b, 99),
            "ttft_p50_ms": pct(firsts_d, 50),
            "baseline_ttft_p50_ms": pct(firsts_b, 50),
        }
        if c0 is not None:
            d = {k: c1[k] - c0[k] for k in c0}
            blocks_in = d["serving.disagg.blocks_in"]
            extras.update({
                "disagg_requests": int(d["router.disagg_requests"]),
                "warm_skips": int(d["router.disagg_warm_skips"]),
                "fallbacks": int(d["router.disagg_fallbacks"]),
                "transfer_mb": round(
                    d["router.transfer_bytes"] / 1e6, 3),
                "blocks_shipped": int(blocks_in),
                "adoption_hit_rate": round(
                    d["serving.disagg.adopt_hits"]
                    / max(blocks_in, 1), 3),
            })
        p99_d = float(np.percentile(gaps_d or [1e-9], 99))
        p99_b = float(np.percentile(gaps_b or [1e-9], 99))
        return p99_b / max(p99_d, 1e-9), p99_d, 0.0, extras
    return run


def _autoscale_leg(trace, engines, n_start, policy, *, ticks,
                   steps_per_tick, stem_len, tail_len, vocab):
    """One policy leg of the autoscale harness: replay ``trace`` over
    a fleet built from ``engines`` under a VIRTUAL clock — each tick
    injects that tick's arrivals, steps every serving replica
    ``steps_per_tick`` decode steps (service capacity is steps, not
    wall time, so the whole leg is deterministic), and, when
    ``policy`` is an Autoscaler factory, runs one scaling decision.
    ``n_start`` engines begin in the route table; the rest are parked
    in the warm pool (idle = not burning replica-ticks).  Returns
    ``(ttft_ticks, replica_ticks, decisions, lost)`` where
    ``ttft_ticks[(tick, index)]`` is first-token latency in ticks for
    every completed arrival."""
    import numpy as np

    from distkeras_tpu.serving import (InProcessReplica, QueueFull,
                                       Router, WarmPool)

    vclock = [0.0]
    replicas = [InProcessReplica(f"r{i}", e)
                for i, e in enumerate(engines)]
    router = Router(replicas[:n_start], clock=lambda: vclock[0])
    scaler = None
    if policy is not None:
        pool = WarmPool(replicas[n_start:])
        scaler = policy(router, pool)
    arrival: dict = {}     # key -> arrival tick
    first: dict = {}       # key -> first-token tick
    rid_of: dict = {}      # key -> fleet request id
    retry: list = []       # QueueFull'd (key, prompt, max_new)
    replica_ticks = 0

    def inject(tick, items):
        still = []
        for key, prompt, max_new in items:
            try:
                rid_of[key] = router.enqueue(prompt, max_new)
            except QueueFull:
                still.append((key, prompt, max_new))
        del tick
        return still

    def observe_first(tick):
        # First-token detection off the live transcripts (the same
        # read Router.stream relays; chaos_suite reads the same
        # private tables for its timeline assertions).
        for key, rid in rid_of.items():
            if key in first:
                continue
            res = router.poll(rid)
            req = router._requests.get(rid)
            part = None
            if res is not None:
                part = res
            elif req is not None and req.replica is not None:
                m = router._members.get(req.replica)
                if m is not None and req.replica_rid is not None:
                    part = m.replica.partial(req.replica_rid)
            if part is not None and \
                    np.asarray(part.tokens).size > int(part.prompt_len):
                first[key] = tick

    t = 0
    while True:
        draining = t >= ticks
        if not draining:
            vclock[0] = float(t)
            reqs = trace.replay(t)
            items = [((r.tick, r.index),
                      trace.prompt(r, stem_len=stem_len,
                                   tail_len=tail_len, vocab=vocab),
                      r.max_new) for r in reqs]
            for key, _p, _n in items:
                arrival[key] = t
            retry = inject(t, retry + items)
        else:
            vclock[0] = float(t)
            retry = inject(t, retry)
        replica_ticks += len(router.replicas_up())
        for _ in range(steps_per_tick):
            router.step()
        observe_first(t)
        if scaler is not None:
            scaler.tick()
        if draining and not retry \
                and all(router.poll(r) is not None
                        for r in rid_of.values()):
            break
        t += 1
        if t > ticks + 400:
            break  # wedged leg: report what completed as lost
    results = {k: router.poll(rid) for k, rid in rid_of.items()}
    lost = [k for k in arrival
            if k not in rid_of or results.get(k) is None
            or results[k].status != "ok"]
    ttft = {k: first[k] - arrival[k] for k in first}
    decisions = scaler.decisions if scaler is not None else []
    return ttft, replica_ticks, decisions, lost


def bench_autoscale(shape):
    """Policy-vs-policy autoscaling rows (round 19): the SAME
    deterministic :class:`TraceReplay` trace replayed over three
    fleet policies — static at the MINIMUM replica count, static at
    the MAXIMUM, and autoscaled between them by the
    :class:`Autoscaler` (warm-pool scale-up, drain-and-reroute
    scale-down) — under a virtual clock where service capacity is
    decode steps per tick, so every leg (arrivals, queue build-up,
    scaling decisions) is bit-reproducible.  Value = static-min p99
    TTFT over autoscaled p99 TTFT through the hot window (>1 means
    the autoscaler beat the small fleet); extras carry the
    replica-ticks each policy burned (autoscaled must undercut
    static-max — elasticity's cost claim), the scaling-decision
    timeline, and a repeat-run determinism check over the decision
    audit trail."""
    def run(ticks=36, min_replicas=1, max_replicas=4, lanes=2,
            steps_per_tick=4, seed=0, base_rate=2.0, spike_rate=14.0,
            spike_at=10, spike_len=8, peak_rate=10.0, period=32,
            stem_len=8, tail_len=2, max_queue=256):
        import numpy as np

        from distkeras_tpu import obs
        from distkeras_tpu.serving import (AutoscalePolicy, Autoscaler,
                                           ContinuousBatcher,
                                           TraceReplay)

        cfg = _cfg()
        params = _params()

        def trace():
            return TraceReplay(shape, seed=seed, base_rate=base_rate,
                               peak_rate=peak_rate, period=period,
                               spike_at=spike_at, spike_len=spike_len,
                               spike_rate=spike_rate, stems=4,
                               max_new=(3, 5))

        def engines(n):
            return [ContinuousBatcher(
                params, cfg, lanes=lanes, max_queue=max_queue,
                prompt_buckets=(stem_len + tail_len - 1,))
                for _ in range(n)]

        def scaler_factory(router, pool):
            sc = Autoscaler(router, pool, policy=AutoscalePolicy(
                min_replicas=min_replicas, max_replicas=max_replicas,
                up_threshold=0.9, down_threshold=0.3, up_after=1,
                down_after=3, cooldown_ticks=1))
            return sc

        kw = dict(ticks=ticks, steps_per_tick=steps_per_tick,
                  stem_len=stem_len, tail_len=tail_len,
                  vocab=cfg.vocab_size)
        legs = {}
        legs["static_min"] = _autoscale_leg(
            trace(), engines(min_replicas), min_replicas, None, **kw)
        legs["static_max"] = _autoscale_leg(
            trace(), engines(max_replicas), max_replicas, None, **kw)
        legs["autoscaled"] = _autoscale_leg(
            trace(), engines(max_replicas), min_replicas,
            scaler_factory, **kw)
        repeat = _autoscale_leg(
            trace(), engines(max_replicas), min_replicas,
            scaler_factory, **kw)

        if shape == "spike":
            hot = range(spike_at, spike_at + spike_len)
        else:
            hot = range(period // 4, (3 * period) // 4)
        hot = set(hot)

        def hot_p99(leg):
            ttft = [v for (tick, _i), v in leg[0].items()
                    if tick in hot]
            return float(np.percentile(ttft, 99)) if ttft else 0.0

        timeline = [(d["tick"], d["action"], d["replica"])
                    for d in legs["autoscaled"][2]]
        timeline2 = [(d["tick"], d["action"], d["replica"])
                     for d in repeat[2]]
        extras = {
            "shape": shape, "ticks": ticks, "seed": seed,
            "min_replicas": min_replicas,
            "max_replicas": max_replicas,
            "deterministic_timeline": timeline == timeline2,
            "scaling_changes": sum(1 for _, a, _r in timeline
                                   if a in ("up", "down")),
        }
        for name, leg in legs.items():
            extras[f"{name}_ttft_p99_ticks"] = round(hot_p99(leg), 2)
            extras[f"{name}_replica_ticks"] = leg[1]
            extras[f"{name}_lost"] = len(leg[3])
        sess = obs.active()
        if sess is not None:
            snap = sess.registry.snapshot()

            def total(name):
                return int(sum(s["value"] for s in
                               snap.get(name, {}).get("series", [])))
            extras["scale_ups"] = total("autoscale.scale_ups")
            extras["scale_downs"] = total("autoscale.scale_downs")
            extras["offered_requests"] = total("traffic.requests")
        p99_auto = extras["autoscaled_ttft_p99_ticks"]
        p99_min = extras["static_min_ttft_p99_ticks"]
        return (p99_min / max(p99_auto, 1e-9), p99_auto, 0.0, extras)
    return run


def bench_canary_rollout():
    """Live weight push under load (round 20): two hot_swap engines
    behind a Router serve a wave of in-flight requests while a
    :class:`CanaryController` promotes a freshly published snapshot
    mid-stream.  Value = victim-request TPOT p99 with the mid-stream
    push over the no-push baseline's (≈1.0 means a live swap is
    invisible to in-flight decodes — the zero-recompile claim measured
    from the victim's seat).  Extras carry the rollout wall-clock
    (canary swap → drift probe → fleet swap → epoch bump), both TPOT
    p99s, and a per-version token-determinism flag: each leg runs
    twice and must produce bit-identical token streams (the swap lands
    between the same two steps, so same params ⇒ same tokens)."""
    def run(n_req=6, max_new=16, push_after=3, lanes=4, seed=0):
        import time

        import jax
        import numpy as np

        from distkeras_tpu.models import transformer as tfm
        from distkeras_tpu.serving import (ContinuousBatcher,
                                           InProcessReplica, Router)
        from distkeras_tpu.serving.canary import CanaryController

        cfg = _cfg()
        params = _params(cfg=cfg)
        v1 = jax.tree.map(np.asarray,
                          tfm.init_params(jax.random.key(1), cfg))
        template = jax.eval_shape(
            lambda: tfm.init_params(jax.random.key(0), cfg))
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)
                   for _ in range(n_req)]

        def leg(push):
            engines = [ContinuousBatcher(params, cfg, lanes=lanes,
                                         hot_swap=True)
                       for _ in range(2)]
            router = Router([InProcessReplica(f"r{i}", e)
                             for i, e in enumerate(engines)])
            ctl = CanaryController(router, None, cfg, template)
            rids = [router.enqueue(p, max_new) for p in prompts]
            gaps, rollout_ms, steps = [], 0.0, 0
            while any(router.poll(r) is None for r in rids):
                if push and steps == push_after:
                    t0 = time.perf_counter()
                    rec = ctl.rollout(1, v1)
                    rollout_ms = (time.perf_counter() - t0) * 1e3
                    assert rec["action"] == "promote", rec
                t0 = time.perf_counter()
                router.step()
                gaps.append(time.perf_counter() - t0)
                steps += 1
            toks = tuple(tuple(int(t) for t in router.take(r).tokens)
                         for r in rids)
            return gaps, rollout_ms, toks

        base_gaps, _, base_toks = leg(push=False)
        _, _, base_toks2 = leg(push=False)
        push_gaps, rollout_ms, push_toks = leg(push=True)
        _, _, push_toks2 = leg(push=True)

        base_p99 = float(np.percentile(base_gaps, 99)) * 1e3
        push_p99 = float(np.percentile(push_gaps, 99)) * 1e3
        deterministic = (base_toks == base_toks2
                         and push_toks == push_toks2)
        extras = {
            "rollout_wallclock_ms": round(rollout_ms, 3),
            "tpot_p99_push_ms": round(push_p99, 3),
            "tpot_p99_baseline_ms": round(base_p99, 3),
            "tokens_deterministic_per_version": deterministic,
            "tokens_changed_at_push": push_toks != base_toks,
            "n_req": n_req, "push_after_steps": push_after,
        }
        ratio = push_p99 / max(base_p99, 1e-9)
        return (ratio, rollout_ms / 1e3, 0.0, extras)
    return run


BENCHES = {
    "decode_greedy_b1": (bench_greedy(1), "tokens/sec/chip"),
    "decode_greedy_b8": (bench_greedy(8), "tokens/sec/chip"),
    "decode_greedy_b64": (bench_greedy(64), "tokens/sec/chip"),
    "decode_sampled_b1": (bench_sampled(1), "tokens/sec/chip"),
    "decode_sampled_b8": (bench_sampled(8), "tokens/sec/chip"),
    "decode_sampled_b64": (bench_sampled(64), "tokens/sec/chip"),
    "decode_int8_b1": (bench_int8(1), "tokens/sec/chip"),
    "decode_int8_b8": (bench_int8(8), "tokens/sec/chip"),
    "decode_int8_b64": (bench_int8(64), "tokens/sec/chip"),
    "prefix_cache_ttft": (bench_prefix_ttft(), "x speedup"),
    "engine_throughput": (bench_engine(), "tokens/sec/chip"),
    "engine_throughput_kvint8": (bench_engine(kv_int8=True),
                                 "tokens/sec/chip"),
    "decode_kv_int8_b8": (bench_kv_int8(8), "tokens/sec/chip"),
    "decode_kv_int8_b64": (bench_kv_int8(64), "tokens/sec/chip"),
    "decode_gqa4_b64": (bench_gqa4(64), "tokens/sec/chip"),
    "decode_rolling_window": (bench_rolling_window(), "tokens/sec/chip"),
    "decode_rolling_window_kvint8": (bench_rolling_window_kvint8(),
                                     "tokens/sec/chip"),
    "beam4": (bench_beam4(), "tokens/sec/chip"),
    "beam4_windowed": (bench_beam4(window=256), "tokens/sec/chip"),
    "beam4_windowed_physical": (bench_beam4(window=256,
                                            beam_impl="physical"),
                                "tokens/sec/chip"),
    "decode_speculative_int8draft": (bench_speculative_int8draft(),
                                     "tokens/sec/chip"),
    "engine_speculative": (bench_engine_speculative(),
                           "tokens/sec/chip"),
    "decode_moe_b8": (bench_moe(8), "tokens/sec/chip"),
    "decode_moe_b64": (bench_moe(64), "tokens/sec/chip"),
    "decode_moe_top2_b8": (bench_moe(8, top_k=2), "tokens/sec/chip"),
    "lora_merged_serve": (bench_lora_merged_serve(), "tokens/sec/chip"),
    # Engine-under-load grid: 3 offered loads x the default 8 lanes,
    # plus the lane-count sweep at the middle load.  Loads bracket the
    # theoretical ceiling, computed chip-level: the engine's aggregate
    # decode rate at 8 full lanes is the measured b8 rate (~8.6k tok/s
    # across ALL lanes), so 128-token requests cap at ~8600/128 ≈ 67
    # req/s minus engine/admission overhead — 8 rps is light, 32
    # moderate, 64 probes saturation (p99 TTFT blows up first).  The
    # ceiling scales with the aggregate tok/s at that lane count, not
    # per-lane: re-derive 4/16-lane loads from the matching batch row.
    "engine_load_8l_low": (bench_engine_load(8, 8.0), "tokens/sec/chip"),
    "engine_load_8l_mid": (bench_engine_load(8, 32.0), "tokens/sec/chip"),
    "engine_load_8l_high": (bench_engine_load(8, 64.0), "tokens/sec/chip"),
    "engine_load_4l_mid": (bench_engine_load(4, 32.0), "tokens/sec/chip"),
    "engine_load_16l_mid": (bench_engine_load(16, 32.0),
                            "tokens/sec/chip"),
    # Round-10 rows.  Elastic + speculative load sweeps (the PR-5
    # follow-up), each row shipping its obs snapshot:
    "engine_load_elastic_mid": (bench_engine_load_elastic((4, 8, 16),
                                                          32.0),
                                "tokens/sec/chip"),
    "engine_load_elastic_high": (bench_engine_load_elastic((4, 8, 16),
                                                           64.0),
                                 "tokens/sec/chip"),
    "engine_load_spec_mid": (bench_engine_load_spec(8, 32.0),
                             "tokens/sec/chip"),
    # Chunked-vs-monolithic long-prompt admission (inter-token gap):
    "engine_longprompt_monolithic": (bench_longprompt(None),
                                     "tokens/sec/chip"),
    "engine_longprompt_chunked": (bench_longprompt(128),
                                  "tokens/sec/chip"),
    # Multi-prefix KV pool reuse at 1/4/16 distinct prefixes:
    "engine_prefix_pool_1": (bench_prefix_reuse(1), "tokens/sec/chip"),
    "engine_prefix_pool_4": (bench_prefix_reuse(4), "tokens/sec/chip"),
    "engine_prefix_pool_16": (bench_prefix_reuse(16),
                              "tokens/sec/chip"),
    # Round-12 paged-KV rows: lane count at fixed slab bytes, shared
    # stems vs re-prefill, and the CoW fork vs a physical cache copy.
    "engine_paged_lanes_at_hbm": (bench_paged_lanes(4),
                                  "tokens/sec/chip"),
    "engine_paged_shared_stem": (bench_paged_shared_stem(16),
                                 "tokens/sec/chip"),
    "engine_paged_cow_fork": (bench_paged_cow_fork(), "x speedup"),
    # Round-13 fleet rows: throughput/latency vs replica count through
    # the Router (equal per-replica config, per-replica step threads),
    # and the cache-aware policy vs round-robin on one trace.
    "router_scale_1": (bench_router_scale(1), "tokens/sec"),
    "router_scale_2": (bench_router_scale(2), "tokens/sec"),
    "router_scale_4": (bench_router_scale(4), "tokens/sec"),
    "router_affinity": (bench_router_affinity(), "tokens/sec"),
    # Round-14 pod-sharded rows: one engine over a model=tp mesh —
    # per-device param+KV bytes and TTFT/TPOT vs the solo engine.
    "engine_sharded_tp2": (bench_engine_sharded(2), "tokens/sec"),
    "engine_sharded_tp4": (bench_engine_sharded(4), "tokens/sec"),
    # Round-17 disaggregated fleet: prefill/decode role split with
    # block shipping vs the co-resident baseline on the same trace —
    # value is the victims' streaming-TPOT p99 immunity ratio.
    "router_disagg": (bench_router_disagg(), "x speedup"),
    # Round 19: policy-vs-policy autoscaling on the deterministic
    # trace-replay harness — static-min vs static-max vs autoscaled
    # on the SAME (seed, tick) trace; value is the p99-TTFT edge over
    # the static-minimum fleet through the hot window.
    "autoscale_spike": (bench_autoscale("spike"),
                        "x ttft vs static-min"),
    "autoscale_diurnal": (bench_autoscale("diurnal"),
                          "x ttft vs static-min"),
    # Round 20: live weight push under load — value is the victim
    # requests' TPOT p99 with a mid-stream canary promote over the
    # no-push baseline's (≈1.0 = the swap is invisible in-flight).
    "canary_rollout": (bench_canary_rollout(),
                       "x no-push tpot p99"),
}


def main(names):
    from bench_suite import run_rows

    def line_of(out, unit):
        rate, step_s, _, extra = out
        return {"value": round(rate, 1), "unit": unit,
                "ms_per_token": round(step_s * 1e3, 3), **extra}

    run_rows(BENCHES, names, line_of)


if __name__ == "__main__":
    main(sys.argv[1:])
