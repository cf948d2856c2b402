"""Shared test fixtures' builders, importable without conftest's
environment mutation (conftest appends XLA_FLAGS at import, which a
subprocess that configured its own device count must not re-run)."""

import contextlib
import functools

import numpy as np


def make_blobs(n=512, dim=16, classes=4, seed=0):
    """Linearly separable gaussian blobs — learnable in a few steps."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 4.0, (classes, dim))
    labels = rng.integers(0, classes, n)
    feats = centers[labels] + rng.normal(0, 0.5, (n, dim))
    return feats.astype(np.float32), labels.astype(np.int64)


def make_mlp(dim=16, classes=4, hidden=32, seed=0):
    import keras

    keras.utils.set_random_seed(seed)
    return keras.Sequential([
        keras.Input((dim,)),
        keras.layers.Dense(hidden, activation="relu"),
        keras.layers.Dense(classes),
    ])


@contextlib.contextmanager
def no_compile_cache():
    """The body compiles past the checkout's compile cache: for a
    test whose program the cache cannot serialize."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


# ------------------------------------------------------------ toy LMs
# The configurations the serving and LM tests mean, stated once: two
# files that build the same engine from the same weights lower the
# same programs, and the checkout's compile cache (conftest.py) then
# compiles each once.  A config that differs on purpose stays in its
# test and says why.  Imports are lazy for the subprocess importers
# (see the module docstring).

SERVE_KW = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                d_ff=64, max_len=32, rope=True)


def serve_cfg(**changes):
    """The 2-layer rope model of test_serving*/disagg/router/
    weight_push/obs_live; ``changes`` name a variant (rolling:
    ``max_len=12, attention_window=5``; ``spec_draft_cfg`` below)."""
    from distkeras_tpu.models import transformer as tfm

    return tfm.TransformerConfig(**{**SERVE_KW, **changes})


def spec_draft_cfg(**changes):
    """The 1-layer speculative draft of the model above."""
    return serve_cfg(**{"d_model": 16, "n_layers": 1, "d_ff": 32,
                        **changes})


# Arguments of the model's entry points that are Python values, not
# arrays: static under jit.
_STATIC = frozenset((
    "cfg", "draft_cfg", "max_new_tokens", "temperature", "top_k", "top_p",
    "min_p", "eos_token", "use_prefill", "exact_top_k", "kv_int8",
    "beam_width", "length_penalty", "beam_impl", "_force_physical",
    "n_draft", "moe_dense_routing", "uniform_pos", "last_logits", "batch",
    "dtype", "attention_fn", "apply_fn", "hidden_fn"))


@functools.cache
def jitted(fn):
    """``fn`` as one program a call shape instead of one a primitive
    (an eager oracle recompiles every primitive at every new shape and
    retraces its scans on every call).  An oracle's meaning does not
    change, only how many programs it is.  The trace is cached: not for
    a test that patches what ``fn`` calls between two calls of it.
    ``prompt_lengths`` and ``prompt_cache`` are read on the host, so
    such calls run ``fn`` itself."""
    import inspect

    import jax

    under_jit = jax.jit(fn, static_argnames=[
        a for a in inspect.signature(fn).parameters if a in _STATIC])

    def call(*args, **kw):
        host_read = any(kw.get(k) is not None
                        for k in ("prompt_lengths", "prompt_cache"))
        return (fn if host_read else under_jit)(*args, **kw)

    return call


class _JittedModule:
    """``jgen.prefill`` is ``jitted(generate.prefill)``."""

    def __init__(self, name):
        self._name = name

    def __getattr__(self, attr):
        import importlib

        return jitted(getattr(importlib.import_module(self._name), attr))


jgen = _JittedModule("distkeras_tpu.models.generate")
jtfm = _JittedModule("distkeras_tpu.models.transformer")


def toy_params(cfg, seed=0):
    """``init_params(key(seed), cfg)`` as ONE program a config (eagerly
    ~60 single-primitive programs); fresh buffers every call, so a
    donating train step cannot delete another test's weights."""
    import jax

    return jtfm.init_params(jax.random.key(seed), cfg)


def generate(*args, **kw):
    """``models.generate.generate``, the solo oracle of every engine
    parity test, under :func:`jitted`."""
    return jgen.generate(*args, **kw)


def base_decode_chunk(params, cache, tokens, pos0, cfg, uniform_pos=False,
                      beam_anc=None, pad_lens=None):
    """The base block's cached decode as it was written before the
    scanned body (``generate._chunk_in_place``) took its features: an
    unrolled layer loop that writes each layer's K/V (int8: and their
    scales) into the cache at the rows' slots, then attends the whole
    cache under the position mask — a ring's: slot ``s`` holds position
    ``p - ((p - s) mod max_len)`` — less a ragged row's pad slots; beam
    ancestry through ``generate._ancestry_attend``; an untyped MoE's
    decode-time experts gathered a token.  ``(logits [B, T, V] f32,
    cache)``: the oracle of those features' substitutions."""
    import jax
    import jax.numpy as jnp

    from distkeras_tpu.models.generate import _ancestry_attend
    from distkeras_tpu.models.quant import (deq, embed_rows, quantize_kv,
                                            unembed_logits)
    from distkeras_tpu.models.transformer import (_moe_gates, _rms_norm,
                                                  rope_angles, rope_rotate)

    f32, dtype = jnp.float32, jnp.dtype(cfg.dtype)
    b, t_len = tokens.shape
    s_len, win, hd = cfg.max_len, cfg.attention_window, cfg.head_dim
    pos_ids = pos0[:, None] + jnp.arange(t_len)[None, :]
    ids = (pos_ids if pad_lens is None
           else jnp.maximum(pos_ids - pad_lens[:, None], 0))
    x = embed_rows(params["tok_emb"], tokens, dtype)
    rope_ang = None
    if cfg.rope:
        rope_ang = rope_angles(ids, hd, cfg.rope_theta)[:, :, None, :]
    else:
        x = x + params["pos_emb"][ids].astype(dtype)
    span = jnp.arange(s_len)[None, None, :]
    if win is None:
        mask = span <= pos_ids[:, :, None]
    else:
        delta = jnp.mod(pos_ids[:, :, None] - span, s_len)
        mask = (delta < win) & (pos_ids[:, :, None] - delta >= 0)
    if pad_lens is not None:
        mask = mask & (span >= pad_lens[:, None, None])
    slots = pos_ids if win is None else jnp.mod(pos_ids, s_len)
    write = jax.vmap(lambda c, r, ix: c.at[ix].set(r.astype(c.dtype)))
    kv_q = "k_scale" in cache
    names = ("k", "v", "k_scale", "v_scale")[:4 if kv_q else 2]
    cache = dict(cache)
    scaled = lambda s: s.transpose(0, 2, 1)[:, None, :, None, :]
    groups = cfg.n_heads // cfg.kv_heads
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = _rms_norm(x, lp["ln1_scale"])
        q, k, v = (jnp.einsum("btd,dhk->bthk", h, deq(lp["attn"][w]))
                   for w in ("wq", "wk", "wv"))
        if rope_ang is not None:
            q, k = rope_rotate(q, rope_ang), rope_rotate(k, rope_ang)
        new = (k, v)
        if kv_q:
            (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
            new = (k, v, ks, vs)
        for name, a in zip(names, new):
            cache[name] = cache[name].at[i].set(
                write(cache[name][i], a, slots))
        ck, cv = cache["k"][i], cache["v"][i]
        qg = q.astype(f32).reshape(b, t_len, cfg.kv_heads, groups, hd)
        if beam_anc is not None:
            anc, w = beam_anc
            attn = _ancestry_attend(
                qg[:, 0], ck, cv, jax.nn.one_hot(anc, w, dtype=f32),
                mask[:, 0].reshape(b // w, w, s_len), cfg, w,
                kv_scales=((cache["k_scale"][i], cache["v_scale"][i])
                           if kv_q else None))[:, None]
        else:
            logits = jnp.einsum("btcgk,bsck->btcgs", qg, ck.astype(f32))
            if kv_q:
                logits = logits * scaled(cache["k_scale"][i])
            logits = jnp.where(mask[:, :, None, None, :],
                               logits / jnp.sqrt(f32(hd)), -1e30)
            probs = jax.nn.softmax(logits, axis=-1)
            if kv_q:
                probs = probs * scaled(cache["v_scale"][i])
            attn = jnp.einsum("btcgs,bsck->btcgk", probs, cv.astype(f32)
                              ).reshape(b, t_len, cfg.n_heads, hd)
        x = x + jnp.einsum("bthk,hkd->btd", attn.astype(dtype),
                           deq(lp["attn"]["wo"]))
        h = _rms_norm(x, lp["ln2_scale"])
        if cfg.num_experts:
            router = jnp.einsum("btd,de->bte", h.astype(f32), lp["moe"]["wg"])
            gates, expert = _moe_gates(jax.nn.softmax(router, -1), cfg)
            hk = jax.nn.gelu(jnp.einsum(
                "btd,btkdf->btkf", h, lp["moe"]["w1"][expert].astype(dtype)))
            yk = jnp.einsum("btkf,btkfd->btkd", hk,
                            lp["moe"]["w2"][expert].astype(dtype))
            y = jnp.einsum("btkd,btk->btd", yk, gates.astype(dtype))
        else:
            y = jnp.einsum("btf,fd->btd", jax.nn.gelu(jnp.einsum(
                "btd,df->btf", h, deq(lp["ffn"]["w1"]))),
                deq(lp["ffn"]["w2"]))
        x = x + y
    x = _rms_norm(x, params["ln_f_scale"])
    return unembed_logits(x, params["tok_emb"], dtype).astype(f32), cache
