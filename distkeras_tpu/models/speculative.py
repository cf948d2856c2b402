"""Speculative decoding: draft-model-assisted generation.

The sequential decode loop is HBM-bandwidth-bound — every generated
token re-reads the full weight set (models/quant's motivation).
Speculative decoding attacks the *step count* instead of the bytes: a
small draft model proposes ``n_draft`` tokens sequentially (cheap
weight reads), and the target model scores all of them in ONE chunked
forward (:func:`~distkeras_tpu.models.generate._decode_chunk` — the
weight reads amortize over n_draft+1 positions exactly like prefill).
Accepted prefixes advance several positions per target pass; mismatches
cost one target pass for one corrective token — never worse than
plain decoding in target-pass count, and the output is EXACT:

- greedy (``temperature=0``): every emitted token is the target's
  argmax given its prefix (acceptance = argmax agreement; the
  corrective token is the target argmax), so the sequence equals
  ``generate``'s greedy rollout up to float ties — the chunked and
  per-step programs reduce in different orders (~1e-6 relative), and
  only a near-exact tie between two vocab entries can flip an argmax
  between them.
- sampled (``temperature>0``): the Leviathan/Chen speculative-sampling
  rule — accept draft token x with probability min(1, p(x)/q(x)), on
  first rejection sample from norm(max(p - q, 0)) — makes every output
  token an exact sample from the target distribution (the classic
  coupling argument), regardless of draft quality.  Draft quality only
  moves the acceptance rate, i.e. the speed.

TPU-shaped: one ``lax.while_loop`` whose body is k static draft steps
+ one static [B, k+1] target chunk; per-row accept divergence is
handled by per-row cache offsets, so the whole batch shares one
compiled program.  The reference has no serving story at all
(reference: distkeras/predictors.py runs the training forward) — this
module is TPU-first surplus on the rebuild's serving axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distkeras_tpu.models.generate import (
    _decode_chunk,
    init_cache,
    prefill,
    rolling_eligible,
)
from distkeras_tpu.models.quant import is_quantized
from distkeras_tpu.models.transformer import TransformerConfig


def _validate(params, draft_params, cfg, draft_cfg, p, max_new_tokens,
              n_draft, temperature, key, eos_token=None):
    from distkeras_tpu.models.generate import _check_eos
    from distkeras_tpu.models.transformer import reject_extended

    for c in (cfg, draft_cfg):
        reject_extended(c, "speculative_generate (speculative decoding)")
    _check_eos(eos_token, cfg)
    if draft_cfg.vocab_size != cfg.vocab_size:
        raise ValueError(
            f"draft vocab_size {draft_cfg.vocab_size} != target "
            f"{cfg.vocab_size} — the models must share a tokenizer")
    if n_draft < 1:
        raise ValueError(f"n_draft must be >= 1, got {n_draft}")
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if p < 1:
        raise ValueError("prompt must contain at least one token")
    if temperature > 0 and key is None:
        raise ValueError("temperature sampling needs an explicit PRNG key")
    total = p + max_new_tokens
    # Full-cache configs: the verify chunk reaches position cur +
    # n_draft <= total - 1 + n_draft, so the cache needs n_draft slots
    # of slack past the generated length (no silent clamping — see
    # _decode_chunk).  Windowed configs (round-5): the ring absorbs
    # any total, but (a) rolling past max_len needs rope + a fitting
    # window (rolling_eligible — same bound as generate), (b) the
    # prompt warm pass writes [0, p) without wrapping, and (c) the
    # write-ahead window must satisfy window + n_draft + 1 <= max_len
    # so a rejected tail's ring slots alias OUTSIDE every live query's
    # band until real decoding overwrites them (the _decode_chunk
    # chunk-fits-ring bound with T = n_draft + 1).
    for name, c in (("cfg", cfg), ("draft_cfg", draft_cfg)):
        if c.attention_window is None:
            if total + n_draft > c.max_len:
                raise ValueError(
                    f"speculative decoding needs cache slack: "
                    f"{name}.max_len={c.max_len} < prompt ({p}) + "
                    f"max_new_tokens ({max_new_tokens}) + n_draft "
                    f"({n_draft})")
            continue
        if c.attention_window + n_draft + 1 > c.max_len:
            raise ValueError(
                f"speculative decoding on a ring cache needs "
                f"{name}.attention_window ({c.attention_window}) + "
                f"n_draft + 1 ({n_draft + 1}) <= max_len "
                f"({c.max_len}): the verify chunk's rejected tail "
                "must alias outside every live query's band")
        if p > c.max_len:
            raise ValueError(
                f"prompt ({p}) exceeds {name}.max_len={c.max_len} "
                "(the prompt warm pass cannot wrap the ring)")
        if total + n_draft > c.max_len and not rolling_eligible(c):
            raise ValueError(
                f"speculative decoding past {name}.max_len={c.max_len} "
                "rolls the ring cache, which needs rope=True and "
                f"attention_window <= max_len (got rope={c.rope})")
    return total


def speculative_accept(p_logp, q_logp, d, u):
    """The Leviathan/Chen acceptance + residual math, shared by the
    solo loop and :class:`~distkeras_tpu.serving.SpeculativeBatcher`
    (their draw KEYS differ — shared key per batch vs per-lane
    iteration-keyed — but this math must stay bit-identical or the
    engine's exact-parity contract silently breaks).

    ``p_logp [B, k+1, V]`` target log-probs, ``q_logp [B, k, V]``
    draft log-probs, ``d [B, k]`` draft tokens, ``u [B, k]`` uniform
    draws.  Returns ``(n [B], corrective_logits [B, V])``: accepted
    prefix lengths and the log-residual ``log(norm(max(p - q, 0)))``
    at the first rejected position (past-the-end the residual reduces
    to p itself — q padded with zeros; rs == 0 iff p == q, where
    rejection has probability 0, but the normalizer is guarded)."""
    k = q_logp.shape[1]
    p_d = jnp.take_along_axis(p_logp[:, :k], d[..., None],
                              axis=-1)[..., 0]
    q_d = jnp.take_along_axis(q_logp, d[..., None], axis=-1)[..., 0]
    accept = u < jnp.exp(jnp.minimum(p_d - q_d, 0.0))      # [B, k]
    n = jnp.cumprod(accept, axis=1).sum(axis=1)            # [B]
    p_n = jnp.take_along_axis(jnp.exp(p_logp), n[:, None, None],
                              axis=1)[:, 0]                # [B, V]
    q_pad = jnp.concatenate(
        [jnp.exp(q_logp), jnp.zeros_like(q_logp[:, :1])], axis=1)
    q_n = jnp.take_along_axis(q_pad, n[:, None, None], axis=1)[:, 0]
    r = jnp.maximum(p_n - q_n, 0.0)
    rs = r.sum(axis=-1, keepdims=True)
    r = jnp.where(rs > 0, r / jnp.maximum(rs, 1e-30), p_n)
    return n, jnp.log(r + 1e-30)


def _warm_cache(model_params, model_cfg, buf, p, kv_int8=False):
    """Fill a cache for prompt positions 0..p-2 (position p-1 is
    re-processed by the first verify/draft chunk, like generate()'s
    prefill path).  Prefill when eligible; otherwise (quantized tree or
    1-token prompt) CHUNKED teacher-forcing — the weight reads amortize
    over up to 128 positions per pass (sequential T=1 warming would
    re-read the full weight set p-1 times, the exact cost this module
    exists to avoid); 128 bounds the [B, T, heads, S] attention
    buffer."""
    b = buf.shape[0]
    if p > 1 and not is_quantized(model_params):
        cache, _ = prefill(model_params, buf[:, :p], model_cfg,
                           last_logits=False, kv_int8=kv_int8)
        return cache
    cache = init_cache(model_cfg, b, kv_int8=kv_int8)
    start = 0
    while start < p - 1:  # static python loop: p is a trace constant
        width = min(128, p - 1 - start)
        _, cache = _decode_chunk(model_params, cache,
                                 buf[:, start:start + width],
                                 jnp.full((b,), start, jnp.int32),
                                 model_cfg, uniform_pos=True)
        start += width
    return cache


def speculative_generate(params, draft_params, prompt, cfg: TransformerConfig,
                         draft_cfg: TransformerConfig, max_new_tokens: int,
                         n_draft: int = 4, temperature: float = 0.0,
                         key=None, eos_token: int | None = None,
                         kv_int8: bool = False):
    """Decode ``max_new_tokens`` past ``prompt [B, P]`` with draft
    assistance; returns ``(tokens [B, P+N], stats)``.

    ``stats`` (device scalars): ``iterations`` — target passes run;
    ``acceptance_rate`` — accepted draft tokens / draft tokens proposed
    by unfinished rows (the serving speedup knob: each target pass
    advances 1 + acceptance_rate * n_draft positions on average).

    ``eos_token`` is sticky like :func:`generate`'s: once a row's
    ACCEPTED stream emits it, the row's remaining generated slots fill
    with ``eos_token`` and the row stops consuming target passes
    (static shapes; trim on the host).

    Uniform-length prompts; no top-k/top-p composition in this entry
    (use :func:`~distkeras_tpu.models.generate.generate` when filtered
    sampling matters more than latency).  Quantized (int8) target or
    draft trees work — the chunk path dequantizes per read, and the
    prompt falls back to sequential warm for a quantized tree.
    ``kv_int8=True`` stores BOTH models' caches int8 (generate's
    cache-byte lever; the per-row accept-divergence writes carry the
    scale leaves through the same row-update path).

    Windowed configs compose (round-5): either model may run a
    rope + ``attention_window`` ring cache — including ROLLING past
    ``max_len`` — under ``window + n_draft + 1 <= max_len`` (verify
    chunks write their ring slots modulo ``max_len`` in
    ``generate._chunk_in_place``; the bound keeps a rejected tail's
    slots outside every live query's band).  Output parity with
    windowed ``generate`` is exact, wraps included.
    """
    from distkeras_tpu.models.generate import _device_tree

    params = _device_tree(params)
    draft_params = _device_tree(draft_params)
    b, p = prompt.shape
    total = _validate(params, draft_params, cfg, draft_cfg, p,
                      max_new_tokens, n_draft, temperature, key,
                      eos_token)
    key = key if key is not None else jax.random.key(0)
    k = n_draft
    prompt = jnp.asarray(prompt, jnp.int32)
    # k+1 scratch columns past `total`: every iteration writes its full
    # [k+1] window at cur+1 unconditionally — rejected-tail garbage
    # lands beyond the row's final position and is either rewritten by
    # the next window (it starts exactly where the accepted prefix
    # ended) or falls in the scratch region; finalized positions are
    # never touched again.  No clamping, no read-modify-write.  The
    # width matters: a DONE row (cur = total-1) still writes its window
    # at start total, so the scratch must hold all k+1 columns —
    # one column less and dynamic_update_slice clamps the start back
    # onto the row's final token and corrupts it (caught by
    # test_nonuniform_acceptance_rows_finish_cleanly).
    buf = jnp.zeros((b, total + k + 1), jnp.int32).at[:, :p].set(prompt)
    tcache = _warm_cache(params, cfg, buf, p, kv_int8=kv_int8)
    dcache = _warm_cache(draft_params, draft_cfg, buf, p, kv_int8=kv_int8)

    cur0 = jnp.full((b,), p - 1, jnp.int32)  # last FINAL position per row
    idx = jnp.arange(k + 1)

    def body(state):
        buf, tcache, dcache, cur, it, acc, props = state
        kit = jax.random.fold_in(key, it)

        # ---- k sequential draft proposals, per-row positions.
        # The FIRST step is a T=2 chunk over [buf[cur-1], buf[cur]]:
        # the draft proposes d_k but never processes it, so after a
        # full-acceptance iteration slot cur-1 (== old cur + k) is
        # unwritten in the draft cache — attending its zero row would
        # silently skew every later proposal.  Rewriting cur-1
        # alongside cur closes the gap (the target cache has no gap:
        # its verify chunk writes all k+1 slots).  At cur == 0 there
        # is no previous slot; the clamped chunk covers positions
        # [0, 1] and slot 1's garbage is overwritten by the j == 0
        # proposal step before anything reads it.
        pos0 = jnp.maximum(cur - 1, 0)
        first = jax.vmap(lambda row, s: jax.lax.dynamic_slice(
            row, (s,), (2,)))(buf, pos0)
        lg2, dcache = _decode_chunk(draft_params, dcache, first, pos0,
                                    draft_cfg)
        lg = jnp.take_along_axis(
            lg2, (cur - pos0)[:, None, None], axis=1)[:, 0]   # [B, V]
        d_toks, q_logps = [], []
        for j in range(k):
            if temperature > 0:
                logp = jax.nn.log_softmax(lg / temperature, axis=-1)
                nxt = jax.random.categorical(
                    jax.random.fold_in(kit, j), logp, axis=-1)
                q_logps.append(logp)
            else:
                nxt = lg.argmax(axis=-1)
            nxt = nxt.astype(jnp.int32)
            d_toks.append(nxt)
            if j < k - 1:
                lgj, dcache = _decode_chunk(draft_params, dcache,
                                            nxt[:, None], cur + 1 + j,
                                            draft_cfg)
                lg = lgj[:, 0]
        d = jnp.stack(d_toks, axis=1)                        # [B, k]

        # ---- one target pass over [token@cur, d_1..d_k]
        chunk = jnp.concatenate(
            [jnp.take_along_axis(buf, cur[:, None], axis=1), d], axis=1)
        tlog, tcache = _decode_chunk(params, tcache, chunk, cur, cfg)

        if temperature > 0:
            p_logp = jax.nn.log_softmax(tlog / temperature, -1)  # [B,k+1,V]
            q_logp = jnp.stack(q_logps, axis=1)                  # [B,k,V]
            u = jax.random.uniform(jax.random.fold_in(kit, k + 1), (b, k))
            # Acceptance + residual: the ONE definition, shared with
            # the serving engine (speculative_accept docstring).
            n, corr_logits = speculative_accept(p_logp, q_logp, d, u)
            corrective = jax.random.categorical(
                jax.random.fold_in(kit, k + 2),
                corr_logits, axis=-1).astype(jnp.int32)
        else:
            t_pred = tlog.argmax(axis=-1).astype(jnp.int32)      # [B, k+1]
            match = d == t_pred[:, :k]
            n = jnp.cumprod(match, axis=1).sum(axis=1)           # [B]
            corrective = jnp.take_along_axis(t_pred, n[:, None],
                                             axis=1)[:, 0]

        # ---- write [d_1..d_n, corrective, <garbage>] at cur+1 per row
        done = cur >= (total - 1)
        advance = jnp.where(done, 0,
                            jnp.minimum(n + 1, total - 1 - cur)
                            ).astype(jnp.int32)
        d_ext = jnp.concatenate([d, d[:, -1:]], axis=1)          # [B, k+1]
        win = jnp.where(idx[None, :] < n[:, None], d_ext,
                        corrective[:, None]).astype(jnp.int32)
        if eos_token is not None:
            # Sticky EOS: truncate the row's advance at its first
            # accepted eos; the tail fill below pads the rest and the
            # cur jump stops the row from consuming further passes.
            is_eos = (win == eos_token) & (idx[None, :] < advance[:, None])
            hit = is_eos.any(axis=1)
            first = jnp.argmax(is_eos, axis=1).astype(jnp.int32)
            advance = jnp.where(hit, first + 1, advance)
        buf = jax.vmap(lambda row, w, s: jax.lax.dynamic_update_slice(
            row, w, (s,)))(buf, win, cur + 1)
        if eos_token is not None:
            span = jnp.arange(buf.shape[1])
            fill = (hit[:, None]
                    & (span[None, :] > (cur + advance)[:, None])
                    & (span[None, :] < total))
            buf = jnp.where(fill, eos_token, buf)
            cur_next = jnp.where(hit, total - 1, cur + advance)
        else:
            cur_next = cur + advance

        live = (~done).astype(jnp.int32)
        acc = acc + (n * live).sum()
        props = props + k * live.sum()
        return (buf, tcache, dcache, cur_next, it + 1, acc, props)

    def cond(state):
        cur = state[3]
        return jnp.any(cur < total - 1)

    state = (buf, tcache, dcache, cur0, jnp.int32(0), jnp.int32(0),
             jnp.int32(0))
    buf, _, _, _, it, acc, props = jax.lax.while_loop(cond, body, state)
    stats = {"iterations": it,
             "acceptance_rate": acc / jnp.maximum(props, 1)}
    return buf[:, :total], stats
