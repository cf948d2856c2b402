"""Autoregressive decoding for the transformer LM (KV-cached).

Inference counterpart of models/transformer.py — the LM analogue of
the reference's ModelPredictor batch-inference path (reference:
distkeras/predictors.py), which only covers fixed-shape feedforward
outputs.  Decoding is XLA-shaped: the KV cache is a static [B, max_len,
H, D] buffer per layer, the loop is ``lax.scan`` over positions (one
compiled program regardless of prompt/output length), and sampling is
functional over an explicit PRNG key.

Decoding strategies: greedy, temperature sampling with top-k / top-p
(nucleus) / min-p filtering (:func:`generate`), and beam search
(:func:`beam_search`).  Uniform prompts run the prefill/decode split
(:func:`prefill`; MoE configs use decode-parity dense routing there);
int8-quantized trees (models/quant) decode on the sequential path.  Batch decoding shards over the mesh ``data``
axis like every other batch op.
"""

from __future__ import annotations

import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models.transformer import (
    TransformerConfig,
    _moe_dense_block,
    _moe_gates,
    _rms_norm,
    _unembed,
    block_apply,
    ffn_apply,
    final_norm,
    head_table,
    latent_kv_b,
    latent_qkv,
    layer_rotates,
    moe_ffn,
    reject_extended,
    rope_angles,
    rope_rotate,
    split_qkv,
)
from distkeras_tpu.models.quant import (
    deq,
    embed_rows,
    is_quantized,
    quantize_kv,
    unembed_logits,
)
from distkeras_tpu.ops.retention import (
    log_gate,
    phi_rows,
    ret_chunk_fwd,
    ret_state_step,
    retention_chunk,
    retention_step,
    step_operands,
    use_ret_chunk_kernel,
    use_ret_kernel,
)
from distkeras_tpu.ops.latent import (
    MLA_TAIL_PARTS,
    mla_decode_attention,
    mla_decode_block,
    mla_decode_twin,
    mla_prefix_attention,
    use_mla_decode,
    use_mla_prefix,
)
from distkeras_tpu.ops.attention import (
    DECODE_TAIL_PARTS,
    decode_block,
    flash_attention,
    flash_decode_attention,
    flash_prefix_attention,
    is_partitioned,
    use_flash_decode,
    use_flash_prefix,
)


# Slots a ring plane is allocated past its ``sliding_window``: one is
# needed (the parking slot); sixteen keep the slots a whole number of
# bf16 sublane tiles — with 129 slots the TPU's default layout put the
# slots outside the K/V heads and every decode step copied both ring
# slabs into the order it reads them in (1.3 ms of 16.3; chip, PR 31).
RING_PARK = 16


def init_cache(cfg: TransformerConfig, batch: int, dtype=None,
               kv_int8: bool = False):
    """Per-layer KV buffers [L, B, max_len, kv_heads, head_dim] — one
    plane per (pass, layer) where the stack is looped: the leading axis
    is ``cfg.kv_planes`` = n_passes * n_layers, plane ``r * L + l``
    holding pass ``r`` of layer ``l``.

    Under GQA (cfg.n_kv_heads < n_heads) the cache carries only the
    shared K/V heads — the n_heads/kv_heads memory and HBM-bandwidth
    saving that is the point of GQA at decode time.

    ``kv_int8``: store K/V as int8 with per-token per-kv-head f32
    scales (``k_scale``/``v_scale`` [L, B, max_len, kv_heads] —
    head_dim x smaller than the data; see quant.quantize_kv).  Halves
    the cache-byte term that dominates batched decode at the HBM
    roofline.  The presence of the scale leaves is what switches the
    decode attention onto the dequantizing einsums.
    """
    if kv_int8:
        reject_extended(cfg, "the int8 KV cache (kv_int8)")
    if cfg.typed:
        # Two kinds of plane side by side, both K/V-HEAD-MAJOR (a head's
        # slots are one [S, head_dim] matrix: what the bounded kernels
        # tile whatever the number of K/V heads): ``k``/``v`` one plane
        # of max_len slots a full layer, ``k_win``/``v_win`` one ring a
        # window layer, of ``sliding_window`` slots (position p at
        # slot p % sliding_window) and RING_PARK slots after them, the
        # first of which is where a parked lane's decode step writes.
        # A third kind where the stack has retention layers: ``s``/``z``
        # one float32 STATE a retention layer (``ops/retention.py``: ``s
        # [.., rows, head_dim, head_dim]``, ``z [.., rows, head_dim]``),
        # of the same size at every position: it has no slots.
        dtype = dtype or jnp.dtype(cfg.dtype)
        full = (cfg.kv_planes, batch, cfg.kv_heads, cfg.max_len,
                cfg.head_dim)
        ring = (cfg.kv_ring_planes, batch, cfg.kv_heads,
                (cfg.sliding_window or 0) + RING_PARK, cfg.head_dim)
        cache = {"k": jnp.zeros(full, dtype), "v": jnp.zeros(full, dtype),
                 "k_win": jnp.zeros(ring, dtype),
                 "v_win": jnp.zeros(ring, dtype)}
        if cfg.state_planes:
            z = (cfg.state_planes, batch, cfg.kv_heads,
                 phi_rows(cfg.head_dim), cfg.head_dim)
            cache["s"] = jnp.zeros(z[:-1] + (cfg.head_dim,) * 2,
                                   jnp.float32)
            cache["z"] = jnp.zeros(z, jnp.float32)
        if cfg.latent_planes:
            # A fourth kind: ``lat``, ONE row a position a latent layer
            # — the normed latent, the rotated key every head shares,
            # zeros up to whole lane tiles (``cfg.latent_width``) — with
            # no heads axis and no V: the values are the row's first
            # ``kv_lora_rank`` columns.  Slots as a full plane's: a
            # stale one is masked by position, nothing to clear.
            cache["lat"] = jnp.zeros((cfg.latent_planes, batch, cfg.max_len,
                                      cfg.latent_width), dtype)
        return cache
    dtype = jnp.int8 if kv_int8 else (dtype or jnp.dtype(cfg.dtype))
    shape = (cfg.kv_planes, batch, cfg.max_len, cfg.kv_heads, cfg.head_dim)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if kv_int8:
        cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
    return cache


def prefill(params, prompt, cfg: TransformerConfig,
            last_logits: bool = True, kv_int8: bool = False):
    """Fill the KV cache for all prompt positions in ONE parallel pass.

    The sequential decode loop costs one ``_decode_step`` per prompt
    position; this runs the training-style batched forward (flash
    attention over [B, P], through the SAME ``block_apply`` body as
    training — ``return_kv=True``) and writes every position's K/V into
    the cache at once.  Prompt processing drops from P sequential steps
    to a single MXU-friendly program, the standard prefill/decode split.

    Returns ``(cache, last [B, V] or None)`` — ``last_logits=False``
    skips the final norm + unembed (``generate`` re-derives the last
    position's logits inside its scan; under jit XLA DCE would prune
    the unused head anyway, the flag keeps eager callers cheap too).

    MoE configs prefill with the same capacity-free dense top-k
    routing as ``_decode_step`` (``_moe_gates`` — Switch top-1 or
    renormalized top-2) — every expert runs on every token (E x the
    dense-FFN compute; prefill happens once) and the selected experts'
    outputs are gathered, so prefilled and sequential prompt
    processing match exactly (the train/decode MoE divergence caveat in
    ``generate`` is unchanged).
    """
    dtype = jnp.dtype(cfg.dtype)
    b, p_len = prompt.shape
    if p_len > cfg.max_len:
        raise ValueError(
            f"prompt length {p_len} exceeds max_len={cfg.max_len} "
            "(the KV cache size)")
    if cfg.n_passes > 1 or cfg.typed:
        # A looped stack has ONE cached body, whose pass loop is one
        # traced body (a typed stack: whose layer knows its kinds):
        # the prompt is a chunk at position 0 against an empty cache.
        logits, cache = _decode_chunk(
            params, init_cache(cfg, b, kv_int8=kv_int8), prompt,
            jnp.zeros((b,), jnp.int32), cfg, uniform_pos=True)
        return cache, (logits[:, -1] if last_logits else None)
    x = params["tok_emb"][prompt].astype(dtype)
    rope_ang = None
    if cfg.rope:
        rope_ang = rope_angles(jnp.arange(p_len), cfg.head_dim,
                               cfg.rope_theta)[None, :, None, :]
    else:
        x = x + params["pos_emb"][:p_len][None].astype(dtype)

    attention_fn = lambda q, k, v: flash_attention(
        q, k, v, True, window=cfg.attention_window)
    cache = init_cache(cfg, b, kv_int8=kv_int8)
    ks, vs = [], []
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        # moe_dense_routing: MoE configs run the capacity-free
        # decode-parity FFN (transformer._moe_dense_block) so prefilled
        # and sequential prompts match; dense configs are unaffected.
        x, _, (k, v) = block_apply(lp, x, cfg, attention_fn, rope_ang,
                                   return_kv=True,
                                   moe_dense_routing=True)
        if kv_int8:  # quantized after the fact, not cast
            ks.append(k)
            vs.append(v)
        else:
            ks.append(k.astype(cache["k"].dtype))
            vs.append(v.astype(cache["v"].dtype))

    if kv_int8:
        kq, k_s = quantize_kv(jnp.stack(ks))  # [L, B, P, C, D]
        vq, v_s = quantize_kv(jnp.stack(vs))
        cache = {
            "k": cache["k"].at[:, :, :p_len].set(kq),
            "v": cache["v"].at[:, :, :p_len].set(vq),
            "k_scale": cache["k_scale"].at[:, :, :p_len].set(k_s),
            "v_scale": cache["v_scale"].at[:, :, :p_len].set(v_s),
        }
    else:
        cache = {
            "k": cache["k"].at[:, :, :p_len].set(jnp.stack(ks)),
            "v": cache["v"].at[:, :, :p_len].set(jnp.stack(vs)),
        }
    if not last_logits:
        return cache, None
    x = _rms_norm(x, params["ln_f_scale"])
    return cache, _unembed(x[:, -1:], params, cfg)[:, 0]


def _ancestry_attend(qg, ck, cv, anc_oh, mask_b, cfg: TransformerConfig,
                     w_beams: int, kv_scales=None):
    """Beam ancestry attention for ONE position, shared by the
    full-cache chunk body and the windowed ring-buffer body.

    ``qg [B, kv_heads, groups, hd]`` f32 queries (beam lanes tiled
    batch-major, B = bt * W), ``ck/cv [B, S, kv_heads, hd]`` the
    per-lane cache, ``anc_oh [bt, W, S, W]`` f32 one-hot ancestor map
    (SLOT s of lane w reads from lane ``anc[b, w, s]`` — slot ==
    position while total <= max_len, and under rolling decode the
    beam body retires a reused slot's ancestry in the same step that
    overwrites its K/V), ``mask_b [bt, W, S]`` bool valid-slot mask
    (position mask full-cache, band mask windowed — the only
    difference between the two callers).  Scores every
    (query-lane, source-lane) pair — the cache is read once, W x the
    tiny decode attention FLOPs — then the one-hot selects each
    position's true ancestor.  ``kv_scales=(cks, cvs) [B, S, kv]``:
    int8-KV dequant scales (slot-indexed, so ring caches compose).
    Returns ``attn [B, n_heads, hd]`` f32.
    """
    b = qg.shape[0]
    s_len = ck.shape[1]
    bt = b // w_beams
    qb = qg.reshape(bt, w_beams, cfg.kv_heads, -1, cfg.head_dim)
    kb = ck.astype(jnp.float32).reshape(
        bt, w_beams, s_len, cfg.kv_heads, cfg.head_dim)
    vb = cv.astype(jnp.float32).reshape(
        bt, w_beams, s_len, cfg.kv_heads, cfg.head_dim)
    la = jnp.einsum("bwcgk,bvsck->bwcgvs", qb, kb)
    if kv_scales is not None:
        # [B, S, C] -> [bt, 1, C, 1, v(=w), S] over la's dims.
        bsc = lambda sc: sc.reshape(
            bt, w_beams, s_len, cfg.kv_heads).transpose(
            0, 3, 1, 2)[:, None, :, None, :, :]
        la = la * bsc(kv_scales[0])
    logits = jnp.einsum("bwcgvs,bwsv->bwcgs", la, anc_oh)
    logits = logits / jnp.sqrt(jnp.float32(cfg.head_dim))
    logits = jnp.where(mask_b[:, :, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    pm = jnp.einsum("bwcgs,bwsv->bwcgvs", probs, anc_oh)
    if kv_scales is not None:
        pm = pm * bsc(kv_scales[1])
    return jnp.einsum("bwcgvs,bvsck->bwcgk", pm, vb).reshape(
        b, cfg.n_heads, cfg.head_dim)


_BASE_ONLY = "a windowed (rolling) or ragged-prompt (prompt_lengths) decode"


def _decode_step(params, cache, tokens, pos, cfg: TransformerConfig,
                 pad_lens=None, beam_anc=None):
    """One position: tokens [B] at position ``pos`` -> (logits [B, V], cache).

    Attention reads the cache up to ``pos`` with a position mask (static
    shapes; masked slots contribute exp(NEG_INF-ish) = 0).

    ``pad_lens [B]`` supports left-padded batches (ragged prompts
    aligned at their ends): positions < pad_lens[i] are excluded from
    row i's attention forever, and position *ids* (rotary angles /
    pos_emb rows) count from the row's true start, so each row decodes
    exactly as it would alone.

    The plain path (no window, no padding) delegates to
    :func:`_decode_chunk` with T = 1 — ONE layer-body definition for
    both; only the ring-buffer slot arithmetic and the ragged pad
    masking below justify a separate body.
    """
    dtype = jnp.dtype(cfg.dtype)
    b = tokens.shape[0]
    if cfg.attention_window is not None or pad_lens is not None:
        reject_extended(cfg, _BASE_ONLY)
    if cfg.attention_window is None and pad_lens is None:
        out, cache = _decode_chunk(params, cache, tokens[:, None],
                                   jnp.full((b,), pos, jnp.int32), cfg,
                                   uniform_pos=True, beam_anc=beam_anc)
        return out[:, 0], cache
    if beam_anc is not None and pad_lens is not None:
        raise ValueError("beam ancestry attention does not compose with "
                         "pad_lens (beam search is uniform-prompt only)")
    if beam_anc is not None:
        anc, w_beams = beam_anc
        anc_oh = jax.nn.one_hot(anc, w_beams, dtype=jnp.float32)
    kv_q = "k_scale" in cache                   # int8 KV cache
    with jax.named_scope("embed"):
        x = embed_rows(params["tok_emb"], tokens, dtype)  # [B, D]
        if pad_lens is None:
            pos_ids = jnp.full((b,), pos)
        else:
            pos_ids = jnp.maximum(pos - pad_lens, 0)
        rope_ang = None
        if cfg.rope:
            # [B, half] per-row angles; broadcast over heads.
            rope_ang = rope_angles(pos_ids, cfg.head_dim,
                                   cfg.rope_theta)[:, None, :]
        else:
            x = x + params["pos_emb"][pos_ids].astype(dtype)

    ck_all, cv_all = cache["k"], cache["v"]     # [L, B, S, kv, hd]
    if kv_q:
        cks_all, cvs_all = cache["k_scale"], cache["v_scale"]
    # [B, S, C] scale -> broadcast over the [B, C, G, S] logits.
    sc_b = lambda s: s.transpose(0, 2, 1)[:, :, None, :]
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = _rms_norm(x, lp["ln1_scale"])
        with jax.named_scope("attn_proj"):
            # deq: int8 weights dequantize here (fused into the matmul
            # read); plain trees pass through untouched.
            q = jnp.einsum("bd,dhk->bhk", h, deq(lp["attn"]["wq"]))
            # Cache dtype: the einsum promotes bf16 activations x f32
            # weights to f32; the cache stays in the compute dtype.
            k = jnp.einsum("bd,dhk->bhk", h, deq(lp["attn"]["wk"]))
            v = jnp.einsum("bd,dhk->bhk", h, deq(lp["attn"]["wv"]))
            if rope_ang is not None:
                # Keys cache post-rotation (each key's rotation depends
                # only on its own position), matching the training
                # forward.
                q, k = rope_rotate(q, rope_ang), rope_rotate(k, rope_ang)
            if kv_q:  # post-rotation, like the bf16 cache
                k, k_s = quantize_kv(k)               # scale [B, C]
                v, v_s = quantize_kv(v)
        # Windowed configs write the ring-buffer slot pos % C (identical
        # to pos while pos < C): with window <= C the cache then
        # supports generation beyond max_len (rolling decode) — the
        # int8 scales ride the same slot arithmetic.
        slot = jnp.asarray(pos % cfg.max_len if cfg.attention_window
                           else pos, jnp.int32)
        with jax.named_scope("kv_slab"):
            ck_all = _layer_slab_update(ck_all, i, k[:, None], slot)
            cv_all = _layer_slab_update(cv_all, i, v[:, None], slot)
            ck, cv = ck_all[i], cv_all[i]
            if kv_q:
                cks_all = _layer_slab_update(cks_all, i, k_s[:, None],
                                             slot)
                cvs_all = _layer_slab_update(cvs_all, i, v_s[:, None],
                                             slot)
                cks, cvs = cks_all[i], cvs_all[i]

        with jax.named_scope("attn"):
            # GQA: grouped einsums read only the kv-head cache — never
            # materialize an expanded per-query-head copy (that repeat
            # would forfeit the cache-bandwidth saving that is GQA's point).
            groups = cfg.n_heads // cfg.kv_heads
            qg = q.astype(jnp.float32).reshape(
                b, cfg.kv_heads, groups, cfg.head_dim)
            span = jnp.arange(cfg.max_len)
            if cfg.attention_window is not None:
                # Ring-buffer band: slot s holds global position
                # g = pos - ((pos - s) mod C).  Keep iff the position is
                # real (g >= 0 — this also excludes every future slot while
                # pos < C, so prefilled prompts stay causal) and inside the
                # window (delta < W).  For pos < C this reduces exactly to
                # span in (pos - W, pos]; for pos >= C it implements the
                # rolling window.  Distances are pad-invariant, so the
                # ragged pad mask below composes unchanged.
                delta = jnp.mod(pos - span, cfg.max_len)
                row_mask = (delta < cfg.attention_window) & (pos - delta >= 0)
            else:
                row_mask = span <= pos
            if beam_anc is not None:
                # Windowed beam ancestry: the ancestor map is SLOT-indexed
                # (identical to positions until the ring wraps; under
                # rolling decode the beam body retires stale entries as
                # slots are rewritten) and only the band mask differs from
                # the full-cache path.
                bt = b // w_beams
                mask_b = jnp.broadcast_to(row_mask[None, None, :],
                                          (bt, w_beams, cfg.max_len))
                attn = _ancestry_attend(qg, ck, cv, anc_oh, mask_b, cfg,
                                        w_beams,
                                        kv_scales=(cks, cvs) if kv_q
                                        else None)
            else:
                logits = jnp.einsum("bcgk,bsck->bcgs", qg,
                                    ck.astype(jnp.float32))
                if kv_q:
                    logits = logits * sc_b(cks)
                logits = logits / jnp.sqrt(jnp.float32(cfg.head_dim))
                mask = row_mask[None, None, None, :]
                if pad_lens is not None:  # left-pad slots never attend
                    mask = mask & (span[None, :] >= pad_lens[:, None]
                                   )[:, None, None, :]
                logits = jnp.where(mask, logits, -1e30)
                probs = jax.nn.softmax(logits, axis=-1)
                attn = jnp.einsum("bcgs,bsck->bcgk",
                                  probs * sc_b(cvs) if kv_q else probs,
                                  cv.astype(jnp.float32)).reshape(
                    b, cfg.n_heads, cfg.head_dim)
        with jax.named_scope("attn_proj"):
            x = x + jnp.einsum("bhk,hkd->bd", attn.astype(dtype),
                               deq(lp["attn"]["wo"]))

        h = _rms_norm(x, lp["ln2_scale"])
        with jax.named_scope("mlp"):
            if cfg.num_experts:
                # Decode-time MoE: dense top-k without capacity (batch is
                # small; correctness over dispatch efficiency).  Same
                # gate rule as training/prefill via _moe_gates.
                router = jnp.einsum("bd,de->be", h.astype(jnp.float32),
                                    lp["moe"]["wg"])
                probs = jax.nn.softmax(router, axis=-1)
                gates, expert = _moe_gates(probs, cfg)   # [B, k]
                w1 = lp["moe"]["w1"][expert]  # [B, k, D, F]
                w2 = lp["moe"]["w2"][expert]  # [B, k, F, D]
                hk = jax.nn.gelu(jnp.einsum("bd,bkdf->bkf", h,
                                            w1.astype(dtype)))
                yk = jnp.einsum("bkf,bkfd->bkd", hk, w2.astype(dtype))
                y = jnp.einsum("bkd,bk->bd", yk, gates.astype(dtype))
            else:
                y = jnp.einsum(
                    "bf,fd->bd",
                    jax.nn.gelu(jnp.einsum("bd,df->bf", h,
                                           deq(lp["ffn"]["w1"]))),
                    deq(lp["ffn"]["w2"]))
            x = x + y

    x = _rms_norm(x, params["ln_f_scale"])
    # Vocab head: int8 trees contract the raw q table and scale the
    # result (int8 stays the HBM operand by construction — see
    # quant.unembed_logits), instead of dequantizing [V, d] per step.
    with jax.named_scope("head"):
        out = unembed_logits(x, params["tok_emb"], dtype)
    cache = {"k": ck_all, "v": cv_all}
    if kv_q:
        cache["k_scale"], cache["v_scale"] = cks_all, cvs_all
    return out.astype(jnp.float32), cache


def _rows_update(cache_layer, rows, pos0):
    """Write ``rows [B, T, kv, hd]`` into ``cache_layer [B, S, kv, hd]``
    at per-row offsets ``pos0 [B]`` (a batched dynamic_update_slice —
    XLA lowers the vmap to a scatter).  Callers clamp pos0 to S - T;
    dynamic_update_slice would silently shift an out-of-range write."""
    return jax.vmap(
        lambda c, r, p: jax.lax.dynamic_update_slice(
            c, r.astype(c.dtype),
            (p,) + (0,) * (c.ndim - 1)))(cache_layer, rows, pos0)


def _rows_update_ring(cache_layer, rows, pos0, max_len):
    """Per-row T-span write at ring slots ``(pos0[b] + t) % max_len`` —
    the modular generalization of :func:`_rows_update` for windowed
    chunks that may WRAP mid-chunk (speculative decoding's divergent
    per-row positions on a ring cache).  A per-row gather-scatter
    (``c.at[idx].set``): a dynamic_update_slice span cannot wrap."""
    t_len = rows.shape[1]
    idx = (pos0[:, None] + jnp.arange(t_len)) % max_len    # [B, T]
    return jax.vmap(lambda c, r, ix: c.at[ix].set(
        r.astype(c.dtype)))(cache_layer, rows, idx)


def _layer_slab_update(cache_all, i, rows, pos):
    """Write ``rows [B, T, kv, hd]`` (all rows at position ``pos``) into
    layer ``i`` of the stacked cache ``[L, B, S, kv, hd]`` — WITHOUT
    slicing the layer out and restacking.

    The decode loop is bandwidth-bound and the cache is its largest
    buffer; the old per-layer ``cache[i]`` + ``jnp.stack`` pattern made
    XLA materialize a full cache copy every step (~6.5 ms per tensor
    per step at batch 64, the dominant term of the b64 decode cliff),
    where this slab dynamic_update_slice stays in place (~0.1 ms;
    measured 2026-07-31 on one v5e, not re-measured since).

    Uniform-position writes only.  Per-row offsets (speculative
    decoding) keep the per-layer ``_rows_update`` + one final stack:
    scatters addressed through axis 1 of the stacked array compile to
    layouts that cost MORE than the single stack copy (measured —
    speculative throughput dropped 2.8x when routed through a
    batch-axis vmap over the stacked cache).
    """
    zero = jnp.int32(0)
    starts = (jnp.int32(i), zero, pos) + (zero,) * (cache_all.ndim - 3)
    return jax.lax.dynamic_update_slice(
        cache_all, rows.astype(cache_all.dtype)[None], starts)


def chunk_attends_prefix(cfg: TransformerConfig, t_len: int, cache,
                         uniform_pos: bool = True, beam: bool = False,
                         sharded: bool = False) -> bool:
    """Whether :func:`_decode_chunk` compiles the BOUNDED attention
    (``ops.attention.flash_prefix_attention``: the chunk's queries read
    cache slots ``[0, pos0 + T)`` only) for a ``t_len``-token chunk
    against ``cache``, or its dense body over all ``max_len`` slots.

    One static predicate on what the call already holds: a multi-token
    chunk at one position for every row, no beam ancestry, no ring, no
    int8 scales; a TPU backend, one device, shapes the kernel tiles.
    T = 1 (every decode step) and per-row positions (speculative
    verify) are :func:`decode_attends_prefix`'s; windowed, int8 and CPU
    calls keep the dense body, which is also the kernels' oracle in
    tests.  The serving engines ask the same question for their
    admission spans' ``attended`` field."""
    return (uniform_pos and t_len > 1 and not beam
            and cfg.attention_window is None and "k_scale" not in cache
            and bool(cfg.kv_planes)
            and use_flash_prefix(t_len, cfg.max_len, cfg.head_dim,
                                 cfg.n_heads // cfg.kv_heads,
                                 cache["k"].dtype, sharded=sharded))


def decode_attends_prefix(cfg: TransformerConfig, t_len: int, cache,
                          uniform_pos: bool = False,
                          sharded: bool = False) -> bool:
    """Whether :func:`_chunk_in_place` compiles the PER-LANE bounded
    attention (``ops.attention.flash_decode_attention``: lane ``b``
    reads the blocks holding slots ``< pos0[b]`` of its row, straight
    from the slab) or its dense body over all ``max_len`` slots of
    every lane.

    :func:`chunk_attends_prefix`'s sibling, for the calls that one
    leaves: T = 1 (every decode step) and per-row chunks (speculative
    verification).  The same kind of question: no ring, no int8 scales;
    a TPU backend, one device, shapes the kernel tiles (one K/V head or
    whole sublane tiles of them, at most ``DECODE_MAX_ROWS`` query
    rows).  The serving engines ask it for ``serving.step``'s
    ``attended``."""
    return (not (uniform_pos and t_len > 1)
            and cfg.attention_window is None and "k_scale" not in cache
            and bool(cfg.kv_planes)
            and use_flash_decode(t_len, cfg.max_len, cfg.head_dim,
                                 *_decode_heads(cfg),
                                 cache["k"].dtype, sharded=sharded))


def latent_chunk_expands(cfg: TransformerConfig, t_len: int, cache,
                         rows: int = 1, sharded: bool = False) -> bool:
    """:func:`chunk_attends_prefix`'s question of a stack of LATENT
    layers: whether a uniform ``t_len``-token chunk of ``rows`` rows
    attends in the EXPANDED form through
    ``ops.latent.mla_prefix_attention`` (one row — an admission — on a
    TPU, shapes the kernel tiles: a block's keys and values rebuilt in
    VMEM from the slab's rows where they lie, cost by the attended rows
    ``[0, pos0 + T)``), or absorbed in the dense body over all
    ``max_len``.  The serving engines ask it for their admission
    spans' ``attended`` too."""
    return (rows == 1 and t_len > 1 and bool(cfg.latent_planes)
            and use_mla_prefix(
                t_len, cfg.max_len, cfg.qk_nope_head_dim,
                cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.latent_width,
                cfg.kv_lora_rank, cache["lat"].dtype, sharded=sharded))


def latent_decode_bounded(cfg: TransformerConfig, cache,
                          sharded: bool = False) -> bool:
    """:func:`decode_attends_prefix`'s question of a stack of latent
    layers: whether a decode step reads each lane's live blocks through
    ``ops.latent.mla_decode_attention``, or its twin every slot."""
    return bool(cfg.latent_planes) and use_mla_decode(
        cfg.n_heads, cfg.max_len, cfg.latent_width, cfg.kv_lora_rank,
        cache["lat"].dtype, sharded)


def latent_read_unit(cfg: TransformerConfig, cache) -> int:
    """Slots the smallest copy of ``mla_decode_attention`` brings in:
    a lane at position ``p`` reads ``p`` rounded up to it."""
    return mla_decode_block(cfg.n_heads, cfg.max_len, cfg.latent_width,
                            cfg.kv_lora_rank,
                            cache["lat"].dtype) // MLA_TAIL_PARTS


def _decode_heads(cfg: TransformerConfig) -> tuple[int, int]:
    """``(query heads, K/V heads)`` of one row of the per-lane kernel:
    the model's, or — a typed stack's K/V-head-major planes — one K/V
    head a row with the query heads that share it (the kernel then
    sees lanes x K/V heads rows of one head each, which it tiles
    whatever their number)."""
    if cfg.typed:
        return cfg.n_heads // cfg.kv_heads, 1
    return cfg.n_heads, cfg.kv_heads


def decode_read_unit(cfg: TransformerConfig, t_len: int, cache) -> int:
    """Cache slots the smallest copy of the per-lane bounded attention
    brings in (a part of a lane's last block): a lane at position ``p``
    reads ``p`` rounded up to it."""
    return decode_block(t_len, cfg.max_len, cfg.head_dim,
                        *_decode_heads(cfg),
                        cache["k"].dtype) // DECODE_TAIL_PARTS


def _attend_before_and_chunk(q, k, v, ck_all, cv_all, plane, pos0,
                             head_major: bool = False):
    """Attention of ``q [B, T, H, hd]`` over plane ``plane``'s slots
    strictly before ``pos0[b]`` (the kernel, straight from the slab)
    and over the chunk's own ``k``/``v [B, T, kv, hd]`` under their
    causal triangle, as ONE softmax: the two terms are merged by their
    log-sum-exps, ``[B, T, H]`` of elementwise work.  Float32.
    ``head_major``: the slab is ``[P, B, kv, S, hd]`` (a typed stack's)
    and the kernel is handed its free view ``[P, B * kv, S, 1, hd]``:
    a row a (lane, K/V head), of the query heads that share the head."""
    b, t_len, h, d = q.shape
    kv = k.shape[2]
    if head_major:
        planes, _, _, s_len, _ = ck_all.shape
        rows = lambda a: a.reshape(planes, b * kv, s_len, 1, d)
        old, lse = flash_decode_attention(
            q.reshape(b, t_len, kv, h // kv, d).transpose(0, 2, 1, 3, 4)
            .reshape(b * kv, t_len, h // kv, d),
            rows(ck_all), rows(cv_all), plane, jnp.repeat(pos0, kv))
        old = old.reshape(b, kv, t_len, h // kv, d).transpose(0, 2, 1, 3, 4)
        lse = lse.reshape(b, kv, t_len, h // kv).transpose(0, 2, 1, 3)
    else:
        old, lse = flash_decode_attention(q, ck_all, cv_all, plane, pos0)
        old = old.reshape(b, t_len, kv, h // kv, d)
        lse = lse.reshape(b, t_len, kv, h // kv)
    qg = q.astype(jnp.float32).reshape(old.shape)
    new = jnp.einsum("btcgk,buck->btcgu", qg,
                     k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t_len, t_len), bool))[None, :, None, None, :]
    new = jnp.where(causal, new, -1e30)
    m = jnp.maximum(lse, new.max(axis=-1))
    p_new = jnp.exp(new - m[..., None])
    w_old = jnp.exp(lse - m)
    out = (w_old[..., None] * old
           + jnp.einsum("btcgu,buck->btcgk", p_new, v.astype(jnp.float32))
           ) / (w_old + p_new.sum(axis=-1))[..., None]
    return out.reshape(b, t_len, h, d)


def base_body_only(cfg: TransformerConfig, params, cache,
                   beam: bool = False) -> str | None:
    """What keeps a :func:`_decode_chunk` call on the base body, by
    name — or None: the call takes :func:`_chunk_in_place`.  One static
    question on what the call already holds (the serving engines ask
    it of their live state to choose the admission program): a ring, a
    MoE feed-forward, beam ancestry, int8 scales in the cache, int8
    weights, a cache the compiler partitions."""
    if cfg.attention_window is not None:
        return "a windowed (rolling) decode"
    if cfg.num_experts and not cfg.typed:
        return "a MoE feed-forward (num_experts > 0) without ffn_types"
    if beam:
        return "beam search"
    if "k_scale" in cache:
        return "the int8 KV cache (kv_int8)"
    if is_quantized(params):
        return "int8-quantized weights (models/quant)"
    if is_partitioned(cache["k"]):
        return "a partitioned cache (a tensor-parallel serving_plan)"
    return None


def _chunk_in_place(params, cache, tokens, pos0, cfg: TransformerConfig,
                    uniform_pos: bool = False, lane=None, n_real=None,
                    with_routes: bool = False, live=None, chunk=None):
    """:func:`_decode_chunk`'s body for every call that
    :func:`base_body_only` does not hold back: ``tokens [B, T]`` at
    positions ``pos0[b] + (0..T-1)`` -> ``(logits [B, T, V] f32, cache)``
    — of the LAST pass where the stack is looped (``cfg.n_passes``
    applications of the same weights).  It implements the extended
    block (gated feed-forward, sandwich norms, untied head, either
    layout of the attention projections).

    One traced layer body: ``lax.scan`` over the layers inside
    ``lax.scan`` over the passes (192 layer applications of a 48-layer
    stack looped four times are one body, not 192 copies), the final
    norm after every pass.  Pass ``r`` of layer ``l`` reads and writes
    plane ``r * L + l`` of the cache only.

    **The slab is never copied.**  Inside the loops it is read-only: a
    layer attends its plane's slots STRICTLY BEFORE the chunk (mask
    ``slot < pos0[b]``) together with the chunk's own K/V, which it
    holds in registers, under one softmax — the same sum the base body
    takes over a cache it has already written into.  The chunk's K/V
    of every plane leave the loops as scan outputs and are written
    once, at the end, in place (the caller donates the cache): one
    ``dynamic_update_slice`` where every row is at one position, one
    per row (a window over all planes) where rows differ — the decode
    step, and speculative verification.  K/V are rounded to the
    cache's dtype BEFORE the chunk attends them, so a token attends
    itself exactly as later tokens will read it.

    ``lane`` (traced int32; requires B == 1, ``uniform_pos``): the
    chunk is admitted into lane ``lane`` of a slab of many lanes
    without cutting the lane out — each plane's ``[1, S]`` row is
    sliced for reading, the chunk is written at ``[:, lane, pos0]``.

    ``uniform_pos`` multi-token chunks on a TPU attend through
    ``flash_prefix_attention`` (:func:`chunk_attends_prefix`): the
    kernel wants the chunk inside the cache, so the plane's row (not
    the slab) gets it first.  Every other call without ``lane`` — one
    token a row, rows at their own positions — attends on a TPU through
    ``flash_decode_attention`` (:func:`decode_attends_prefix`): the
    kernel is handed the slab and the plane's index, reads of row ``b``
    only the blocks before ``pos0[b]``, and its result is merged with
    the chunk's own term (:func:`_attend_before_and_chunk`) — the dense
    body's sum, without its read of the dead slots.

    ``chunk = (rows [1, C], lane, off)`` (traced; a decode step — one
    token a row, no ``lane`` — of an untyped one-pass stack only): the
    step ALSO admits ``rows`` into lane ``lane`` at ``off``, in the
    same layer body.  The lanes' tokens and the chunk's are embedded
    and laid side by side as ONE row of ``B + C`` positions, so every
    norm, projection and feed-forward product is one product a matrix
    (they are row-wise) and a layer's weights stream once for both.
    Attention splits the rows again — the lanes' as the step above
    attends, the chunk's as an admission with ``lane=`` does — and
    both sets of K/V are written at the end, the chunk's first (where
    the admission before the step would have put it).  The slab is
    read-only inside the loop, so no lane sees the chunk in this call:
    ``lane`` must not decode here (the engine parks it).  The logits
    are the lanes' ``[B, 1, V]``; the chunk's are never computed.

    **A typed stack** (``cfg.typed``: a layer is an (attention kind,
    feed-forward kind) pair) runs the same layer body, one scan a run
    of consecutive layers of one kind over that kind's group of the
    parameter tree; its cache is ``init_cache``'s four K/V-head-major
    leaves.  A FULL layer reads and writes its ``max_len``-slot plane
    as above (the kernels handed the head-major planes).  A WINDOW
    layer's plane is a ring: slot ``s`` holds the latest position
    ``p = s (mod sliding_window)`` before the chunk, so the layer attends
    the ring under the mask ``0 <= p`` and ``i - p < sliding_window``
    — plain ``jax.numpy`` in slot order, the positions worked out from
    ``pos0`` — together with the chunk's own K/V under their band,
    one softmax; the chunk's last ``sliding_window`` REAL positions are
    written modulo the ring at the end.  ``n_real`` (traced int32;
    uniform chunks): how many of the chunk's tokens are real — an
    admission's padding is not written into a ring, where it would
    overwrite live positions (in a full plane it lands past the
    frontier, masked until overwritten).  A row at position
    ``max_len - 1`` is PARKED (free, done or admitting: no live row
    ever stands there, since a request's last token is never fed
    back): its decode step writes the ring's parking slot.  A sparse
    layer is ``transformer.moe_ffn``; ``with_routes`` appends, to the
    result, ``[sparse layers, B, T, k]``: each assignment's index among
    the held experts (their number: an absent expert's).

    A RETENTION layer's plane is a state (``init_cache``), carried
    through the layers' scans and updated in place where it lies.  One
    token a row (``T == 1``, no ``lane``) is the recurrent form: on the
    TPU the kernel ``ret_state_step``, elsewhere the same arithmetic
    (``ops/retention.py``); ``live [B]`` names the rows that decode —
    every other row's state is left as it is, unread.  A chunk is the
    chunked form through the row's (``lane``'s) state — one row at heads
    of 128 on the TPU: the kernel ``ret_chunk_fwd`` on the state where
    it lies; else ``retention_chunk`` on a copy cut out and put back
    (``use_ret_chunk_kernel``) — and ``n_real`` keeps
    an admission's padding out of it.  A row at position 0 starts from
    a zero state whatever its plane holds: a stale state is not masked
    by position as stale slots are.

    A LATENT layer's plane holds one row a position (``init_cache``'s
    ``lat``: the latent after its norm, the shared key after its
    rotation) and no K or V.  One token a row (no ``lane``) attends in
    the ABSORBED form — ``wkv_b``'s key half folded into the queries,
    its value half into the output — each lane its live rows straight
    from the slab, once for scores and values (on the TPU the kernel
    ``ops.latent.mla_decode_attention``, elsewhere its twin), merged
    with the token's own row; ``live [B]`` names the rows that decode:
    every other row reads none of its plane.  One row's chunk on the
    TPU attends EXPANDED (:func:`latent_chunk_expands`): its rows go
    into the slab first, where it lies, and ``mla_prefix_attention``
    rebuilds a block's keys and values in VMEM; every other chunk
    absorbed in the dense body.  Slots as a full plane's: a
    stale one is masked by position, padding lands past the frontier,
    ``n_real`` is not needed."""
    dtype = jnp.dtype(cfg.dtype)
    b, t_len = tokens.shape
    n_layers, s_len = cfg.n_layers, cfg.max_len
    groups = cfg.n_heads // cfg.kv_heads
    if lane is not None and (b != 1 or not uniform_pos):
        raise ValueError("lane= admits ONE row at one position")
    typed = cfg.typed
    if typed and t_len > 1 and not uniform_pos:
        raise ValueError(
            "a typed stack takes multi-token chunks at one position for "
            "every row only (per-row chunks: speculative verification)")
    if chunk is not None:
        if (typed or cfg.n_passes != 1 or t_len != 1 or uniform_pos
                or lane is not None):
            raise ValueError(
                "chunk= rides a decode step (one token a row, no lane=) "
                "of an untyped one-pass stack")
        c_rows, c_lane, c_off = chunk
        c_off = jnp.reshape(c_off, (1,)).astype(jnp.int32)
        c_len = c_rows.shape[1]
        # The stream is one row: the lanes' tokens, then the chunk's.
        # (Its B + C rows are not padded to a tile: 32 + 512 run in
        # 12.62 ms, padded to 640 in 13.60; chip, PR 36.)
        tokens = jnp.concatenate([tokens.reshape(1, b), c_rows], axis=1)
    # [R*L, B|lanes, S, kv, hd]; a typed stack's: [full layers, B|lanes,
    # kv, S, hd] and the rings [window layers, B|lanes, kv, ring + park, hd].
    ck_all, cv_all = cache["k"], cache["v"]
    eps, pre_norm = cfg.norm_eps, cfg.post_norms != "only"
    with jax.named_scope("embed"):
        x = params["tok_emb"][tokens].astype(dtype)           # [B, T, D]
        pos_ids = pos0[:, None] + jnp.arange(t_len)[None, :]  # [B, T]
        if chunk is not None:                                 # [1, B + C]
            pos_ids = jnp.concatenate(
                [pos0[None, :], c_off[:, None] + jnp.arange(c_len)[None, :]],
                axis=1)
        rope_ang = None
        if cfg.rope:
            rope_ang = rope_angles(pos_ids, cfg.rope_dim,
                                   cfg.rope_theta)[:, :, None, :]
        else:
            x = x + params["pos_emb"][pos_ids].astype(dtype)
    sharded = is_partitioned(x)

    def rows_at(pos0, t_len, lane, uniform_pos):
        """How rows of ``t_len`` tokens from ``pos0[b]`` — every lane's,
        or lane ``lane``'s alone — attend: the kernel their shapes
        allow, and the dense body's two masks: cache slots before the
        chunk, and the chunk's own causal triangle ([B, T, kv, g, S | T])."""
        return types.SimpleNamespace(
            pos0=pos0, lane=lane,
            bounded=chunk_attends_prefix(
                cfg, t_len, cache, uniform_pos=uniform_pos, sharded=sharded),
            per_lane=lane is None and decode_attends_prefix(
                cfg, t_len, cache, uniform_pos=uniform_pos, sharded=sharded),
            before=(jnp.arange(s_len)[None, :] < pos0[:, None]
                    )[:, None, None, None, :],
            causal=jnp.tril(jnp.ones((t_len, t_len), bool)
                            )[None, :, None, None, :])

    own = rows_at(pos0, t_len, lane, uniform_pos)
    bounded, per_lane, before, causal = (own.bounded, own.per_lane,
                                         own.before, own.causal)
    if chunk is not None:
        admitted = rows_at(c_off, c_len, c_lane, True)
    scale = 1.0 / jnp.sqrt(jnp.float32(cfg.head_dim))
    if typed and cfg.kv_ring_planes:
        rk_all, rv_all = cache["k_win"], cache["v_win"]
        window = cfg.sliding_window
        # Ring slot s holds position p_s, the latest one = s (mod window)
        # before the chunk (negative: never written by this occupant);
        # query i sees it iff 0 <= p_s and i - p_s < window.
        # The plane is attended whole — a cut of its first ``window``
        # slots would be a copy a layer — with the slots past them
        # (parking) masked like one never written.
        last = pos0[:, None] - 1
        slot = jnp.arange(rk_all.shape[3])[None, :]
        p_s = jnp.where(slot < window,
                        last - jnp.mod(last - slot, window), -1)
        in_ring = ((p_s[:, None, :] >= 0)
                   & (pos_ids[:, :, None] - p_s[:, None, :] < window)
                   )[:, :, None, None, :]       # [B, T, 1, 1, window + park]
        t_ids = jnp.arange(t_len)
        band = causal & (t_ids[:, None] - t_ids[None, :] < window
                         )[None, :, None, None, :]

    def plane_rows(a_all, plane, lane=lane):
        """Plane ``plane``'s rows, for reading: of every row, or of
        lane ``lane`` alone."""
        if lane is not None:
            return jax.lax.dynamic_slice(
                a_all, (plane, lane) + (0,) * (a_all.ndim - 2),
                (1, 1) + a_all.shape[2:])[0]
        return jax.lax.dynamic_index_in_dim(a_all, plane, 0, keepdims=False)

    def attend_plain(q, k, v, plane, rows=own):
        """An untyped stack's attention over its token-major plane
        ``[B, S, kv, hd]`` and the chunk's own K/V, of the rows ``rows``
        describes (:func:`rows_at`; the call's own by default)."""
        b, t_len = q.shape[:2]
        pos0, lane = rows.pos0, rows.lane
        with jax.named_scope("kv_slab"):
            # This plane's rows, for reading (the per-lane kernel takes
            # the slab and the plane's index).
            if lane is not None or not rows.per_lane:
                ck, cv = (plane_rows(a, plane, lane) for a in (ck_all, cv_all))
            if rows.bounded:
                at = (jnp.int32(0), pos0[0], jnp.int32(0), jnp.int32(0))
                ck = jax.lax.dynamic_update_slice(ck, k, at)
                cv = jax.lax.dynamic_update_slice(cv, v, at)
        with jax.named_scope("attn"):
            if rows.per_lane:
                return _attend_before_and_chunk(q, k, v, ck_all, cv_all,
                                                plane, pos0)
            if rows.bounded:
                return flash_prefix_attention(q.astype(ck.dtype), ck, cv,
                                              pos0[0])
            qg = q.astype(jnp.float32).reshape(
                b, t_len, cfg.kv_heads, groups, cfg.head_dim)
            old = jnp.einsum("btcgk,bsck->btcgs", qg,
                             ck.astype(jnp.float32)) * scale
            new = jnp.einsum("btcgk,buck->btcgu", qg,
                             k.astype(jnp.float32)) * scale
            probs = jax.nn.softmax(jnp.concatenate(
                [jnp.where(rows.before, old, -1e30),
                 jnp.where(rows.causal, new, -1e30)], axis=-1), axis=-1)
            return (jnp.einsum("btcgs,bsck->btcgk", probs[..., :s_len],
                               cv.astype(jnp.float32))
                    + jnp.einsum("btcgu,buck->btcgk", probs[..., s_len:],
                                 v.astype(jnp.float32))).reshape(
                b, t_len, cfg.n_heads, cfg.head_dim)

    def attend_round(q, k, v, plane):
        """``chunk=``: the stream's one row split again — the lanes'
        tokens attend as a decode step's rows, the chunk's as an
        admission's one row — and laid side by side once more."""
        by_lane = attend_plain(*(a[0, :b, None] for a in (q, k, v)), plane)
        by_chunk = attend_plain(*(a[:, b:] for a in (q, k, v)), plane,
                                admitted)
        return jnp.concatenate([by_lane.astype(dtype)[None, :, 0],
                                by_chunk.astype(dtype)], axis=1)

    def attend_typed(q, k, v, plane, kind):
        """A typed stack's attention: the plane before the chunk and
        the chunk's own K/V, one softmax (see the docstring)."""
        if kind == "window":
            with jax.named_scope("kv_slab"):
                ck, cv = (plane_rows(a, plane) for a in (rk_all, rv_all))
            keep_old, keep_new = in_ring, band
        else:
            keep_old, keep_new = before, causal
            if per_lane:
                return _attend_before_and_chunk(
                    q, k, v, ck_all, cv_all, plane, pos0, head_major=True)
            with jax.named_scope("kv_slab"):
                ck, cv = (plane_rows(a, plane) for a in (ck_all, cv_all))
                if bounded:
                    at = (jnp.int32(0), jnp.int32(0), pos0[0], jnp.int32(0))
                    ck = jax.lax.dynamic_update_slice(
                        ck, k.transpose(0, 2, 1, 3), at)
                    cv = jax.lax.dynamic_update_slice(
                        cv, v.transpose(0, 2, 1, 3), at)
            if bounded:
                return flash_prefix_attention(q.astype(ck.dtype), ck, cv,
                                              pos0[0], head_major=True)
        # Operands in the cache's dtype, float32 accumulation and
        # softmax, the logits scaled after the product: the kernels'
        # arithmetic (a ring is read whole every step: no float32 copy).
        f32 = dict(preferred_element_type=jnp.float32)
        qg = q.astype(ck.dtype).reshape(
            b, t_len, cfg.kv_heads, groups, cfg.head_dim)
        old = jnp.einsum("btcgk,bcsk->btcgs", qg, ck, **f32) * scale
        new = jnp.einsum("btcgk,buck->btcgu", qg, k, **f32) * scale
        n_old = old.shape[-1]
        probs = jax.nn.softmax(jnp.concatenate(
            [jnp.where(keep_old, old, -1e30),
             jnp.where(keep_new, new, -1e30)], axis=-1), axis=-1
        ).astype(cv.dtype)
        return (jnp.einsum("btcgs,bcsk->btcgk", probs[..., :n_old], cv,
                           **f32)
                + jnp.einsum("btcgu,buck->btcgk", probs[..., n_old:], v,
                             **f32)).reshape(
            b, t_len, cfg.n_heads, cfg.head_dim)

    def attend_state(h, q, k, v, lp, plane, state):
        """A retention layer's attention, through its state."""
        s_all, z_all = state
        fresh = pos0 == 0
        with jax.named_scope("ret_gate"):
            logg = log_gate(h, lp["attn"]["wg"], lp["attn"]["bg"])
        if lane is None and t_len == 1:
            with jax.named_scope("ret_state"):
                ops = step_operands(q[:, 0], k[:, 0], v[:, 0], logg[:, 0],
                                    fresh)
                on = (jnp.ones((b,), jnp.int32) if live is None
                      else live.astype(jnp.int32))
                step = (ret_state_step if use_ret_kernel(
                    cfg.head_dim, groups, s_all.dtype, sharded)
                    else retention_step)
                y, s_all, z_all = step(ops, s_all, z_all, plane, on)
            return (y[:, :, :groups].reshape(b, 1, cfg.n_heads,
                                             cfg.head_dim),
                    (s_all, z_all))
        # Chunks: each row through its own state (one row: the lane's).
        row0 = jnp.int32(0) if lane is None else lane
        if use_ret_chunk_kernel(cfg.head_dim, groups, t_len, b, s_all.dtype,
                                sharded):
            # The state read and written where it lies, by the kernel.
            with jax.named_scope("ret_chunk"):
                y, s_all, z_all = ret_chunk_fwd(
                    q[0].astype(dtype), k[0], v[0], logg[0], s_all, z_all,
                    plane, row0, n_real, fresh[0])
            return y[None], (s_all, z_all)
        at = (plane, row0) + (jnp.int32(0),) * 4
        with jax.named_scope("ret_state"):
            s_in = jax.lax.dynamic_slice(s_all, at, (1, b) + s_all.shape[2:])
            z_in = jax.lax.dynamic_slice(z_all, at[:-1],
                                         (1, b) + z_all.shape[2:])
        real = None if n_real is None else jnp.broadcast_to(n_real, (b,))
        y, s_new, z_new = jax.vmap(
            retention_chunk, in_axes=(0, 0, 0, 0, 0, 0,
                                      None if real is None else 0, 0))(
            q.astype(dtype), k, v, logg, s_in[0], z_in[0], real, fresh)
        with jax.named_scope("ret_state"):
            s_all = jax.lax.dynamic_update_slice(s_all, s_new[None], at)
            z_all = jax.lax.dynamic_update_slice(z_all, z_new[None], at[:-1])
        return y, (s_all, z_all)

    def attend_latent(q_abs, lat, plane):
        """A latent layer's attention in the absorbed form: ``q_abs [B,
        T, H, W]`` float32 (``wkv_b``'s key half folded in, the rotary
        part beside it) over its plane's rows before the chunk and the
        chunk's own ``lat [B, T, W]``, one softmax; ``[B, T, H,
        kv_lora_rank]``: the probabilities over the rows' latent part."""
        lat_all, vals, w = cache["lat"], cfg.kv_lora_rank, cfg.latent_width
        f32 = dict(preferred_element_type=jnp.float32)
        qa = q_abs.astype(lat.dtype)
        if lane is None and t_len == 1:
            # A decode step: each lane its live rows straight from the
            # slab, read once for scores and values (the kernel; off
            # the TPU its twin), merged with the token's own row by
            # their log-sum-exps.  A lane that does not decode
            # (``live``) reads nothing.
            at = pos0 if live is None else jnp.where(live > 0, pos0, 0)
            fn = (mla_decode_attention if use_mla_decode(
                cfg.n_heads, s_len, w, vals, lat.dtype, sharded)
                else mla_decode_twin)
            old, lse = fn(qa[:, 0], lat_all, plane, at, scale=lat_scale,
                          values=vals)
            own = jnp.einsum("bhw,bw->bh", qa[:, 0], lat[:, 0],
                             **f32) * lat_scale
            m = jnp.maximum(lse, own)
            w_old, p_own = jnp.exp(lse - m), jnp.exp(own - m)
            out = (w_old[..., None] * old + p_own[..., None]
                   * lat[:, 0, None, :vals].astype(jnp.float32)
                   ) / (w_old + p_own)[..., None]
            return out[:, None]
        with jax.named_scope("kv_slab"):
            rows = plane_rows(lat_all, plane)              # [B | 1, S, W]
        old = jnp.einsum("bthw,bsw->bths", qa, rows, **f32) * lat_scale
        new = jnp.einsum("bthw,buw->bthu", qa, lat, **f32) * lat_scale
        probs = jax.nn.softmax(jnp.concatenate(
            [jnp.where(before[:, :, 0], old, -1e30),
             jnp.where(causal[:, :, 0], new, -1e30)], axis=-1), axis=-1
        ).astype(lat.dtype)
        return (jnp.einsum("bths,bsv->bthv", probs[..., :s_len],
                           rows[..., :vals], **f32)
                + jnp.einsum("bthu,buv->bthv", probs[..., s_len:],
                             lat[..., :vals], **f32))

    lat_expands = (typed and uniform_pos and latent_chunk_expands(
        cfg, t_len, cache, rows=b, sharded=sharded))
    if typed and cfg.latent_planes:
        lat_scale = 1.0 / float(np.sqrt(cfg.qk_nope_head_dim
                                        + cfg.qk_rope_head_dim))

    def latent_attn(h, lp, plane, slab):
        """A latent layer's attention sublayer over the normed stream:
        ``(its output [B, T, D], the chunk's rows [B, T, W] for the
        plane, the slab)``.  ``slab``: the latent planes where the
        chunk attends EXPANDED (``lat_expands``: one row's chunk on the
        TPU) — its rows are then written into the slab here, in place,
        and the kernel reads the lane's blocks from it, rebuilding
        their keys and values in VMEM; None on the absorbed paths,
        which leave the slab read-only and hand the rows to the end."""
        attn = lp["attn"]
        with jax.named_scope("attn_proj"):
            q_nope, q_pe, c, k_pe = latent_qkv(attn, h, rope_ang, cfg)
            wk, wv = latent_kv_b(attn, cfg)
            # What is cached: the latent after its norm, the key after
            # its rotation, rounded to the cache's dtype BEFORE the
            # chunk attends them.
            to_width = lambda a: jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [
                (0, cfg.latent_width - a.shape[-1])])
            lat = to_width(jnp.concatenate([c, k_pe], axis=-1)
                           ).astype(cache["lat"].dtype)
        if slab is not None:
            row0 = jnp.int32(0) if lane is None else lane
            with jax.named_scope("kv_slab"):
                slab = jax.lax.dynamic_update_slice(
                    slab, lat[None], (plane, row0, pos0[0], jnp.int32(0)))
            with jax.named_scope("attn"):
                o = mla_prefix_attention(
                    q_nope[0], q_pe[0], attn["wkv_b"], slab, plane, row0,
                    pos0[0], scale=lat_scale,
                    rank=cfg.kv_lora_rank)[None].astype(dtype)
            with jax.named_scope("attn_proj"):
                return jnp.einsum(
                    "btk,kd->btd", o.reshape(o.shape[:2] + (-1,)),
                    attn["wo"]), lat, slab
        with jax.named_scope("attn"):
            with jax.named_scope("mla_absorb"):
                q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, wk,
                                   preferred_element_type=jnp.float32)
                q_abs = to_width(jnp.concatenate(
                    [q_lat, q_pe.astype(jnp.float32)], axis=-1))
            o_lat = attend_latent(q_abs, lat, plane)
            with jax.named_scope("mla_absorb"):
                o = jnp.einsum("bthr,rhv->bthv", o_lat.astype(dtype), wv)
        with jax.named_scope("attn_proj"):
            return jnp.einsum("btk,kd->btd", o.reshape(o.shape[:2] + (-1,)),
                              attn["wo"]), lat, None

    def layer(x, lp, plane, kind=None, experts=None, state=None):
        h = _rms_norm(x, lp["ln1_scale"], eps) if pre_norm else x
        if kind is not None and kind[0] == "latent":
            a, lat, state = latent_attn(h, lp, plane, state)
            return layer_tail(x, a, lp, kind, experts, state,
                              () if lat_expands else (lat,))
        with jax.named_scope("attn_proj"):
            if cfg.fused_qkv:
                q, k, v = split_qkv(
                    jnp.einsum("btd,dk->btk", h, lp["attn"]["wqkv"]), cfg)
            else:
                q, k, v = (jnp.einsum("btd,dhk->bthk", h, lp["attn"][w])
                           for w in ("wq", "wk", "wv"))
            if cfg.qk_norm:
                q = _rms_norm(q, lp["attn"]["q_scale"], eps)
                k = _rms_norm(k, lp["attn"]["k_scale"], eps)
            if rope_ang is not None and layer_rotates(cfg, kind):
                q, k = rope_rotate(q, rope_ang), rope_rotate(k, rope_ang)
            k, v = k.astype(ck_all.dtype), v.astype(cv_all.dtype)
        if chunk is not None:
            attn = attend_round(q, k, v, plane)
        elif kind is None:
            attn = attend_plain(q, k, v, plane)
        elif kind[0] == "retention":
            with jax.named_scope("attn"):
                attn, state = attend_state(h, q, k, v, lp, plane, state)
        else:
            with jax.named_scope("attn"):
                attn = attend_typed(q, k, v, plane, kind[0])
        with jax.named_scope("attn_proj"):
            attn = attn.astype(dtype)
            if cfg.fused_qkv:
                a = jnp.einsum("btk,kd->btd",
                               attn.reshape(x.shape[:2] + (-1,)),
                               lp["attn"]["wo"])
            else:
                a = jnp.einsum("bthk,hkd->btd", attn, lp["attn"]["wo"])
        return layer_tail(x, a, lp, kind, experts, state,
                          () if kind is not None
                          and kind[0] == "retention" else (k, v))

    def layer_tail(x, a, lp, kind, experts, state, cached):
        """What follows a layer's attention ``a``: the residual sums
        and the feed-forward; ``cached``: what the layer hands to its
        plane (the scans' outputs)."""
        if cfg.post_norms:
            a = _rms_norm(a, lp["ln1_post_scale"], eps)
        # (The stream keeps the compute dtype whatever the weights'
        # is: a scan's carry cannot widen on the way.)
        with jax.named_scope("attn_proj"):
            x = x + a.astype(dtype)
        h = _rms_norm(x, lp["ln2_scale"], eps) if pre_norm else x
        routes = None
        with jax.named_scope("mlp"):
            if kind is not None and kind[1] == "sparse":
                y, routes = moe_ffn(lp, h, cfg, with_routes=True,
                                    stacked=experts)
            else:
                y = ffn_apply(lp["ffn"], h, cfg)
        if cfg.post_norms:
            y = _rms_norm(y, lp["ln2_post_scale"], eps)
        with jax.named_scope("mlp"):
            x = x + y.astype(dtype)
        if kind is None:
            return x, cached
        return (x, state), cached + (() if routes is None else (routes,))

    def one_pass(x, r):
        x, kv = jax.lax.scan(
            lambda x, lw: layer(x, lw[0], r * n_layers + lw[1]), x,
            (params["layers"], jnp.arange(n_layers)))
        return final_norm(x, params, cfg).astype(dtype), kv

    if typed:
        state = ((cache["s"], cache["z"]) if cfg.state_planes
                 else cache["lat"] if lat_expands else None)
        return _typed_tail(*_typed_runs(params, x, cfg, layer, state),
                           params, cache, pos0, cfg, uniform_pos, lane,
                           n_real, with_routes)
    x, (new_k, new_v) = jax.lax.scan(one_pass, x, jnp.arange(cfg.n_passes))
    with jax.named_scope("head"):
        if chunk is not None:   # the lanes' rows only: [B, 1, D]
            x = x[0, :b, None]
        out = jnp.einsum("btd,vd->btv", x,
                         head_table(params, cfg).astype(dtype))
    with jax.named_scope("kv_slab"):
        # [R, L, B, T, kv, hd] -> planes first, then ONE write in place.
        new_k, new_v = (a.reshape((cfg.kv_planes,) + a.shape[2:])
                        for a in (new_k, new_v))
        zero = jnp.int32(0)
        if chunk is not None:
            # The chunk's [L, 1, C, kv, hd] into its lane, then the
            # lanes' [L, B, 1, kv, hd] a row below.
            at = (zero, c_lane, c_off[0], zero, zero)
            ck_all = jax.lax.dynamic_update_slice(ck_all, new_k[:, :, b:], at)
            cv_all = jax.lax.dynamic_update_slice(cv_all, new_v[:, :, b:], at)
            new_k, new_v = (a[:, 0, :b, None] for a in (new_k, new_v))
        if uniform_pos:
            at = (zero, zero if lane is None else lane, pos0[0], zero, zero)
            ck_all = jax.lax.dynamic_update_slice(ck_all, new_k, at)
            cv_all = jax.lax.dynamic_update_slice(cv_all, new_v, at)
        else:
            # One window over all planes per row, unrolled: a
            # ``fori_loop`` over the rows with both slabs as carry
            # trips a RET_CHECK in the TPU compiler (AOT, PR 27).
            for i in range(b):
                at = (zero, jnp.int32(i), pos0[i], zero, zero)
                ck_all = jax.lax.dynamic_update_slice(
                    ck_all, new_k[:, i:i + 1], at)
                cv_all = jax.lax.dynamic_update_slice(
                    cv_all, new_v[:, i:i + 1], at)
    return out.astype(jnp.float32), {"k": ck_all, "v": cv_all}


def _typed_runs(params, x, cfg: TransformerConfig, layer, state=None):
    """A typed stack's layers over the stream ``x``: one ``lax.scan`` a
    run of consecutive layers of one kind, over that kind's slice of
    its group's stacked leaves (the whole group where the kind makes
    one run).  ``state``: the retention layers' state slabs ``(s,
    z)``, carried through every scan beside the stream and updated
    where they lie (None: no such layer).  Returns ``(x, new)``:
    ``new[kind]`` the runs' scan outputs of the attention kinds
    ``"full"`` and ``"window"`` — ``(k, v)`` — and ``"latent"`` — the
    rows, unless the layers wrote them into ``state`` (then the latent
    slab) themselves — ``new["routes"]`` the sparse runs' routes, each
    a list of leaves stacked by layer, and ``new["state"]`` the slabs
    after the last layer."""
    new = {"full": [], "window": [], "latent": [], "routes": []}
    planes = {"full": 0, "window": 0, "retention": 0, "latent": 0}
    for group, first, count in cfg.layer_runs:
        kind = tuple(group.split("."))
        leaves, heavy = params["layers"][group], None
        if kind[1] == "sparse":
            # The experts' weights stay out of the scanned leaves: a
            # grouped product takes the group's whole stack and the
            # layer's index (``transformer.moe_held_experts``).
            moe = dict(leaves["moe"])
            heavy = (moe.pop("w13"), moe.pop("w2"))
            leaves = {**leaves, "moe": moe}
        if (first, count) != (0, cfg.layer_kinds.count(kind)):
            leaves = jax.tree.map(lambda a: a[first:first + count], leaves)
        p0 = planes[kind[0]]
        planes[kind[0]] += count
        (x, state), outs = jax.lax.scan(
            lambda c, lw: layer(c[0], lw[0], p0 + lw[1], kind,
                                heavy and heavy + (first + lw[1],), c[1]),
            (x, state), (leaves, jnp.arange(count)))
        if kind[0] == "latent":
            if len(outs) > (kind[1] == "sparse"):   # not written in place
                new["latent"].append(outs[0])
        elif kind[0] != "retention":
            new[kind[0]].append(outs[:2])
        if kind[1] == "sparse":
            new["routes"].append(outs[-1])
    new["state"] = state
    return x, new


def _typed_tail(x, new, params, cache, pos0, cfg: TransformerConfig,
                uniform_pos, lane, n_real, with_routes):
    """What follows a typed stack's layers in :func:`_chunk_in_place`:
    the final norm and the head, then the chunk's K/V written in place
    — into the full planes at their positions, into the rings modulo
    ``sliding_window`` (see that docstring for ``n_real`` and parking)."""
    dtype = jnp.dtype(cfg.dtype)
    b, t_len = x.shape[:2]
    ring = cfg.sliding_window
    x = final_norm(x, params, cfg).astype(dtype)
    with jax.named_scope("head"):
        out = jnp.einsum("btd,vd->btv", x,
                         head_table(params, cfg).astype(dtype))
    cache = dict(cache)
    if new["state"] is not None:   # written where they lay, layer by layer
        if cfg.state_planes:
            cache["s"], cache["z"] = new["state"]
        else:
            cache["lat"] = new["state"]
    zero = jnp.int32(0)
    row0 = zero if lane is None else lane
    with jax.named_scope("kv_slab"):
        for kind, names in (("full", ("k", "v")),
                            ("window", ("k_win", "v_win"))):
            if not new[kind]:
                continue
            # Where a row's chunk starts in its plane: its position, or
            # in a ring its slot (a parked row's: the parking slot).
            start = pos0 if kind == "full" else jnp.where(
                pos0 >= cfg.max_len - 1, ring, jnp.mod(pos0, ring))
            for name, i in zip(names, (0, 1)):
                # [layers of the kind, B, T, kv, hd] -> head-major rows.
                a = jnp.concatenate([run[i] for run in new[kind]], axis=0
                                    ).transpose(0, 1, 3, 2, 4)
                slab = cache[name]
                if kind == "window" and uniform_pos:
                    # Slot s takes the latest REAL position of the chunk
                    # that is = s (mod ring), or keeps what it holds.
                    end = pos0[0] + (t_len if n_real is None else n_real)
                    cand = (end - 1) - jnp.mod(
                        end - 1 - jnp.arange(ring), ring)
                    take = (cand >= pos0[0])[None, None, None, :, None]
                    at = (zero, row0, zero, zero, zero)
                    held = jax.lax.dynamic_slice(
                        slab, at, a.shape[:3] + (ring,) + a.shape[4:])
                    picked = jnp.take(
                        a, jnp.clip(cand - pos0[0], 0, t_len - 1), axis=3)
                    slab = jax.lax.dynamic_update_slice(
                        slab, jnp.where(take, picked, held), at)
                elif uniform_pos:
                    slab = jax.lax.dynamic_update_slice(
                        slab, a, (zero, row0, zero, start[0], zero))
                else:  # one window over all planes per row, unrolled
                    for r in range(b):
                        slab = jax.lax.dynamic_update_slice(
                            slab, a[:, r:r + 1],
                            (zero, jnp.int32(r), zero, start[r], zero))
                cache[name] = slab
        if new["latent"]:
            # [latent layers, B, T, W] at the rows' positions: a stale
            # slot is masked by position until it is written, so a new
            # occupant clears nothing and an admission's padding lands
            # past the frontier, as in a full plane (where a state says
            # the opposite on both counts).
            a, slab = jnp.concatenate(new["latent"], axis=0), cache["lat"]
            if uniform_pos:
                slab = jax.lax.dynamic_update_slice(
                    slab, a, (zero, row0, pos0[0], zero))
            else:
                for r in range(b):
                    slab = jax.lax.dynamic_update_slice(
                        slab, a[:, r:r + 1],
                        (zero, jnp.int32(r), pos0[r], zero))
            cache["lat"] = slab
    if with_routes:
        return (out.astype(jnp.float32), cache,
                jnp.concatenate(new["routes"], axis=0))
    return out.astype(jnp.float32), cache


def _decode_chunk(params, cache, tokens, pos0, cfg: TransformerConfig,
                  uniform_pos: bool = False, beam_anc=None, lane=None,
                  n_real=None, with_routes: bool = False, live=None,
                  chunk=None):
    """Process T new tokens per row against the cache in ONE pass:
    ``tokens [B, T]`` at global positions ``pos0[b] + (0..T-1)`` ->
    ``(logits [B, T, V] f32, cache)``.

    The chunked generalization of :func:`_decode_step` (T = 1 is the
    same math): queries attend every cached position <= their own
    global position — in-chunk causality included — and the chunk's
    K/V land in the cache at per-row offsets, so rows at different
    positions (speculative decoding's per-row accept divergence) share
    one compiled program.

    Two bodies.  Every PLAIN call — no ring, MoE, beam ancestry, int8
    cache or weights, partitioned cache (:func:`base_body_only`) —
    takes :func:`_chunk_in_place`: one traced layer under ``lax.scan``,
    the slab written in place, the extended block and the pass loop of
    a looped stack.  The rest of this docstring is the BASE block's
    body below, which keeps those features and nothing else.

    Windowed (``attention_window``) configs run in three shapes:
    (a) the per-row path with T == 1 — each row writes its ring slot
    ``pos0[b] % max_len`` and attends under the per-row band mask,
    which is the rolling-decode arithmetic vectorized over rows at
    DIFFERENT positions (the serving engine's decode step); (b) the
    uniform_pos chunk path under the caller contract that the chunk
    does not wrap (``pos0[0] % max_len + T <= max_len`` — admission
    prefills satisfy it by bucket construction; unverifiable here
    because pos0 is traced); (c) per-row MULTI-token chunks (round-5,
    speculative decoding on a ring): writes go through a modular
    scatter that may wrap mid-chunk, guarded by
    ``T + window <= max_len`` so in-chunk future positions and
    rejected-tail garbage always alias OUTSIDE every live query's
    band.  Windowed x kv_int8 composes on all three shapes: the scale
    slabs take the same ring-slot updates as the K/V they scale
    (round-5; parity vs the bf16-cache run in tests/test_serving.py
    and test_generate.py).

    Stale cache slots beyond a row's final position are harmless by
    construction: the position mask excludes them (for ring caches the
    band-mask's implied-position formula sends slots the row has not
    reached to negative positions), and every slot is rewritten before
    the row's position passes it.

    ``uniform_pos`` (static): promise that every row of ``pos0`` holds
    the same value, so the cache write is one slab update instead of a
    per-row scatter (see _layer_slab_update).  The plain decode loop
    and prefix warm-up qualify; speculative decoding (per-row accept
    divergence) does not.

    ``beam_anc = (anc [B/W, W, S] int32, W)``: beam-search ancestry
    attention (requires T == 1, uniform_pos, no window).  Rows are
    beam lanes (batch-major tiling b*W + w); each lane writes its own
    cache lane in place, and attention resolves lane ``w``'s history
    through ``anc`` — position ``s`` is read from lane ``anc[b, w,
    s]`` — by computing every (query-lane, source-lane) score and
    folding a one-hot of ``anc`` into the softmax/PV einsums.  The
    cache is read ONCE per step with no beam-reorder rewrite; the
    price is score intermediates of ``B/W x W^2 x n_heads x S`` f32
    per layer — ~4 MB at the benched config (b8 W4 S1025 H8), but
    quadratic in beam width (b64 W8 S2048 H16 would be ~1 GB/layer;
    at that scale revisit before trusting this path).  This replaced
    the physical parent-gather of the cache, which cost more than the
    whole attention read (measured 2026-07-31 on one v5e, not
    re-measured since).
    """
    why = base_body_only(cfg, params, cache, beam=beam_anc is not None)
    if why is None:
        # Every plain call: one traced layer under scans, the slab
        # written in place.  What follows is the BASE block's body for
        # what that one does not express (the docstring above).
        return _chunk_in_place(params, cache, tokens, pos0, cfg,
                               uniform_pos=uniform_pos, lane=lane,
                               n_real=n_real, with_routes=with_routes,
                               live=live, chunk=chunk)
    reject_extended(cfg, why)
    if lane is not None or chunk is not None:
        raise ValueError(f"lane= (in-place admission into one lane of a "
                         f"slab) and chunk= (an admission inside a decode "
                         f"step) do not compose with {why}: cut the "
                         "lane out of the slab")
    dtype = jnp.dtype(cfg.dtype)
    b, t_len = tokens.shape
    with jax.named_scope("embed"):
        x = embed_rows(params["tok_emb"], tokens, dtype)      # [B, T, D]
        pos_ids = pos0[:, None] + jnp.arange(t_len)[None, :]  # [B, T]
        rope_ang = None
        if cfg.rope:
            rope_ang = rope_angles(pos_ids, cfg.head_dim,
                                   cfg.rope_theta)[:, :, None, :]
        else:
            x = x + params["pos_emb"][pos_ids].astype(dtype)

    kv_q = "k_scale" in cache                   # int8 KV cache
    win = cfg.attention_window is not None
    bounded = chunk_attends_prefix(
        cfg, t_len, cache, uniform_pos=uniform_pos,
        beam=beam_anc is not None, sharded=is_partitioned(x))
    if (win and t_len > 1 and not uniform_pos
            and t_len + cfg.attention_window > cfg.max_len):
        # A per-row ring chunk may WRAP, and then two invariants need
        # chunk + window <= max_len: an in-chunk future position
        # q > t wrapped onto slot q % C must alias to implied position
        # q - C with delta C - (q - t) >= window (masked), and a
        # speculative chunk's rejected-tail garbage must never fall
        # inside a live query's band (speculative._validate states the
        # same bound with T = n_draft + 1).  uniform_pos chunks are
        # exempt: their no-wrap caller contract keeps every future
        # slot at implied position q - C < 0, masked unconditionally.
        raise ValueError(
            f"windowed per-row chunk of {t_len} tokens + "
            f"attention_window={cfg.attention_window} exceeds the ring "
            f"size (max_len={cfg.max_len}); shrink the chunk or grow "
            "the ring")
    ck_all, cv_all = cache["k"], cache["v"]     # [L, B, S, kv, hd]
    if kv_q:
        cks_all, cvs_all = cache["k_scale"], cache["v_scale"]
        new_ks, new_vs = [], []
    new_k, new_v = [], []                       # per-row path accumulates
    span = jnp.arange(cfg.max_len)
    if win:
        # Ring band mask, per row (see _decode_step's windowed body for
        # the slot->implied-position derivation; here pos differs per
        # row/chunk position).
        delta = jnp.mod(pos_ids[:, :, None] - span[None, None, :],
                        cfg.max_len)
        mask = ((delta < cfg.attention_window)
                & (pos_ids[:, :, None] - delta >= 0)
                )[:, :, None, None, :]            # [B, T, 1, 1, S]
        wr_pos = pos0 % cfg.max_len               # ring write slots
    else:
        mask = (span[None, None, :] <= pos_ids[:, :, None]
                )[:, :, None, None, :]            # [B, T, 1, 1, S]
        wr_pos = pos0
    # [B, S, C] scale -> broadcast over the [B, T, C, G, S] logits.
    sc_b = lambda s: s.transpose(0, 2, 1)[:, None, :, None, :]
    if beam_anc is not None:
        anc, w_beams = beam_anc
        if t_len != 1 or not uniform_pos or cfg.attention_window:
            raise ValueError("beam ancestry attention requires T == 1, "
                             "uniform positions, and no window")
        # One-hot over source lanes, f32 for the einsum contractions.
        anc_oh = jax.nn.one_hot(anc, w_beams, dtype=jnp.float32)
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = _rms_norm(x, lp["ln1_scale"])
        with jax.named_scope("attn_proj"):
            q = jnp.einsum("btd,dhk->bthk", h, deq(lp["attn"]["wq"]))
            k = jnp.einsum("btd,dhk->bthk", h, deq(lp["attn"]["wk"]))
            v = jnp.einsum("btd,dhk->bthk", h, deq(lp["attn"]["wv"]))
            if rope_ang is not None:
                q, k = rope_rotate(q, rope_ang), rope_rotate(k, rope_ang)
            if kv_q:  # post-rotation, like the bf16 cache
                k, k_s = quantize_kv(k)
                v, v_s = quantize_kv(v)
        with jax.named_scope("kv_slab"):
            if uniform_pos:
                ck_all = _layer_slab_update(ck_all, i, k, wr_pos[0])
                cv_all = _layer_slab_update(cv_all, i, v, wr_pos[0])
                ck, cv = ck_all[i], cv_all[i]
                if kv_q:
                    cks_all = _layer_slab_update(cks_all, i, k_s, wr_pos[0])
                    cvs_all = _layer_slab_update(cvs_all, i, v_s, wr_pos[0])
                    cks, cvs = cks_all[i], cvs_all[i]
            else:
                if win and t_len > 1:
                    # A multi-token ring chunk at divergent row positions
                    # can wrap mid-chunk: modular per-element scatter.
                    upd = lambda c, r: _rows_update_ring(c, r, pos0,
                                                         cfg.max_len)
                else:
                    upd = lambda c, r: _rows_update(c, r, wr_pos)
                ck = upd(ck_all[i], k)
                cv = upd(cv_all[i], v)
                new_k.append(ck)
                new_v.append(cv)
                if kv_q:
                    cks = upd(cks_all[i], k_s)
                    cvs = upd(cvs_all[i], v_s)
                    new_ks.append(cks)
                    new_vs.append(cvs)

        with jax.named_scope("attn"):
            groups = cfg.n_heads // cfg.kv_heads
            qg = q.astype(jnp.float32).reshape(
                b, t_len, cfg.kv_heads, groups, cfg.head_dim)
            if bounded:
                # The chunk is in the cache already (kv_slab above), so
                # one mask covers prefix and chunk; slots past
                # pos0 + T are never read.
                attn = flash_prefix_attention(q.astype(ck.dtype), ck, cv,
                                              pos0[0])
            elif beam_anc is not None:
                # Ancestry attention (shared body: _ancestry_attend) — the
                # cache is read once, W x the (tiny) decode attention
                # FLOPs, and the one-hot selects each position's true
                # ancestor lane.
                bt = b // w_beams
                mask_b = mask[:, 0, 0, 0, :].reshape(bt, w_beams,
                                                     cfg.max_len)
                attn = _ancestry_attend(
                    qg[:, 0], ck, cv, anc_oh, mask_b, cfg, w_beams,
                    kv_scales=(cks, cvs) if kv_q else None,
                )[:, None]  # restore T = 1
            else:
                logits = jnp.einsum("btcgk,bsck->btcgs", qg,
                                    ck.astype(jnp.float32))
                if kv_q:
                    logits = logits * sc_b(cks)
                logits = logits / jnp.sqrt(jnp.float32(cfg.head_dim))
                logits = jnp.where(mask, logits, -1e30)
                probs = jax.nn.softmax(logits, axis=-1)
                attn = jnp.einsum("btcgs,bsck->btcgk",
                                  probs * sc_b(cvs) if kv_q else probs,
                                  cv.astype(jnp.float32)).reshape(
                    b, t_len, cfg.n_heads, cfg.head_dim)
        with jax.named_scope("attn_proj"):
            x = x + jnp.einsum("bthk,hkd->btd", attn.astype(dtype),
                               deq(lp["attn"]["wo"]))

        h = _rms_norm(x, lp["ln2_scale"])
        with jax.named_scope("mlp"):
            if cfg.num_experts and t_len > 1:
                # Multi-token chunks take the batched dense-routing block
                # (all experts on all tokens, one-hot combine): peak memory
                # is [B, T, E, F] ACTIVATIONS, where the per-token weight
                # gather below would materialize B*T*k copies of the [D, F]
                # expert mats — GBs per layer at warm-chunk T.  Same math
                # (_moe_gates shared), same decode-parity semantics.
                y = _moe_dense_block(lp["moe"], h, cfg)
            elif cfg.num_experts:
                # T = 1 (the decode step): gather the k selected experts'
                # slabs per row — fewer HBM bytes than all E at small
                # batch, which is what the bandwidth-bound loop wants.
                router = jnp.einsum("btd,de->bte", h.astype(jnp.float32),
                                    lp["moe"]["wg"])
                gates, expert = _moe_gates(jax.nn.softmax(router, -1), cfg)
                w1 = lp["moe"]["w1"][expert]          # [B, T, k, D, F]
                w2 = lp["moe"]["w2"][expert]
                hk = jax.nn.gelu(jnp.einsum("btd,btkdf->btkf", h,
                                            w1.astype(dtype)))
                yk = jnp.einsum("btkf,btkfd->btkd", hk, w2.astype(dtype))
                y = jnp.einsum("btkd,btk->btd", yk, gates.astype(dtype))
            else:
                y = jnp.einsum(
                    "btf,fd->btd",
                    jax.nn.gelu(jnp.einsum("btd,df->btf", h,
                                           deq(lp["ffn"]["w1"]))),
                    deq(lp["ffn"]["w2"]))
            x = x + y

    x = _rms_norm(x, params["ln_f_scale"])
    with jax.named_scope("head"):
        out = unembed_logits(x, params["tok_emb"], dtype)
    if not uniform_pos:
        with jax.named_scope("kv_slab"):
            ck_all, cv_all = jnp.stack(new_k), jnp.stack(new_v)
            if kv_q:
                cks_all, cvs_all = jnp.stack(new_ks), jnp.stack(new_vs)
    cache = {"k": ck_all, "v": cv_all}
    if kv_q:
        cache["k_scale"], cache["v_scale"] = cks_all, cvs_all
    return out.astype(jnp.float32), cache


def top_k_mask(logits, k: int, exact: bool = False):
    """Keep the k highest logits per row; the rest go to -inf.

    Static ``k`` (a Python int): the mask is a compare against the k-th
    value from a top-k reduction — no dynamic shapes, scan/jit
    friendly.

    By default the k-th value comes from ``lax.approx_max_k`` (recall
    0.99): on TPU the exact ``lax.top_k`` over a [B, 32k] vocab costs
    more than the whole rest of a decode step (~7.8 ms vs 0.7 ms at
    batch 64; measured 2026-07-31 on one v5e, not re-measured since),
    while the approximate threshold misidentifies only logits in a ~1%
    band around the k-th value — sampling-support noise far below the
    sampling noise itself.  Pass ``exact=True`` (or
    ``generate(..., exact_top_k=True)``) to restore the exact
    semantics of releases before round 3.
    """
    if k < 1:
        raise ValueError(f"top_k must be >= 1, got {k}")
    if exact or k > logits.shape[-1] // 2:
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
    else:
        kth = jax.lax.approx_max_k(logits, k, recall_target=0.99,
                                   aggregate_to_topk=True)[0][..., -1:]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _validate_unit_interval(name, p, zero_ok: bool = False):
    """Range-check a sampling filter value whenever it is CONCRETE —
    scalars and per-row arrays alike; only tracers pass through (their
    values are validated by the caller: the serving engine's
    submit/constructor, generate's argument checks).

    Round-6 fix: non-scalar concrete values used to skip validation
    entirely, so a direct ``top_p_mask``/``min_p_mask`` caller with an
    out-of-range array (e.g. a negative min_p) got silent NaN masking
    instead of an error.  ``zero_ok`` admits 0.0 in per-row ARRAYS
    only — the serving engines' explicit "no min-p filter" slot value
    (log 0 = -inf keeps every token); a scalar 0.0 stays an error (the
    scalar no-op spelling is None), and top_p keeps the open lower
    bound everywhere (a 0.0 nucleus would mask every token).
    """
    if isinstance(p, jax.core.Tracer):
        return
    vals = np.asarray(p)
    zero_ok = zero_ok and vals.ndim > 0
    lo_ok = (vals >= 0.0) if zero_ok else (vals > 0.0)
    if not np.all(lo_ok & (vals <= 1.0)):
        lo = "[0, 1]" if zero_ok else "(0, 1]"
        raise ValueError(
            f"{name} must be in {lo}, got "
            f"{p if np.ndim(p) == 0 else vals}")


def min_p_mask(logits, min_p):
    """Keep tokens whose probability is at least ``min_p`` times the
    top token's probability; the rest go to -inf.

    The entropy-adaptive filter (min-p sampling): permissive when the
    model is uncertain (flat distribution -> many tokens clear the
    relative bar), strict when confident.  Static shapes; the top token
    always survives (ratio 1 >= min_p).

    ``min_p`` may be a per-row ``[B, 1]`` array (the serving engine's
    per-request path); a row of 0.0 is a no-op (log 0 = -inf keeps
    everything).  Concrete values are range-checked here (arrays
    [0, 1]; scalars (0, 1] — the scalar no-op spelling is None);
    traced values are validated by the caller.
    """
    _validate_unit_interval("min_p", min_p, zero_ok=True)
    # log p_i - log p_max >= log(min_p), computed on logits directly
    # (the softmax normalizer cancels in the difference).
    gap = logits - logits.max(axis=-1, keepdims=True)
    return jnp.where(gap >= jnp.log(min_p), logits, -jnp.inf)


def top_p_mask(logits, p: float):
    """Nucleus filtering: keep the smallest set of tokens whose
    probability mass reaches ``p``; the rest go to -inf.

    Sort-based with an exclusive cumulative sum, so the top token is
    always kept (exclusive mass 0 < p) — static shapes throughout.

    ``p`` may be a per-row ``[B, 1]`` array (the serving engine's
    per-request path); a row of 1.0 is a no-op.  Concrete values —
    scalar or array — are range-checked here ((0, 1]); traced values
    are validated by the caller.
    """
    _validate_unit_interval("top_p", p)
    sl = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
    probs = jax.nn.softmax(sl, axis=-1)
    exclusive = jnp.cumsum(probs, axis=-1) - probs
    keep = exclusive < p
    thr = jnp.min(jnp.where(keep, sl, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(logits < thr, -jnp.inf, logits)


def _device_tree(params):
    """Coerce a host-numpy tree (load_lm output) to jnp leaves: a raw
    numpy leaf cannot be fancy-indexed by the scan's traced tokens
    (TracerArrayConversionError); asarray is a no-op for leaves already
    on device, so placed/sharded trees pass through untouched."""
    return jax.tree.map(jnp.asarray, params)


def rolling_eligible(cfg: TransformerConfig) -> bool:
    """Can this config decode past ``max_len`` on the ring-buffer
    cache?  Rope (positions beyond max_len have no learned-table
    embedding) + a window that fits the ring.  The ONE definition —
    generate/beam_search budgets and the serving engine's rolling-lane
    gate must never drift (the engine's contract is exact parity with
    solo runs)."""
    return (cfg.rope and cfg.attention_window is not None
            and cfg.attention_window <= cfg.max_len)


def _check_decode_budget(p: int, max_new_tokens: int,
                         cfg: TransformerConfig,
                         eos_token: int | None,
                         rolling_ok: bool = False) -> int:
    """Shared prompt/length/eos validation for generate and beam_search;
    returns ``total``.

    ``rolling_ok``: a rope + attention_window config decodes past
    ``max_len`` on a ring-buffer cache (the window must fit the cache),
    so the total-length cap is waived for eligible callers.
    """
    if p < 1:
        raise ValueError(
            "prompt must contain at least one token (decoding starts from "
            "its last position; pass a BOS token for unconditional samples)")
    total = p + max_new_tokens
    rolling = rolling_ok and rolling_eligible(cfg)
    if total > cfg.max_len and not rolling:
        raise ValueError(
            f"prompt ({p}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_len={cfg.max_len}" + (
                "" if cfg.attention_window is None or not cfg.rope else
                " (rolling decode past max_len needs rope=True, an "
                "attention_window <= max_len, and a uniform-length "
                "generate() or beam_search() call without "
                "prompt_cache)"))
    _check_eos(eos_token, cfg)
    return total


def _check_eos(eos_token, cfg: TransformerConfig) -> None:
    """ONE eos_token range check — generate, beam_search, and
    speculative_generate share it (duplicates drift)."""
    if eos_token is not None and not 0 <= eos_token < cfg.vocab_size:
        raise ValueError(
            f"eos_token must be in [0, vocab_size={cfg.vocab_size}), "
            f"got {eos_token}")


def _resolve_prefill(params, cfg: TransformerConfig, p: int,
                     use_prefill: bool | None, ragged: bool) -> bool:
    """Shared prefill-eligibility rule (ONE definition: generate and
    beam_search must not drift)."""
    can = (not ragged and 1 < p <= cfg.max_len
           and not is_quantized(params))
    if use_prefill is None:
        return can
    if use_prefill and not can:
        raise ValueError(
            "use_prefill=True needs a uniform-length (no prompt_lengths) "
            "prompt of >= 2 tokens that fits the cache (p <= max_len; "
            "longer rolling prompts teacher-force sequentially) and "
            "full-precision params (the batched prefill forward wants "
            "the training weights — quantize for decode-heavy work)")
    return use_prefill


def _resolve_prompt_cache(prompt_cache, cfg, b, p, max_new_tokens,
                          kv_int8, use_prefill):
    """ONE definition of the prompt_cache contract (generate and
    beam_search must not drift): validates the config/budget/
    quantization/batch constraints and returns ``(cache, cached_len)``
    with a batch-1 prefix fanned out to ``b`` rows."""
    pc_cache, cached_len = prompt_cache
    reject_extended(cfg, "prompt_cache (a prefilled shared prefix)")
    if cfg.attention_window is not None:
        raise ValueError("prompt_cache requires a full-cache config "
                         "(no attention_window)")
    if use_prefill is not None:
        raise ValueError(
            "use_prefill has no effect with prompt_cache (the suffix "
            "always runs as one chunked pass); drop the argument")
    if cached_len < 1:
        raise ValueError(
            f"cached prefix length must be >= 1, got {cached_len} "
            "(an empty prefix is just a plain call)")
    if cached_len > cfg.max_len - p - max_new_tokens:
        raise ValueError(
            f"cached prefix length {cached_len} + prompt {p} + "
            f"{max_new_tokens} new tokens must fit max_len="
            f"{cfg.max_len}")
    if ("k_scale" in pc_cache) != kv_int8:
        raise ValueError(
            "prompt_cache quantization must match kv_int8= (build "
            "the prefix cache with prefill(..., kv_int8=...))")
    pcb = pc_cache["k"].shape[1]
    if pcb == b:
        return pc_cache, cached_len
    if pcb == 1:
        # Shared prefix (e.g. a system prompt) prefilled once at
        # batch 1, fanned out per request.
        return jax.tree.map(
            lambda a: jnp.repeat(a, b, axis=1), pc_cache), cached_len
    raise ValueError(
        f"prompt_cache batch {pcb} incompatible with prompt "
        f"batch {b} (must match or be 1)")


def generate(params, prompt, cfg: TransformerConfig, max_new_tokens: int,
             temperature: float = 0.0, key=None,
             top_k: int | None = None, top_p: float | None = None,
             min_p: float | None = None,
             prompt_lengths=None, eos_token: int | None = None,
             use_prefill: bool | None = None,
             exact_top_k: bool = False, kv_int8: bool = False,
             prompt_cache=None):
    """Decode ``max_new_tokens`` past ``prompt [B, P]``; returns [B, P+N].

    Prefill/decode split: uniform-length prompts run through
    :func:`prefill` (one batched flash-attention forward fills the
    whole cache — MoE configs use decode-parity dense routing) and the
    scan covers only generation positions; ragged prompts fall back to
    teacher-forcing every prompt position through the cached step.
    ``use_prefill`` overrides the automatic choice (True raises if the
    config cannot prefill).
    temperature == 0 is greedy argmax; with temperature
    > 0, ``top_k``, ``top_p`` (nucleus) and/or ``min_p`` restrict the
    sampling support — all applied to the temperature-scaled logits in
    that order (top-k, then nucleus, then the min-p relative-
    probability floor), the standard composition.  ``top_k`` uses the
    approximate-threshold mask by default (round-3 change — see
    top_k_mask: exact lax.top_k costs more than the rest of the decode
    step at large vocab); ``exact_top_k=True`` restores the exact
    support.  ``top_p=1.0`` / ``min_p=0.0`` are the explicit "no
    filter" values (identical to None, and legal even on greedy
    calls; round-6 change) — the same contract as the serving
    engines' ``submit``, so parameters accepted by a served request
    replay solo exactly.

    ``prompt_cache=(cache, cached_len)`` reuses a prefilled prefix —
    the system-prompt pattern: ``prefill`` the shared prefix once (at
    the request batch or batch 1, which fans out), then pass each
    request's remaining prompt here.  The suffix is processed in ONE
    chunked pass against the existing cache, and emitted tokens match
    the concatenated-prompt run exactly (sampling is position-keyed,
    so even sampled streams agree).  Full-cache configs only; the
    cache's quantization must match ``kv_int8``.  Returns [B, p + N]
    (the prefix tokens are the caller's already).

    PRNG stream contract (changed in round 2): the key for position
    ``pos`` is ``jax.random.fold_in(key, pos)`` — a pure function of
    (key, position) — NOT the earlier sequential ``jax.random.split``
    chain.  This makes the prefill and all-sequential paths sample
    identically (the prefill scan skips prompt positions), at the cost
    that a given ``key`` emits different tokens than the pre-fold_in
    release; seed-pinned downstream tests should re-pin.

    ``eos_token`` makes completion sticky: once a row emits it, every
    later generated slot in that row is ``eos_token`` (static shapes —
    the scan always runs ``max_new_tokens`` positions; trim on the
    host).  Ragged batches: pass right-padded prompts plus
    ``prompt_lengths [B]`` (1 <= L_i <= P).  Rows are internally left-aligned at their
    ends (per-row roll), pad slots are masked out of attention and
    position ids count from each row's true start, so every row decodes
    exactly as it would alone; the result returns in the input layout —
    row i carries its L_i prompt tokens, then its N generated tokens,
    then the original padding.

    MoE caveat: decode-time routing is dense top-k *without* expert
    capacity, so logits diverge from the TRAINING forward
    (``transformer.apply`` default routing) for any token the training
    router would capacity-drop.  The matching batched semantics is
    ``apply(..., moe_dense_routing=True)`` / ``lm_nll(...,
    moe_dense_routing=True)`` — exact decode parity at any capacity
    factor (tested at 1.25); the measured capacity-vs-dense NLL gap on
    a trained model is bounded in
    tests/test_generate.py::test_moe_capacity_vs_dense_divergence_bounded.
    """
    params = _device_tree(params)
    b, p = prompt.shape
    total = _check_decode_budget(p, max_new_tokens, cfg, eos_token,
                                 rolling_ok=prompt_lengths is None)
    if temperature > 0 and key is None:
        raise ValueError("temperature sampling needs an explicit PRNG key")
    # The explicit no-op values — top_p=1.0 / min_p=0.0, the serving
    # engines' "no filter" spellings — stay legal on greedy calls too,
    # so replaying a served request's parameters solo never rejects
    # what submit() accepted (round-6 parity contract).
    if ((top_k is not None
         or (top_p is not None and top_p < 1.0)
         or (min_p is not None and min_p > 0.0))
            and temperature <= 0):
        raise ValueError(
            "top_k/top_p/min_p filter a sampling distribution; they "
            "need temperature > 0 (greedy decoding always takes the "
            "single best token, so filtering would be a no-op)")
    if top_k is not None and not 1 <= top_k <= cfg.vocab_size:
        raise ValueError(
            f"top_k must be in [1, vocab_size={cfg.vocab_size}], got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if min_p is not None and not 0.0 <= min_p <= 1.0:
        # 0.0 is the explicit "no min-p filter" value (like submit()).
        raise ValueError(f"min_p must be in [0, 1], got {min_p}")
    cached_len = 0
    if prompt_cache is not None:
        if prompt_lengths is not None:
            raise ValueError(
                "prompt_cache requires uniform prompts "
                "(no prompt_lengths)")
        cache, cached_len = _resolve_prompt_cache(
            prompt_cache, cfg, b, p, max_new_tokens, kv_int8,
            use_prefill)
    key = key if key is not None else jax.random.key(0)

    pad_lens = None
    if prompt_lengths is not None:
        host_lens = np.asarray(prompt_lengths)
        if host_lens.shape != (b,):
            raise ValueError(
                f"prompt_lengths must be [batch={b}], got {host_lens.shape}")
        if host_lens.min() < 1 or host_lens.max() > p:
            raise ValueError(
                f"prompt_lengths must lie in [1, {p}] (the padded prompt "
                f"width), got range [{host_lens.min()}, {host_lens.max()}]")
        lens = jnp.asarray(host_lens, jnp.int32)
        pad_lens = p - lens  # left-pad sizes after end-alignment
        # Right-align each row: [tok..., pad...] -> [pad..., tok...].
        prompt = jax.vmap(jnp.roll)(prompt, pad_lens)

    # prompt_cache takes its own suffix-chunk path: prefill
    # eligibility is moot there (and its >= 2-token / full-precision
    # preconditions do not apply to _decode_chunk; the helper already
    # rejected an explicit use_prefill).
    if prompt_cache is None:
        use_prefill = _resolve_prefill(params, cfg, p, use_prefill,
                                       ragged=pad_lens is not None)

    # Buffer of emitted tokens; absolute positions — the prompt
    # occupies [cached_len, cached_len + p).
    total = cached_len + total
    buf = jnp.zeros((b, total), jnp.int32
                    ).at[:, cached_len:cached_len + p].set(prompt)
    if prompt_cache is not None:
        # Suffix prefill against the existing prefix cache: ONE chunked
        # pass writes the prompt's K/V at [cached_len, cached_len + p)
        # and attends prefix + in-chunk-causal prompt (the same
        # _decode_chunk speculative decoding trusts).  The scan then
        # starts at the last prompt position, recomputing it in place —
        # the same convention as the prefill path below.
        _, cache = _decode_chunk(params, cache, prompt,
                                 jnp.full((b,), cached_len, jnp.int32),
                                 cfg, uniform_pos=True)
        start = cached_len + p - 1
    elif use_prefill:
        # Cache holds K/V for [0, p); the scan starts at the last
        # prompt position (its step recomputes identical K/V in place
        # and yields the logits that sample token p).
        # (A state is not a slot: recomputed, the last position would
        # enter it twice.  A stack with retention layers prefills the
        # prompt but for its last token.)
        cache, _ = prefill(params,
                           prompt[:, :-1] if cfg.state_planes else prompt,
                           cfg, last_logits=False, kv_int8=kv_int8)
        start = p - 1
    else:
        cache = init_cache(cfg, b, kv_int8=kv_int8)
        start = 0
    done = jnp.zeros((b,), bool)

    def body(carry, pos):
        buf, cache, done = carry
        tok = jax.lax.dynamic_index_in_dim(buf, pos, axis=1, keepdims=False)
        logits, cache = _decode_step(params, cache, tok, pos, cfg, pad_lens)
        # Position-keyed stream (not a split chain): the sampled tokens
        # are a function of (key, position) alone, so the prefill path
        # — whose scan skips the prompt positions — samples identically
        # to the all-sequential path.
        sub = jax.random.fold_in(key, pos)
        if temperature > 0:
            scaled = logits / temperature
            if top_k is not None:
                scaled = top_k_mask(scaled, top_k, exact=exact_top_k)
            # top_p >= 1.0 is "no nucleus filter", matching the serving
            # engines (round-6 parity fix): the sorted cumsum can
            # float-overshoot 1.0 and drop an underflowed tail token
            # that an unfiltered draw could sample, so 1.0 must mean
            # bypass everywhere or solo and served runs diverge.
            if top_p is not None and top_p < 1.0:
                scaled = top_p_mask(scaled, top_p)
            if min_p is not None and min_p > 0.0:
                scaled = min_p_mask(scaled, min_p)
            nxt = jax.random.categorical(sub, scaled, axis=-1)
        else:
            nxt = logits.argmax(axis=-1)
        nxt = nxt.astype(jnp.int32)
        # Only write past the prompt (prompt positions are forced).
        write_pos = jnp.minimum(pos + 1, total - 1)
        gen = write_pos >= cached_len + p
        if eos_token is not None:
            nxt = jnp.where(done & gen, eos_token, nxt)  # sticky fill
            done = done | (gen & (nxt == eos_token))
        keep = jax.lax.dynamic_index_in_dim(buf, write_pos, axis=1,
                                            keepdims=False)
        nxt = jnp.where(gen, nxt, keep)
        buf = jax.lax.dynamic_update_index_in_dim(buf, nxt, write_pos, axis=1)
        return (buf, cache, done), None

    (buf, _, _), _ = jax.lax.scan(body, (buf, cache, done),
                                  jnp.arange(start, total - 1))
    if pad_lens is not None:
        # Back to the input layout: prompt, generation, then padding.
        buf = jax.vmap(jnp.roll)(buf, -pad_lens)
    # prompt_cache callers get [B, p + new] — the prefix tokens are
    # theirs already; positions stay absolute internally.
    return buf[:, cached_len:] if cached_len else buf


# Ancestry attention materializes per-layer score tensors of
# B x W^2 x n_heads x S f32 (x2: scores + the post-softmax select) —
# quadratic in beam width.  Above this ceiling the physical
# parent-gather, though slower per step, is the path that fits.
ANCESTRY_SCORE_LIMIT_BYTES = 1 << 28  # 256 MiB per layer


def _ancestry_score_bytes(b: int, w: int, cfg: TransformerConfig) -> int:
    """Estimated per-layer peak of the ancestry attention intermediates:
    the [B, W, kv_heads, groups, W, S] f32 score tensor (``b`` is the
    UNtiled batch; both beam-width dims appear — quadratic in W) and
    its post-softmax one-hot select (same shape) — see _decode_chunk."""
    return 2 * b * w * w * cfg.n_heads * cfg.max_len * 4


def beam_search(params, prompt, cfg: TransformerConfig,
                max_new_tokens: int, beam_width: int = 4,
                eos_token: int | None = None,
                use_prefill: bool | None = None,
                length_penalty: float = 0.0,
                kv_int8: bool = False, prompt_cache=None,
                beam_impl: str = "auto",
                _force_physical: bool = False):
    """Beam search decode: ``prompt [B, P]`` -> ``(sequences, scores)``
    with ``sequences [B, W, P+N]`` and ``scores [B, W]`` (sum of token
    log-probabilities of the generated part), best beam first.

    ``length_penalty`` > 0 re-ranks the RETURNED beams by the GNMT
    normalization ``score / ((5 + n) / 6) ** alpha`` over each beam's
    generated length n (frozen beams stop counting at their eos), so
    short finished hypotheses compete fairly with long ones; the search
    itself still prunes on raw scores (the standard construction), and
    the returned ``scores`` are the normalized values.  0 = raw
    log-probability ordering.

    XLA-shaped like :func:`generate`: static beam width, one compiled
    ``lax.scan`` over positions, the KV cache tiled to ``B*W`` rows and
    reordered each step by a parent gather.  The first expansion runs
    on the un-tiled batch (top-W first tokens), so beams start distinct
    instead of W copies of the greedy token.  ``eos_token`` freezes a
    finished beam: its only continuation is another ``eos_token`` at
    unchanged score, so finished and live beams compete in the same
    top-W.  Uniform-length prompts only (use :func:`generate` for
    ragged batches); quantized trees decode like everywhere else, but
    force the sequential prompt path.

    ``prompt_cache=(cache, cached_len)``: reuse a prefilled shared
    prefix exactly as in :func:`generate` — the suffix runs as one
    chunked pass, hypotheses match beaming the concatenated prompt,
    and the returned sequences cover [prompt, generation] only.

    ``beam_impl`` selects how beams read their divergent histories:

    - ``"auto"`` (default): ancestry attention — unless its per-layer
      score intermediate (quadratic in beam width; see
      :data:`ANCESTRY_SCORE_LIMIT_BYTES`) would exceed the limit, in
      which case it falls back to the physical parent-gather with a
      warning.  Windowed (``attention_window``) configs take ancestry
      too — the ancestor map indexes ring SLOTS, so it stays exact
      both within ``max_len`` and on ROLLING decodes past it (rope +
      window configs, same eligibility as ``generate``; a reused
      slot's ancestry is retired in the step that overwrites its K/V).
      Round-4: previously the windowed path always paid the physical
      gather and rolling beam decode did not exist.
    - ``"ancestry"``: force ancestry attention; raises above the
      intermediate-size limit instead of silently changing cost class.
    - ``"physical"``: force the parent-gather cache reorder (the
      pre-round-3 construction; exact same hypotheses, more HBM
      traffic per step at moderate beam widths).
    """
    reject_extended(cfg, "beam_search")
    params = _device_tree(params)
    b, p = prompt.shape
    w = beam_width
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if not 1 <= w <= cfg.vocab_size:
        raise ValueError(
            f"beam_width must be in [1, vocab_size={cfg.vocab_size}], "
            f"got {w}")
    if length_penalty < 0:
        raise ValueError(
            f"length_penalty must be >= 0, got {length_penalty}")
    # ``_force_physical`` is the deprecated private spelling of
    # beam_impl="physical" (kept for back-compat).  Resolved HERE, with
    # the other argument checks: an invalid beam_impl or an over-limit
    # ancestry config must raise before any prompt-pass device work
    # (the checks need only b, w, cfg).
    if beam_impl not in ("auto", "ancestry", "physical"):
        raise ValueError(
            f"beam_impl must be 'auto', 'ancestry', or 'physical', "
            f"got {beam_impl!r}")
    if _force_physical:
        beam_impl = "physical"
    use_anc = beam_impl != "physical"
    if use_anc:
        est = _ancestry_score_bytes(b, w, cfg)
        if est > ANCESTRY_SCORE_LIMIT_BYTES:
            msg = (
                f"ancestry attention's per-layer score intermediate "
                f"would be ~{est / 2**20:.0f} MiB "
                f"(batch {b} x width {w}^2 x {cfg.n_heads} heads x "
                f"max_len {cfg.max_len}, f32 x2) — over the "
                f"{ANCESTRY_SCORE_LIMIT_BYTES / 2**20:.0f} MiB limit")
            if beam_impl == "ancestry":
                raise ValueError(
                    msg + "; use beam_impl='physical' (exact same "
                    "hypotheses via cache reorder) or shrink "
                    "batch/beam_width/max_len")
            warnings.warn(msg + "; falling back to the physical "
                          "parent-gather (same hypotheses, more HBM "
                          "traffic per step)", stacklevel=2)
            use_anc = False
    # Rolling decode past max_len mirrors generate()'s eligibility
    # (rope + window <= max_len ring; checked inside the budget):
    # slots wrap, and the slot-indexed ancestry update below stays
    # exact (prompt_cache is full-cache-only, hence the guard).
    total = _check_decode_budget(p, max_new_tokens, cfg, eos_token,
                                 rolling_ok=prompt_cache is None)
    prompt = jnp.asarray(prompt, jnp.int32)
    off = 0
    if prompt_cache is not None:
        # Shared-prefix reuse, same contract as generate()'s: the
        # suffix runs as ONE chunked pass against the prefix cache, the
        # search continues at absolute positions, and the returned
        # sequences cover [prompt, generation] only.
        cache, off = _resolve_prompt_cache(
            prompt_cache, cfg, b, p, max_new_tokens, kv_int8,
            use_prefill)
        _, cache = _decode_chunk(params, cache, prompt,
                                 jnp.full((b,), off, jnp.int32), cfg,
                                 uniform_pos=True)
    else:
        use_prefill = _resolve_prefill(params, cfg, p, use_prefill,
                                       ragged=False)

    # ---- prompt pass on the un-tiled [B] batch -----------------------
    if prompt_cache is not None:
        pass  # suffix chunk above already filled [off, off + p)
    elif use_prefill:
        cache, _ = prefill(params, prompt, cfg, last_logits=False,
                           kv_int8=kv_int8)
    elif p > 1:
        # One compiled scan, like generate()'s sequential path — an
        # unrolled eager loop would pay per-op dispatch for every
        # prompt position (quantized params always land here).
        def warm(cache, q):
            tok = jax.lax.dynamic_index_in_dim(prompt, q, axis=1,
                                               keepdims=False)
            _, cache = _decode_step(params, cache, tok, q, cfg)
            return cache, None

        cache, _ = jax.lax.scan(warm, init_cache(cfg, b, kv_int8=kv_int8),
                                jnp.arange(p - 1))
    else:
        cache = init_cache(cfg, b, kv_int8=kv_int8)
    # Logits for the first generated position (recomputes the last
    # prompt position in place, same as generate()'s prefill path).
    logits, cache = _decode_step(params, cache, prompt[:, p - 1],
                                 off + p - 1, cfg)
    logp0 = jax.nn.log_softmax(logits, axis=-1)  # [B, V]

    # ---- first expansion: top-W distinct first tokens ----------------
    scores, first = jax.lax.top_k(logp0, w)          # [B, W] each
    first = first.astype(jnp.int32)
    done = ((first == eos_token) if eos_token is not None
            else jnp.zeros((b, w), bool))
    lengths = jnp.ones((b, w), jnp.int32)  # generated tokens per beam

    # Tile prompt/cache per beam: row b's beams are b*W .. b*W+W-1.
    # Positions are absolute (prefix offset ``off``); the prefix region
    # of buf stays zero and is never read — the scan starts past it.
    total = off + total
    buf = jnp.zeros((b, w, total), jnp.int32)
    buf = buf.at[:, :, off:off + p].set(prompt[:, None, :])
    buf = buf.at[:, :, off + p].set(first)
    cache = jax.tree.map(
        lambda a: jnp.repeat(a, w, axis=1), cache)  # [L, B*W, S, ...]

    neg_inf = jnp.float32(-1e30)
    # Ancestry mode (full-cache configs): the tiled cache is never
    # reordered — each lane writes itself in place, and attention
    # resolves lane w's history through ``anc[b, w, s]`` = the lane
    # that wrote position s of beam w's hypothesis (see _decode_chunk's
    # beam_anc).  The physical parent-gather it replaces rewrote the
    # whole [L, B*W, S, kv, hd] cache every step and cost more than the
    # attention itself (measured 2026-07-31 on one v5e, not re-measured
    # since).  Windowed configs use it too, rolling decodes included:
    # the ancestor map is SLOT-indexed — identical to positions until
    # the ring wraps, and the scan body retires a reused slot's entry
    # in the same step that overwrites its K/V (_ancestry_attend under
    # the band mask).
    # (use_anc resolved with the other argument checks at the top —
    # beam_impl errors must fire before any prompt-pass device work.)
    anc0 = jnp.broadcast_to(
        jnp.arange(w, dtype=jnp.int32)[None, :, None],
        (b, w, cfg.max_len))  # prompt + first token: every lane is its
    #                           own ancestor (the tiled copies agree)

    def body(carry, q):
        buf, cache, anc, scores, done, lengths = carry
        tok = jax.lax.dynamic_index_in_dim(
            buf.reshape(b * w, total), q, axis=1, keepdims=False)
        logits, cache = _decode_step(
            params, cache, tok, q, cfg,
            beam_anc=(anc, w) if use_anc else None)
        logp = jax.nn.log_softmax(logits, axis=-1).reshape(b, w, -1)
        v = logp.shape[-1]
        cand = scores[:, :, None] + logp           # [B, W, V]
        if eos_token is not None:
            # A finished beam's only continuation is eos at unchanged
            # score; everything else is pruned.
            frozen = jnp.full((v,), neg_inf).at[eos_token].set(0.0)
            cand = jnp.where(done[:, :, None],
                             scores[:, :, None] + frozen[None, None, :],
                             cand)
        scores, idx = jax.lax.top_k(cand.reshape(b, w * v), w)
        parent = (idx // v).astype(jnp.int32)      # [B, W]
        token = (idx % v).astype(jnp.int32)
        # Reorder beams by parent: buf rows, done flags — and either
        # the ancestry map (cheap) or the cache rows (physical impl).
        buf = jnp.take_along_axis(buf, parent[:, :, None], axis=1)
        buf = buf.at[:, :, q + 1].set(token)
        done = jnp.take_along_axis(done, parent, axis=1)
        lengths = jnp.take_along_axis(lengths, parent, axis=1)
        lengths = jnp.where(done, lengths, lengths + 1)
        if eos_token is not None:
            done = done | (token == eos_token)
        if use_anc:
            # Kept beam w inherits parent's ancestry for s <= q (the
            # parent's lane wrote position q this step); next step's
            # write SLOT is its own lane.  Slot-indexed (pos % C): the
            # identity while total <= max_len, and under ROLLING decode
            # it retires the reused slot's stale ancestry in the same
            # step that overwrites its K/V — the attention for step q
            # runs before this update, so no read ever sees the reset
            # early, and the band mask never reaches the evicted
            # position afterwards.
            anc = jnp.take_along_axis(anc, parent[:, :, None], axis=1)
            anc = anc.at[:, :, (q + 1) % cfg.max_len].set(
                jnp.arange(w, dtype=jnp.int32)[None, :])
        else:
            flat_parent = (parent
                           + jnp.arange(b, dtype=jnp.int32)[:, None] * w
                           ).reshape(b * w)
            cache = jax.tree.map(lambda a: a[:, flat_parent], cache)
        return (buf, cache, anc, scores, done, lengths), None

    if max_new_tokens > 1:
        (buf, _, _, scores, _, lengths), _ = jax.lax.scan(
            body, (buf, cache, anc0, scores, done, lengths),
            jnp.arange(off + p, total - 1))
    if length_penalty > 0:
        norm = scores / jnp.power((5.0 + lengths) / 6.0, length_penalty)
        order = jnp.argsort(-norm, axis=1)
        buf = jnp.take_along_axis(buf, order[:, :, None], axis=1)
        scores = jnp.take_along_axis(norm, order, axis=1)
    return (buf[:, :, off:] if off else buf), scores
