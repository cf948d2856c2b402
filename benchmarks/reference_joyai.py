"""The plain reference of ``joyai-llm-flash_l10-ep8``
(``configs/joyai-llm-flash_l10-ep8.json``): one chip's share of
JoyAI-LLM-Flash's block — DeepSeek-V3's keys: latent attention (MLA)
over routed experts — in ``jax.numpy``, float32, ``highest`` matmul
precision, in the EXPANDED form: every head's keys and values are
rebuilt from the latent, block by block, and met by every later query
again.  No kernel, no cache, no absorbed product: the program serves
the same function through a cache of latent rows with ``wkv_b`` folded
into the queries and the output, so the comparison is between two
derivations and not two copies of one.  It shares no code with
``distkeras_tpu``; from the program it takes only the weights
(``tok_emb``, ``head``, ``ln_f_scale``, ``layers/latent.<ffn>/...``
stacked on a leading axis in the order the layers occur).

    x = tok_emb[tokens];  eps = norm_eps;  no bias anywhere
    for l in 0..L-1:
      h  = rms(x)·ln1_l
      cq = rms(h·wq_a)·q_a_scale;   q = cq·wq_b -> [H, nope | rope]
      ckv = h·wkv_a -> [kv_lora_rank | rope]
      c  = rms(ckv[:rank])·kv_a_scale;   k_pe = ckv[rank:]  (ONE a token)
      rotate q_pe (every head) and k_pe by pos · theta^(-i/(rope/2)) over
          the pairs (2i, 2i+1) — the tree holds those columns
          de-interleaved (evens, then odds: a layout), so they are put
          back in the published order first
      [k_nope | v]_h = c·wkv_b -> [H, nope | v];   k_h = k_nope_h | k_pe
      a = softmax_causal(q·k / sqrt(nope + rope))·v;   x = x + flat(a)·wo
      h = rms(x)·ln2_l
      DENSE:  x = x + (silu(h·w1) * (h·w3))·w2
      SPARSE: ``reference_kexaone._sparse`` — the same router (sigmoid
              scores, the k largest of score + bias, the chosen scores
              over their sum times the scale), the held experts, the
              shared expert unweighted
    logits = rms(x)·ln_f · head^T            (the rows held here)

Positions go through a layer Q_BLOCK at a time against the latent of
every earlier position, K_BLOCK at a time (the block's keys and values
are rebuilt from it each time), under an online softmax; wide matrices
COLS columns at a time, the vocabulary in blocks: a 30k-token prompt
fits beside an engine that fills the chip.  Every program has ONE shape
whatever the sequence's length (the latent lies in a buffer of
``s_max`` positions), as ``reference_brumby``'s.

Exports ``forward``, ``latent_rows``, ``logits_at``, ``gaps_at``,
``sparse_layer``, ``check_serving``, ``check_rows``.  ``fault=``
computes a deliberately WRONG model, for the tests and the readings
that show the comparison is tight (FAULTS):

``kv_float8``       the latent and the rotary key (what a cache would
                    hold) rounded to float8_e4m3
``matmul_float8``   both operands of every product rounded to it
``scale_nope``      scores over sqrt(nope) for sqrt(nope + rope)
``no_kv_norm``      the latent's norm (and its scale) left out
``no_q_norm``       the queries' inner norm (and its scale) left out
``rope_halves``     the published columns rotated halves-split, not in
                    pairs (2i, 2i+1)
``no_k_rope``       the shared key is not rotated
``top_k_less_one``, ``no_select_bias``, ``no_route_scale``,
``no_shared_expert``    the router's and the experts', as
                    ``reference_kexaone``'s
"""

from __future__ import annotations

import functools
import math

import numpy as np

from reference_brumby import LANE_HISTORY, earlier_occupant, gaps_at, logits_at
from reference_kexaone import _gated, _pad_to, _products, _rms, _sparse

Q_BLOCK = 256
K_BLOCK = 2048
FAULTS = (None, "kv_float8", "matmul_float8", "scale_nope", "no_kv_norm",
          "no_q_norm", "rope_halves", "no_k_rope", "top_k_less_one",
          "no_select_bias", "no_route_scale", "no_shared_expert")

__all__ = ["FAULTS", "LANE_HISTORY", "check_rows", "check_serving",
           "earlier_occupant", "forward", "gaps_at", "latent_rows",
           "logits_at", "sparse_layer"]


def _published(x):
    """The rotary columns in the published order: the tree holds the
    even ones, then the odd ones."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], axis=-1).reshape(x.shape)


def _rotate(x, pos, theta, interleaved, halves):
    """``x [T, ..., rope]`` (the tree's layout) rotated at ``pos [T]``,
    in the published order of its columns.  ``interleaved``: pair ``i``
    is columns ``(2i, 2i + 1)``; else ``(i, i + rope / 2)``, which is
    also what ``halves`` (the fault) does to interleaved columns."""
    import jax.numpy as jnp

    if interleaved:
        x = _published(x)
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)           # [T, half]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + (half,))
    c, s = jnp.cos(ang), jnp.sin(ang)
    if interleaved and not halves:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _spec(tc, interleave=True):
    """The hashable part of ``transformer_config`` a layer needs, and
    the published ``rope_interleave`` (the program has no such option:
    its columns are a layout)."""
    held = tc.get("moe_held")
    return {"n_heads": tc["n_heads"], "rank": tc["kv_lora_rank"],
            "nope": tc["qk_nope_head_dim"], "rope": tc["qk_rope_head_dim"],
            "v": tc["v_head_dim"],
            "interleave": bool(interleave),
            "theta": float(tc.get("rope_theta", 1e4)),
            "eps": float(tc.get("norm_eps", 1e-6)),
            "top_k": tc.get("moe_top_k", 1),
            "scale": float(tc.get("moe_route_scale", 1.0)),
            "held": tuple(held if held is not None
                          else range(tc.get("num_experts", 0)))}


def _norm_or_not(x, scale, eps, skip):
    return x if skip else _rms(x, scale, eps)


def _latent(h, pos, w, spec, mm, f8, fault):
    """What the model keeps of one block of normed positions ``h``:
    the latent after its norm ``[T, rank]`` and the shared key after
    its rotation ``[T, rope]`` (published order)."""
    a = w["attn"]
    ckv = mm(h, a["wkv_a"])
    c = _norm_or_not(ckv[:, :spec["rank"]], a["kv_a_scale"], spec["eps"],
                     fault == "no_kv_norm")
    k_pe = ckv[:, spec["rank"]:]
    if fault == "no_k_rope":
        k_pe = _published(k_pe) if spec["interleave"] else k_pe
    else:
        k_pe = _rotate(k_pe, pos, spec["theta"], spec["interleave"],
                       fault == "rope_halves")
    if fault in ("kv_float8", "matmul_float8"):
        c, k_pe = f8(c), f8(k_pe)
    return c, k_pe


def _queries(h, pos, w, spec, mm, f8, fault):
    """``(q_nope [T, H, nope], q_pe [T, H, rope])``, ``q_pe`` rotated."""
    a = w["attn"]
    cq = _norm_or_not(mm(h, a["wq_a"]), a["q_a_scale"], spec["eps"],
                      fault == "no_q_norm")
    q = mm(cq, a["wq_b"]).reshape(h.shape[0], spec["n_heads"], -1)
    q_nope = q[..., :spec["nope"]]
    q_pe = _rotate(q[..., spec["nope"]:], pos, spec["theta"],
                   spec["interleave"], fault == "rope_halves")
    if fault == "matmul_float8":
        q_nope, q_pe = f8(q_nope), f8(q_pe)
    return q_nope, q_pe


@functools.lru_cache(maxsize=None)
def _layer_fns(kind, spec_items, fault):
    """The jitted programs of one kind of layer over one block of
    Q_BLOCK positions (``group``: the kind's stacked leaves as the
    program holds them, ``at``: this layer's index among them — cut out
    in here, where the compiler reads a layer's slice in place):

    ``lat(x, pos, i, cbuf, pebuf, group, at)`` -> the two buffers ``[S,
    rank]``, ``[S, rope]`` (donated) with block ``i``'s latent and
    shared key written in;
    ``block(x, pos, i, cbuf, pebuf, n_real, group, at)`` -> the block
    after the layer, its queries meeting the keys and values REBUILT
    from ``cbuf`` a block of K_BLOCK at a time, as far as their own."""
    import jax
    import jax.numpy as jnp

    spec = dict(spec_items)
    float8 = {"kv_float8": "kv", "matmul_float8": "matmul"}.get(fault)
    mm, f8 = _products(float8)
    nh, nope, dv = spec["n_heads"], spec["nope"], spec["v"]
    layer_of = lambda group, at: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False),
        group)

    def lat(x, pos, i, cbuf, pebuf, group, at):
        w = layer_of(group, at)
        new = _latent(_rms(x, w["ln1_scale"], spec["eps"]), pos, w, spec, mm,
                      f8, fault)
        return tuple(jax.lax.dynamic_update_slice_in_dim(
            buf, blk, i * Q_BLOCK, axis=0)
            for buf, blk in zip((cbuf, pebuf), new))

    def block(x, pos, i, cbuf, pebuf, n_real, group, at):
        w = layer_of(group, at)
        q_nope, q_pe = _queries(_rms(x, w["ln1_scale"], spec["eps"]), pos, w,
                                spec, mm, f8, fault)
        idx = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        scale = 1.0 / math.sqrt(nope if fault == "scale_nope"
                                else nope + spec["rope"])

        def keys_block(j, acc):
            m, l, o = acc
            cut = lambda a: jax.lax.dynamic_slice_in_dim(
                a, j * K_BLOCK, K_BLOCK)
            kv = mm(cut(cbuf), w["attn"]["wkv_b"]).reshape(K_BLOCK, nh, -1)
            k_nope, v = kv[..., :nope], kv[..., nope:]
            if float8 == "matmul":
                k_nope, v = f8(k_nope), f8(v)
            s = (jnp.einsum("qhn,shn->hqs", q_nope, k_nope)
                 + jnp.einsum("qhr,sr->hqs", q_pe, cut(pebuf))) * scale
            kpos = j * K_BLOCK + jnp.arange(K_BLOCK)
            ok = (kpos[None, :] <= idx[:, None]) & (kpos[None, :] < n_real)
            s = jnp.where(ok[None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - safe[..., None])
            corr = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
            l = l * corr + p.sum(axis=-1)
            if float8 == "matmul":
                p = f8(p)
            return (m_new, l,
                    o * corr[..., None] + jnp.einsum("hqs,shv->hqv", p, v))

        zero = (jnp.full((nh, Q_BLOCK), -jnp.inf),
                jnp.zeros((nh, Q_BLOCK), jnp.float32),
                jnp.zeros((nh, Q_BLOCK, dv), jnp.float32))
        # Key blocks up to the one that holds the block's last query.
        _, l, o = jax.lax.fori_loop(
            0, (i * Q_BLOCK + Q_BLOCK - 1) // K_BLOCK + 1, keys_block, zero)
        a = (o / jnp.maximum(l, 1e-30)[..., None])           # [H, Q, v]
        a = jnp.moveaxis(a, 0, 1).reshape(Q_BLOCK, -1)
        x = x + mm(a, w["attn"]["wo"])
        h = _rms(x, w["ln2_scale"], spec["eps"])
        if kind == "sparse":
            return x + _sparse(h, w, spec, mm, fault)
        return x + _gated(h, w["ffn"]["w1"], w["ffn"]["w3"], w["ffn"]["w2"],
                          mm)

    return jax.jit(lat, donate_argnums=(3, 4)), jax.jit(block)


@functools.lru_cache(maxsize=None)
def _norm_fn(eps):
    import jax

    return jax.jit(lambda x, scale: _rms(x, scale, eps))


def _check(tc):
    want = {"ffn_gated": True, "tie_head": False, "rope": True}
    for key, value in want.items():
        if tc.get(key) != value:
            raise ValueError(
                f"reference_joyai is JoyAI-LLM-Flash's reference: "
                f"transformer_config[{key!r}] must be {value!r}, got "
                f"{tc.get(key)!r}")
    if set(tc.get("layer_types") or ()) != {"latent"} or tc.get(
            "post_norms") or tc.get("qk_norm"):
        raise ValueError("reference_joyai: every layer is a latent layer, "
                         "pre-norm, no norm over a head")


def _stream(params, tc, tokens, device, fault, s_max, interleave,
            rows_of=()):
    """One sequence through the layers: ``(the stream's blocks after
    the last layer run, its norm's eps, {layer: the rows a cache would
    hold of it [T, rank + rope]})``.  With ``rows_of`` the run stops at
    the last layer named, before its attention."""
    import jax
    import jax.numpy as jnp

    _check(tc)
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    t_pad = _pad_to(t, Q_BLOCK)
    s_len = _pad_to(max(t_pad, s_max), K_BLOCK)
    tokens = np.concatenate([tokens, np.zeros(t_pad - t, np.int32)])
    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    spec = _spec(tc, interleave)
    items = tuple(sorted(spec.items()))
    blocks = range(0, t_pad, Q_BLOCK)
    kinds = tc.get("ffn_types") or ["dense"] * tc["n_layers"]
    rows = {}
    with jax.default_matmul_precision("highest"):
        emb = put(params["tok_emb"])
        # The stream waits on the HOST between layers, a block at a
        # time on the device.
        x = [np.asarray(emb[put(tokens[i:i + Q_BLOCK])].astype(jnp.float32))
             for i in blocks]
        pos = [put(np.arange(i, i + Q_BLOCK, dtype=np.int32)) for i in blocks]
        # Positions past the sequence keep what an earlier layer wrote
        # (or zeros): finite, and masked out by ``n_real``.
        bufs = (put(np.zeros((s_len, spec["rank"]), np.float32)),
                put(np.zeros((s_len, spec["rope"]), np.float32)))
        seen = {}
        for layer, kind in enumerate(kinds):
            at = seen.get(kind, 0)
            seen[kind] = at + 1
            group = jax.tree.map(put, params["layers"]["latent." + kind])
            lat_fn, block_fn = _layer_fns(kind, items, fault)
            for i, xb in enumerate(x):
                bufs = lat_fn(put(xb), pos[i], i, *bufs, group, at)
            if layer in rows_of:
                rows[layer] = np.concatenate(
                    [np.asarray(buf[:t]) for buf in bufs], axis=-1)
                if layer == max(rows_of):
                    break
            for i in range(len(x)):
                x[i] = np.asarray(block_fn(put(x[i]), pos[i], i, *bufs, t,
                                           group, at))
    return x, spec["eps"], rows


def forward(params, tc, tokens, seg=None, device=None, fault=None,
            keep_from=0, s_max=0, interleave=True):
    """The normed stream ``[T_pad - keep_from, D]`` float32 (numpy) of
    one sequence from position ``keep_from`` on — what the logits are
    the head of.  The latent lies in buffers of ``s_max`` positions, or
    of the sequence's own length where that is more, rounded up to
    whole K_BLOCKs: the layer's programs are compiled once a buffer
    length, so a caller with many sequences names the longest.
    ``interleave``: the configuration's published ``rope_interleave``
    (JoyAI-LLM-Flash's: true)."""
    import jax
    import jax.numpy as jnp

    if seg is not None:
        raise ValueError("reference_joyai: no packed documents")
    x, eps, _ = _stream(params, tc, tokens, device, fault, s_max, interleave)
    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    with jax.default_matmul_precision("highest"):
        scale, norm = put(params["ln_f_scale"]), _norm_fn(eps)
        return np.concatenate([np.asarray(norm(put(xb), scale))
                               for xb in x])[keep_from:]


def latent_rows(params, tc, tokens, layers, device=None, fault=None,
                s_max=0, interleave=True):
    """What a cache would hold of one sequence: ``{layer: [T, rank +
    rope]}`` float32 — the latent after its norm beside the shared key
    after its rotation, the rotary columns in the published order — for
    the ``layers`` named (the run stops at the last of them)."""
    return _stream(params, tc, tokens, device, fault, s_max, interleave,
                   rows_of=tuple(layers))[2]


def sparse_layer(w, tc, h, fault=None):
    """The sparse feed-forward alone (routed experts held + the shared
    expert) of ``h [T, D]`` with one layer's weights ``w``: for the
    test that the shares add up."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda h, w: _sparse(
            h, w, _spec(tc), _products(None)[0], fault))(
                jnp.asarray(h, jnp.float32), w))


def check_rows(ctx, params, loop, fault=None):
    """The latent rows the ENGINE holds when the run ends, against the
    rows the reference computes of the same tokens: what the logits
    cannot tell (the routers' near-ties over the sparse layers drown a
    cache's rounding there), the cache's own contents can.  Of
    ``correct.row_lanes`` lanes still decoding (the one that holds the
    most rows, the others by the seed), the rows of ``correct.
    row_layers`` — layers no router's choice has reached: layer 0's
    rows are a function of the token alone, layer 1's have been through
    layer 0's attention over the cache and its dense feed-forward —
    for every position whose row must be there (the prompt's and every
    read token's but the last).  The number: the largest, over lanes,
    layers and the row's two parts (latent, shared key), of ``|engine -
    reference| / |reference|`` (Frobenius), held under
    ``correct.latent_row_tol``.  ``loop``: the driver's ``Loop`` (its
    engine's ``cache["lat"]`` and lane table)."""
    import jax.numpy as jnp

    spec = ctx.cell["correct"]
    tol = float(spec["latent_row_tol"])
    tc = ctx.conf["transformer_config"]
    rank, rope = tc["kv_lora_rank"], tc["qk_rope_head_dim"]
    layers = tuple(int(i) for i in spec["row_layers"])
    held = sorted(((len(r.prompt) + len(r.tokens) - 1, lane)
                   for lane, r in (loop.by_lane.items() if loop else ())
                   if r.tokens), reverse=True)
    if not held:
        return {"ok": False, "latent_row_tol": tol,
                "why": "no decoding lane whose rows could be read"}
    rng = np.random.default_rng([ctx.seed, 1])
    rest = [int(j) for j in rng.permutation(len(held) - 1) + 1]
    pick = [held[j] for j in [0] + rest[:int(spec["row_lanes"]) - 1]]
    s_max = sum(int(ctx.mix.get(key, {}).get("max", 0))
                for key in ("prompt_len", "output_len"))
    slab, worst, each = loop.engine.cache["lat"], 0.0, []
    for n, lane in pick:
        r = loop.by_lane[lane]
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])[:n]
        want = latent_rows(params, tc, seq, layers, fault=fault, s_max=s_max,
                           interleave=bool(ctx.conf.get("rope_interleave",
                                                        True)))
        for layer in layers:
            got = np.asarray(slab[layer, lane].astype(jnp.float32))[:n]
            parts = ((got[:, :rank], want[layer][:, :rank]),
                     (np.asarray(_published(got[:, rank:rank + rope])),
                      want[layer][:, rank:]))
            err = [float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))
                   for g, w in parts]
            worst = max([worst] + [e if np.isfinite(e) else np.inf
                                   for e in err])
            each.append({"lane": lane, "rows": n, "layer": layer,
                         "latent": err[0], "shared_key": err[1]})
    return {"ok": worst < tol, "latent_row_err": worst,
            "latent_row_tol": tol, "cache_dtype": str(slab.dtype),
            "by_lane": each}


def check_serving(ctx, params, finished, fault=None, loop=None):
    """A seeded sample of finished requests, teacher-forced through the
    reference over prompt + output (as ``reference_brumby.
    check_serving``: logits, not tokens; chunked prefill into the latent
    cache and then the absorbed decode against this expanded full
    forward).  Every token the engine chose lies within ``logit_tol``
    of the reference's best logit at its position, and over all checked
    tokens the MEAN distance to the best logit is under
    ``mean_gap_tol``.  The reference routes for itself.  Where the cell
    names ``latent_row_tol``, the engine's cached rows are held to the
    reference's besides (:func:`check_rows`, ``loop``: the driver's).

    The sample holds at least one request whose prompt is longer than
    ``min_long`` (a prefix read through that many positions of chunks)
    and at least one whose lane had an EARLIER OCCUPANT (stale rows
    past the new prompt must stay masked): the longest finished one,
    and the first finished one with an earlier occupant, take places
    of the sample if the draw holds none.  ``fault`` plants a FAULTS
    entry in the reference: the comparison then has to come out not
    ``ok``."""
    spec = ctx.cell["correct"]
    tol = float(spec["logit_tol"])
    mean_tol = float(spec["mean_gap_tol"])
    tc = ctx.conf["transformer_config"]
    interleave = bool(ctx.conf.get("rope_interleave", True))
    rng = np.random.default_rng(ctx.seed)
    pool = [r for r in finished if r.tokens]
    if not pool:
        return {"ok": False, "why": "no finished request to check"}
    pick = [int(j) for j in rng.choice(
        len(pool), size=min(int(spec["requests"]), len(pool)),
        replace=False)]
    long = int(spec.get("min_long", 0))
    if long and not any(len(pool[j].prompt) > long for j in pick):
        longest = max(range(len(pool)), key=lambda j: len(pool[j].prompt))
        if longest not in pick:
            pick[-1] = longest
    if not any(earlier_occupant(pool[j]) is not None for j in pick):
        reused = next((j for j, r in enumerate(pool) if j not in pick
                       and earlier_occupant(r) is not None), None)
        if reused is not None:
            pick[0 if len(pick) > 1 else -1] = reused
    worst, hits, total, gaps, bad, each = 0.0, 0, 0, 0.0, [], []
    longest_checked, reused_checked = 0, 0
    # One buffer length for every sequence of every run: the longest
    # the traffic can send.
    s_max = sum(int(ctx.mix.get(key, {}).get("max", 0))
                for key in ("prompt_len", "output_len"))
    for j in pick:
        r = pool[j]
        p, n = len(r.prompt), len(r.tokens)
        longest_checked = max(longest_checked, p)
        reused_checked += earlier_occupant(r) is not None
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        normed = forward(params, tc, seq[:-1], fault=fault, keep_from=p - 1,
                         s_max=s_max, interleave=interleave)
        gap = gaps_at(params, normed, np.arange(n), r.tokens)
        if not np.isfinite(gap).all():
            bad.append({"request": r.idx, "why": "non-finite logits"})
            continue
        worst = max(worst, float(gap.max()))
        hits += int((gap == 0).sum())
        total += n
        gaps += float(gap.sum())
        each.append({"prompt_len": p, "tokens": n, "mean_gap": float(
            gap.mean()), "worst_gap": float(gap.max())})
        if gap.max() >= tol:
            bad.append({"request": r.idx, "token": int(gap.argmax()),
                        "gap": float(gap.max()), "prompt_len": p})
    mean_gap = gaps / max(total, 1)
    if mean_gap >= mean_tol:
        bad.append({"why": "mean distance to the reference's best logit",
                    "mean_gap": mean_gap, "mean_gap_tol": mean_tol})
    if longest_checked <= long:
        bad.append({"why": "no checked prompt read a prefix that long",
                    "longest_prompt": longest_checked, "min_long": long})
    if spec.get("need_reused_lane") and not reused_checked:
        bad.append({"why": "no checked request's lane had an earlier "
                    "occupant"})
    rows = {}
    if "latent_row_tol" in spec:
        rows = {"rows": check_rows(ctx, params, loop, fault=fault)}
        if not rows["rows"]["ok"]:
            bad.append({"why": "the engine's cached latent rows against "
                        "the reference's", **{k: v for k, v in rows[
                            "rows"].items() if k != "by_lane"}})
    return {"ok": not bad, **rows, "requests": len(pick), "tokens": total,
            "argmax_of_reference": hits,
            "worst_gap_to_best_logit": worst,
            "mean_gap_to_best_logit": mean_gap, "logit_tol": tol,
            "mean_gap_tol": mean_tol, "longest_prompt": longest_checked,
            "reused_lanes_checked": reused_checked, "by_request": each,
            "failures": bad[:5]}
