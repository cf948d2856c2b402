"""The plain reference of ``k-exaone-236b-a23b_l5-ep8``
(``configs/k-exaone-236b-a23b_l5-ep8.json``): one chip's share of
K-EXAONE's block in ``jax.numpy``, float32, ``highest`` matmul
precision — no kernel, no cache, no batching, one layer's weights at a
time, positions in blocks of ``Q_BLOCK`` and wide matrices in blocks
of ``COLS`` columns, so that it fits beside an engine that fills the
chip.  It shares no code with ``distkeras_tpu``; from the program it
takes only the weights (the typed stack's tree: ``tok_emb``, ``head``,
``ln_f_scale``, ``layers/<attention>.<ffn>/...`` with the layers of
one kind stacked on a leading axis in the order they occur).

    x = tok_emb[tokens];  eps = norm_eps;  no bias anywhere
    for l in 0..L-1:        kinds from layer_types[l], ffn_types[l]
      q, k, v = x·wqkv_l  (q then k then v, a head's columns together)
      q = rms_head(q)·q_scale_l;  k = rms_head(k)·k_scale_l
      WINDOW layer: rotate q, k by pos · theta^(-i/half), halves
                    split;  key j visible to query i iff 0 <= i-j < W
      FULL layer:   no rotation;  key j visible iff j <= i
      a = softmax(q·k^T / sqrt(head)) · v,  G query heads a K/V head
      x = x + rms(a·wo_l)·ln1_post_l        # norm on the OUTPUT only
      DENSE:  f = (silu(x·w1) * (x·w3))·w2
      SPARSE: s = sigmoid(x·wg);  sel = the k largest of s + bias
              w_e = scale · s_e / sum_{j in sel} s_j
              f = sum_{e in sel, e held} w_e · E_e(x)  +  S(x)
              (E_e: the dense form of w13_e = [w1 | w3] and w2_e;
               S: the shared expert; experts not held add nothing)
      x = x + rms(f)·ln2_post_l
    logits = rms(x)·ln_f · head^T            (the rows held here)

Exports what ``reference.py`` exports to the serving driver
(``forward``, ``logits_at``, ``check_serving``) and ``sparse_layer``
for the tests.  ``fault=`` computes a deliberately WRONG model, for
the tests and the readings that show the comparison is tight (FAULTS).
"""

from __future__ import annotations

import functools
import math

import numpy as np

Q_BLOCK = 256
COLS = 2048
FAULTS = (None, "window_half", "top_k_less_one", "no_route_scale",
          "no_shared_expert", "no_select_bias", "rope_on_full",
          "kv_float8", "matmul_float8")


def _rms(x, scale, eps):
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _rotate(x, pos, theta):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)           # [T, half]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _products(float8):
    """``mm(a, w)``: one product of the model, both operands as given
    (``w`` widened to float32) or rounded to float8_e4m3fn; a matrix
    wider than COLS is taken COLS columns at a time, so that its
    float32 copy is never whole."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    f8 = lambda a: f32(a.astype(jnp.float8_e4m3fn))
    one = ((lambda a, w: f8(a) @ f8(w)) if float8 == "matmul"
           else (lambda a, w: a @ f32(w)))

    def mm(a, w):
        n = w.shape[1]
        if n <= COLS or n % COLS:
            return one(a, w)
        out = jax.lax.map(
            lambda j: one(a, jax.lax.dynamic_slice_in_dim(
                w, j * COLS, COLS, axis=1)), jnp.arange(n // COLS))
        return jnp.moveaxis(out, 0, 1).reshape(a.shape[0], n)

    return mm, f8


def _gated(h, w1, w3, w2, mm):
    """``(silu(h·w1) * (h·w3))·w2``, the hidden width COLS at a time."""
    import jax
    import jax.numpy as jnp

    f = w1.shape[1]
    if f <= COLS or f % COLS:
        return mm(jax.nn.silu(mm(h, w1)) * mm(h, w3), w2)

    def part(acc, j):
        cut = lambda w, axis: jax.lax.dynamic_slice_in_dim(
            w, j * COLS, COLS, axis=axis)
        return acc + mm(jax.nn.silu(mm(h, cut(w1, 1))) * mm(h, cut(w3, 1)),
                        cut(w2, 0)), None

    return jax.lax.scan(part, jnp.zeros((h.shape[0], w2.shape[1]),
                                        jnp.float32),
                        jnp.arange(f // COLS))[0]


def _route(h, moe, spec, fault):
    """``[T, E]`` float32: a token's weight for every expert of the
    model, zero where it did not choose it."""
    import jax
    import jax.numpy as jnp

    k = spec["top_k"] - (fault == "top_k_less_one")
    score = jax.nn.sigmoid(h @ moe["wg"].astype(jnp.float32))
    pick = score if fault == "no_select_bias" else (
        score + moe["bias"].astype(jnp.float32))
    kth = jnp.sort(pick, axis=-1)[:, -k][:, None]
    chosen = jnp.where(pick >= kth, score, 0.0)
    chosen = chosen / chosen.sum(axis=-1, keepdims=True)
    return chosen * (1.0 if fault == "no_route_scale" else spec["scale"])


def _sparse(h, w, spec, mm, fault):
    """The sparse feed-forward of ``h [T, D]``: every held expert over
    every token, weighted by the router (zero where not chosen)."""
    import jax
    import jax.numpy as jnp

    weight = _route(h, w["moe"], spec, fault)[:, jnp.asarray(spec["held"])]

    def expert(acc, e):
        w13, w2, we = e
        f = w13.shape[1] // 2
        up = mm(h, w13)
        return acc + we[:, None] * mm(jax.nn.silu(up[:, :f]) * up[:, f:],
                                      w2), None

    out = jax.lax.scan(expert, jnp.zeros(h.shape, jnp.float32),
                       (w["moe"]["w13"], w["moe"]["w2"], weight.T))[0]
    if "shared" in w and fault != "no_shared_expert":
        s = w["shared"]
        out = out + _gated(h, s["w1"], s["w3"], s["w2"], mm)
    return out


def _spec(tc):
    """The hashable part of ``transformer_config`` a layer needs."""
    held = tc.get("moe_held")
    return {"n_heads": tc["n_heads"],
            "n_kv": tc.get("n_kv_heads") or tc["n_heads"],
            "theta": float(tc.get("rope_theta", 1e4)),
            "eps": float(tc.get("norm_eps", 1e-6)),
            "window": tc.get("sliding_window"),
            "rope_kinds": tuple(tc.get("rope_layer_types")
                                or ("window", "full")),
            "top_k": tc.get("moe_top_k", 1),
            "scale": float(tc.get("moe_route_scale", 1.0)),
            "held": tuple(held if held is not None
                          else range(tc.get("num_experts", 0)))}


def _qkv(x, pos, w, kind, spec, mm, f8, fault):
    import jax.numpy as jnp

    nh, nkv, t = spec["n_heads"], spec["n_kv"], x.shape[0]
    qkv = mm(x, w["attn"]["wqkv"])
    hd = qkv.shape[-1] // (nh + 2 * nkv)
    q = qkv[:, :nh * hd].reshape(t, nh, hd)
    k = qkv[:, nh * hd:(nh + nkv) * hd].reshape(t, nkv, hd)
    v = qkv[:, (nh + nkv) * hd:].reshape(t, nkv, hd)
    q = _rms(q, w["attn"]["q_scale"], spec["eps"])
    k = _rms(k, w["attn"]["k_scale"], spec["eps"])
    if kind[0] in spec["rope_kinds"] or fault == "rope_on_full":
        q, k = _rotate(q, pos, spec["theta"]), _rotate(k, pos, spec["theta"])
    if fault in ("kv_float8", "matmul_float8"):
        k, v = f8(k), f8(v)
    if fault == "matmul_float8":
        q = f8(q)
    return q, k, v


@functools.lru_cache(maxsize=None)
def _layer_fns(kind, spec_items, fault):
    """The two jitted programs of one kind of layer over one block of
    Q_BLOCK positions: ``kv(x, i, group, at)`` -> the block's keys and
    values; ``block(x, i, keys, values, n_real, group, at)`` -> the
    block after the layer, attending ``keys``/``values [S, kv, head]``
    (every position of the sequence, zero past it)."""
    import jax
    import jax.numpy as jnp

    spec = dict(spec_items)
    float8 = {"kv_float8": "kv", "matmul_float8": "matmul"}.get(fault)
    mm, f8 = _products(float8)
    f32 = lambda a: a.astype(jnp.float32)

    # ``group``: the kind's stacked leaves as the program holds them,
    # ``at``: this layer's index among them — cut out in here, where
    # the compiler reads a layer's slice in place (cut out by the
    # caller it would be a copy: 1.5 GB a sparse layer).
    layer_of = lambda group, at: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False),
        group)

    def kv(x, i, group, at):
        pos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        return _qkv(x, pos, layer_of(group, at), kind, spec, mm, f8,
                    fault)[1:]

    def block(x, i, keys, values, n_real, group, at):
        w = layer_of(group, at)
        pos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        q = _qkv(x, pos, w, kind, spec, mm, f8, fault)[0]
        g = spec["n_heads"] // spec["n_kv"]
        kpos = jnp.arange(keys.shape[0])
        ok = (kpos[None, :] <= pos[:, None]) & (kpos[None, :] < n_real)
        if kind[0] == "window":
            window = spec["window"] // (2 if fault == "window_half" else 1)
            ok &= pos[:, None] - kpos[None, :] < window
        scale = 1.0 / math.sqrt(q.shape[-1])

        def head(c):          # one K/V head and the G query heads on it
            qc = jax.lax.dynamic_slice_in_dim(q, c * g, g, axis=1)
            s = jnp.einsum("qgk,sk->gqs", qc, keys[:, c]) * scale
            s = jnp.where(ok[None], s, -jnp.inf)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
            p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
            if float8 == "matmul":
                p = f8(p)
            return jnp.einsum("gqs,sk->qgk", p, values[:, c])

        a = jax.lax.map(head, jnp.arange(spec["n_kv"]))  # [kv, Q, G, hd]
        a = jnp.moveaxis(a, 0, 1).reshape(Q_BLOCK, -1)
        x = x + _rms(mm(a, w["attn"]["wo"]), w["ln1_post_scale"],
                     spec["eps"])
        if kind[1] == "sparse":
            f = _sparse(x, w, spec, mm, fault)
        else:
            f = _gated(x, w["ffn"]["w1"], w["ffn"]["w3"], w["ffn"]["w2"],
                       mm)
        return x + _rms(f, w["ln2_post_scale"], spec["eps"])

    return jax.jit(kv), jax.jit(block)


def _pad_to(n, block):
    return n + (-n) % block


def _check(tc):
    want = {"ffn_gated": True, "tie_head": False, "post_norms": "only",
            "fused_qkv": True, "rope": True, "qk_norm": True}
    for key, value in want.items():
        if tc.get(key) != value:
            raise ValueError(
                f"reference_kexaone is K-EXAONE's reference: "
                f"transformer_config[{key!r}] must be {value!r}, got "
                f"{tc.get(key)!r}")


def _kinds(tc):
    n = tc["n_layers"]
    return list(zip(tc.get("layer_types") or ["full"] * n,
                    tc.get("ffn_types") or ["dense"] * n))


def forward(params, tc, tokens, seg=None, device=None, fault=None):
    """The normed stream ``[T_pad, D]`` float32 of one sequence — what
    the logits are the head of.  ``seg`` is not this model's (one
    document a sequence).  Shapes do not follow the sequence's length:
    positions go through the layers a block of Q_BLOCK at a time
    against keys and values padded to the cache's length."""
    import jax
    import jax.numpy as jnp

    if seg is not None:
        raise ValueError("reference_kexaone: no packed documents")
    _check(tc)
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    t_pad = _pad_to(t, Q_BLOCK)
    s_len = max(_pad_to(int(tc.get("max_len", t_pad)), Q_BLOCK), t_pad)
    tokens = np.concatenate([tokens, np.zeros(t_pad - t, np.int32)])
    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    spec = tuple(sorted(_spec(tc).items()))
    with jax.default_matmul_precision("highest"):
        emb = put(params["tok_emb"])
        # The stream waits on the HOST between layers, a block at a
        # time on the device: 190 MB at 7,680 positions that the chip
        # does not have to spare.
        x = [np.asarray(emb[put(tokens[i:i + Q_BLOCK])].astype(jnp.float32))
             for i in range(0, t_pad, Q_BLOCK)]
        seen = {}
        for kind in _kinds(tc):
            at = seen.get(kind, 0)
            seen[kind] = at + 1
            group = jax.tree.map(put, params["layers"][".".join(kind)])
            kv_fn, block_fn = _layer_fns(kind, spec, fault)
            kv = [kv_fn(put(xb), i, group, at) for i, xb in enumerate(x)]
            pad = [(0, s_len - t_pad), (0, 0), (0, 0)]
            keys, values = (jnp.pad(jnp.concatenate([b[j] for b in kv]), pad)
                            for j in (0, 1))
            del kv
            for i in range(len(x)):
                x[i] = np.asarray(block_fn(put(x[i]), i, keys, values, t,
                                           group, at))
        return _rms(put(np.concatenate(x)), put(params["ln_f_scale"]),
                    float(tc.get("norm_eps", 1e-6)))


@functools.lru_cache(maxsize=None)
def _head_fn():
    import jax
    import jax.numpy as jnp

    def head(normed, table, positions):
        # The vocabulary in blocks: the table's float32 copy is never
        # whole next to an engine that fills the chip.
        h = normed[positions]
        blocks = table.reshape(8, -1, table.shape[-1])
        out = jax.lax.map(lambda tb: h @ tb.astype(jnp.float32).T, blocks)
        return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1)

    return jax.jit(head)


def logits_at(params, normed, positions, device=None):
    """Float32 logits ``[len(positions), V]`` (numpy) of the untied
    head over an already-normed stream at the given positions, in
    padded blocks of 256 positions."""
    import jax
    import jax.numpy as jnp

    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    positions = np.asarray(positions, np.int32)
    n = len(positions)
    padded = np.concatenate([positions,
                             np.zeros(_pad_to(n, 256) - n, np.int32)])
    with jax.default_matmul_precision("highest"):
        out = _head_fn()(normed, put(params["head"]), put(padded))
    return np.asarray(out)[:n]


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


def check_serving(ctx, params, finished, fault=None):
    """A seeded sample of finished requests, teacher-forced through the
    reference over prompt + output (as ``reference_ouro.check_serving``:
    logits, not tokens; prefill then decode through both kinds of
    plane against this full forward).  Every token the engine chose
    lies within ``logit_tol`` of the reference's best logit at its
    position, and over all checked tokens the MEAN distance to the best
    logit is under ``mean_gap_tol``.  The reference routes for itself:
    it is never given the engine's choices, so a near-tie at the k-th
    place that bf16 rounding flips shows as a jump in one token's
    logits (the worst) and hardly in the mean.  ``min_wrapped``: at
    least one checked request's prompt is longer than that (window +
    chunk: a ring has wrapped under a chunk), the longest finished one
    taking the last place of the sample if the draw holds none.
    ``fault`` plants a FAULTS entry in the reference: the comparison
    then has to come out not ``ok``."""
    spec = ctx.cell["correct"]
    tol = float(spec["logit_tol"])
    mean_tol = float(spec["mean_gap_tol"])
    tc = ctx.conf["transformer_config"]
    rng = np.random.default_rng(ctx.seed)
    pool = [r for r in finished if r.tokens]
    if not pool:
        return {"ok": False, "why": "no finished request to check"}
    pick = [int(j) for j in rng.choice(
        len(pool), size=min(int(spec["requests"]), len(pool)),
        replace=False)]
    wrapped = int(spec.get("min_wrapped", 0))
    if wrapped and not any(len(pool[j].prompt) > wrapped for j in pick):
        longest = max(range(len(pool)), key=lambda j: len(pool[j].prompt))
        if longest not in pick:
            pick[-1] = longest
    worst, hits, total, gaps, bad, longest_checked = 0.0, 0, 0, 0.0, [], 0
    for j in pick:
        r = pool[j]
        p, n = len(r.prompt), len(r.tokens)
        longest_checked = max(longest_checked, p)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        normed = forward(params, tc, seq[:-1], fault=fault)
        lg = logits_at(params, normed, np.arange(p - 1, p - 1 + n))
        if not np.isfinite(lg).all():
            bad.append({"request": r.idx, "why": "non-finite logits"})
            continue
        best = lg.max(-1)
        gap = best - lg[np.arange(n), np.asarray(r.tokens)]
        worst = max(worst, float(gap.max()))
        hits += int((gap == 0).sum())
        total += n
        gaps += float(gap.sum())
        if gap.max() >= tol:
            bad.append({"request": r.idx, "token": int(gap.argmax()),
                        "gap": float(gap.max()), "prompt_len": p})
    mean_gap = gaps / max(total, 1)
    if mean_gap >= mean_tol:
        bad.append({"why": "mean distance to the reference's best logit",
                    "mean_gap": mean_gap, "mean_gap_tol": mean_tol})
    if longest_checked <= wrapped:
        bad.append({"why": "no checked prompt wrapped a ring under a chunk",
                    "longest_prompt": longest_checked,
                    "min_wrapped": wrapped})
    return {"ok": not bad, "requests": len(pick), "tokens": total,
            "argmax_of_reference": hits,
            "worst_gap_to_best_logit": worst,
            "mean_gap_to_best_logit": mean_gap, "logit_tol": tol,
            "mean_gap_tol": mean_tol, "longest_prompt": longest_checked,
            "failures": bad[:5]}
