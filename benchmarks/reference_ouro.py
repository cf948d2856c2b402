"""The plain reference of the looped language model ``ouro-2.6b``
(``configs/ouro-2.6b.json``): its equations in ``jax.numpy``, float32,
``highest`` matmul precision — no kernel, no cache, no batching, one
layer's weights at a time, queries in blocks.  It shares no code with
``distkeras_tpu``; from the program it takes only the weights (the
tree's layout: ``tok_emb``, ``head``, ``ln_f_scale``, ``exit_w``,
``exit_b``, ``layers/{ln1_scale, ln1_post_scale, ln2_scale,
ln2_post_scale, attn/{wqkv,wo}, ffn/{w1,w3,w2}}``, per-layer leaves
stacked on a leading axis of L — NOT R·L: every pass uses the same; the
projections are matrices: ``wqkv [D, (heads + 2 kv_heads) * head]``, q
then k then v, a head's columns together; ``wo [heads * head, D]``).

    x = tok_emb[tokens]
    for r in 0..R-1:                      # the SAME L layers' weights
      for l in 0..L-1:
        h = rms(x; ln1_l);  q,k,v = h·wq_l, h·wk_l, h·wv_l
        rotate q,k by pos * theta^(-i/half), halves split
        a = softmax(q·k^T / sqrt(head), over positions <= t, of THIS
                    pass's k,v of layer l) · v
        x = x + rms(a·wo_l; ln1_post_l)   # norm on the sublayer's OUTPUT
        h = rms(x; ln2_l);  m = (silu(h·w1_l) * (h·w3_l)) · w2_l
        x = x + rms(m; ln2_post_l)
      x = rms(x; ln_f)                    # after EVERY pass
      lambda_r = sigmoid(x·exit_w + exit_b);  logits_r = x · head^T
    p_r = lambda_r · prod_{j<r}(1 - lambda_j), r < R-1
    p_{R-1} = prod_{j<R-1}(1 - lambda_j)
    served (exit threshold 1): logits_{R-1}
    rms(x; g) = x / sqrt(mean(x^2) + eps) * g;  no bias anywhere

Exports what ``reference.py`` exports to the serving driver
(``forward``, ``logits_at``, ``check_serving``) and ``all_passes`` for
the tests.  ``fault=`` computes a deliberately WRONG model, for the
tests and the readings that show the comparison is tight
(``one_pass_short``: R-1 passes; ``previous_plane``: pass r > 0 attends
pass r-1's keys and values of the same layer; ``kv_float8``: keys and
values rounded to float8_e4m3fn as a lower-precision cache would hold
them; ``matmul_float8``: both operands of every product of the layers
rounded to float8_e4m3fn, the nearest precision below the bfloat16 the
configuration is served in).
"""

from __future__ import annotations

import functools
import math

import numpy as np

Q_BLOCK = 512
FAULTS = (None, "one_pass_short", "previous_plane", "kv_float8",
          "matmul_float8")
EPS = 1e-6          # rms_norm_eps of the source; the program's constant


def _rms(x, scale):
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) * scale


def _rotate(x, pos, theta):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half)           # [T, half]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


@functools.lru_cache(maxsize=None)
def _layer_fn(n_heads, n_kv, theta, float8):
    """One block over one sequence ``x [T, D]`` (T a multiple of
    Q_BLOCK); ``n_real`` positions are real, the rest padding that
    attends nothing and is never read.  ``kv_in``: the keys and values
    to attend INSTEAD of this pass's own (``previous_plane``), or None.
    ``float8``: None, ``"kv"`` or ``"matmul"`` (the two precision
    faults).  Returns ``(x, (k, v))``."""
    import jax
    import jax.numpy as jnp

    def layer(x, n_real, w, kv_in):
        f32 = lambda a: a.astype(jnp.float32)
        f8 = lambda a: f32(a.astype(jnp.float8_e4m3fn))
        # A product of the layer: both operands as given, or rounded.
        mm = ((lambda a, b: f8(a) @ f8(b)) if float8 == "matmul"
              else (lambda a, b: a @ f32(b)))
        t = x.shape[0]
        pos = jnp.arange(t)
        h = _rms(x, f32(w["ln1_scale"]))
        qkv = mm(h, w["attn"]["wqkv"])
        hd = qkv.shape[-1] // (n_heads + 2 * n_kv)
        q = qkv[:, :n_heads * hd].reshape(t, n_heads, hd)
        k = qkv[:, n_heads * hd:(n_heads + n_kv) * hd].reshape(t, n_kv, hd)
        v = qkv[:, (n_heads + n_kv) * hd:].reshape(t, n_kv, hd)
        q, k = _rotate(q, pos, theta), _rotate(k, pos, theta)
        if float8:
            k, v = f8(k), f8(v)
        if float8 == "matmul":
            q = f8(q)
        own = (k, v)
        if kv_in is not None:
            k, v = kv_in
        g = n_heads // n_kv
        kr, vr = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scale = 1.0 / math.sqrt(q.shape[-1])

        def q_block(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
            pi = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.einsum("qhk,shk->hqs", qi, kr) * scale
            ok = (pos[None, :] <= pi[:, None]) & (pos[None, :] < n_real)
            s = jnp.where(ok[None], s, -jnp.inf)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
            p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
            if float8 == "matmul":
                p = f8(p)
            return jnp.einsum("hqs,shk->qhk", p, vr)

        a = jax.lax.map(q_block, jnp.arange(t // Q_BLOCK))
        a = mm(a.reshape(t, -1), w["attn"]["wo"])
        x = x + _rms(a, f32(w["ln1_post_scale"]))
        h = _rms(x, f32(w["ln2_scale"]))
        m = mm(jax.nn.silu(mm(h, w["ffn"]["w1"])) * mm(h, w["ffn"]["w3"]),
               w["ffn"]["w2"])
        return x + _rms(m, f32(w["ln2_post_scale"])), own

    return jax.jit(layer)


def _pad_to(n, block):
    return n + (-n) % block


def _check(tc):
    want = {"ffn_gated": True, "tie_head": False, "post_norms": True,
            "fused_qkv": True, "rope": True}
    for key, value in want.items():
        if tc.get(key) != value:
            raise ValueError(
                f"reference_ouro is the looped model's reference: "
                f"transformer_config[{key!r}] must be {value!r}, got "
                f"{tc.get(key)!r}")
    if tc.get("attention_window") is not None:
        raise ValueError("reference_ouro: every layer is full attention")


def passes(params, tc, tokens, device=None, fault=None):
    """The normed stream after each pass, ``[R][T_pad, D]`` float32,
    for one sequence (a list; ``R - 1`` long under ``one_pass_short``).
    Shapes are padded to a multiple of Q_BLOCK so that runs with other
    lengths find their programs in the compile cache."""
    import jax
    import jax.numpy as jnp

    _check(tc)
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    t_pad = _pad_to(t, Q_BLOCK)
    tokens = np.concatenate([tokens, np.zeros(t_pad - t, np.int32)])
    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    layer = _layer_fn(tc["n_heads"], tc.get("n_kv_heads") or tc["n_heads"],
                      float(tc.get("rope_theta", 1e4)),
                      {"kv_float8": "kv", "matmul_float8": "matmul"}.get(
                          fault))
    n_passes = int(tc["n_passes"]) - (fault == "one_pass_short")
    out, planes = [], {}
    with jax.default_matmul_precision("highest"):
        x = put(params["tok_emb"])[put(tokens)].astype(jnp.float32)
        ln_f = put(params["ln_f_scale"]).astype(jnp.float32)
        for r in range(n_passes):
            for i in range(tc["n_layers"]):
                w = jax.tree.map(lambda a: put(a[i]), params["layers"])
                kv_in = planes.get(i) if fault == "previous_plane" else None
                x, kv = layer(x, t, w, kv_in)
                if fault == "previous_plane":
                    planes[i] = kv
            x = _rms(x, ln_f)
            out.append(x)
    return out


def forward(params, tc, tokens, seg=None, device=None, fault=None):
    """The LAST pass's normed stream ``[T_pad, D]`` float32 — what the
    served logits are the head of.  ``seg`` is not this model's (one
    document a sequence)."""
    if seg is not None:
        raise ValueError("reference_ouro: no packed documents")
    return passes(params, tc, tokens, device=device, fault=fault)[-1]


@functools.lru_cache(maxsize=None)
def _head_fn():
    import jax
    import jax.numpy as jnp

    def head(normed, table, positions):
        # The vocabulary in blocks: the table as float32 is 403 MB at
        # the published size, next to an engine that fills the chip.
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


def all_passes(params, tc, tokens, fault=None):
    """``(logits [R, T, V], exit_probs [R, T])`` float32 numpy: every
    pass's logits and the exit distribution over the passes."""
    import jax
    import jax.numpy as jnp

    t = len(tokens)
    streams = passes(params, tc, tokens, fault=fault)
    logits = np.stack([logits_at(params, x, np.arange(t)) for x in streams])
    with jax.default_matmul_precision("highest"):
        lam = np.stack([np.asarray(jax.nn.sigmoid(
            x[:t] @ jnp.asarray(params["exit_w"], jnp.float32)
            + jnp.asarray(params["exit_b"], jnp.float32)))
            for x in streams])
    stay = np.cumprod(1.0 - lam[:-1], axis=0)
    before = np.concatenate([np.ones_like(lam[:1]), stay])
    return logits, np.concatenate([lam[:-1] * before[:-1], before[-1:]])


def check_serving(ctx, params, finished, fault=None):
    """A seeded sample of finished requests, teacher-forced through the
    reference over prompt + output (as ``reference.check_serving``).
    Every token the engine chose lies within ``logit_tol`` of the
    reference's best logit at its position, and over all checked
    tokens the MEAN distance to the best logit is under
    ``mean_gap_tol`` — the steadier of the two limits: the worst of a
    thousand tokens is a tail, the mean is not.  (``reference.py``'s
    third rule, the first token IS the reference's best unless its two
    best logits are closer than the tolerance, says nothing here: the
    two best of ~N(0, 1) logits over the vocabulary lie ~0.2 apart,
    under any tolerance 192 layer applications in bfloat16 allow, so
    it would always pass.  A first token is held like every other.)
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
    pick = rng.choice(len(pool), size=min(int(spec["requests"]), len(pool)),
                      replace=False)
    worst, hits, total, gaps, bad = 0.0, 0, 0, 0.0, []
    for j in pick:
        r = pool[int(j)]
        p, n = len(r.prompt), len(r.tokens)
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
    return {"ok": not bad, "requests": len(pick), "tokens": total,
            "argmax_of_reference": hits,
            "worst_gap_to_best_logit": worst,
            "mean_gap_to_best_logit": mean_gap, "logit_tol": tol,
            "mean_gap_tol": mean_tol,
            "failures": bad[:5]}
