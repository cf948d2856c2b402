"""The plain reference: the block's equations as the configuration file
states them, in ``jax.numpy``, float32, ``highest`` matmul precision —
no kernel, no cache, no batching, one layer's weights at a time.  It
shares no code with ``distkeras_tpu``; from the program it takes only
the weights (the tree's layout: ``tok_emb``, ``pos_emb``,
``ln_f_scale``, ``layers/{ln1_scale, ln2_scale, attn/{wq,wk,wv,wo},
ffn/{w1,w2}}``, per-layer leaves stacked on a leading axis).

    x = tok_emb[tokens] (+ pos_emb[positions] when not rotary)
    per layer:  h = rms(x) * ln1;  q,k,v = h·wq, h·wk, h·wv
                rotary: rotate q,k by pos * theta^(-i/half), halves split
                k,v repeated to the query heads (grouped/multi-query)
                a = softmax(q·k / sqrt(head) over the causal, windowed,
                            same-document positions) · v
                x = x + a·wo;  h = rms(x) * ln2
                x = x + gelu_tanh(h·w1)·w2
    logits = (rms(x) * ln_f) · tok_emb^T
    rms(x) = x / sqrt(mean(x^2) + 1e-6)

``check_serving`` and ``check_training`` are the comparisons that
decide ``correct``; their tolerances are read from the cell's file.
"""

from __future__ import annotations

import functools
import math

import numpy as np

EPS = 1e-6
Q_BLOCK = 512


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
def _layer_fn(n_heads, n_kv, rope, theta, window):
    """One block over one sequence ``x [T, D]`` (T a multiple of
    Q_BLOCK); ``seg [T]`` int document ids, 0 = padding."""
    import jax
    import jax.numpy as jnp

    def layer(x, seg, w):
        f32 = lambda a: a.astype(jnp.float32)
        t = x.shape[0]
        pos = jnp.arange(t)
        h = _rms(x, f32(w["ln1_scale"]))
        q = jnp.einsum("td,dhk->thk", h, f32(w["attn"]["wq"]))
        k = jnp.einsum("td,dhk->thk", h, f32(w["attn"]["wk"]))
        v = jnp.einsum("td,dhk->thk", h, f32(w["attn"]["wv"]))
        if rope:
            q, k = _rotate(q, pos, theta), _rotate(k, pos, theta)
        g = n_heads // n_kv
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        scale = 1.0 / math.sqrt(q.shape[-1])

        def q_block(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
            pi = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            si = jax.lax.dynamic_slice_in_dim(seg, i * Q_BLOCK, Q_BLOCK, 0)
            s = jnp.einsum("qhk,shk->hqs", qi, k) * scale
            ok = (pos[None, :] <= pi[:, None]) & (
                seg[None, :] == si[:, None]) & (si[:, None] != 0)
            if window is not None:
                ok &= pi[:, None] - pos[None, :] < window
            s = jnp.where(ok[None], s, -jnp.inf)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
            p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
            return jnp.einsum("hqs,shk->qhk", p, v)

        a = jax.lax.map(q_block, jnp.arange(t // Q_BLOCK))
        a = a.reshape(t, n_heads, -1)
        x = x + jnp.einsum("thk,hkd->td", a, f32(w["attn"]["wo"]))
        h = _rms(x, f32(w["ln2_scale"]))
        y = jax.nn.gelu(h @ f32(w["ffn"]["w1"]), approximate=True)
        return x + y @ f32(w["ffn"]["w2"])

    return jax.jit(layer)


def _pad_to(n, block):
    return n + (-n) % block


@functools.lru_cache(maxsize=None)
def _embed_fn(rope):
    import jax
    import jax.numpy as jnp

    def embed(tok_emb, pos_emb, tokens, positions):
        x = tok_emb[tokens].astype(jnp.float32)
        if not rope:
            x = x + pos_emb[positions].astype(jnp.float32)
        return x

    return jax.jit(embed)


@functools.lru_cache(maxsize=None)
def _head_fn():
    import jax
    import jax.numpy as jnp

    def head(hidden, ln_f, tok_emb, positions):
        h = _rms(hidden[positions], ln_f.astype(jnp.float32))
        return h @ tok_emb.astype(jnp.float32).T

    return jax.jit(head)


def forward(params, tc, tokens, seg=None, device=None):
    """Hidden states before the final norm, ``[T_pad, D]`` float32, for
    one sequence.  Every shape is padded to a multiple of Q_BLOCK, so
    that runs with other lengths find their programs in the compile
    cache; padding (document id 0) attends nothing and is never read.
    ``tc`` is the configuration file's ``transformer_config`` dict."""
    import jax
    import jax.numpy as jnp

    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    t_pad = _pad_to(t, Q_BLOCK)
    seg = np.ones(t, np.int32) if seg is None else np.asarray(seg, np.int32)
    seg = np.concatenate([seg, np.zeros(t_pad - t, np.int32)])
    tokens = np.concatenate([tokens, np.zeros(t_pad - t, np.int32)])
    positions = np.minimum(np.arange(t_pad), tc["max_len"] - 1)
    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    layer = _layer_fn(tc["n_heads"], tc.get("n_kv_heads") or tc["n_heads"],
                      bool(tc.get("rope")), float(tc.get("rope_theta", 1e4)),
                      tc.get("attention_window"))
    with jax.default_matmul_precision("highest"):
        x = _embed_fn(bool(tc.get("rope")))(
            put(params["tok_emb"]), put(params.get("pos_emb", 0.0)),
            put(tokens), put(positions))
        seg = put(seg)
        for i in range(tc["n_layers"]):
            w = jax.tree.map(lambda a: put(a[i]), params["layers"])
            x = layer(x, seg, w)
    return x


def logits_at(params, hidden, positions, device=None):
    """Float32 logits ``[len(positions), V]`` (numpy) of the final norm
    and the tied head at the given positions, computed in padded
    blocks of 256 positions."""
    import jax
    import jax.numpy as jnp

    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    positions = np.asarray(positions, np.int32)
    n = len(positions)
    padded = np.concatenate([positions,
                             np.zeros(_pad_to(n, 256) - n, np.int32)])
    with jax.default_matmul_precision("highest"):
        out = _head_fn()(hidden, put(params["ln_f_scale"]),
                         put(params["tok_emb"]), put(padded))
    return np.asarray(out)[:n]


def check_serving(ctx, params, finished):
    """A seeded sample of finished requests, teacher-forced through the
    reference over prompt + output.  Every token the engine chose lies
    within ``logit_tol`` of the reference's best logit at its position,
    and the first token IS the reference's best unless its two best
    logits are closer than that."""
    spec = ctx.cell["correct"]
    tol = float(spec["logit_tol"])
    tc = ctx.conf["transformer_config"]
    rng = np.random.default_rng(ctx.seed)
    pool = [r for r in finished if r.tokens]
    if not pool:
        return {"ok": False, "why": "no finished request to check"}
    pick = rng.choice(len(pool), size=min(int(spec["requests"]), len(pool)),
                      replace=False)
    worst, first_ok, hits, total, bad = 0.0, 0, 0, 0, []
    for j in pick:
        r = pool[int(j)]
        p, n = len(r.prompt), len(r.tokens)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        hidden = forward(params, tc, seq[:-1])
        lg = logits_at(params, hidden, np.arange(p - 1, p - 1 + n))
        if not np.isfinite(lg).all():
            bad.append({"request": r.idx, "why": "non-finite logits"})
            continue
        best = lg.max(-1)
        gap = best - lg[np.arange(n), np.asarray(r.tokens)]
        worst = max(worst, float(gap.max()))
        hits += int((gap == 0).sum())
        total += n
        top2 = np.sort(lg[0])[-2:]
        if gap[0] == 0 or top2[1] - top2[0] < tol:
            first_ok += 1
        else:
            bad.append({"request": r.idx, "why": "first token is not the "
                        "reference's best", "gap": float(gap[0])})
        if gap.max() >= tol:
            bad.append({"request": r.idx, "token": int(gap.argmax()),
                        "gap": float(gap.max()), "prompt_len": p})
    return {"ok": not bad, "requests": len(pick), "tokens": total,
            "argmax_of_reference": hits, "first_tokens_ok": first_ok,
            "worst_gap_to_best_logit": worst, "logit_tol": tol,
            "failures": bad[:5]}


@functools.lru_cache(maxsize=None)
def _nll_fn():
    import jax
    import jax.numpy as jnp

    def nll(hidden, ln_f, tok_emb, targets, valid):
        lg = _rms(hidden, ln_f.astype(jnp.float32)) @ tok_emb.astype(
            jnp.float32).T
        m = lg.max(-1)
        lse = jnp.log(jnp.exp(lg - m[:, None]).sum(-1)) + m
        tgt = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        return jnp.where(valid, lse - tgt, 0.0).sum()

    return jax.jit(nll)


def loss(params, tc, rows, segs, device=None):
    """Mean next-token loss over the targets that lie in the same
    document as their input (padding and document boundaries train
    nothing) — the loss ``LMTrainer`` reports for packed rows."""
    import jax
    import jax.numpy as jnp

    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    total, count = 0.0, 0
    for row, seg in zip(rows, segs):
        row, seg = np.asarray(row), np.asarray(seg)
        hidden = forward(params, tc, row[:-1], seg[:-1], device=device)
        pad = hidden.shape[0] - (len(row) - 1)
        valid = np.concatenate([(seg[1:] == seg[:-1]) & (seg[:-1] != 0),
                                np.zeros(pad, bool)])
        targets = np.concatenate([row[1:], np.zeros(pad, row.dtype)])
        with jax.default_matmul_precision("highest"):
            total += float(_nll_fn()(
                hidden, put(params["ln_f_scale"]), put(params["tok_emb"]),
                put(targets.astype(np.int32)), put(valid)))
        count += int(valid.sum())
    return total / max(count, 1)


def check_training(ctx, make_params, rows, segs, history, device):
    """The reference's loss at the initial parameters on the first
    step's rows against the trainer's first loss; every loss finite;
    the loss lower at the end than at the start."""
    spec = ctx.cell["correct"]
    tol = float(spec["loss_tol"])
    out = {"loss_tol": tol, "steps": len(history)}
    finite = all(math.isfinite(v) for v in history)
    fell = len(history) > 1 and history[-1] < history[0]
    n = int(spec.get("rows", len(rows)))
    ref = loss(make_params(), ctx.conf["transformer_config"], rows[:n],
               segs[:n], device=device)
    out.update(reference_loss=ref, trainer_loss=history[0] if history else None,
               reference_rows=n, finite=finite, fell=fell,
               first_last=[history[0], history[-1]] if history else None)
    # With fewer rows than the batch the two means are over different
    # targets: the tolerance in the file has to allow for that.
    out["ok"] = bool(finite and fell and history
                     and abs(ref - history[0]) < tol)
    return out
