"""The plain reference of ``brumby-14b-base_l8``
(``configs/brumby-14b-base_l8.json``): Brumby-14B-Base's block — Qwen3's
with every layer's softmax attention replaced by power retention of
degree 2 (arXiv:2507.04239) — in ``jax.numpy``, float32, ``highest``
matmul precision, in the ATTENTION FORM: no kernel, no cache, no
state.  The program computes the same function through a running state
(recurrent and chunked forms); here every query meets every earlier key
again, so the comparison is between two derivations and not two copies
of one.  It shares no code with ``distkeras_tpu``; from the program it
takes only the weights (``tok_emb``, ``head``, ``ln_f_scale``,
``layers/retention.dense/...`` stacked on a leading axis).

    x = tok_emb[tokens];  eps = norm_eps;  no bias but the gate's
    for l in 0..L-1:
      h = rms(x)·ln1_l
      q, k, v = h·wqkv_l  (q then k then v, a head's columns together)
      q = rms_head(q)·q_scale_l;  k = rms_head(k)·k_scale_l
      rotate q, k by pos · theta^(-i/half), halves split
      log g = log_sigmoid(h·wg_l + bg_l)          one scalar a K/V head
      A_ij = (q_i·k_j / sqrt(head))^2 · exp(sum_{m=j+1..i} log g_m), j <= i
      a_i = sum_j A_ij v_j / (sum_j A_ij + 1e-6),  G query heads a K/V head
      x = x + a·wo_l
      x = x + (silu(rms(x)·ln2_l·w1) * (rms(x)·ln2_l·w3))·w2
    logits = rms(x)·ln_f · head^T

Positions go through a layer a block of Q_BLOCK at a time, keys a block
of K_BLOCK at a time up to the query block's own (there is no softmax:
the sums over keys simply add up), wide matrices COLS columns at a time
and the vocabulary in blocks, so that a 24k-token prompt fits beside an
engine that fills the chip.  Every program has ONE shape whatever the
sequence's length (keys lie in a buffer of ``s_max`` positions, the
head takes 256 rows): a run compiles a handful of programs, not some
for every request it checks.

Exports ``forward``, ``logits_at``, ``gaps_at``, ``check_serving``.
``fault=`` computes a deliberately WRONG model, for the tests and the
readings that show the comparison is tight (FAULTS):

``degree_1``        ``|q·k / sqrt(head)|`` for its square
``no_gate``         ``g = 1``: nothing decays
``no_normaliser``   the sum over keys is not divided
``state_bf16``      the RECURRENT form, its state (the full ``[head,
                    head, head]`` tensor a K/V head) rounded to
                    bfloat16 after every STATE_BLOCK positions
``kv_float8``       keys and values rounded to float8_e4m3
``matmul_float8``   both operands of every product rounded to it
``no_rope``         q and k are not rotated
``wrong_kv_head``   a query head reads the K/V head after its own
``stale_state``     the sequence is computed after ``carry`` (another
                    request's tokens) with positions starting again at
                    0: a lane whose earlier occupant's state was not
                    cleared
"""

from __future__ import annotations

import functools
import math

import numpy as np

Q_BLOCK = 256
K_BLOCK = 2048
COLS = 2048
STATE_BLOCK = 16   # positions between two roundings of ``state_bf16``
EPS = 1e-6
CARRY_MAX = 1024     # tokens of an earlier occupant a stale state keeps
FAULTS = (None, "degree_1", "no_gate", "no_normaliser", "state_bf16",
          "kv_float8", "matmul_float8", "no_rope", "wrong_kv_head",
          "stale_state")


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


def _spec(tc):
    """The hashable part of ``transformer_config`` a layer needs."""
    return {"n_heads": tc["n_heads"],
            "n_kv": tc.get("n_kv_heads") or tc["n_heads"],
            "theta": float(tc.get("rope_theta", 1e4)),
            "eps": float(tc.get("norm_eps", 1e-6))}


def _qkvg(h, pos, w, spec, mm, f8, fault):
    """q, k, v (heads split out) and the gate's logarithm ``[T, kv]``
    of one block of normed positions ``h``."""
    import jax
    import jax.numpy as jnp

    nh, nkv, t = spec["n_heads"], spec["n_kv"], h.shape[0]
    qkv = mm(h, w["attn"]["wqkv"])
    hd = qkv.shape[-1] // (nh + 2 * nkv)
    q = qkv[:, :nh * hd].reshape(t, nh, hd)
    k = qkv[:, nh * hd:(nh + nkv) * hd].reshape(t, nkv, hd)
    v = qkv[:, (nh + nkv) * hd:].reshape(t, nkv, hd)
    q = _rms(q, w["attn"]["q_scale"], spec["eps"])
    k = _rms(k, w["attn"]["k_scale"], spec["eps"])
    if fault != "no_rope":
        q, k = _rotate(q, pos, spec["theta"]), _rotate(k, pos, spec["theta"])
    if fault in ("kv_float8", "matmul_float8"):
        k, v = f8(k), f8(v)
    if fault == "matmul_float8":
        q = f8(q)
    logg = jax.nn.log_sigmoid(
        h @ w["attn"]["wg"].astype(jnp.float32)
        + w["attn"]["bg"].astype(jnp.float32))
    if fault == "no_gate":
        logg = jnp.zeros_like(logg)
    return q, k, v, logg


def _weights(score, fault):
    import jax.numpy as jnp

    return jnp.abs(score) if fault == "degree_1" else jnp.square(score)


@functools.lru_cache(maxsize=None)
def _layer_fns(spec_items, fault):
    """The jitted programs of a layer over one block of Q_BLOCK
    positions (``group``: the stacked leaves as the program holds
    them, ``at``: this layer's index — cut out in here, where the
    compiler reads a layer's slice in place):

    ``kv(x, pos, i, keys, values, logg, group, at)`` -> the three
    buffers ``[S, kv, ...]`` (donated) with block ``i``'s keys, values
    and gates written in;
    ``block(x, pos, i, keys, values, cum, n_real, group, at)`` -> the
    block after the layer, its queries meeting ``keys``/``values [S,
    kv, head]`` as far as their own block under ``cum [S, kv]``, the
    running sum of the gates' logarithms;
    ``recur(x, pos, i, state, n_real, group, at)`` -> ``(block, state)``:
    the same through a state carried from STATE_BLOCK positions to the
    next and rounded to bfloat16 between them (``state_bf16`` only)."""
    import jax
    import jax.numpy as jnp

    spec = dict(spec_items)
    float8 = {"kv_float8": "kv", "matmul_float8": "matmul"}.get(fault)
    mm, f8 = _products(float8)
    g = spec["n_heads"] // spec["n_kv"]
    layer_of = lambda group, at: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False),
        group)
    # The K/V head a group of query heads reads.
    src = lambda c: (c + 1) % spec["n_kv"] if fault == "wrong_kv_head" else c

    def kv(x, pos, i, keys, values, logg, group, at):
        w = layer_of(group, at)
        new = _qkvg(_rms(x, w["ln1_scale"], spec["eps"]), pos, w, spec, mm,
                    f8, fault)[1:]
        return tuple(jax.lax.dynamic_update_slice_in_dim(
            buf, blk, i * Q_BLOCK, axis=0)
            for buf, blk in zip((keys, values, logg), new))

    def finish(x, a, w):
        """The output projection, the residual sums, the feed-forward."""
        x = x + mm(a, w["attn"]["wo"])
        h = _rms(x, w["ln2_scale"], spec["eps"])
        return x + _gated(h, w["ffn"]["w1"], w["ffn"]["w3"], w["ffn"]["w2"],
                          mm)

    def divide(num, den):
        return num if fault == "no_normaliser" else num / (den + EPS)[..., None]

    def block(x, pos, i, keys, values, cum, n_real, group, at):
        w = layer_of(group, at)
        q = _qkvg(_rms(x, w["ln1_scale"], spec["eps"]), pos, w, spec, mm, f8,
                  fault)[0]
        idx = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        scale = 1.0 / math.sqrt(q.shape[-1])

        def head(c):          # one K/V head and the G query heads on it
            qc = jax.lax.dynamic_slice_in_dim(q, c * g, g, axis=1)
            kc, vc, cc = keys[:, src(c)], values[:, src(c)], cum[:, src(c)]
            mine = jax.lax.dynamic_slice_in_dim(cc, i * Q_BLOCK, Q_BLOCK)

            def keys_block(j, acc):
                kpos = j * K_BLOCK + jnp.arange(K_BLOCK)
                cut = lambda a: jax.lax.dynamic_slice_in_dim(
                    a, j * K_BLOCK, K_BLOCK)
                ok = (kpos[None, :] <= idx[:, None]) & (kpos[None, :] < n_real)
                decay = jnp.exp(jnp.where(
                    ok, mine[:, None] - cut(cc)[None, :], -jnp.inf))
                a = _weights(jnp.einsum("qgk,sk->gqs", qc, cut(kc)) * scale,
                             fault) * decay[None]
                if float8 == "matmul":
                    a = f8(a)
                return (acc[0] + jnp.einsum("gqs,sk->qgk", a, cut(vc)),
                        acc[1] + a.sum(axis=-1).T)

            zero = (jnp.zeros((Q_BLOCK, g, q.shape[-1]), jnp.float32),
                    jnp.zeros((Q_BLOCK, g), jnp.float32))
            # Key blocks up to the one that holds the block's last query.
            return divide(*jax.lax.fori_loop(
                0, (i * Q_BLOCK + Q_BLOCK - 1) // K_BLOCK + 1, keys_block,
                zero))

        a = jax.lax.map(head, jnp.arange(spec["n_kv"]))      # [kv, Q, G, hd]
        return finish(x, jnp.moveaxis(a, 0, 1).reshape(Q_BLOCK, -1), w)

    def recur(x, pos, i, state, n_real, group, at):
        w = layer_of(group, at)
        q, k, v, logg = _qkvg(_rms(x, w["ln1_scale"], spec["eps"]), pos, w,
                              spec, mm, f8, fault)
        d = q.shape[-1]
        real = i * Q_BLOCK + jnp.arange(Q_BLOCK) < n_real
        logg = jnp.where(real[:, None], logg, 0.0)
        bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        # [Q_BLOCK, ...] -> [Q_BLOCK / STATE_BLOCK, STATE_BLOCK, ...]
        parts = lambda a: a.reshape((-1, STATE_BLOCK) + a.shape[1:])

        def head(args):
            c, state = args            # s [hd, hd, hd]: S[a, b, v]; z [hd, hd]
            qc = jax.lax.dynamic_slice_in_dim(q, c * g, g, axis=1)

            def part(state, xs):
                s, z = state
                qs, ks, vs, ls, rs = xs
                cc = jnp.cumsum(ls)
                causal = jnp.tril(jnp.ones((STATE_BLOCK,) * 2, bool)) & rs[None]
                decay = jnp.exp(jnp.where(causal, cc[:, None] - cc[None, :],
                                          -jnp.inf))
                a = jnp.square(jnp.einsum("qgk,sk->gqs", qs, ks)
                               / math.sqrt(d)) * decay[None]
                grow = jnp.exp(cc)
                pq = jnp.einsum("qga,qgb->qgab", qs, qs) / math.sqrt(d)
                num = (jnp.einsum("gqs,sk->qgk", a, vs) + grow[:, None, None]
                       * jnp.einsum("qgab,abv->qgv", pq, s))
                den = a.sum(axis=-1).T + grow[:, None] * jnp.einsum(
                    "qgab,ab->qg", pq, z)
                left = jnp.where(rs, jnp.exp(cc[-1] - cc), 0.0)
                pk = (jnp.einsum("sa,sb->sab", ks, ks) / math.sqrt(d)
                      * left[:, None, None])
                s = bf16(grow[-1] * s + jnp.einsum("sab,sv->abv", pk, vs))
                z = bf16(grow[-1] * z + pk.sum(axis=0))
                return (s, z), num / (den + EPS)[..., None]

            state, y = jax.lax.scan(part, state, tuple(parts(a) for a in (
                qc, k[:, c], v[:, c], logg[:, c], real)))
            return y.reshape((Q_BLOCK,) + y.shape[2:]), state

        a, state = jax.lax.map(head, (jnp.arange(spec["n_kv"]), state))
        return finish(x, jnp.moveaxis(a, 0, 1).reshape(Q_BLOCK, -1), w), state

    return (jax.jit(kv, donate_argnums=(3, 4, 5)), jax.jit(block),
            jax.jit(recur))


@functools.lru_cache(maxsize=None)
def _stream_fns(eps):
    """``cum(logg, n_real)``: the running sum of the real positions'
    gates; ``norm(x, scale)``: the final norm of one block."""
    import jax
    import jax.numpy as jnp

    def cum(logg, n_real):
        real = jnp.arange(logg.shape[0])[:, None] < n_real
        return jnp.cumsum(jnp.where(real, logg, 0.0), axis=0)

    return jax.jit(cum), jax.jit(lambda x, scale: _rms(x, scale, eps))


def _pad_to(n, block):
    return n + (-n) % block


def _check(tc):
    want = {"ffn_gated": True, "tie_head": False, "fused_qkv": True,
            "rope": True, "qk_norm": True}
    for key, value in want.items():
        if tc.get(key) != value:
            raise ValueError(
                f"reference_brumby is Brumby's reference: "
                f"transformer_config[{key!r}] must be {value!r}, got "
                f"{tc.get(key)!r}")
    if set(tc.get("layer_types") or ()) != {"retention"} or set(
            tc.get("ffn_types") or ("dense",)) != {"dense"}:
        raise ValueError("reference_brumby: every layer is a retention "
                         "layer with a dense feed-forward")


def forward(params, tc, tokens, seg=None, device=None, fault=None,
            carry=None, keep_from=0, s_max=0):
    """The normed stream ``[T_pad - keep_from, D]`` float32 (numpy) of
    one sequence from position ``keep_from`` on — what the logits are
    the head of.  ``carry`` (``stale_state`` only): tokens that went
    through the lane before; the stream returned is still the
    sequence's own positions.  Keys lie in buffers of ``s_max``
    positions, or of the sequence's own length where that is more,
    rounded up to whole K_BLOCKs: the layer's programs are compiled
    once a buffer length, so a caller with many sequences names the
    longest."""
    import jax
    import jax.numpy as jnp

    if seg is not None:
        raise ValueError("reference_brumby: no packed documents")
    _check(tc)
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    tokens = np.asarray(tokens, np.int32)
    pos = np.arange(len(tokens))
    lead = 0
    if fault == "stale_state" and carry is not None and len(carry):
        carry = np.asarray(carry, np.int32)[-CARRY_MAX:]
        lead = len(carry)
        tokens = np.concatenate([carry, tokens])
        pos = np.concatenate([np.arange(lead), pos])
    t = len(tokens)
    t_pad = _pad_to(t, Q_BLOCK)
    s_len = _pad_to(max(t_pad, s_max), K_BLOCK)
    tokens = np.concatenate([tokens, np.zeros(t_pad - t, np.int32)])
    pos = np.concatenate([pos, np.zeros(t_pad - t, pos.dtype)]).astype(
        np.int32)
    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    spec = _spec(tc)
    hd = tc.get("d_head") or tc["d_model"] // tc["n_heads"]
    blocks = range(0, t_pad, Q_BLOCK)
    with jax.default_matmul_precision("highest"):
        emb = put(params["tok_emb"])
        # The stream waits on the HOST between layers, a block at a
        # time on the device.
        x = [np.asarray(emb[put(tokens[i:i + Q_BLOCK])].astype(jnp.float32))
             for i in blocks]
        pos = [put(pos[i:i + Q_BLOCK]) for i in blocks]
        group = jax.tree.map(put, params["layers"]["retention.dense"])
        kv_fn, block_fn, recur_fn = _layer_fns(tuple(sorted(spec.items())),
                                               fault)
        cum_fn, norm_fn = _stream_fns(spec["eps"])
        # Positions past the sequence keep what an earlier layer wrote
        # (or zeros): finite, and masked out by ``n_real``.
        kv = tuple(put(np.zeros((s_len,) + tail, np.float32)) for tail in (
            (spec["n_kv"], hd), (spec["n_kv"], hd), (spec["n_kv"],)))
        for at in range(tc["n_layers"]):
            if fault == "state_bf16":
                state = (jnp.zeros((spec["n_kv"], hd, hd, hd), jnp.float32),
                         jnp.zeros((spec["n_kv"], hd, hd), jnp.float32))
                for i in range(len(x)):
                    out, state = recur_fn(put(x[i]), pos[i], i, state, t,
                                          group, at)
                    x[i] = np.asarray(out)
                continue
            for i, xb in enumerate(x):
                kv = kv_fn(put(xb), pos[i], i, *kv, group, at)
            cum = cum_fn(kv[2], t)
            for i in range(len(x)):
                x[i] = np.asarray(block_fn(put(x[i]), pos[i], i, kv[0], kv[1],
                                           cum, t, group, at))
        scale = put(params["ln_f_scale"])
        return np.concatenate([np.asarray(norm_fn(put(xb), scale))
                               for xb in x])[lead + keep_from:]


HEAD_ROWS = 256    # positions the head takes at a time


def _vocab_blocks(v):
    """Blocks the vocabulary is taken in: the most, up to 64, that
    divide it (151936 = 64 x 2374)."""
    return max(n for n in (1, 2, 4, 8, 16, 32, 64) if v % n == 0)


@functools.lru_cache(maxsize=None)
def _head_fn(gaps: bool):
    import jax
    import jax.numpy as jnp

    def head(h, table, chosen):
        # The vocabulary in blocks: the table's float32 copy is never
        # whole next to an engine that fills the chip.
        blocks = table.reshape(_vocab_blocks(table.shape[0]), -1,
                               table.shape[-1])
        if not gaps:
            out = jax.lax.map(lambda tb: h @ tb.astype(jnp.float32).T, blocks)
            return jnp.moveaxis(out, 0, 1).reshape(h.shape[0], -1)
        rows = blocks.shape[1]

        def one(acc, args):
            j, tb = args
            lg = h @ tb.astype(jnp.float32).T                  # [n, rows]
            at = chosen - j * rows
            mine = jnp.take_along_axis(
                lg, jnp.clip(at, 0, rows - 1)[:, None], axis=1)[:, 0]
            return (jnp.maximum(acc[0], lg.max(axis=1)),
                    jnp.where((at >= 0) & (at < rows), mine, acc[1]),
                    acc[2] & jnp.isfinite(lg).all(axis=1)), None

        n = h.shape[0]
        best, mine, finite = jax.lax.scan(
            one, (jnp.full((n,), -jnp.inf), jnp.zeros((n,)),
                  jnp.ones((n,), bool)),
            (jnp.arange(blocks.shape[0]), blocks))[0]
        return jnp.where(finite, best - mine, jnp.nan)

    return jax.jit(head)


def _head_over(params, normed, positions, chosen, gaps, device):
    """The head over ``normed[positions]``, HEAD_ROWS rows at a time
    (one program whatever the stream's length), on the host between."""
    import jax
    import jax.numpy as jnp

    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    n = len(positions)
    rows = np.zeros((_pad_to(n, HEAD_ROWS), normed.shape[-1]), np.float32)
    rows[:n] = np.asarray(normed)[np.asarray(positions, np.int32)]
    chosen = np.concatenate([np.asarray(chosen, np.int32),
                             np.zeros(len(rows) - n, np.int32)])
    with jax.default_matmul_precision("highest"):
        table = put(params["head"])
        out = [np.asarray(_head_fn(gaps)(put(rows[i:i + HEAD_ROWS]), table,
                                         put(chosen[i:i + HEAD_ROWS])))
               for i in range(0, len(rows), HEAD_ROWS)]
    return np.concatenate(out)[:n]


def logits_at(params, normed, positions, device=None):
    """Float32 logits ``[len(positions), V]`` (numpy) of the untied
    head over an already-normed stream: for small sizes (the tests)."""
    return _head_over(params, normed, positions,
                      np.zeros(len(positions), np.int32), False, device)


def gaps_at(params, normed, positions, chosen, device=None):
    """``best logit - the chosen token's logit`` at each position
    (numpy ``[len(positions)]``; NaN where a logit is not finite),
    reduced on the device: the logits of 1,536 positions over 151,936
    entries would be 0.9 GB."""
    return _head_over(params, normed, positions, chosen, True, device)


# Which request sat in which lane, in order: the serving driver fills
# it (``drivers/serve_retention.py``), ``check_serving`` reads it.
LANE_HISTORY: dict[int, list] = {}


def earlier_occupant(r):
    """The request that held ``r``'s lane before it, or None."""
    for held in LANE_HISTORY.values():
        at = next((i for i, q in enumerate(held) if q is r), None)
        if at is not None:
            return held[at - 1] if at else None
    return None


def check_serving(ctx, params, finished, fault=None):
    """A seeded sample of finished requests, teacher-forced through the
    reference over prompt + output (as ``reference_kexaone.
    check_serving``: logits, not tokens; chunked prefill through the
    state and then decode against this full forward).  Every token the
    engine chose lies within ``logit_tol`` of the reference's best
    logit at its position, and over all checked tokens the MEAN
    distance to the best logit is under ``mean_gap_tol``.

    The sample holds at least one request whose prompt is longer than
    ``min_long`` (a state carried through that many positions of
    chunks) and at least one whose lane had an EARLIER OCCUPANT (a
    stale state would show): the longest finished one, and the first
    finished one with an earlier occupant, take the last places of the
    sample if the draw holds none.  ``fault`` plants a FAULTS entry in
    the reference: the comparison then has to come out not ``ok``."""
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
    worst, hits, total, gaps, bad = 0.0, 0, 0, 0.0, []
    longest_checked, reused_checked = 0, 0
    # One buffer length for every sequence of every run: the longest
    # the traffic can send (and a stale occupant's carry before it).
    s_max = sum(int(ctx.mix.get(key, {}).get("max", 0))
                for key in ("prompt_len", "output_len")) + CARRY_MAX * (
                    fault == "stale_state")
    for j in pick:
        r = pool[j]
        p, n = len(r.prompt), len(r.tokens)
        longest_checked = max(longest_checked, p)
        before = earlier_occupant(r)
        reused_checked += before is not None
        carry = None
        if fault == "stale_state":
            # The lane's earlier occupant; a request whose lane had
            # none is given the request finished before it.
            before = before or pool[j - 1]
            carry = np.concatenate([before.prompt,
                                    np.asarray(before.tokens, np.int32)])
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        normed = forward(params, tc, seq[:-1], fault=fault, carry=carry,
                         keep_from=p - 1, s_max=s_max)
        gap = gaps_at(params, normed, np.arange(n), r.tokens)
        if not np.isfinite(gap).all():
            bad.append({"request": r.idx, "why": "non-finite logits"})
            continue
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
    if longest_checked <= long:
        bad.append({"why": "no checked prompt carried a state that far",
                    "longest_prompt": longest_checked, "min_long": long})
    if spec.get("need_reused_lane") and not reused_checked:
        bad.append({"why": "no checked request's lane had an earlier "
                    "occupant"})
    return {"ok": not bad, "requests": len(pick), "tokens": total,
            "argmax_of_reference": hits,
            "worst_gap_to_best_logit": worst,
            "mean_gap_to_best_logit": mean_gap, "logit_tol": tol,
            "mean_gap_tol": mean_tol, "longest_prompt": longest_checked,
            "reused_lanes_checked": reused_checked, "failures": bad[:5]}
