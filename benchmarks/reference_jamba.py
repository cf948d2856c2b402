"""The plain reference of ``ai21-jamba2-3b`` (``configs/ai21-jamba2-3b.json``):
AI21-Jamba2-3B's block in ``jax.numpy``, float32, ``highest`` matmul
precision — no kernel, no cache, no batching.  A Mamba layer's
recurrence is written as the published sequential scan, one position
after the other; the program computes it in blocks of parallel-prefix
scans and in two Pallas kernels, so the comparison is between two
derivations and not two copies of one.  It shares no code with
``distkeras_tpu``; from the program it takes only the weights
(``tok_emb`` — the head is tied — ``ln_f_scale``, ``layers/ssm.dense/...``
and ``layers/full.dense/...`` stacked on a leading axis), each layer's
cast to float32 inside the program that uses it.

    x = tok_emb[tokens];  eps = norm_eps;  E = ssm_expand · D
    for each layer l (``layer_types``):
      h = rms(x)·ln1_l
      ssm:   [u, z] = h·w_in                          (no bias)
             x_t = silu(conv_b + sum_j conv_w[j] u_{t-K+1+j})   u_{<0} = 0
             [d, B, C] = x·w_x;  d, B, C = rms(d)·dt_scale, rms(B)·b_scale,
                                           rms(C)·c_scale
             Delta = softplus(d·w_dt + b_dt);  A = -exp(A_log)
             s_t = exp(Delta_t A) s_{t-1} + Delta_t B_t x_t    s_{-1} = 0
             y_t = C_t·s_t + D x_t
             a = (y · silu(z))·w_out
      full:  q, k, v = h·wqkv (q then k then v), n_heads queries over
             n_kv_heads keys and values, NO position encoding;
             a = softmax_causal(q k^T / sqrt(head))·v ·wo
      x = x + a
      x = x + (silu(rms(x)·ln2_l·w1) * (rms(x)·ln2_l·w3))·w2
    logits = rms(x)·ln_f · tok_emb^T

Positions go through a layer BLOCK at a time, the state and the conv's
last inputs carried from block to block; every program has ONE shape
whatever the sequence's length (keys lie in a buffer of ``s_max``
positions, the head takes HEAD_ROWS rows): a run compiles a handful of
programs, not some for every request it checks.  Nothing of size
``[T, N, E]`` is ever formed: the scan holds one ``[N, E]`` state.

Exports ``forward``, ``ssm_states``, ``logits_at``, ``gaps_at``,
``check_states``, ``check_serving``.
``fault=`` computes a deliberately WRONG model, for the tests and the
readings that show the comparison is tight (FAULTS):

``matmul_float8``  both operands of every product rounded to float8_e4m3
``state_bf16``     the state rounded to bfloat16 after every position
``stale_state``    every ssm layer starts from the state and conv tail
                   that ``carry`` (another request's tokens) left: a
                   lane whose earlier occupant's state was not cleared
``no_dt_norm``, ``no_b_norm``, ``no_c_norm``
                   that inner RMSNorm left out
``no_d_term``      ``D x`` left out of ``y``
``no_conv_bias``   the conv's bias left out
``conv_shift``     the conv's taps shifted one position back
                   (``conv_w[j]`` meets ``u_{t-K+j}``)
``no_gate``        ``y`` not multiplied by ``silu(z)``
``rope``           the attention layers' q and k rotated (halves split,
                   ``rope_theta``): Jamba's have no position encoding
"""

from __future__ import annotations

import functools
import math

import numpy as np

BLOCK = 512          # positions a layer's program takes at a time
HEAD_ROWS = 256      # positions the head takes at a time
CARRY_MAX = 1024     # tokens of an earlier occupant a stale state keeps
FAULTS = (None, "matmul_float8", "state_bf16", "stale_state", "no_dt_norm",
          "no_b_norm", "no_c_norm", "no_d_term", "no_conv_bias",
          "conv_shift", "no_gate", "rope")


def _rms(x, scale, eps):
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return (x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _spec(tc):
    """The hashable part of ``transformer_config`` a layer needs."""
    return {"n_heads": tc["n_heads"],
            "n_kv": tc.get("n_kv_heads") or tc["n_heads"],
            "head": tc.get("d_head") or tc["d_model"] // tc["n_heads"],
            "theta": float(tc.get("rope_theta", 1e4)),
            "eps": float(tc.get("norm_eps", 1e-6))}


@functools.lru_cache(maxsize=None)
def _layer_fns(spec_items, fault):
    """The jitted programs of a layer over one block of BLOCK positions
    (``group``: the stacked leaves as the program holds them, ``at``:
    this layer's index — cut out in here, in place):

    ``mamba(x, real, state, tail, group, at)`` -> ``(block, state,
    tail)``: an ssm layer and its feed-forward, the first ``real``
    positions of the block carried into the state (``tail``: the conv's
    last K inputs, float32);
    ``kv(x, pos, i, keys, values, group, at)`` -> the buffers ``[S, kv,
    head]`` (donated) with block ``i``'s keys and values written in;
    ``attend(x, pos, i, keys, values, group, at)`` -> the block after an
    attention layer and its feed-forward."""
    import jax
    import jax.numpy as jnp

    spec = dict(spec_items)
    eps = spec["eps"]
    f32 = lambda a: a.astype(jnp.float32)
    f8 = lambda a: f32(f32(a).astype(jnp.float8_e4m3fn))
    mm = ((lambda a, w: f8(a) @ f8(w)) if fault == "matmul_float8"
          else (lambda a, w: f32(a) @ f32(w)))
    layer_of = lambda group, at: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, at, 0, keepdims=False),
        group)
    norm = lambda v, scale, off: v if fault == off else _rms(v, scale, eps)

    def ffn(x, w):
        h = _rms(x, w["ln2_scale"], eps)
        f = w["ffn"]
        return x + mm(jax.nn.silu(mm(h, f["w1"])) * mm(h, f["w3"]), f["w2"])

    def mamba(x, real, state, tail, group, at):
        w = layer_of(group, at)
        m = w["attn"]
        e = m["D"].shape[-1]
        k = m["conv_w"].shape[0]
        n = m["b_scale"].shape[-1]
        r = m["dt_scale"].shape[-1]
        uz = mm(_rms(x, w["ln1_scale"], eps), m["w_in"])
        u, z = uz[:, :e], uz[:, e:]
        live = jnp.arange(x.shape[0]) < real
        # The conv's inputs: the K before the block, then the block's.
        seq = jnp.concatenate([tail, u])                      # [K + L, E]
        back = 1 if fault == "conv_shift" else 0
        acc = sum(f32(m["conv_w"][j]) * jax.lax.dynamic_slice_in_dim(
            seq, 1 + j - back, x.shape[0]) for j in range(k))
        if fault != "no_conv_bias":
            acc = acc + f32(m["conv_b"])
        xs = jax.nn.silu(acc)
        dbc = mm(xs, m["w_x"])
        d = norm(dbc[:, :r], m["dt_scale"], "no_dt_norm")
        b = norm(dbc[:, r:r + n], m["b_scale"], "no_b_norm")
        c = norm(dbc[:, r + n:], m["c_scale"], "no_c_norm")
        delta = jax.nn.softplus(mm(d, m["w_dt"]) + f32(m["b_dt"]))
        a = -jnp.exp(f32(m["A_log"]))                          # [N, E]
        dd = 0.0 if fault == "no_d_term" else f32(m["D"])

        def position(s, row):
            xt, dt, bt, ct, on = row
            s_new = jnp.exp(dt[None] * a) * s + (dt * xt)[None] * bt[:, None]
            if fault == "state_bf16":
                # reduce_precision, not a cast there and back: the TPU
                # compiler drops such a pair (excess precision allowed).
                s_new = jax.lax.reduce_precision(s_new, exponent_bits=8,
                                                 mantissa_bits=7)
            s = jnp.where(on, s_new, s)
            return s, ct @ s + dd * xt

        state, y = jax.lax.scan(position, state, (xs, delta, b, c, live))
        if fault != "no_gate":
            y = y * jax.nn.silu(z)
        x = x + mm(y, m["w_out"])
        tail = jax.lax.dynamic_slice_in_dim(seq, real, k)
        return ffn(x, w), state, tail

    def qkv(x, pos, w):
        nh, nkv, hd = spec["n_heads"], spec["n_kv"], spec["head"]
        out = mm(_rms(x, w["ln1_scale"], eps), w["attn"]["wqkv"])
        q = out[:, :nh * hd].reshape(-1, nh, hd)
        k = out[:, nh * hd:(nh + nkv) * hd].reshape(-1, nkv, hd)
        v = out[:, (nh + nkv) * hd:].reshape(-1, nkv, hd)
        if fault == "rope":
            half = hd // 2
            ang = pos[:, None].astype(jnp.float32) * spec["theta"] ** (
                -jnp.arange(half, dtype=jnp.float32) / half)
            cs, sn = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
            turn = lambda t: jnp.concatenate(
                [t[..., :half] * cs - t[..., half:] * sn,
                 t[..., :half] * sn + t[..., half:] * cs], axis=-1)
            q, k = turn(q), turn(k)
        if fault == "matmul_float8":
            q, k, v = f8(q), f8(k), f8(v)
        return q, k, v

    def kv(x, pos, i, keys, values, group, at):
        _, k, v = qkv(x, pos, layer_of(group, at))
        put = lambda buf, blk: jax.lax.dynamic_update_slice_in_dim(
            buf, blk, i * BLOCK, axis=0)
        return put(keys, k), put(values, v)

    def attend(x, pos, i, keys, values, group, at):
        w = layer_of(group, at)
        q, _, _ = qkv(x, pos, w)
        nh, nkv, hd = spec["n_heads"], spec["n_kv"], spec["head"]
        g = nh // nkv
        qg = q.reshape(q.shape[0], nkv, g, hd)
        score = jnp.einsum("qcgd,scd->cgqs", qg, keys) / math.sqrt(hd)
        mine = i * BLOCK + jnp.arange(x.shape[0])
        seen = jnp.arange(keys.shape[0])[None, :] <= mine[:, None]
        p = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), axis=-1)
        if fault == "matmul_float8":
            p = f8(p)
        o = jnp.einsum("cgqs,scd->qcgd", p, values).reshape(x.shape[0], -1)
        return ffn(x + mm(o, w["attn"]["wo"]), w)

    return (jax.jit(mamba), jax.jit(kv, donate_argnums=(3, 4)),
            jax.jit(attend))


@functools.lru_cache(maxsize=None)
def _norm_fn(eps):
    import jax

    return jax.jit(lambda x, scale: _rms(x, scale, eps))


def _pad_to(n, block):
    return n + (-n) % block


def _check(tc):
    want = {"ffn_gated": True, "tie_head": True, "fused_qkv": True}
    for key, value in want.items():
        if tc.get(key, {"tie_head": True}.get(key)) != value:
            raise ValueError(
                f"reference_jamba is Jamba's reference: transformer_config"
                f"[{key!r}] must be {value!r}, got {tc.get(key)!r}")
    if set(tc.get("layer_types") or ()) - {"ssm", "full"} or set(
            tc.get("ffn_types") or ("dense",)) != {"dense"}:
        raise ValueError("reference_jamba: every layer is an ssm or a full "
                         "attention layer with a dense feed-forward")
    if tc.get("rope") and tc.get("rope_layer_types") != []:
        raise ValueError("reference_jamba: Jamba's attention has no "
                         "position encoding (rope_layer_types: [])")


def _run(params, tc, tokens, fault, states, s_max, device, stop=None):
    """The stream after every layer (the first ``stop``), BLOCK positions
    a block, and the ssm layers' ``(state, tail)`` after the sequence's
    last position."""
    import jax
    import jax.numpy as jnp

    t = len(tokens)
    t_pad = _pad_to(max(t, 1), BLOCK)
    s_len = _pad_to(max(t_pad, s_max), BLOCK)
    tokens = np.concatenate([tokens, np.zeros(t_pad - t, np.int32)])
    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    spec = _spec(tc)
    mamba_fn, kv_fn, attend_fn = _layer_fns(tuple(sorted(spec.items())),
                                            fault)
    blocks = range(0, t_pad, BLOCK)
    emb = put(params["tok_emb"])
    x = [emb[put(tokens[i:i + BLOCK])].astype(jnp.float32) for i in blocks]
    pos = [put(np.arange(i, i + BLOCK, dtype=np.int32)) for i in blocks]
    real = [put(np.int32(min(max(t - i, 0), BLOCK))) for i in blocks]
    m = tc["ssm_expand"] * tc["d_model"]
    out_states, seen = [], {"ssm": 0, "full": 0}
    for kind in tc["layer_types"][:stop]:
        at = seen[kind]
        seen[kind] += 1
        group = params["layers"][f"{kind}.dense"]
        if kind == "ssm":
            state, tail = (states[at] if states is not None else (
                jnp.zeros((tc["ssm_state"], m), jnp.float32),
                jnp.zeros((tc["ssm_conv"], m), jnp.float32)))
            for i in range(len(x)):
                x[i], state, tail = mamba_fn(x[i], real[i], state, tail,
                                             group, at)
            out_states.append((state, tail))
            continue
        keys = jnp.zeros((s_len, spec["n_kv"], spec["head"]), jnp.float32)
        values = jnp.zeros_like(keys)
        for i in range(len(x)):
            keys, values = kv_fn(x[i], pos[i], i, keys, values, group, at)
        x = [attend_fn(x[i], pos[i], i, keys, values, group, at)
             for i in range(len(x))]
    return x, out_states


def forward(params, tc, tokens, device=None, fault=None, carry=None,
            keep_from=0, s_max=0):
    """The normed stream ``[T_pad - keep_from, D]`` float32 (numpy) of
    one sequence from position ``keep_from`` on — what the logits are
    the head of.  ``carry`` (``stale_state`` only): tokens that went
    through the lane before; the ssm layers start from what they left,
    the attention layers see the sequence's own positions only.  Keys
    lie in buffers of ``s_max`` positions, or of the sequence's own
    length where that is more, rounded up to whole BLOCKs."""
    import jax
    import jax.numpy as jnp

    _check(tc)
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    tokens = np.asarray(tokens, np.int32)
    with jax.default_matmul_precision("highest"):
        states = None
        if fault == "stale_state" and carry is not None and len(carry):
            carry = np.asarray(carry, np.int32)[-CARRY_MAX:]
            states = _run(params, tc, carry, None, None, s_max, device)[1]
        x, _ = _run(params, tc, tokens, fault, states, s_max, device)
        put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
        scale = put(params["ln_f_scale"])
        norm = _norm_fn(float(tc.get("norm_eps", 1e-6)))
        return np.concatenate([np.asarray(norm(xb, scale))
                               for xb in x])[keep_from:]


def ssm_states(params, tc, tokens, layers, device=None, fault=None,
               s_max=0):
    """The states ``s [N, E]`` float32 (numpy) that the ssm layers
    ``layers`` (their indices among the ssm layers) hold after the last
    of ``tokens``; only the layers up to the last one named are run."""
    import jax

    _check(tc)
    kinds = tc["layer_types"]
    ssm_at = [i for i, k in enumerate(kinds) if k == "ssm"]
    stop = ssm_at[max(layers)] + 1
    with jax.default_matmul_precision("highest"):
        _, states = _run(params, tc, np.asarray(tokens, np.int32), fault,
                         None, s_max, device, stop=stop)
    return {j: np.asarray(states[j][0]) for j in layers}


def _vocab_blocks(v):
    """Blocks the vocabulary is taken in: the most, up to 64, that
    divide it (65536 = 64 x 1024)."""
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
    """The tied head over ``normed[positions]``, HEAD_ROWS rows at a
    time (one program whatever the stream's length)."""
    import jax
    import jax.numpy as jnp

    put = (lambda a: jax.device_put(a, device)) if device else jnp.asarray
    n = len(positions)
    rows = np.zeros((_pad_to(n, HEAD_ROWS), normed.shape[-1]), np.float32)
    rows[:n] = np.asarray(normed)[np.asarray(positions, np.int32)]
    chosen = np.concatenate([np.asarray(chosen, np.int32),
                             np.zeros(len(rows) - n, np.int32)])
    with jax.default_matmul_precision("highest"):
        table = put(params["tok_emb"])
        out = [np.asarray(_head_fn(gaps)(put(rows[i:i + HEAD_ROWS]), table,
                                         put(chosen[i:i + HEAD_ROWS])))
               for i in range(0, len(rows), HEAD_ROWS)]
    return np.concatenate(out)[:n]


def logits_at(params, normed, positions, device=None):
    """Float32 logits ``[len(positions), V]`` (numpy) of the tied head
    over an already-normed stream: for small sizes (the tests)."""
    return _head_over(params, normed, positions,
                      np.zeros(len(positions), np.int32), False, device)


def gaps_at(params, normed, positions, chosen, device=None):
    """``best logit - the chosen token's logit`` at each position
    (numpy ``[len(positions)]``; NaN where a logit is not finite),
    reduced on the device."""
    return _head_over(params, normed, positions, chosen, True, device)


# Which request sat in which lane, in order: the serving driver fills
# it (``drivers/serve_ssm.py``), ``check_serving`` reads it.
LANE_HISTORY: dict[int, list] = {}


def earlier_occupant(r):
    """The request that held ``r``'s lane before it, or None."""
    for held in LANE_HISTORY.values():
        at = next((i for i, q in enumerate(held) if q is r), None)
        if at is not None:
            return held[at - 1] if at else None
    return None


def check_states(ctx, params, loop, fault=None):
    """The states the ENGINE holds when the run ends, against the states
    the reference computes of the same tokens: a state's rounding, which
    the logits may not tell, shows in the state itself.  Of
    ``correct.state_lanes`` lanes still decoding (the one that has
    processed the most positions, the others by the seed), the ``s`` of
    the ssm layers ``correct.state_layers`` (indices among the ssm
    layers; the first ones: no attention layer before them) after every
    token the lane has processed: its prompt and every token read, the
    last one too where a decode round in flight holds the lane.  The
    number: the largest, over lanes and layers, of ``|engine -
    reference| / |reference|`` (Frobenius), held under
    ``correct.ssm_state_tol``.  ``loop``: the driver's ``Loop`` (its
    engine's ``cache["ssm"]``, lane table and round in flight)."""
    spec = ctx.cell["correct"]
    tol = float(spec["ssm_state_tol"])
    tc = ctx.conf["transformer_config"]
    layers = tuple(int(i) for i in spec["state_layers"])
    eng = loop.engine if loop else None
    flight = getattr(eng, "_inflight", None)
    ahead = {lane for lane, _ in flight[1]} if flight else set()
    held = sorted(((len(r.prompt) + len(r.tokens) - (lane not in ahead), lane)
                   for lane, r in (loop.by_lane.items() if loop else ())
                   if r.tokens), reverse=True)
    if not held:
        return {"ok": False, "ssm_state_tol": tol,
                "why": "no decoding lane whose state could be read"}
    rng = np.random.default_rng([ctx.seed, 1])
    rest = [int(j) for j in rng.permutation(len(held) - 1) + 1]
    pick = [held[j] for j in [0] + rest[:int(spec["state_lanes"]) - 1]]
    s_max = sum(int(ctx.mix.get(key, {}).get("max", 0))
                for key in ("prompt_len", "output_len"))
    slab, worst, each = eng.cache["ssm"], 0.0, []
    for n, lane in pick:
        r = loop.by_lane[lane]
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])[:n]
        want = ssm_states(params, tc, seq, layers,
                          fault=None if fault == "stale_state" else fault,
                          s_max=s_max)
        for layer in layers:
            got = np.asarray(slab[layer, lane], np.float32)
            err = float(np.linalg.norm(got - want[layer])
                        / max(np.linalg.norm(want[layer]), 1e-30))
            worst = max(worst, err if np.isfinite(err) else np.inf)
            each.append({"lane": lane, "positions": n, "layer": layer,
                         "err": err})
    return {"ok": worst < tol, "ssm_state_err": worst,
            "ssm_state_tol": tol, "state_dtype": str(slab.dtype),
            "by_lane": each}


def check_serving(ctx, params, finished, fault=None, loop=None):
    """A seeded sample of finished requests, teacher-forced through the
    reference over prompt + output (logits, not tokens: chunked prefill
    through the state and then decode against this full forward).
    Every token the engine chose lies within ``logit_tol`` of the
    reference's best logit at its position, and over all checked
    tokens the MEAN distance to the best logit is under
    ``mean_gap_tol``.

    The sample holds at least one request whose prompt is longer than
    ``min_long`` (a state carried through that many positions of
    chunks) and at least one whose lane had an EARLIER OCCUPANT (a
    stale state would show): the longest finished one, and the first
    finished one with an earlier occupant, take the last places of the
    sample if the draw holds none.  ``fault`` plants a FAULTS entry in
    the reference: the comparison then has to come out not ``ok``.
    Where the cell names ``ssm_state_tol``, the engine's states are held
    to the reference's besides (:func:`check_states`, ``loop``: the
    driver's)."""
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
    # the traffic can send.
    s_max = sum(int(ctx.mix.get(key, {}).get("max", 0))
                for key in ("prompt_len", "output_len"))
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
    states = None
    if "ssm_state_tol" in spec:
        states = check_states(ctx, params, loop, fault=fault)
        if not states["ok"]:
            bad.append({"why": "the engine's states against the "
                        "reference's", "ssm_state_err": states.get(
                            "ssm_state_err")})
    return {"ok": not bad, "requests": len(pick), "tokens": total,
            "argmax_of_reference": hits,
            "worst_gap_to_best_logit": worst,
            "mean_gap_to_best_logit": mean_gap, "logit_tol": tol,
            "mean_gap_tol": mean_tol, "longest_prompt": longest_checked,
            "reused_lanes_checked": reused_checked, "failures": bad[:5],
            **({} if states is None else {"states": states})}
