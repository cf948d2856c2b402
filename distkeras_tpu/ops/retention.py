"""Power retention of degree 2 (arXiv:2507.04239): attention whose
weights are ``(q . k / sqrt(d))^2`` under a per-head decay, and whose
running state is therefore a fixed-size matrix and not keys and values.

Per K/V head (``d`` = head size; ``G`` query heads read one K/V head),
with a gate ``log g_t <= 0`` a position:

- **attention form** (:func:`retention_attention`; ``apply`` and the
  tests): ``A_ij = (q_i . k_j / sqrt d)^2 exp(sum_{m=j+1..i} log g_m)``
  for ``j <= i``; ``y_i = sum_j A_ij v_j / (sum_j A_ij + eps)``.
- **recurrent form** (:func:`retention_step`; the decode step):
  ``S_t = g_t S_{t-1} + phi(k_t) v_t^T``, ``z_t = g_t z_{t-1} +
  phi(k_t)``, ``y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)`` where
  ``phi(q) . phi(k) = (q . k / sqrt d)^2``.
- **chunked form** (a prefill chunk after a state): the attention form
  inside the chunk plus the state's term.  Two paths, picked by the
  shapes (:func:`use_ret_chunk_kernel`): :func:`retention_chunk`, plain
  ``jax.numpy`` on a state cut out of the slabs — everywhere, and the
  other's oracle — and on the TPU, for one lane, heads of 128 and an
  engine's chunk lengths, the Pallas kernel ``ret_chunk_fwd``
  (:func:`ret_chunk_fwd`): one K/V head a grid step, the lane's state
  aliased in and out where it lies, ``phi`` of the chunk's queries and
  keys built an offset at a time in VMEM between a lane rotation and
  the MXU, never in HBM.

**phi and the state's layout.**  ``phi(x)`` holds the ``d (d + 1) / 2``
products ``x_a x_b``, ``a <= b``, of ``x / d^(1/4)``, the off-diagonal
ones times ``sqrt 2``.  They are laid out by CYCLIC OFFSET: row ``o`` of
``phi(x) [d/2 + 1, d]`` is ``c_o x_a x_{(a + o) mod d}`` — ``o = 0`` the
squares, ``0 < o < d/2`` every pair at that cyclic distance once, ``o =
d/2`` each pair twice, so its upper half is zero.  A row is one lane
rotation of ``x`` and one multiply: no gather, on the TPU or off it.
The layout spends ``d / 2`` zeros on ``d (d + 1) / 2`` entries (8,320
rows for 8,256 at ``d = 128``: 0.8 %).  The state is ``s [..., d/2 + 1,
d (v), d (a)]`` — the value's index before the feature's, so that a
row of ``phi`` lies along the lanes of a tile of ``s`` — and ``z [...,
d/2 + 1, d]``, both float32: a sum over thousands of positions in
bfloat16 drops every addend 256 times smaller than itself.

The decode update is the Pallas kernel ``ret_state_step`` on the TPU
(:func:`ret_state_step`): the state aliased in and out, a lane that is
not decoding neither fetched nor written.  Elsewhere
:func:`retention_step` is the same arithmetic in ``jax.numpy``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.attention import _on_tpu

EPS = 1e-6          # of the normaliser; the reference uses the same
_LANES = 128


def phi_rows(d: int) -> int:
    """Rows of ``phi`` (and of a state) at head size ``d``."""
    return d // 2 + 1


def _phi_coef(d: int) -> np.ndarray:
    """``c_o`` of every entry, ``[d/2 + 1, d]``: 1 on the squares,
    sqrt 2 elsewhere, 0 on the second copy of a pair at offset d/2."""
    c = np.full((phi_rows(d), d), math.sqrt(2.0), np.float32)
    c[0] = 1.0
    c[-1, d // 2:] = 0.0
    return c


def phi(x):
    """``x [..., d]`` (ALREADY divided by ``d^(1/4)``) -> ``[..., d/2 +
    1, d]``, in ``x``'s dtype."""
    d = x.shape[-1]
    rolled = jnp.stack([jnp.roll(x, -o, axis=-1)
                        for o in range(phi_rows(d))], axis=-2)
    return x[..., None, :] * rolled * jnp.asarray(_phi_coef(d), x.dtype)


def scale_qk(q, k):
    """q and k each divided by ``d^(1/4)``: the ``1 / sqrt d`` inside
    the power, split between the two factors."""
    s = q.shape[-1] ** -0.25
    return q * s, k * s


def log_gate(x, wg, bg):
    """``log g [..., kv] = log_sigmoid(x . wg + bg)``, float32."""
    return jax.nn.log_sigmoid(
        jnp.einsum("...d,dc->...c", x.astype(jnp.float32),
                   wg.astype(jnp.float32)) + bg.astype(jnp.float32))


def _decay(cum, keep):
    """``exp(cum_i - cum_j)`` where ``keep[i, j]``, else 0: masked
    BEFORE the exponential (above the diagonal the exponent is
    positive and may overflow)."""
    diff = cum[..., :, None] - cum[..., None, :]
    return jnp.exp(jnp.where(keep, diff, -jnp.inf))


def retention_attention(q, k, v, logg):
    """The attention form, plain and quadratic: ``q [B, S, H, d]``,
    ``k``/``v [B, S, KV, d]``, ``logg [B, S, KV]`` -> ``[B, S, H, d]``
    float32.  Float32 throughout."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    q, k = scale_qk(q.astype(jnp.float32), k.astype(jnp.float32))
    qg = q.reshape(b, s, kv, h // kv, d)
    score = jnp.einsum("bicgd,bjcd->bcgij", qg, k)
    cum = jnp.cumsum(logg.astype(jnp.float32), axis=1).transpose(0, 2, 1)
    causal = jnp.tril(jnp.ones((s, s), bool))
    a = jnp.square(score) * _decay(cum, causal)[:, :, None]
    y = jnp.einsum("bcgij,bjcd->bicgd", a, v.astype(jnp.float32))
    den = a.sum(axis=-1).transpose(0, 3, 1, 2)[..., None]
    return (y / (den + EPS)).reshape(b, s, h, d)


def retention_chunk(q, k, v, logg, s, z, n_real=None, fresh=None):
    """The chunked form for ONE row: ``q [C, H, d]``, ``k``/``v [C, KV,
    d]``, ``logg [C, KV]`` after the state ``s [KV, R, d, d]``, ``z [KV,
    R, d]`` -> ``(y [C, H, d] float32, s', z')``.

    ``n_real`` (traced int32): positions ``>= n_real`` are padding —
    they neither add ``phi(k) v^T`` nor decay (their ``y`` is of no
    use).  ``fresh`` (traced bool): the state before the chunk is zero
    whatever ``s`` holds — a lane's new occupant.

    Products take their operands in ``q``'s dtype with float32
    accumulation, as the attention kernels do; gates, decays, the
    normaliser and the state's sums are float32.  One K/V head at a
    time (``lax.map``): ``phi`` of a chunk's queries is ``C x G x R x
    d`` numbers a head."""
    c_len, h, d = q.shape
    kv = k.shape[1]
    dt = q.dtype
    f32 = dict(preferred_element_type=jnp.float32)
    q, k = scale_qk(q, k)
    real = jnp.ones((c_len,), bool) if n_real is None else (
        jnp.arange(c_len) < n_real)
    logg = jnp.where(real[:, None], logg.astype(jnp.float32), 0.0)
    keep_state = jnp.float32(1.0) if fresh is None else jnp.where(
        fresh, 0.0, 1.0)
    causal = jnp.tril(jnp.ones((c_len, c_len), bool)) & real[None, :]

    def head(args):
        qc, kc, vc, lg, sc, zc = args     # [C,G,d] [C,d] [C,d] [C] [R,d,d] [R,d]
        cum = jnp.cumsum(lg)
        grow = jnp.exp(cum) * keep_state                       # G_i
        with jax.named_scope("ret_chunk"):
            score = jnp.einsum("igd,jd->gij", qc, kc, **f32)
            a = jnp.square(score) * _decay(cum, causal)[None]
            num = jnp.einsum("gij,jd->igd", a.astype(dt), vc, **f32)
            den = a.sum(axis=-1).T                             # [C, G]
            pq = phi(qc)                                       # [C, G, R, d]
            num = num + grow[:, None, None] * jnp.einsum(
                "igoa,ova->igv", pq, sc.astype(dt), **f32)
            den = den + grow[:, None] * jnp.einsum(
                "igoa,oa->ig", pq.astype(jnp.float32), zc)
        with jax.named_scope("ret_state"):
            # What each position still weighs at the chunk's end.
            left = jnp.where(real, jnp.exp(cum[-1] - cum), 0.0)
            pk = phi(kc.astype(jnp.float32)) * left[:, None, None]
            s_new = grow[-1] * sc + jnp.einsum(
                "joa,jv->ova", pk.astype(dt), vc, **f32)
            z_new = grow[-1] * zc + pk.sum(axis=0)
        return num / (den + EPS)[..., None], s_new, z_new

    y, s_new, z_new = jax.lax.map(head, (
        q.reshape(c_len, kv, h // kv, d).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        logg.T, s, z))
    return y.transpose(1, 0, 2, 3).reshape(c_len, h, d), s_new, z_new


def step_operands(q, k, v, logg, fresh):
    """What one decode step of a layer hands the state's update, as
    one ``[B, KV, 8, d]`` float32 tile a (lane, K/V head): rows ``0 ..
    G-1`` the head's queries and row 5 its key (both divided by
    ``d^(1/4)``), row 6 the gate ``g`` on every lane, row 7 the value.
    A ``fresh`` lane (position 0: a new occupant) gets ``g = 0``, which
    clears what the lane held — finite by construction: the engine's
    own earlier sums."""
    b, h, d = q.shape
    kv = k.shape[1]
    g = h // kv
    if g > 5:
        raise ValueError(f"{g} query heads a K/V head: the tile holds 5")
    q, k = scale_qk(q.astype(jnp.float32), k.astype(jnp.float32))
    gate = jnp.where(fresh[:, None], 0.0, jnp.exp(logg.astype(jnp.float32)))
    row = lambda a: a[:, :, None, :]
    return jnp.concatenate([
        q.reshape(b, kv, g, d), jnp.zeros((b, kv, 5 - g, d), jnp.float32),
        row(k), row(jnp.broadcast_to(gate[..., None], (b, kv, d))),
        row(v.astype(jnp.float32))], axis=2)


def step_math(x, s, z, live):
    """The recurrent form for one token a lane on ONE plane's states
    ``s [B, KV, R, d, d]``, ``z [B, KV, R, d]``: ``(y [B, KV, 8, d]:
    rows 0 .. G-1, s', z')``; a lane that is not ``live`` keeps its
    state, bit for bit."""
    ph = phi(x[:, :, :6])                                  # [B, KV, 6, R, d]
    pq, pk = ph[:, :, :5], ph[:, :, 5]
    gate, val = x[:, :, 6, 0], x[:, :, 7]
    s_new = (gate[..., None, None, None] * s
             + val[:, :, None, :, None] * pk[:, :, :, None, :])
    z_new = gate[..., None, None] * z + pk
    num = jnp.einsum("bcgoa,bcova->bcgv", pq, s_new)
    den = jnp.einsum("bcgoa,bcoa->bcg", pq, z_new)
    y = jnp.pad(num / (den + EPS)[..., None], ((0, 0),) * 2 + ((0, 3), (0, 0)))
    on = live.astype(bool)
    return (y, jnp.where(on[:, None, None, None, None], s_new, s),
            jnp.where(on[:, None, None, None], z_new, z))


def retention_step(x, s_all, z_all, plane, live):
    """:func:`step_math` on plane ``plane`` of the state slabs ``s_all
    [P, B, KV, R, d, d]``, ``z_all [P, B, KV, R, d]`` (``x`` of
    :func:`step_operands`) -> ``(y, s_all', z_all')``."""
    s = jax.lax.dynamic_index_in_dim(s_all, plane, 0, keepdims=False)
    z = jax.lax.dynamic_index_in_dim(z_all, plane, 0, keepdims=False)
    y, s_new, z_new = step_math(x, s, z, live)
    at = (plane,) + (jnp.int32(0),) * 5
    return (y,
            jax.lax.dynamic_update_slice(s_all, s_new[None], at),
            jax.lax.dynamic_update_slice(z_all, z_new[None], at[:-1]))


# ------------------------------------------------------------- the kernel

# Value rows (sublanes of a state tile) one pass of the kernel's inner
# loop over the offsets holds in registers: its G accumulators are
# ``ROWS / 8`` vregs each.
STEP_ROWS = 32
STEP_VMEM_BYTES = 64 * 1024 * 1024


def _ret_step_kernel(plane_ref, src_ref, head_ref, mode_ref, x_ref, s_ref,
                     z_ref, y_ref, s_out, z_out, ph_scr, acc_scr, *,
                     rows: int):
    """One (lane, K/V head) of the decode update: the head's state tile
    ``s [R, d, d]`` is read once, decayed, given ``v (x) phi(k)`` and
    written once, and on its way multiplied by ``phi`` of the head's
    queries.

    ``mode_ref[lane]``: 1 the lane decodes; 0 it does not — its grid
    steps map onto a block a decoding lane's steps fetch anyway
    (``src_ref``, ``head_ref``: :func:`_step_maps`), so nothing is
    fetched or written for it and the body leaves the block alone; 2
    no lane decodes at all and this one's first block is handed through
    (a block mapped to is written back whatever the body did).

    ``phi`` of the six rows (queries, key) is one lane rotation and two
    multiplies a row of the state, into ``ph_scr [R, 8, d]`` first; the
    main loop then walks the tile ``rows`` value rows at a time with
    the G accumulators in registers."""
    lane, c = pl.program_id(0), pl.program_id(1)
    mode = mode_ref[lane]
    n_off, d = z_ref.shape
    half = d // 2
    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(jnp.logical_and(mode == 2, c == 0))
    def _hand_through():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]

    @pl.when(mode == 1)
    def _update():
        x = x_ref[...]                                        # [8, d]
        lanes_i = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        root2 = jnp.float32(math.sqrt(2.0))
        for o in range(n_off):
            other = x if o == 0 else pltpu.roll(x, d - o, 1)  # x[a + o]
            p = x * other
            if o:
                p = p * root2
            if o == half:
                p = jnp.where(lanes_i < half, p, 0.0)
            ph_scr[o] = p
        gate = x[6:7, :]                                      # [1, d]
        # The value down the sublanes, the same on every lane.
        val = jnp.broadcast_to(x[7:8, :], (d, d)).T
        zero = jnp.zeros((rows, d), jnp.float32)
        for r0 in range(0, d, rows):
            v_blk = val[r0:r0 + rows]

            def offset(o, accs, r0=r0, v_blk=v_blk):
                ph = ph_scr[o]                                # [8, d]
                s_new = (gate * s_ref[o, pl.ds(r0, rows), :]
                         + v_blk * ph[5:6, :])
                s_out[o, pl.ds(r0, rows), :] = s_new
                return tuple(acc + s_new * ph[g:g + 1, :]
                             for g, acc in enumerate(accs))

            accs = jax.lax.fori_loop(0, n_off, offset, (zero,) * 5)
            for g, acc in enumerate(accs):
                acc_scr[g, pl.ds(r0, rows), :] = acc

        def norm(o, dacc):
            ph = ph_scr[o]
            z_row = gate * z_ref[pl.ds(o, 1), :] + ph[5:6, :]
            z_out[pl.ds(o, 1), :] = z_row
            return dacc + ph * z_row

        den = jnp.sum(jax.lax.fori_loop(
            0, n_off, norm, jnp.zeros((8, d), jnp.float32)),
            axis=1, keepdims=True)                            # [8, 1]
        # [d (v), d (a)] summed over a, then laid along the lanes.
        num = jnp.concatenate(
            [jnp.sum(acc_scr[g].T, axis=0, keepdims=True) for g in range(5)]
            + [jnp.zeros((3, d), jnp.float32)], axis=0)       # [8, d (v)]
        y_ref[...] = num / (den + EPS)


def _step_maps(live):
    """Where each lane's grid steps point: ``(src, head, mode)``, int32
    ``[B]`` each.  A decoding lane points at itself (mode 1).  One
    that is not points at the FIRST block of the next decoding lane —
    which that lane's first step then finds fetched — or, after the
    last one, at the LAST block of the last decoding lane, which stays
    resident until the call ends: a block is fetched when the index
    changes and written back when it changes again, so such a lane
    moves no byte.  ``head`` is the K/V head to point at (-1: the
    step's own).  No lane decoding: every step points at lane 0's
    first block and lane 0 hands it through (mode 2)."""
    b = live.shape[0]
    on = live.astype(bool)
    idx = jnp.arange(b, dtype=jnp.int32)
    nxt = jnp.flip(jax.lax.cummin(jnp.flip(jnp.where(on, idx, b))))
    prv = jax.lax.cummax(jnp.where(on, idx, -1))
    src = jnp.where(on, idx, jnp.where(nxt < b, nxt, jnp.maximum(prv, 0)))
    head = jnp.where(on, -1, jnp.where(nxt < b, 0, -2)).astype(jnp.int32)
    none = ~on.any()
    mode = jnp.where(none & (idx == 0), 2, on.astype(jnp.int32))
    head = jnp.where(none, 0, head)
    return src.astype(jnp.int32), head, mode.astype(jnp.int32)


def use_ret_kernel(d: int, groups: int, dtype, sharded: bool = False) -> bool:
    """Kernel or :func:`retention_step`: the backend, the placement and
    the shapes decide (a head of one lane tile, at most 5 query heads a
    K/V head, a float32 state)."""
    return (_on_tpu() and not sharded and d == _LANES and groups <= 5
            and jnp.dtype(dtype) == jnp.float32)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"),
                   donate_argnames=("s_all", "z_all"))
def ret_state_step(x, s_all, z_all, plane, live, rows: int = STEP_ROWS,
                   interpret: bool = False):
    """:func:`retention_step` as ONE Pallas call, ``ret_state_step``:
    grid (lanes, K/V heads), the state slabs aliased in and out
    (nothing of them is copied: the caller donates them), the plane's
    index and the lanes' ``live`` mask scalar-prefetched into the index
    maps (:func:`_step_maps`)."""
    b, kv, _, d = x.shape
    n_off = phi_rows(d)
    src, head, mode = _step_maps(live)

    def state_map(tail):
        def index(ln, c, plane_ref, src_ref, head_ref, mode_ref):
            hd = head_ref[ln]
            c = jnp.where(hd == -1, c, jnp.where(hd == -2, kv - 1, hd))
            return (plane_ref[0], src_ref[ln], c) + (0,) * tail
        return index

    own = lambda ln, c, *_: (ln, c, 0, 0)
    s_spec = pl.BlockSpec((None, None, None, n_off, d, d), state_map(3))
    z_spec = pl.BlockSpec((None, None, None, n_off, d), state_map(2))
    x_spec = pl.BlockSpec((None, None, 8, d), own)

    def call(): return pl.pallas_call(
        functools.partial(_ret_step_kernel, rows=rows),
        name="ret_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, kv),
            in_specs=[x_spec, s_spec, z_spec],
            out_specs=[x_spec, s_spec, z_spec],
            scratch_shapes=[pltpu.VMEM((n_off, 8, d), jnp.float32),
                            pltpu.VMEM((5, d, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct(z_all.shape, z_all.dtype)],
        # Operands are counted with the four prefetched scalars.
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=STEP_VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=14 * b * kv * n_off * d * d, transcendentals=0,
            bytes_accessed=8 * b * kv * n_off * d * (d + 1)),
    )(jnp.reshape(plane, (1,)).astype(jnp.int32), src, head, mode,
      x, s_all, z_all)

    if interpret:
        with pltpu.force_tpu_interpret_mode():
            return tuple(call())
    return tuple(call())


# --------------------------------------------------- the chunk's kernel

# Offsets of phi(q) one product of the chunk kernel takes side by side
# (its contraction is ``CHUNK_GROUP d`` long), and offsets of phi(k) one
# pass over the new state writes: chosen on the chip
# (``scripts/sweep_ret_chunk.py``); both divide the d/2 offsets after
# the first and the d/2 + 1 offsets.
CHUNK_GROUP = 4
STATE_GROUP = 5
CHUNK_LENGTHS = (64, 128, 256, 512)
CHUNK_VMEM_BYTES = 64 * 1024 * 1024


def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    """A product on the MXU with float32 accumulation; float32 operands
    (the tests' compute dtype) at full precision."""
    exact = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(a, b, dims, precision=exact,
                               preferred_element_type=jnp.float32)


def _ret_chunk_kernel(sc_ref, q_ref, k_ref, v_ref, vt_ref, col_ref, row_ref,
                      coef_ref, s_ref, z_ref, y_ref, s_out, z_out, qf_scr,
                      qb_scr, kf_scr, kl_scr, sb_scr, zc_scr, num_scr, den_scr, *,
                      groups: int):
    """One K/V head of :func:`retention_chunk`: the head's state ``s [R,
    d, d]``, ``z [R, d]`` comes in once and goes out once, and ``phi``
    of the chunk's queries and keys exists a few offsets at a time, in
    VMEM, between a lane rotation and the MXU.

    ``sc_ref``: (plane, lane, n_real, fresh).  ``q_ref [C, G d]``,
    ``k_ref``/``v_ref [C, d]``, ``vt_ref [d, C]`` in the compute dtype,
    q and k divided by ``d^(1/4)``; ``col_ref [C, 4]`` the gate's
    cumulative sum, ``grow`` and ``left`` down the sublanes, ``row_ref
    [1, C]`` the cumulative sum along the lanes (float32).

    *The state's query* (skipped by a ``fresh`` chunk), a query head
    at a time: row ``o`` of ``phi(q)`` is ``sqrt 2 x roll(x, o)`` in
    float32 — the ``sqrt 2`` of ``c_o`` folded into one factor once, the
    rotation run on the queries as they came (in bfloat16 two rows to a
    32-bit lane: half the vregs through the rotate unit); offset 0 (the
    squares) stands before the loop, and the second copy of a pair at
    offset ``d / 2`` meets zeros in the state's copy — rounded to the
    compute dtype ONCE, and that rounded value goes both to the MXU
    (``CHUNK_GROUP`` offsets side by side against ``[CHUNK_GROUP d, d]``
    of the state's copy ``sb_scr [(o, a), v]``, in the compute dtype)
    and, times ``z[o]``, into the normaliser: numerator and normaliser
    weigh a position alike, as :func:`retention_chunk`'s do.  *The new
    state*, ``STATE_GROUP`` offsets a pass: ``v^T [phi_o(k) left]`` and
    the column sums, float32 but for the product's operands.  *The
    chunk's own pairs* as :func:`retention_chunk` has them, and the
    quotient."""
    c_len, d = k_ref.shape
    n_off = z_ref.shape[0]
    half = d // 2
    dt = q_ref.dtype
    f32 = jnp.float32
    n_real, fresh = sc_ref[2], sc_ref[3]
    cum_i, grow = col_ref[:, 0:1], col_ref[:, 1:2]            # [C, 1]
    # grow at the chunk's end, along the lanes ([1, d]): zero if fresh
    g_last = col_ref[c_len - 1:c_len, 1:2] + jnp.zeros((1, d), f32)
    back = lambda o: (d - o) % d               # roll(x, back(o)): x[a + o]

    for g in range(groups):
        q_g = q_ref[:, g * d:(g + 1) * d]
        mine = slice(g * c_len, (g + 1) * c_len)
        qb_scr[mine, :] = q_g
        qf_scr[mine, :] = q_g.astype(f32) * f32(2.0 ** 0.5)
    kf_scr[...] = k_ref[...].astype(f32)
    kl_scr[...] = kf_scr[...] * col_ref[:, 2:3]

    @pl.when(fresh != 0)
    def _no_state():
        num_scr[...] = jnp.zeros_like(num_scr)
        den_scr[...] = jnp.zeros_like(den_scr)

    @pl.when(fresh == 0)
    def _query_state():
        def cast(o, carry):
            sb_scr[pl.ds(pl.multiple_of(o * d, d), d), :] = (
                s_ref[o].T.astype(dt))
            return carry

        jax.lax.fori_loop(0, n_off, cast, 0)
        last = (n_off - 1) * d
        sb_scr[last + half:last + d, :] = jnp.zeros((half, d), dt)
        zc_scr[...] = jnp.where(coef_ref[...] != 0, z_ref[...], 0.0)

        def head(g, carry):
            mine = pl.ds(pl.multiple_of(g * c_len, c_len), c_len)
            x0 = qb_scr[mine, :].astype(f32)
            p0 = (x0 * x0).astype(dt)
            num_scr[mine, :] = _mm(p0, sb_scr[0:d, :])
            den_scr[mine, :] = p0.astype(f32) * zc_scr[0:1, :]

            def group(gi, carry):
                o0 = 1 + gi * CHUNK_GROUP
                ps, den = [], 0.0
                for o in (o0 + j for j in range(CHUNK_GROUP)):
                    other = pltpu.bitcast(pltpu.roll(pltpu.bitcast(
                        qb_scr[mine, :], jnp.int32), back(o), 1), dt)
                    ps.append((qf_scr[mine, :] * other.astype(f32)
                               ).astype(dt))
                    den = den + ps[-1].astype(f32) * zc_scr[pl.ds(o, 1), :]
                num_scr[mine, :] += _mm(
                    jnp.concatenate(ps, axis=1),
                    sb_scr[pl.ds(pl.multiple_of(o0 * d, d), CHUNK_GROUP * d),
                           :])
                den_scr[mine, :] += den
                return carry

            jax.lax.fori_loop(0, (n_off - 1) // CHUNK_GROUP, group, 0)
            return carry

        jax.lax.fori_loop(0, groups, head, 0)

    def state(gi, carry):
        pks = []
        for o in (gi * STATE_GROUP + j for j in range(STATE_GROUP)):
            pk = (kl_scr[...] * pltpu.roll(kf_scr[...], back(o), 1)
                  * coef_ref[pl.ds(o, 1), :])
            z_out[pl.ds(o, 1), :] = (g_last * z_ref[pl.ds(o, 1), :]
                                     + jnp.sum(pk, axis=0, keepdims=True))
            pks.append(pk.astype(dt))
        new = _mm(vt_ref[...], jnp.concatenate(pks, axis=1))
        for j in range(STATE_GROUP):
            o = gi * STATE_GROUP + j
            s_out[o] = g_last * s_ref[o] + new[:, j * d:(j + 1) * d]
        return carry

    jax.lax.fori_loop(0, n_off // STATE_GROUP, state, 0)

    row_i = jax.lax.broadcasted_iota(jnp.int32, (c_len, c_len), 0)
    col_j = jax.lax.broadcasted_iota(jnp.int32, (c_len, c_len), 1)
    keep = jnp.logical_and(col_j <= row_i, col_j < n_real)
    decay = jnp.where(
        keep, jnp.exp(jnp.where(keep, cum_i - row_ref[...], 0.0)), 0.0)
    for g in range(groups):
        mine = slice(g * c_len, (g + 1) * c_len)
        score = _mm(q_ref[:, g * d:(g + 1) * d], k_ref[...],
                    (((1,), (1,)), ((), ())))
        a = score * score * decay
        den = (jnp.sum(a, axis=1, keepdims=True) + grow * jnp.sum(
            den_scr[mine, :], axis=1, keepdims=True))
        num = _mm(a.astype(dt), v_ref[...]) + grow * num_scr[mine, :]
        y_ref[:, g * d:(g + 1) * d] = (num / (den + EPS)).astype(y_ref.dtype)


def use_ret_chunk_kernel(d: int, groups: int, c_len: int, rows: int, dtype,
                         sharded: bool = False) -> bool:
    """Kernel or :func:`retention_chunk` for a chunk: the backend, the
    placement and the shapes decide (ONE row, a head of one lane tile,
    a chunk length the kernel's tiles were built for, a float32
    state)."""
    return (_on_tpu() and not sharded and rows == 1 and d == _LANES
            and groups <= 8 and c_len in CHUNK_LENGTHS
            and jnp.dtype(dtype) == jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("s_all", "z_all"))
def ret_chunk_fwd(q, k, v, logg, s_all, z_all, plane, lane, n_real=None,
                  fresh=None, interpret: bool = False):
    """:func:`retention_chunk` through lane ``lane`` of plane ``plane``
    of the state slabs ``s_all [P, B, KV, R, d, d]``, ``z_all [P, B, KV,
    R, d]`` as ONE Pallas call, ``ret_chunk_fwd`` -> ``(y [C, H, d] in
    q's dtype, s_all', z_all')``: grid (K/V heads,), the slabs aliased
    in and out with the plane and the lane scalar-prefetched into their
    index maps, so the lane's state is read and written where it lies
    and no other block is touched.  The gate's cumulative sums, ``grow``
    and ``left`` are :func:`retention_chunk`'s, worked out here."""
    c_len, h, d = q.shape
    kv = k.shape[1]
    groups = h // kv
    n_off = phi_rows(d)
    dt = q.dtype
    q, k = scale_qk(q, k)
    n_real = jnp.int32(c_len) if n_real is None else n_real
    fresh = jnp.bool_(False) if fresh is None else fresh
    real = jnp.arange(c_len) < n_real
    cum = jnp.cumsum(jnp.where(real[:, None], logg.astype(jnp.float32), 0.0),
                     axis=0)                                   # [C, KV]
    grow = jnp.exp(cum) * jnp.where(fresh, 0.0, 1.0)
    left = jnp.where(real[:, None], jnp.exp(cum[-1] - cum), 0.0)
    cols = jnp.stack([cum, grow, left, jnp.zeros_like(cum)],
                     axis=-1).transpose(1, 0, 2)               # [KV, C, 4]
    scalars = jnp.stack([jnp.asarray(a, jnp.int32).reshape(())
                         for a in (plane, lane, n_real, fresh)])

    def state_map(tail):
        return lambda c, sc: (sc[0], sc[1], c) + (0,) * tail

    head = lambda c, sc: (0, c)
    own = lambda c, sc: (c, 0, 0)
    s_spec = pl.BlockSpec((None, None, None, n_off, d, d), state_map(3))
    z_spec = pl.BlockSpec((None, None, None, n_off, d), state_map(2))
    q_spec = pl.BlockSpec((c_len, groups * d), head)
    kv_spec = pl.BlockSpec((c_len, d), head)

    def call(): return pl.pallas_call(
        functools.partial(_ret_chunk_kernel, groups=groups),
        name="ret_chunk_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kv,),
            in_specs=[q_spec, kv_spec, kv_spec,
                      pl.BlockSpec((None, d, c_len), own),
                      pl.BlockSpec((None, c_len, 4), own),
                      pl.BlockSpec((None, 1, c_len), own),
                      pl.BlockSpec((n_off, d), lambda c, sc: (0, 0)),
                      s_spec, z_spec],
            out_specs=[q_spec, s_spec, z_spec],
            scratch_shapes=[pltpu.VMEM((groups * c_len, d), jnp.float32),
                            pltpu.VMEM((groups * c_len, d), dt),
                            pltpu.VMEM((c_len, d), jnp.float32),
                            pltpu.VMEM((c_len, d), jnp.float32),
                            pltpu.VMEM((n_off * d, d), dt),
                            pltpu.VMEM((n_off, d), jnp.float32),
                            pltpu.VMEM((groups * c_len, d), jnp.float32),
                            pltpu.VMEM((groups * c_len, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((c_len, h * d), dt),
                   jax.ShapeDtypeStruct(s_all.shape, s_all.dtype),
                   jax.ShapeDtypeStruct(z_all.shape, z_all.dtype)],
        # Operands are counted with the prefetched scalars.
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=CHUNK_VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=kv * c_len * d * (2 * (groups + 1) * n_off * d
                                    + 4 * groups * c_len),
            transcendentals=kv * c_len * c_len,
            bytes_accessed=8 * kv * n_off * d * (d + 1)),
    )(scalars, q.reshape(c_len, h * d), k.reshape(c_len, kv * d),
      v.reshape(c_len, kv * d), v.transpose(1, 2, 0), cols,
      cum.T[:, None, :], jnp.asarray(_phi_coef(d)), s_all, z_all)

    if interpret:
        with pltpu.force_tpu_interpret_mode():
            y, s_all, z_all = call()
    else:
        y, s_all, z_all = call()
    return y.reshape(c_len, h, d), s_all, z_all
