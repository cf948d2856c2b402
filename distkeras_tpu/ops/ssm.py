"""The selective state-space mixer of Mamba-1 (arXiv:2312.00752), with
Jamba's three inner RMSNorms (arXiv:2403.19887): a layer whose running
state is, a channel, a vector of ``N`` numbers and a short tail of the
convolution's input — not keys and values, and of one size at every
position.

Per channel ``e`` of the inner width ``E`` and state ``n < N``, over
positions ``t`` of one sequence (``models/transformer.py`` makes the
projections; this module runs what lies between them):

    x_t         = silu(conv_b + sum_{j<K} conv_w[j] u_{t-K+1+j})   (u_{<0} = 0)
    delta, B, C = rms(x_t w_x) split [R | N | N], each with its own scale
    Delta_t     = softplus(delta w_dt + b_dt)                       float32
    s_t[n, e]   = exp(Delta_t[e] A[n, e]) s_{t-1}[n, e]
                  + Delta_t[e] B_t[n] x_t[e]                         float32
    y_t[e]      = sum_n C_t[n] s_t[n, e] + D[e] x_t[e]

``A = -exp(A_log)``.  A lane's state is ``s [N, E]`` float32 — states
down the sublanes, channels along the 128-wide lanes — and the conv's
tail, the last ``K - 1`` rows of ``u`` side by side, ``[(K-1) E]``, in
the activations' dtype (they are rounded to it before the conv:
storing them adds no rounding).

Three forms of the recurrence, one function:

- **step by step** (:func:`ssm_steps`): ``lax.scan`` over positions —
  the tests' oracle;
- **chunked** (:func:`ssm_chunked`): blocks of ``SCAN_BLOCK`` positions,
  each an associative (parallel-prefix) scan of the pairs ``(exp(Delta
  A), Delta B x)``, the state carried from block to block — ``apply``'s
  no-cache path and a prefill chunk off the TPU;
- **one step** (:func:`ssm_step_math`): a decode step's update of every
  lane at once.

On the TPU two Pallas kernels: ``ssm_state_step`` (:func:`ssm_state_step`;
a decode step: grid over lanes, each lane's ``s`` aliased in and out
where it lies, a lane that does not decode neither fetched nor written)
and ``ssm_chunk_fwd`` (:func:`ssm_chunk_fwd`; one lane's prefill chunk:
the recurrence sequential over the chunk's rows, channels along the
lanes and the ``N`` states down the sublanes, ``exp(Delta A)`` formed
in VMEM a row at a time, so nothing of size ``[rows, N, E]`` reaches
HBM; the rows go ``GROUP`` at a time, unrolled, so that what a row needs
besides the state runs beside the row before).  The products ``w_x``
and ``w_dt``, the inner norms and the conv stay in XLA beside them:
``Delta``, ``B`` and ``C`` are products of the conv's output, so the
conv has to be done before either kernel starts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distkeras_tpu.ops.attention import _on_tpu
from distkeras_tpu.ops.retention import _step_maps

_LANES = 128
SCAN_BLOCK = 16      # positions of one associative scan (chunked form)
STEP_SUB = 512       # channels the decode kernel holds in registers at once
CHUNK_TILE = 512     # channels of one grid step of the chunk kernel
GROUP = 8            # rows of the chunk kernel's step: one sublane tile
CHUNK_UNROLL = 4     # groups a trip of the chunk kernel's row loop
VMEM_BYTES = 64 * 1024 * 1024


def causal_conv(u, tail, w, b, n_real=None, keep=None):
    """The depthwise causal conv over ``u [B, T, E]`` after ``tail [B,
    (K-1) E]`` (the ``K - 1`` rows before ``u``'s first, side by side as
    the cache holds them; zeros where ``keep [B]`` is False — a lane's
    new occupant): ``(x [B, T, E] float32 = silu(b + sum_j w[j]
    u_{t-K+1+j}), the new tail [B, (K-1) E] in tail's dtype)``.  The new
    tail is the last ``K - 1`` REAL rows (``n_real``: the chunk's rows
    past it are padding, which never enters a tail).  One row a lane (a
    decode step) is taken by lane-aligned slices of the tail, so that no
    array of the lanes changes its layout."""
    bsz, t_len, e = u.shape
    k1 = tail.shape[-1] // e
    f32 = jnp.float32
    if keep is not None:
        tail = jnp.where(keep[:, None], tail, jnp.zeros_like(tail))
    w, b = w.astype(f32), b.astype(f32)
    if t_len == 1 and n_real is None:
        rows = ([tail[:, j * e:(j + 1) * e] for j in range(k1)]
                + [u[:, 0].astype(tail.dtype)])
        acc = b + sum(w[j] * rows[j].astype(f32) for j in range(k1 + 1))
        return jax.nn.silu(acc)[:, None], jnp.concatenate(rows[1:], axis=-1)
    full = jnp.concatenate([tail.reshape(bsz, k1, e).astype(f32),
                            u.astype(f32)], axis=1)
    acc = b + sum(w[j] * full[:, j:j + t_len] for j in range(k1 + 1))
    end = t_len if n_real is None else n_real
    new_tail = jax.lax.dynamic_slice_in_dim(full, end, k1, axis=1)
    return jax.nn.silu(acc), new_tail.reshape(bsz, k1 * e).astype(tail.dtype)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                             ) * scale.astype(jnp.float32)


def selective_inputs(x, mixer, eps, dtype):
    """``(Delta [..., E], B [..., N], C [..., N])``, float32, of the
    conv's output ``x [..., E]``: the product ``w_x`` (operands in
    ``dtype``, float32 accumulation), Jamba's three inner RMSNorms
    (``dt_scale``, ``b_scale``, ``c_scale``), the product ``w_dt`` and
    ``softplus(. + b_dt)``."""
    f32 = dict(preferred_element_type=jnp.float32)
    n = mixer["b_scale"].shape[-1]
    r = mixer["dt_scale"].shape[-1]
    dbc = jnp.einsum("...e,ek->...k", x.astype(dtype),
                     mixer["w_x"].astype(dtype), **f32)
    delta = _rms(dbc[..., :r], mixer["dt_scale"], eps)
    bmat = _rms(dbc[..., r:r + n], mixer["b_scale"], eps)
    cmat = _rms(dbc[..., r + n:], mixer["c_scale"], eps)
    pre = jnp.einsum("...r,re->...e", delta.astype(dtype),
                     mixer["w_dt"].astype(dtype), **f32)
    return (jax.nn.softplus(pre + mixer["b_dt"].astype(jnp.float32)),
            bmat, cmat)


def state_matrix(a_log):
    """``A = -exp(A_log)``, float32 (``A_log [..., N, E]``)."""
    return -jnp.exp(a_log.astype(jnp.float32))


# ----------------------------------------------------------- the forms


def ssm_steps(x, delta, bmat, cmat, a, d, s0):
    """Step by step, position after position: ``x``, ``delta [T, E]``,
    ``bmat``, ``cmat [T, N]``, ``a [N, E]``, ``d [E]``, ``s0 [N, E]`` ->
    ``(y [T, E], s_T)``, float32."""
    def step(s, row):
        xt, dt, bt, ct = row
        s = jnp.exp(dt[None] * a) * s + (dt * xt)[None] * bt[:, None]
        return s, (ct[:, None] * s).sum(axis=0) + d * xt

    f32 = lambda v: v.astype(jnp.float32)
    s, y = jax.lax.scan(step, f32(s0),
                        (f32(x), f32(delta), f32(bmat), f32(cmat)))
    return y, s


def ssm_chunked(x, delta, bmat, cmat, a, d, s0, n_real=None):
    """The chunked form of :func:`ssm_steps`: blocks of SCAN_BLOCK
    positions, each an associative scan of ``(exp(Delta A), Delta B x)``
    composed as ``(a1 a2, a2 b1 + b2)``; ``[SCAN_BLOCK, N, E]`` at a
    time.  Rows ``>= n_real`` are padding: their ``Delta`` is taken as
    0, so they neither decay the state nor add to it."""
    t_len, e = x.shape
    f32 = jnp.float32
    x, delta, bmat, cmat = (v.astype(f32) for v in (x, delta, bmat, cmat))
    if n_real is not None:
        delta = jnp.where((jnp.arange(t_len) < n_real)[:, None], delta, 0.0)
    pad = (-t_len) % SCAN_BLOCK
    cut = lambda v: jnp.pad(v, ((0, pad), (0, 0))).reshape(
        (-1, SCAN_BLOCK) + v.shape[1:])

    def combine(p, q):
        return p[0] * q[0], q[0] * p[1] + q[1]

    def block(s, blk):
        xb, db, bb, cb = blk
        decay = jnp.exp(db[:, None, :] * a[None])              # [L, N, E]
        add = (db * xb)[:, None, :] * bb[:, :, None]
        grow, acc = jax.lax.associative_scan(combine, (decay, add))
        states = grow * s[None] + acc
        y = jnp.einsum("ln,lne->le", cb, states) + d * xb
        return states[-1], y

    s, y = jax.lax.scan(block, s0.astype(f32),
                        tuple(cut(v) for v in (x, delta, bmat, cmat)))
    return y.reshape(-1, e)[:t_len], s


def step_operands(x, delta, bmat, cmat, keep):
    """What one decode step hands the state's update: ``rows [B, 2, E]``
    float32 (``x`` then ``Delta``) and ``bc [B, N, 3]`` float32 (``B``,
    ``C``, and 1 where the lane keeps its state, 0 where it starts
    afresh — a lane's new occupant at position 0)."""
    f32 = jnp.float32
    keep = jnp.broadcast_to(keep.astype(f32)[:, None], bmat.shape)
    return (jnp.stack([x.astype(f32), delta.astype(f32)], axis=1),
            jnp.stack([bmat.astype(f32), cmat.astype(f32), keep], axis=-1))


def ssm_step_math(rows, bc, a, d, s, live):
    """One position a lane on ONE plane's states ``s [B, N, E]``: ``(y
    [B, E], s')``; a lane that is not ``live`` keeps its state, bit for
    bit."""
    x, dt = rows[:, 0], rows[:, 1]
    bmat, cmat, keep = bc[..., 0], bc[..., 1], bc[..., 2]
    held = jnp.where(keep[..., None] > 0, s, 0.0)
    s_new = (jnp.exp(dt[:, None] * a[None]) * held
             + (dt * x)[:, None] * bmat[..., None])
    y = (cmat[..., None] * s_new).sum(axis=1) + d * x
    on = live.astype(bool)[:, None, None]
    return y, jnp.where(on, s_new, s)


def ssm_step(rows, bc, a, d, s_all, plane, live):
    """:func:`ssm_step_math` on plane ``plane`` of the slab ``s_all [P,
    B, N, E]`` -> ``(y [B, E], s_all')``."""
    s = jax.lax.dynamic_index_in_dim(s_all, plane, 0, keepdims=False)
    y, s_new = ssm_step_math(rows, bc, a, d, s, live)
    return y, jax.lax.dynamic_update_slice(
        s_all, s_new[None], (plane,) + (jnp.int32(0),) * 3)


# ------------------------------------------------------ the decode kernel


def _ssm_step_kernel(plane_ref, src_ref, mode_ref, rows_ref, bc_ref, a_ref,
                     d_ref, s_ref, y_ref, s_out, *, sub: int):
    """One lane of a decode step: its ``s [N, E]`` read once, decayed,
    given ``Delta B x`` and written once, queried by ``C`` on its way.
    ``mode_ref[lane]``: 1 the lane decodes; 0 it does not (its grid
    step maps onto a block a decoding lane's step fetches anyway:
    nothing is fetched or written for it); 2 no lane decodes, and this
    one's block is handed through (``ops.retention._step_maps``)."""
    lane = pl.program_id(0)
    mode = mode_ref[lane]
    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(mode == 2)
    def _hand_through():
        s_out[...] = s_ref[...]

    @pl.when(mode == 1)
    def _update():
        bc = bc_ref[...]                                      # [N, 3]
        b, c, keep = bc[:, 0:1], bc[:, 1:2], bc[:, 2:3]
        for j in range(s_ref.shape[-1] // sub):
            cols = slice(j * sub, (j + 1) * sub)
            x, dt = rows_ref[0:1, cols], rows_ref[1:2, cols]  # [1, sub]
            held = jnp.where(keep > 0, s_ref[:, cols], 0.0)
            s = jnp.exp(dt * a_ref[:, cols]) * held + (dt * x) * b
            s_out[:, cols] = s
            y_ref[:, cols] = (jnp.sum(s * c, axis=0, keepdims=True)
                              + d_ref[:, cols] * x)


def use_ssm_kernel(e: int, dtype, sharded: bool = False) -> bool:
    """Kernel or :func:`ssm_step`: the backend, the placement and the
    shapes decide (whole lane tiles of channels, a float32 state)."""
    return (_on_tpu() and not sharded and e % _LANES == 0
            and jnp.dtype(dtype) == jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("s_all",))
def ssm_state_step(rows, bc, a, d, s_all, plane, live,
                   interpret: bool = False):
    """:func:`ssm_step` as ONE Pallas call, ``ssm_state_step``: grid over
    lanes, the state slab aliased in and out (nothing of it is copied:
    the caller donates it), the plane's index and the lanes' ``live``
    mask scalar-prefetched into the index maps."""
    b, _, e = rows.shape
    n = bc.shape[1]
    src, _, mode = _step_maps(live)
    sub = STEP_SUB if e % STEP_SUB == 0 else _LANES

    own = lambda ln, *_: (ln, 0, 0)
    whole = lambda ln, *_: (0, 0)
    s_spec = pl.BlockSpec((None, None, n, e),
                          lambda ln, plane_ref, src_ref, mode_ref: (
                              plane_ref[0], src_ref[ln], 0, 0))

    def call(): return pl.pallas_call(
        functools.partial(_ssm_step_kernel, sub=sub),
        name="ssm_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[pl.BlockSpec((None, 2, e), own),
                      pl.BlockSpec((None, n, 3), own),
                      pl.BlockSpec((n, e), whole),
                      pl.BlockSpec((1, e), whole), s_spec],
            out_specs=[pl.BlockSpec((None, 1, e), own), s_spec]),
        out_shape=[jax.ShapeDtypeStruct((b, 1, e), jnp.float32),
                   jax.ShapeDtypeStruct(s_all.shape, s_all.dtype)],
        # Operands are counted with the three prefetched scalars.
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=7 * b * n * e, transcendentals=b * n * e,
            bytes_accessed=8 * b * n * e),
    )(jnp.reshape(plane, (1,)).astype(jnp.int32), src, mode,
      rows.astype(jnp.float32), bc.astype(jnp.float32),
      a.astype(jnp.float32), jnp.reshape(d, (1, e)).astype(jnp.float32),
      s_all)

    if interpret:
        with pltpu.force_tpu_interpret_mode():
            y, s_all = call()
    else:
        y, s_all = call()
    return y[:, 0], s_all


# -------------------------------------------------- the chunk's kernel

CHUNK_LENGTHS = (64, 128, 256, 512)


def _row_sums(parts):
    """Eight ``[GROUP, w]`` blocks ``q_0 .. q_7`` -> ONE ``[GROUP, w]``
    whose row ``r`` is ``q_r`` summed over its rows: three rounds of
    folds, a round halving the blocks, each fold adding a block to
    itself rolled by ``k`` sublanes and keeping the rows whose bit ``k``
    is clear from one block and the others from its partner.  The blocks
    go in bit-reversed, so that row ``r`` comes out as ``q_r``'s sum."""
    sub = jax.lax.broadcasted_iota(jnp.int32, parts[0].shape, 0)
    parts = [parts[i] for i in (0, 4, 2, 6, 1, 5, 3, 7)]
    for k in (4, 2, 1):
        low = (sub & k) == 0
        parts = [jnp.where(low, u + pltpu.roll(u, GROUP - k, 0),
                           v + pltpu.roll(v, k, 0))
                 for u, v in zip(parts[::2], parts[1::2])]
    return parts[0]


def _ssm_chunk_kernel(sc_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                      s_ref, y_ref, s_out, *, unroll: int = CHUNK_UNROLL):
    """One tile of channels of one lane's chunk: the tile's state ``[N,
    tile]`` comes in once, is carried in registers through the chunk's
    REAL rows and goes out once.  The rows go GROUP at a time (one
    sublane tile of ``x``, ``Delta`` and ``y``), the group's rows
    unrolled: a row's ``exp(Delta A)``, ``Delta B x`` and ``C . s`` do
    not wait on the row before, only ``s = exp(Delta A) s + Delta B x``
    does, so they run beside its chain.  The group's ``y`` is stored as
    whole registers.  A group's rows past ``n_real`` take ``Delta`` and
    ``x`` 0 — ``exp(0) = 1`` and ``0 B`` add nothing, the state leaves
    them as it came — and ``y`` 0; groups past the last real row are
    only zeroed.  ``unroll`` groups a trip of the loop, the last few
    one a trip.  ``sc_ref``: (plane, lane, n_real, fresh)."""
    n_real, fresh = sc_ref[2], sc_ref[3]
    a, dv = a_ref[...], d_ref[...]
    n, tile = a.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, (GROUP, tile), 0)

    def group(g, s):
        at = pl.multiple_of(g * GROUP, GROUP)
        rows = pl.ds(at, GROUP)
        real = at + sub < n_real
        dt = jnp.where(real, dt_ref[rows, :], 0.0)              # [8, tile]
        x = jnp.where(real, x_ref[rows, :], 0.0)
        dtx = dt * x
        b, c = b_ref[rows], c_ref[rows]                         # [8, N, 1]
        parts = []
        for r in range(GROUP):
            s = jnp.exp(dt[r:r + 1] * a) * s + dtx[r:r + 1] * b[r]
            p = s * c[r]
            q = p[:GROUP]
            for i in range(GROUP, n, GROUP):
                q = q + p[i:i + GROUP]
            parts.append(q)
        y_ref[rows, :] = jnp.where(real, _row_sums(parts) + dv * x, 0.0)
        return s

    def groups(g, s):
        for u in range(unroll):
            s = group(g * unroll + u, s)
        return s

    used = (n_real + GROUP - 1) // GROUP
    s = jnp.where(fresh != 0, 0.0, s_ref[...])
    s = jax.lax.fori_loop(0, used // unroll, groups, s)
    s_out[...] = jax.lax.fori_loop(used // unroll * unroll, used, group, s)

    def zero(g, carry):
        y_ref[pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP), :] = (
            jnp.zeros((GROUP, tile), jnp.float32))
        return carry

    jax.lax.fori_loop(used, y_ref.shape[0] // GROUP, zero, 0)


def use_ssm_chunk_kernel(e: int, t_len: int, rows: int, dtype,
                         sharded: bool = False) -> bool:
    """Kernel or :func:`ssm_chunked` for a chunk: the backend, the
    placement and the shapes decide (ONE row, whole lane tiles of
    channels, a chunk length of an engine's buckets, a float32
    state)."""
    return (_on_tpu() and not sharded and rows == 1 and e % _LANES == 0
            and t_len in CHUNK_LENGTHS and jnp.dtype(dtype) == jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("s_all",))
def ssm_chunk_fwd(x, delta, bmat, cmat, a, d, s_all, plane, lane,
                  n_real=None, fresh=None, interpret: bool = False):
    """:func:`ssm_chunked` through lane ``lane`` of plane ``plane`` of the
    slab ``s_all [P, B, N, E]`` as ONE Pallas call, ``ssm_chunk_fwd`` ->
    ``(y [T, E] float32, s_all')``: grid over tiles of channels, the
    slab aliased in and out with the plane and the lane
    scalar-prefetched into its index map, so the lane's state is read
    and written where it lies and no other block is touched.  Rows ``>=
    n_real`` are padding (the state passes them unchanged, their ``y``
    is 0); ``fresh``: the state before the chunk is zero whatever the
    slab holds.  The rows and the ``N`` states go in whole sublane
    tiles (multiples of GROUP)."""
    return _chunk_call(_ssm_chunk_kernel, x, delta, bmat, cmat, a, d, s_all,
                       plane, lane, n_real, fresh, interpret)


def _chunk_call(kernel, x, delta, bmat, cmat, a, d, s_all, plane, lane,
                n_real, fresh, interpret):
    """The Pallas call of :func:`ssm_chunk_fwd` around the chunk kernel
    ``kernel`` (``scripts/sweep_ssm.py`` hands it other forms)."""
    t_len, e = x.shape
    n = bmat.shape[1]
    if t_len % GROUP or n % GROUP:
        raise ValueError(f"ssm_chunk_fwd takes rows and states in multiples "
                         f"of {GROUP}, not {t_len} and {n}")
    tile = CHUNK_TILE if e % CHUNK_TILE == 0 else _LANES
    n_real = jnp.int32(t_len) if n_real is None else n_real
    fresh = jnp.bool_(False) if fresh is None else fresh
    scalars = jnp.stack([jnp.asarray(v, jnp.int32).reshape(())
                         for v in (plane, lane, n_real, fresh)])
    cols = lambda c, sc: (0, c)
    whole = lambda c, sc: (0, 0, 0)
    s_spec = pl.BlockSpec((None, None, n, tile),
                          lambda c, sc: (sc[0], sc[1], 0, c))
    f32 = jnp.float32

    def call(): return pl.pallas_call(
        kernel,
        name="ssm_chunk_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(e // tile,),
            in_specs=[pl.BlockSpec((t_len, tile), cols),
                      pl.BlockSpec((t_len, tile), cols),
                      pl.BlockSpec((t_len, n, 1), whole),
                      pl.BlockSpec((t_len, n, 1), whole),
                      pl.BlockSpec((n, tile), cols),
                      pl.BlockSpec((1, tile), cols), s_spec],
            out_specs=[pl.BlockSpec((t_len, tile), cols), s_spec]),
        out_shape=[jax.ShapeDtypeStruct((t_len, e), f32),
                   jax.ShapeDtypeStruct(s_all.shape, s_all.dtype)],
        # Operands are counted with the prefetched scalars.
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=7 * t_len * n * e, transcendentals=t_len * n * e,
            bytes_accessed=4 * (2 * n * e + 3 * t_len * e + 2 * t_len * n)),
    )(scalars, x.astype(f32), delta.astype(f32),
      bmat.astype(f32)[..., None], cmat.astype(f32)[..., None],
      a.astype(f32), jnp.reshape(d, (1, e)).astype(f32), s_all)

    if interpret:
        with pltpu.force_tpu_interpret_mode():
            return tuple(call())
    return tuple(call())
