"""The two ssm kernels alone, on the chip, at the Jamba cell's shapes.

    python scripts/sweep_ssm.py [--lanes L] [--iters N] [--out FILE]
        [ROWS ...]

Holds ``ops/ssm.py::ssm_state_step`` (a decode step's update of L lanes'
float32 states ``[16, 5120]``, every other lane left alone) to
``ssm_step``, its ``jax.numpy`` twin, then each form of the chunk
kernel to ``ssm_chunked`` at each chunk length ROWS (64, 256 and 512 by
default): a state an earlier chunk left, a chunk short of its length by
3 rows (``y`` past ``n_real`` must be 0) and a ``fresh`` one, the
slab's other lanes unchanged bit for bit.  The forms: ``row`` (the
kernel as it stood before the rows went in groups: one row a trip of
its loop, built here) and ``group_x<k>``, the kernel of
``ssm_chunk_fwd`` (``GROUP`` rows a group, unrolled) at k groups a trip
of its loop; it runs ``CHUNK_UNROLL``.

Times are device times: ONE program runs ``--iters`` calls back to back
in a ``lax.fori_loop``, and a call's time is the difference of the host
clock around ``2 --iters`` and ``--iters`` calls over ``--iters`` (what
the dispatch costs cancels).  One JSON line a kernel and form: the
state step against the least its state's bytes allow at 819 GB/s, a
chunk form in ms a call and ns a row a 512-channel tile.  Exits non-zero
on any backend but TPU, or where a form departs from its twin by more
than float32 rounding.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from distkeras_tpu.ops import ssm

HBM = 819e9
N, E, PLANES = 16, 5120, 26
TILES = E // ssm.CHUNK_TILE


def _row_kernel(sc_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, s_ref,
                y_ref, s_out):
    """The chunk kernel one row a trip of its loop: the form the grouped
    kernel replaced, kept here to be timed beside it."""
    n_real, fresh = sc_ref[2], sc_ref[3]
    y_ref[...] = jnp.zeros_like(y_ref)
    a, dv = a_ref[...], d_ref[...]
    s0 = jnp.where(fresh != 0, 0.0, s_ref[...])

    def row(t, s):
        dt = dt_ref[pl.ds(t, 1), :]
        x = x_ref[pl.ds(t, 1), :]
        s = jnp.exp(dt * a) * s + (dt * x) * b_ref[t]
        y_ref[pl.ds(t, 1), :] = (jnp.sum(s * c_ref[t], axis=0, keepdims=True)
                                 + dv * x)
        return s

    s_out[...] = jax.lax.fori_loop(0, n_real, row, s0)


FORMS = {"row": _row_kernel,
         **{f"group_x{k}": functools.partial(ssm._ssm_chunk_kernel, unroll=k)
            for k in (1, 2, 4, 8)}}


def _form(kernel):
    return jax.jit(functools.partial(ssm._chunk_call, kernel,
                                     interpret=False),
                   donate_argnames=("s_all",))


def device_s(step, carry, iters):
    """A call's device time: ``step`` (carry -> carry) ``iters`` and
    ``2 iters`` times in one program each, the host clock's difference
    over ``iters``."""
    def loop(k):
        return jax.jit(lambda c: jax.lax.fori_loop(0, k, lambda i, c: step(c),
                                                   c), donate_argnums=0)
    spans = []
    for k in (iters, 2 * iters):
        fn = loop(k)
        carry = jax.block_until_ready(fn(carry))          # compile, warm
        best = float("inf")
        for _ in range(3):
            t = time.perf_counter()
            carry = jax.block_until_ready(fn(carry))
            best = min(best, time.perf_counter() - t)
        spans.append(best)
    return (spans[1] - spans[0]) / iters, carry


def state_step(lanes, iters, a, d, draw, rng):
    slab = draw(PLANES, lanes, N, E)
    live = jnp.asarray(rng.random(lanes) < 0.8, jnp.int32)
    rows, bc = ssm.step_operands(
        draw(lanes, E), jax.nn.softplus(draw(lanes, E) - 3), draw(lanes, N),
        draw(lanes, N), jnp.ones((lanes,), bool))
    plane = jnp.int32(3)
    with jax.default_matmul_precision("highest"):
        y1, s1 = jax.jit(ssm.ssm_step)(rows, bc, a, d, slab, plane, live)
        s1 = np.asarray(s1[3])
    y2, slab = ssm.ssm_state_step(rows, bc, a, d, slab, plane, live)
    on = np.asarray(live, bool)
    line = {"kernel": "ssm_state_step", "lanes": lanes,
            "decoding": int(on.sum()),
            "state_err": float(np.abs(np.asarray(slab[3]) - s1).max()),
            "y_err": float(np.abs(np.asarray(y2 - y1))[on].max())}
    step = lambda s: ssm.ssm_state_step.__wrapped__(rows, bc, a, d, s, plane,
                                                    live)[1]
    t, _ = device_s(step, slab, iters)
    least = 2 * int(on.sum()) * N * E * 4 / HBM
    line.update(ms=1e3 * t, least_ms=1e3 * least, roofline=100 * least / t)
    return line, line["state_err"] > 1e-3 or line["y_err"] > 1e-2


def chunk_parity(fn, t_len, a, d, draw):
    """The form against ``ssm_chunked`` on lane (1, 1) of a small slab:
    a whole chunk after a state, one short of its length by 3 rows, a
    ``fresh`` one over garbage; the worst relative distances of ``y``
    and of the state, and whether ``y`` past ``n_real`` and the other
    lanes came out as they must."""
    x, dt = draw(t_len, E), jax.nn.softplus(draw(t_len, E) - 3)
    bm, cm = draw(t_len, N), draw(t_len, N)
    slab0 = draw(2, 2, N, E)
    errs, exact = [0.0, 0.0], True
    for n_real, fresh in ((t_len, False), (t_len - 3, False), (t_len - 3, True)):
        held = jnp.zeros((N, E)) if fresh else slab0[1, 1]
        with jax.default_matmul_precision("highest"):
            want_y, want_s = jax.jit(ssm.ssm_chunked)(x, dt, bm, cm, a, d,
                                                      held, n_real)
        y, slab = fn(x, dt, bm, cm, a, d, slab0 + 0, jnp.int32(1),
                     jnp.int32(1), jnp.int32(n_real), jnp.bool_(fresh))
        y, slab = np.asarray(y), np.asarray(slab)
        want_y, want_s = np.asarray(want_y), np.asarray(want_s)
        errs[0] = max(errs[0], float(np.abs(y[:n_real] - want_y[:n_real]).max()
                                     / np.abs(want_y[:n_real]).max()))
        errs[1] = max(errs[1], float(np.abs(slab[1, 1] - want_s).max()
                                     / np.abs(want_s).max()))
        others = np.ones((2, 2), bool)
        others[1, 1] = False
        exact &= bool((y[n_real:] == 0).all()
                      and (slab[others] == np.asarray(slab0)[others]).all())
    return errs, exact


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=540)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--out")
    ap.add_argument("rows", nargs="*", type=int, default=[64, 256, 512])
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("sweep_ssm: TPU only", file=sys.stderr)
        return 3
    rng = np.random.default_rng(0)
    draw = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    a = ssm.state_matrix(jnp.log(jnp.broadcast_to(
        jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, E))))
    d = draw(E)
    line, bad = state_step(args.lanes, args.iters, a, d, draw, rng)
    lines = [line]
    print(json.dumps(line), flush=True)
    for t_len in args.rows:
        x, dt = draw(t_len, E), jax.nn.softplus(draw(t_len, E) - 3)
        bm, cm = draw(t_len, N), draw(t_len, N)
        for name, kernel in FORMS.items():
            fn = _form(kernel)
            errs, exact = chunk_parity(fn, t_len, a, d, draw)
            step = lambda s: fn.__wrapped__(
                x, dt, bm, cm, a, d, s, jnp.int32(1), jnp.int32(1),
                jnp.int32(t_len), jnp.bool_(False))[1]
            t, _ = device_s(step, draw(2, 2, N, E), args.iters)
            line = {"kernel": "ssm_chunk_fwd", "form": name, "rows": t_len,
                    "y_rel_err": errs[0], "state_rel_err": errs[1],
                    "padding_and_other_lanes_exact": exact, "ms": 1e3 * t,
                    "ns_a_row_a_tile": 1e9 * t / (t_len * TILES)}
            bad |= errs[0] > 1e-4 or errs[1] > 1e-4 or not exact
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
