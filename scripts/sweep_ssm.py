"""The two ssm kernels alone, on the chip, at the Jamba cell's shapes.

    python scripts/sweep_ssm.py [--lanes L] [--iters N] [--out FILE]

Holds ``ops/ssm.py::ssm_state_step`` (a decode step's update of L lanes'
float32 states ``[16, 5120]``, every other lane left alone) to
``ssm_step``, its ``jax.numpy`` twin, and ``ssm_chunk_fwd`` (a lane's
chunk of 64 and 512 rows) to ``ssm_chunked`` on a state an earlier
chunk left, then times each kernel by the host clock around
``block_until_ready`` (the median of ``--iters``) and prints, a line
each, the time against the least the state's bytes allow at 819 GB/s.
Exits non-zero on any backend but TPU, or where a kernel departs from
its twin by more than float32 rounding.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.ops import ssm

HBM = 819e9
N, E, PLANES = 16, 5120, 26


def _timed(fn, iters):
    out = []
    for _ in range(iters):
        t = time.perf_counter()
        jax.block_until_ready(fn())
        out.append(time.perf_counter() - t)
    return statistics.median(out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--lanes", type=int, default=540)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("sweep_ssm: TPU only", file=sys.stderr)
        return 3
    rng = np.random.default_rng(0)
    draw = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    a = ssm.state_matrix(jnp.log(jnp.broadcast_to(
        jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, E))))
    d = draw(E)
    lanes = args.lanes
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
    lines = [{"kernel": "ssm_state_step", "lanes": lanes,
              "decoding": int(on.sum()),
              "state_err": float(np.abs(np.asarray(slab[3]) - s1).max()),
              "y_err": float(np.abs(np.asarray(y2 - y1))[on].max())}]

    def step():
        nonlocal slab
        _, slab = ssm.ssm_state_step(rows, bc, a, d, slab, plane, live)
        return slab

    t = _timed(step, args.iters)
    least = 2 * int(on.sum()) * N * E * 4 / HBM
    lines[-1].update(ms=1e3 * t, least_ms=1e3 * least, roofline=100 * least / t)
    for t_len in (64, 512):
        x, dt = draw(t_len, E), jax.nn.softplus(draw(t_len, E) - 3)
        bm, cm = draw(t_len, N), draw(t_len, N)
        held = np.asarray(slab[5, 7])
        want_y, want_s = jax.jit(ssm.ssm_chunked)(x, dt, bm, cm, a, d,
                                                  jnp.asarray(held))
        y, slab = ssm.ssm_chunk_fwd(x, dt, bm, cm, a, d, slab, jnp.int32(5),
                                    jnp.int32(7))
        scale = float(np.abs(np.asarray(want_y)).max())
        err = (float(np.abs(np.asarray(y - want_y)).max()) / scale,
               float(np.abs(np.asarray(slab[5, 7] - want_s)).max())
               / float(np.abs(np.asarray(want_s)).max()))

        def chunk():
            nonlocal slab
            _, slab = ssm.ssm_chunk_fwd(x, dt, bm, cm, a, d, slab,
                                        jnp.int32(5), jnp.int32(7))
            return slab

        t = _timed(chunk, args.iters)
        least = (t_len * 4 * (3 * E + 2 * N) + 2 * N * E * 4) / HBM
        lines.append({"kernel": "ssm_chunk_fwd", "rows": t_len,
                      "y_rel_err": err[0], "state_rel_err": err[1],
                      "ms": 1e3 * t, "least_bytes_ms": 1e3 * least,
                      "us_a_row": 1e6 * t / t_len})
    bad = (lines[0]["state_err"] > 1e-3 or lines[0]["y_err"] > 1e-2
           or any(r["y_rel_err"] > 1e-4 or r["state_rel_err"] > 1e-4
                  for r in lines[1:]))
    for r in lines:
        print(json.dumps(r), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
