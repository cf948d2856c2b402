"""The chunk's retention alone, on the chip: the kernel ``ops/retention.py::
ret_chunk_fwd`` against the form it replaces on the TPU —
``retention_chunk`` between the ``dynamic_slice`` /
``dynamic_update_slice`` pair of ``generate.py::attend_state`` — at the
retention cell's shapes (8 K/V heads of 128, 5 query heads each) over
the engine's chunk lengths.

First the parity, in the compute dtype: 8 chunks in a row through each
form (the first ``fresh`` over a plane of garbage, the last short of its
length), queries and keys of unit scale, values with a common part, the
gate's bias 6 as the cell draws it — so the states are what earlier
chunks left, as an engine's always are — and both held to the same
chain in float32 at full precision: ``*_vs_f32`` is the mean and the
largest distance of the second half's outputs (rounded to the compute
dtype, as the layer rounds them) and of the last state.  Then the time:
ONE program applies the form to the slabs' 8 planes in turn, as an
admission's 8 layers do, and ``ms`` is the host's clock around
``--iters`` such programs / 8 — a call's device time once the programs
queue back to back.  One JSON line a group; TPU only.

    python scripts/sweep_ret_chunk.py [--iters N] [--dtype D] [--times-only]
        [C ...]
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from distkeras_tpu.ops import retention as R

KV, G, D, P, LANES, LANE = 8, 5, 128, 8, 3, 1
CHAIN = 8           # chunks in a row, for the parity


def old_form(q, k, v, logg, s_all, z_all, plane, lane, n_real, fresh):
    """``attend_state``'s chunk branch as it was: the lane's state cut
    out, ``retention_chunk``, the new state written back."""
    at = (plane, lane) + (jnp.int32(0),) * 4
    s = jax.lax.dynamic_slice(s_all, at, (1, 1) + s_all.shape[2:])[0, 0]
    z = jax.lax.dynamic_slice(z_all, at[:-1], (1, 1) + z_all.shape[2:])[0, 0]
    y, s, z = R.retention_chunk(q, k, v, logg, s, z, n_real, fresh)
    return (y, jax.lax.dynamic_update_slice(s_all, s[None, None], at),
            jax.lax.dynamic_update_slice(z_all, z[None, None], at[:-1]))


def chain(form, q, k, v, logg, dt, kv=2):
    """``CHAIN`` chunks through ``form`` in lane 1 of plane 1 of slabs
    of two K/V heads: the outputs as the layer rounds them, the last
    state, and whether every other block is what it was."""
    c_len = q.shape[0] // CHAIN
    keys = jax.random.split(jax.random.key(1), 2)
    s0 = jax.random.normal(keys[0], (2, 2, kv, 65, D, D))
    z0 = jax.random.normal(keys[1], (2, 2, kv, 65, D))
    s, z, ys = s0 + 0, z0 + 0, []     # (the kernel donates its slabs)
    for t in range(CHAIN):
        part = slice(t * c_len, (t + 1) * c_len)
        n = c_len - 7 if t == CHAIN - 1 else c_len
        y, s, z = form(q[part].astype(dt), k[part].astype(dt),
                       v[part].astype(dt), logg[part], s, z, jnp.int32(1),
                       jnp.int32(1), jnp.int32(n), jnp.bool_(t == 0))
        ys.append(y[:n].astype(q.dtype).astype(jnp.float32))
    same = bool((s[0] == s0[0]).all() and (s[1, 0] == s0[1, 0]).all()
                and (z[0] == z0[0]).all() and (z[1, 0] == z0[1, 0]).all())
    return jnp.concatenate(ys), s[1, 1], z[1, 1], same


def parity(c_len, dt):
    kv = 2
    ks = jax.random.split(jax.random.key(0), 5)
    draw = lambda i, *shape: jax.random.normal(ks[i], shape)
    unit = lambda a: a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True))
    n = CHAIN * c_len
    q = unit(draw(0, n, kv * G, D)).astype(dt)
    k = unit(draw(1, n, kv, D)).astype(dt)
    v = (2.0 * draw(2, 1, kv, D) + draw(3, n, kv, D)).astype(dt)
    logg = jax.nn.log_sigmoid(6 + draw(4, n, kv))
    with jax.default_matmul_precision("highest"):
        want = chain(jax.jit(old_form), q, k, v, logg, jnp.float32)
    dist = lambda a, b: {"mean": float(jnp.abs(a - b).mean()),
                         "max": float(jnp.abs(a - b).max())}
    out = {"y_scale": float(jnp.abs(want[0]).mean()),
           "s_scale": float(jnp.abs(want[1]).mean())}
    half = want[0].shape[0] // 2
    for name, form in (("retention_chunk", jax.jit(old_form)),
                       ("ret_chunk_fwd", R.ret_chunk_fwd)):
        y, s, z, same = chain(form, q, k, v, logg, dt)
        out[name + "_vs_f32"] = {
            "y": dist(y[half:], want[0][half:]), "s": dist(s, want[1]),
            "z": dist(z, want[2]), "other_blocks_untouched": same}
    return out


def layers(form):
    """``form`` on every plane in turn, the slabs donated."""
    @functools.partial(jax.jit, donate_argnums=(4, 5))
    def run(q, k, v, logg, s_all, z_all, n_real, fresh):
        ys = []
        for plane in range(P):
            y, s_all, z_all = form(q, k, v, logg, s_all, z_all,
                                   jnp.int32(plane), jnp.int32(LANE),
                                   n_real, fresh)
            ys.append(y.astype(jnp.float32).sum())
        return jnp.stack(ys), s_all, z_all
    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--times-only", action="store_true")
    ap.add_argument("lengths", nargs="*", type=int,
                    default=list(R.CHUNK_LENGTHS))
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the kernel is timed on a TPU only")
    dt = jnp.dtype(args.dtype)
    ks = jax.random.split(jax.random.key(0), 4)
    draw = lambda i, *shape: jax.random.normal(ks[i], shape)
    forms = {"retention_chunk": old_form, "ret_chunk_fwd": R.ret_chunk_fwd}
    for c_len in args.lengths:
        if not args.times_only:
            print(json.dumps({"chunk": c_len, "dtype": dt.name,
                              "parity": parity(c_len, dt)}), flush=True)
        q = draw(0, c_len, KV * G, D).astype(dt)
        k, v = (draw(i, c_len, KV, D).astype(dt) for i in (1, 2))
        logg = jax.nn.log_sigmoid(6 + draw(3, c_len, KV))
        times = {}
        n, f = jnp.int32(c_len), jnp.bool_(False)
        for name, form in forms.items():
            run = layers(form)
            s = jnp.full((P, LANES, KV, 65, D, D), 0.01, jnp.float32)
            z = jnp.full((P, LANES, KV, 65, D), 1.0, jnp.float32)
            t0 = time.perf_counter()
            ys, s, z = run(q, k, v, logg, s, z, n, f)
            jax.block_until_ready(ys)
            first = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(args.iters):
                ys, s, z = run(q, k, v, logg, s, z, n, f)
            jax.block_until_ready((ys, s))
            ms = 1e3 * (time.perf_counter() - t0) / args.iters / P
            times[name] = {"ms": ms, "us_a_head": 1e3 * ms / KV,
                           "first_call_s": first}
            del s, z
        print(json.dumps({"chunk": c_len, "dtype": dt.name, "times": times}),
              flush=True)


if __name__ == "__main__":
    main()
