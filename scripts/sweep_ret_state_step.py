"""The state-update kernel alone, on the chip: ``ops/retention.py::
ret_state_step`` against ``step_math`` (the same arithmetic in
``jax.numpy``) on random states, then timed at the retention cell's
shapes (22 lanes, 8 K/V heads of 128) over ``rows`` (value rows a pass
of the kernel's inner loop) with every lane, every other lane and no
lane decoding.  One JSON line a group; TPU only.

    python scripts/sweep_ret_state_step.py [rows ...]

``roofline`` is the share of the bytes' time: the decoding lanes' ``S``
and ``z`` at the least layout (8,256 rows a K/V head), read and written
once, at 819 GB/s.  Host clock around ten calls, so ``none`` reads the
dispatch's own cost (~0.85 ms), not the device's.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.ops import retention as R

B, KV, H, D, P = 22, 8, 40, 128, 2


def main(rows_list):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the kernel is timed on a TPU only")
    ks = jax.random.split(jax.random.key(0), 6)
    draw = lambda i, *shape: jax.random.normal(ks[i], shape)
    x = R.step_operands(draw(0, B, H, D), draw(1, B, KV, D), draw(2, B, KV, D),
                        jax.nn.log_sigmoid(6 + draw(3, B, KV)),
                        jnp.zeros((B,), bool).at[3].set(True))
    s0, z0 = draw(4, P, 3, KV, 65, D, D), 3 + draw(5, P, 3, KV, 65, D)
    parity = {}
    for live in ([1, 0, 1], [0, 1, 0], [0, 0, 0], [1, 1, 1]):
        lv, on = jnp.asarray(live), np.asarray(live, bool)
        y1, s1, z1 = jax.jit(R.step_math)(x[:3], s0[1], z0[1], lv)
        y2, s2, z2 = R.ret_state_step(x[:3], s0 + 0, z0 + 0, jnp.int32(1), lv)
        parity[str(live)] = {
            "y": float(jnp.abs((y1 - y2)[on]).max()) if on.any() else None,
            "y_scale": float(jnp.abs(y1[on]).max()) if on.any() else None,
            "s": float(jnp.abs(s1 - s2[1]).max()),
            "z": float(jnp.abs(z1 - z2[1]).max()),
            "other_plane_untouched": bool((s2[0] == s0[0]).all())}
    print(json.dumps({"parity": parity}), flush=True)
    del s0, z0, s1, z1, s2, z2
    s = jnp.full((P, B, KV, 65, D, D), 0.5, jnp.float32)
    z = jnp.full((P, B, KV, 65, D), 1.0, jnp.float32)
    masks = {"all": np.ones(B, np.int32), "half": np.arange(B) % 2,
             "none": np.zeros(B, np.int32)}
    for rows in rows_list:
        times = {}
        for name, live in masks.items():
            lv = jnp.asarray(live, jnp.int32)
            y, s, z = R.ret_state_step(x, s, z, jnp.int32(1), lv, rows=rows)
            jax.block_until_ready(y)
            t0 = time.perf_counter()
            for _ in range(10):
                y, s, z = R.ret_state_step(x, s, z, jnp.int32(1), lv,
                                           rows=rows)
            jax.block_until_ready((y, s))
            dt = (time.perf_counter() - t0) / 10
            gb = 2 * int(live.sum()) * KV * 8256 * 129 * 4 / 1e9
            times[name] = {"ms": 1e3 * dt,
                           "roofline": 100 * gb / 819 / dt if gb else None}
        print(json.dumps({"rows": rows, "times": times}), flush=True)


if __name__ == "__main__":
    main([int(a) for a in sys.argv[1:]] or [8, 32, 128])
