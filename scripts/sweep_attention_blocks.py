"""Sweep the segmented training kernels' blocks and tiles on packed rows.

The three Pallas kernels (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``) at the train cells' shapes (StarCoder2-3B: 24 heads
of 128, rows of 4096, window 4096, float32 operands; 8 rows, two steps of
``sc2-3b.train.pack4k``) over rows drawn by the
benchmark's own generator (``benchmarks/traffic/packed_docs.py`` with
``pack4k.json``).  Each point is a ``(block_q, block_k, tile_q,
tile_k)``: the grid's blocks and the tiles inside them at which a tile
no document spans is skipped (``ops/attention.py::_tile_bounds``).  The
first line is the launch that only masks (``skip_dead=False``: the
kernels as they were before the skip).  Every kernel is timed BY NAME
from a profiler trace of the launches (device time, mean of ``--iters``
calls), and printed as one JSON line a point with the share of the
causal band's tiles it computes (``live_tile_share``).  The segmented
defaults (``SEGMENT_BLOCK_*``, ``SEGMENT_TILES``) came from this
table.  A point whose kernel the compiler refuses is printed with its
error.  TPU only.

Usage: python scripts/sweep_attention_blocks.py [--iters N] [--seed N]
           [--rows N] [--dtype D] [--out FILE] [bq,bk,tq,tk ...]
"""

import argparse
import functools
import json
import os
import sys
import tempfile

os.environ.setdefault("KERAS_BACKEND", "jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
POINTS = [
    (1024, 1024, 1024, 1024), (1024, 1024, 1024, 256),
    (1024, 1024, 512, 512), (1024, 1024, 512, 256), (1024, 1024, 256, 256),
    (512, 1024, 512, 256), (512, 512, 512, 512), (512, 512, 512, 256),
    (512, 512, 256, 256), (256, 256, 256, 256),
    (2048, 2048, 512, 512), (2048, 2048, 512, 256), (2048, 1024, 512, 256),
]
HEADS, HEAD_DIM, WINDOW = 24, 128, 4096


def kernel_ms(run, iters):
    """Device milliseconds a call of each kernel, from a trace of
    ``iters`` calls of ``run`` (warmed before)."""
    import jax
    import trace_reduce

    run()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    with tempfile.TemporaryDirectory() as prof:
        jax.profiler.start_trace(prof, profiler_options=options)
        for _ in range(iters):
            run()
        jax.profiler.stop_trace()
        events = trace_reduce.load_events(prof)
    (ops,) = [d["ops"] for d in events["devices"].values() if d["ops"]]
    out = {}
    for name in KERNELS:
        durs = [dur for label, _, dur in ops
                if trace_reduce.op_group(label) == f"mosaic:{name}"]
        if len(durs) != iters:
            raise SystemExit(f"{name}: {len(durs)} events for {iters} calls")
        out[name] = sum(durs) / iters / 1e6
    return out


def main():
    import jax
    import numpy as np

    from distkeras_tpu.ops import attention
    from traffic import packed_docs

    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=3100000413)
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--dtype", default="float32",
                    help="of q, k, v, dO: the trainer's are float32 "
                    "(bf16 activations x float32 weights)")
    ap.add_argument("--out")
    ap.add_argument("points", nargs="*")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("the sweep times the kernels on a TPU")
    points = [tuple(int(x) for x in p.split(",")) for p in args.points] \
        or POINTS

    with open(os.path.join(REPO, "benchmarks", "traffic", "pack4k.json")) as f:
        mix = json.load(f)
    _, segs = packed_docs.make(mix, args.seed, 49152, args.rows)
    segs = segs[:, :-1]
    rng = np.random.default_rng(args.seed)
    shape = (args.rows, segs.shape[1], HEADS, HEAD_DIM)
    q, k, v, g = (jax.device_put(rng.normal(size=shape).astype(np.float32)
                                 ).astype(args.dtype) for _ in range(4))
    seg = jax.device_put(segs)
    lines = []
    for point in [None] + points:
        bq, bk, tq, tk = point or (attention.DEFAULT_BLOCK_Q,
                                   attention.DEFAULT_BLOCK_K, None, None)
        kw = dict(causal=True, scale=HEAD_DIM ** -0.5, block_q=bq, block_k=bk,
                  interpret=False, window=WINDOW,
                  tiles=point and (tq, tk), skip_dead=point is not None)
        fwd = jax.jit(functools.partial(attention._flash_fwd_local,
                                        with_lse=True, **kw))
        bwd = jax.jit(functools.partial(attention._flash_bwd_local, **kw))

        def run():
            out, lse = fwd(q, k, v, seg)
            jax.block_until_ready(bwd(q, k, v, out, lse, g, seg))

        point_is = {"block_q": bq, "block_k": bk, "tile_q": tq, "tile_k": tk}
        try:
            ms = kernel_ms(run, args.iters)
        except Exception as e:      # a tile the compiler refuses: say so, go on
            print(json.dumps({**point_is, "error": str(e)[:300]}), flush=True)
            continue
        line = {**point_is, "skip_dead": point is not None,
                "live_tile_share": 1.0 if point is None else
                attention.live_tile_share(segs, tq, tk, WINDOW),
                **{f"{n}_ms": round(t, 4) for n, t in ms.items()},
                "sum_ms": round(sum(ms.values()), 4),
                "step_ms": round(2 * ms["flash_fwd"] + ms["flash_bwd_dq"]
                                 + ms["flash_bwd_dkv"], 4)}
        print(json.dumps(line), flush=True)
        lines.append(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"seed": args.seed, "rows": args.rows, "iters": args.iters,
                       "dtype": args.dtype,
                       "points": lines}, f, indent=1)


if __name__ == "__main__":
    main()
