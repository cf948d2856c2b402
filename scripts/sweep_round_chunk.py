"""A round that holds a continuation chunk, alone on the chip: the ONE
program ``jit_round_chunk_p`` (``serving/lanes.py::_make_round_chunk``)
against the pair it replaces, ``jit__admit`` then ``jit_step_n_p``, on
the engine of ``sc1b.serve.batch`` (its configuration file: 32 lanes,
chunks of 512, 8192 slots) with every lane but the admitting one about
half full.

First the parity, in the engine's dtype: both forms from one state; the
tokens of the decoding lanes must be equal and the slabs equal but for
the parked lane's last slot.  Then the time: each form's programs
queued back to back under the profiler, ``ms`` their device time by
program name (median) and ``ops`` the device time of a round by
operation.  One JSON line a form; TPU only.  (PR 36 also timed the
fused program with its 544 side-by-side rows padded to 640: 13.60 ms
for 12.62, so the stream is not padded and the option went.)

    python scripts/sweep_round_chunk.py [--iters N]
"""

import argparse
import json
import os
import statistics
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import serving
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.utils.misc import configure_compile_cache

LANE = 5            # the admitting lane
OFF = 2048          # where its chunk goes


def build(conf):
    cfg = tfm.TransformerConfig(**conf["transformer_config"])
    dtype = jnp.dtype(conf["param_dtype"])
    params = jax.jit(lambda k: jax.tree.map(
        lambda a: a.astype(dtype), tfm.init_params(k, cfg)))(jax.random.key(7))
    eng = getattr(serving, conf["engine"]["class"])(
        params, cfg, **conf["engine"]["kwargs"])
    return cfg, eng


def state(cfg, eng, seed=0):
    """Lane positions about half the slab (the cell's fill), the
    admitting lane parked; the slab holds what the warm-up left and
    zeros, which costs what live K/V costs."""
    rng = np.random.default_rng(seed)
    pos = rng.integers(1024, 7168, size=eng.lanes).astype(np.int32)
    pos[LANE] = cfg.max_len - 1
    cur = rng.integers(0, cfg.vocab_size, size=eng.lanes).astype(np.int32)
    rows = rng.integers(0, cfg.vocab_size,
                        size=(1, eng.prefill_chunk)).astype(np.int32)
    return jnp.asarray(pos), jnp.asarray(cur), jnp.asarray(rows)


def pair(eng, cache, cur, pos, rows):
    cache = eng._admit(eng.params, cache, rows, jnp.int32(LANE),
                       jnp.int32(OFF))
    return eng._steps[1](eng.params, cache, cur, pos, eng.keys, eng.temps,
                         eng.tps, eng.mps)


def fused(eng, cache, cur, pos, rows):
    return eng._round_chunk(
        eng.params, cache, cur, pos, eng.keys, eng.temps, eng.tps, eng.mps,
        rows, jnp.int32(LANE), jnp.int32(OFF))


def timed(form, eng, pos, cur, rows, iters):
    """``iters`` rounds of ``form`` under the profiler: device time by
    program and by operation, a round."""
    import trace_reduce

    cache = eng.cache
    for _ in range(3):
        cache, *_ = form(eng, cache, cur, pos, rows)
    jax.block_until_ready(cache)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(iters):
            cache, *_ = form(eng, cache, cur, pos, rows)
        jax.block_until_ready(cache)
        jax.profiler.stop_trace()
        events = trace_reduce.load_events(d)
    eng.cache = cache
    dev = events["devices"][sorted(events["devices"])[0]]
    programs, ops = {}, {}
    for label, _, dur in dev["modules"]:
        programs.setdefault(trace_reduce.op_name(label).split("(")[0],
                            []).append(dur / 1e6)
    for label, _, dur in dev["ops"]:
        g = trace_reduce.op_group(label)
        ops[g] = ops.get(g, 0.0) + dur / 1e6 / iters
    return ({k: round(statistics.median(v), 4) for k, v in programs.items()},
            {k: round(v, 4) for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:14]})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--config", default="starcoderbase-1b_repo-block")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("TPU only")
    configure_compile_cache()
    with open(os.path.join(REPO, "benchmarks", "configs",
                           args.config + ".json")) as f:
        conf = json.load(f)
    cfg, eng = build(conf)
    pos, cur, rows = state(cfg, eng)

    # Parity: both forms from copies of one state.
    copy = lambda: jax.tree.map(jnp.copy, eng.cache)
    c2, cur2, _, t2 = pair(eng, copy(), cur, pos, rows)
    c1, cur1, _, t1 = fused(eng, copy(), cur, pos, rows)
    live = np.arange(eng.lanes) != LANE
    diff = {}
    for name in ("k", "v"):
        d = np.abs(np.asarray(c1[name], np.float32)
                   - np.asarray(c2[name], np.float32))
        d[:, LANE, cfg.max_len - 1] = 0
        diff[name] = float(d.max())
    print(json.dumps({"note": "parity", "tokens_equal": bool(
        (np.asarray(t1)[live] == np.asarray(t2)[live]).all()),
        "slab_max_abs_diff": diff}), flush=True)
    del c1, c2

    for name, form in (("pair", pair), ("fused", fused)):
        programs, ops = timed(form, eng, pos, cur, rows, args.iters)
        print(json.dumps({"form": name, "rows": eng.lanes + rows.shape[1],
                          "ms": programs, "ops": ops}), flush=True)


if __name__ == "__main__":
    main()
