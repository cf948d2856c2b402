"""Latent attention's two served paths alone, on the chip, at the
latent cell's shapes (32 heads, rows of 640 = 512 latent + 64 rotary +
64 zeros, bf16).  JSON lines; TPU only.

    python scripts/sweep_mla.py [--iters N] [decode|chunk] [P ...]

Both groups run unless one is named.

``decode``: ``ops/latent.py::mla_decode_attention`` held to its
``jax.numpy`` twin on random rows (lanes at positions 0, 1, a block's
edge, mid-block, the whole row), then timed over ``LANES`` lanes at
``P`` live positions each, one plane a call: ``roofline`` is the share
of the bytes' time (1,152 B a position at the least layout, 819 GB/s).

``chunk``: a 512-row admission chunk's attention against a prefix of
``P`` positions, a layer, in three forms — the engine runs the last
(``generate._chunk_in_place``); the first two are ISSUE 37's
alternatives, built here alone to be timed beside it —
``absorbed``: the
    queries with ``wkv_b``'s key half folded in, every head on the ONE
    row a position as key and value, through ``flash_prefix_attention``
    (cost by the attended prefix, no temporaries), the value half
    folded out after;
``expanded``: ``c · wkv_b`` for the WHOLE prefix again (keys and values
    of 32 heads rebuilt: 20 KB a position of temporaries), heads of 192
    / 128 padded to the kernel's 256, through the same kernel with 32
    K/V heads;
``kernel``: ``ops/latent.py::mla_prefix_attention`` — the expanded form
    with a block's keys and values rebuilt in VMEM, the rows read from
    the slab where they lie — its line says the heads a step of its
    head loop (``MLA_PREFIX_HEAD_GROUP``).
Host clock around ``iters`` calls of one jitted program each.
"""

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.ops.attention import flash_prefix_attention
from distkeras_tpu.ops.latent import (MLA_PREFIX_HEAD_GROUP,
                                      mla_decode_attention, mla_decode_twin,
                                      mla_prefix_attention)

H, W, RANK, ROPE, NOPE, V = 32, 640, 512, 64, 128, 128
LANES, S, T = 26, 32768, 512
SCALE = 1.0 / math.sqrt(NOPE + ROPE)
HBM = 819e9


def timed(fn, *args, iters):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def rows(key, *lead):
    """Random cached rows: unit latent and key, zeros past 576."""
    x = jax.random.normal(key, lead + (W,), jnp.float32)
    return jnp.where(jnp.arange(W) < RANK + ROPE, x, 0).astype(jnp.bfloat16)


def decode(positions, iters):
    ks = jax.random.split(jax.random.key(0), 3)
    lat = rows(ks[0], 2, 6, 2048)
    q = rows(ks[1], 6, H)
    pos = jnp.asarray([0, 1, 512, 700, 2048, 0], jnp.int32)
    o1, l1 = jax.jit(mla_decode_twin, static_argnums=(4, 5))(
        q, lat, 1, pos, SCALE, RANK)
    o2, l2 = mla_decode_attention(q, lat, jnp.int32(1), pos, scale=SCALE,
                                  values=RANK)
    print(json.dumps({"decode_parity": {
        "out": float(jnp.abs(o1 - o2).max()),
        "out_scale": float(jnp.abs(o1).max()),
        "lse": float(jnp.abs(jnp.where(pos[:, None] > 0, l1 - l2, 0)).max()),
        "empty_lane": [float(jnp.abs(o2[0]).max()), float(l2[0].max())]}}),
        flush=True)
    del lat
    lat = rows(ks[0], 1, LANES, S)
    q = rows(ks[1], LANES, H)
    for p in positions:
        for name, at in (("all", np.full(LANES, p)),
                         ("half", np.where(np.arange(LANES) % 2, p, 0))):
            at = jnp.asarray(at, jnp.int32)
            t = timed(lambda: mla_decode_attention(
                q, lat, jnp.int32(0), at, scale=SCALE, values=RANK),
                iters=iters)
            live = int(at.sum())
            print(json.dumps({"decode": name, "positions": p, "ms": 1e3 * t,
                              "roofline": live * (RANK + ROPE) * 2 / HBM / t,
                              "ns_a_position": 1e9 * t / max(live, 1)}),
                  flush=True)


def chunk(positions, iters):
    ks = jax.random.split(jax.random.key(1), 5)
    wkv_b = (jax.random.normal(ks[0], (RANK, H * (NOPE + V)))
             / math.sqrt(RANK)).astype(jnp.bfloat16)
    q_nope = jax.random.normal(ks[1], (1, T, H, NOPE)).astype(jnp.bfloat16)
    q_pe = jax.random.normal(ks[2], (1, T, H, ROPE)).astype(jnp.bfloat16)
    split = lambda: (wkv_b.reshape(RANK, H, NOPE + V)[..., :NOPE],
                     wkv_b.reshape(RANK, H, NOPE + V)[..., NOPE:])

    @jax.jit
    def absorbed(plane, off):
        wk, wv = split()
        q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, wk,
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat, q_pe.astype(jnp.float32),
                             jnp.zeros((1, T, H, W - RANK - ROPE))], -1)
        q = (q * (SCALE * math.sqrt(W))).astype(jnp.bfloat16)
        out = flash_prefix_attention(q, plane[:, :, None], plane[:, :, None],
                                     off)[..., :RANK]
        return jnp.einsum("bthr,rhv->bthv", out, wv)

    def expanded_at(s_len):
        @jax.jit
        def expanded(plane, off):
            wk, wv = split()
            c, k_pe = plane[0, :s_len, :RANK], plane[0, :s_len, RANK:RANK + ROPE]
            k = jnp.concatenate(
                [jnp.einsum("sr,rhn->shn", c, wk),
                 jnp.broadcast_to(k_pe[:, None], (s_len, H, ROPE)),
                 jnp.zeros((s_len, H, 256 - NOPE - ROPE), jnp.bfloat16)], -1)
            v = jnp.concatenate(
                [jnp.einsum("sr,rhv->shv", c, wv),
                 jnp.zeros((s_len, H, 256 - V), jnp.bfloat16)], -1)
            q = jnp.concatenate(
                [q_nope, q_pe, jnp.zeros((1, T, H, 256 - NOPE - ROPE),
                                         jnp.bfloat16)], -1)
            q = (q.astype(jnp.float32) * (SCALE * 16.0)).astype(jnp.bfloat16)
            return flash_prefix_attention(q, k[None], v[None], off)[..., :V]
        return expanded

    @jax.jit
    def kernel(plane, off):
        return mla_prefix_attention(
            q_nope[0], q_pe[0], wkv_b, plane[None], jnp.int32(0),
            jnp.int32(0), off, scale=SCALE, rank=RANK)

    plane = rows(ks[3], 1, S)
    for p in positions:
        off = jnp.int32(p)
        s_len = -(-(p + T) // 1024) * 1024
        expanded = expanded_at(s_len)
        a, e = absorbed(plane, off), expanded(plane, off)
        k = kernel(plane, off)[None]
        print(json.dumps({
            "chunk_prefix": p, "group": MLA_PREFIX_HEAD_GROUP,
            "kernel_ms": 1e3 * timed(kernel, plane, off, iters=iters),
            "kernel_differs_by": float(jnp.abs(
                k.astype(jnp.float32) - e.astype(jnp.float32)).max()),
            "absorbed_ms": 1e3 * timed(absorbed, plane, off, iters=iters),
            "expanded_ms": 1e3 * timed(expanded, plane, off, iters=iters),
            "expanded_temporaries_mb": 2 * s_len * H * 256 * 2 / 1e6,
            "forms_differ_by": float(jnp.abs(a.astype(jnp.float32)
                                             - e.astype(jnp.float32)).max()),
            "out_scale": float(jnp.abs(a.astype(jnp.float32)).max())}),
            flush=True)


def main(argv):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the kernels are timed on a TPU only")
    iters = 10
    if argv[:1] == ["--iters"]:
        iters, argv = int(argv[1]), argv[2:]
    which = {"decode", "chunk"}
    if argv[:1] in (["decode"], ["chunk"]):
        which, argv = {argv[0]}, argv[1:]
    positions = [int(a) for a in argv] or [4096, 10240, 30208]
    if "decode" in which:
        decode(positions, iters)
    if "chunk" in which:
        chunk(positions, iters)


if __name__ == "__main__":
    main(sys.argv[1:])
