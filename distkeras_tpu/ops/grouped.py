"""Grouped matrix product: the rows of ``lhs`` come sorted by group,
and group ``g``'s rows multiply ``rhs[g]``.  The product of a routed
feed-forward's held experts (models/transformer.py
``moe_held_experts``).

On the TPU it is JAX's megablox kernel (Pallas; in a device trace the
Mosaic calls named ``gmm``, after that library's jitted entry point), which
walks only the row tiles that the groups fill and reads only their
groups' weights; elsewhere, and for shapes it does not tile,
``jax.lax.ragged_dot``.  Measured on one v5e at the benchmark's sizes,
16 held experts of ``[6144, 4096]`` then ``[2048, 6144]`` in bf16, both
products, ms (chip, PR 31; the experts' 1.21 GB take 1.47 ms at the
HBM peak): the kernel at tiles (128, 2048, 1024) **1.90** with 166-189
live rows of 1,536-1,792 (a decode step of 190-217 lanes) and **2.20**
with 516 of 4,096 (a chunk of 512), at (128, 512, 512) 2.31 and 2.73;
``ragged_dot`` 2.69-3.99 and 4.25.  Empty groups before the live ones
(a group's whole stack handed over, one layer's groups filled) cost
nothing in either.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distkeras_tpu.ops.attention import _on_tpu

# Row, contraction and column tiles of the kernel: a weight tile of
# 2048 x 1024 in bf16 is 4 MB, two of them in flight.
TILE_M = 128
TILES_K = (2048, 1024, 512, 256, 128)
TILES_N = (1024, 512, 256, 128)


def grouped_tiles(k: int, n: int, dtype) -> tuple[int, int, int] | None:
    """Tile rule of the kernel (backend-independent): the ``(tm, tk,
    tn)`` it launches with, or None where it has no tiling — another
    dtype than bf16 or float32, a contraction or a width that no lane
    multiple of the tables divides."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return None
    tk = next((t for t in TILES_K if k % t == 0), None)
    tn = next((t for t in TILES_N if n % t == 0), None)
    return None if tk is None or tn is None else (TILE_M, tk, tn)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [M, K]`` (rows sorted by group) x ``rhs [G, K, N]`` under
    ``group_sizes [G]`` int32 (their sum at most M) -> ``[M, N]`` in
    ``lhs``'s dtype, float32 accumulation.  Rows past the groups hold
    whatever the product left there: the caller selects them away."""
    tiles = grouped_tiles(rhs.shape[1], rhs.shape[2], lhs.dtype)
    if not _on_tpu() or tiles is None or lhs.dtype != rhs.dtype:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m = lhs.shape[0]
    pad = -m % TILE_M
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    return gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
               tiling=tiles)[:m]
