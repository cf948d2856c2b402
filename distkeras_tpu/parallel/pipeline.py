"""Pipeline parallelism: GPipe-style microbatch schedule on the mesh.

Absent from the reference (SURVEY.md §2 census: no PP), present here
because stage-partitioned models are part of the first-class parallelism
surface.  The construction is the idiomatic TPU one: no runtime
scheduler process (the reference would have used its socket fabric) —
the schedule is *compiled into the program* as a `lax.scan` over clock
ticks inside a `shard_map` that is manual over only the ``pipeline``
axis.  Each tick every stage applies itself to its current microbatch
and `ppermute`s the activation to its right neighbour over ICI; after
``microbatches + n_stages - 1`` ticks the last stage has produced every
microbatch (the classic GPipe bubble).  Because only ``pipeline`` is
manual, data/tensor/expert sharding inside the stage function stays
XLA-automatic, so PP composes with DP/TP/EP.

Differentiable end-to-end: scan + ppermute transpose cleanly, so
`jax.grad` through a pipelined forward runs the reverse schedule.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def make_pipeline(stage_fn: Callable, mesh: Mesh, microbatches: int,
                  axis_name: str = "pipeline", x_spec: P = P(),
                  extras_spec: P | None = None):
    """Build ``f(stage_params, x) -> (y, aux)`` running ``stage_fn`` as a
    pipeline.

    ``stage_params``: pytree whose leaves have a leading [n_stages] axis
    (stage i consumes slice i).  ``x``: [B, ...] global batch, split
    into ``microbatches`` equal microbatches.
    ``stage_fn(params, u) -> (u_out, aux)`` must be shape-preserving on
    ``u`` ([mb, ...] -> [mb, ...]) and return a scalar auxiliary loss
    (0 when it has none); stages that change activation shape belong
    outside the pipeline (embed / head), matching how GPipe slices a
    residual trunk.

    ``extras_spec`` non-None adds a third input: per-microbatch
    side data ``extras`` with leaves ``[microbatches, mb, ...]``
    (e.g. packed-sequence segment ids).  It is NOT piped stage to
    stage: every stage holds the whole (small) array and indexes the
    microbatch it is currently processing (tick t, stage i works
    microbatch t - i), receiving it as ``stage_fn(params, u, extra)``.
    Bubble ticks see a clamped index — garbage in, garbage out, masked
    like the activations.  The spec names any extra manual axes the
    trailing dims shard over (e.g. ``P(None, None, 'seq')``).

    ``aux`` is the per-stage aux summed over stages, averaged over
    microbatches — each microbatch computes its own full-forward aux, so
    the mean keeps it on the same scale as an un-pipelined forward.
    Bubble ticks (a stage holding no real microbatch) are masked out of
    the accumulation.

    ``x_spec`` extends the manual axis set: a PartitionSpec over ``x``'s
    dims naming further mesh axes (e.g. ``P(None, 'seq')`` for sequence
    parallelism) makes the body manual over those too, with ``x``
    entering as the named shard.  ``stage_fn`` then runs with those axes
    manual in context, so it may call collective bodies (ring attention)
    directly — nesting a second shard_map inside the pipeline does not
    transpose under AD, composing manual axes in one shard_map does.
    Every other mesh axis (data, model, expert) stays XLA-automatic.
    """
    n_stages = int(mesh.shape[axis_name])
    extra_axes = {a for dim in x_spec for a in (
        dim if isinstance(dim, tuple) else (dim,)) if a is not None}
    if axis_name in extra_axes:
        raise ValueError(f"x_spec {x_spec} must not name the pipeline "
                         f"axis {axis_name!r}")

    def run(stage_params, x, *maybe_extras):
        for leaf in jax.tree.leaves(stage_params):
            if leaf.shape[0] != 1:
                raise ValueError(
                    f"stage_params leading axis must equal n_stages="
                    f"{n_stages} (got a shard of {leaf.shape[0]} — stack "
                    "exactly one param slice per pipeline stage)")
        local = jax.tree.map(lambda a: a[0], stage_params)
        idx = jax.lax.axis_index(axis_name)
        b = x.shape[0]
        if b % microbatches:
            raise ValueError(f"batch {b} not divisible into {microbatches} "
                             "microbatches")
        mb = b // microbatches
        x_mb = x.reshape(microbatches, mb, *x.shape[1:])
        ticks = microbatches + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            recv, outputs, aux_acc = carry
            t_in = jnp.clip(t, 0, microbatches - 1)
            inp = jnp.where(idx == 0, x_mb[t_in], recv)
            if maybe_extras:
                cur = jnp.clip(t - idx, 0, microbatches - 1)
                extra = jax.tree.map(lambda a: a[cur], maybe_extras[0])
                out, aux = stage_fn(local, inp, extra)
            else:
                out, aux = stage_fn(local, inp)
            # Stage `idx` holds real microbatch t - idx at tick t; other
            # ticks are bubble garbage and must not pollute the aux sum.
            valid = (t >= idx) & (t - idx < microbatches)
            aux_acc = aux_acc + jnp.where(valid, aux.astype(jnp.float32), 0.0)
            recv_next = jax.lax.ppermute(out, axis_name, perm)
            # Stage n-1 finishes microbatch t-(n-1) at tick t.
            mb_i = t - (n_stages - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                outputs, out, jnp.maximum(mb_i, 0), 0)
            outputs = jnp.where((idx == n_stages - 1) & (mb_i >= 0),
                                upd, outputs)
            return (recv_next, outputs, aux_acc), None

        zero_act = jnp.zeros((mb, *x.shape[1:]), x.dtype)
        zero_out = jnp.zeros((microbatches, mb, *x.shape[1:]), x.dtype)
        (_, outputs, aux_acc), _ = jax.lax.scan(
            tick, (zero_act, zero_out, jnp.zeros((), jnp.float32)),
            jnp.arange(ticks))
        # aux differs across the extra manual axes (e.g. each seq shard
        # routes its own tokens through MoE), but its out_spec only
        # names the pipeline axis — reduce explicitly so the claimed
        # replication is real (check_vma=False would not catch it).
        for ax in sorted(extra_axes):
            aux_acc = jax.lax.pmean(aux_acc, ax)
        # Leading stage axis: only the last stage's slice is the result;
        # aux contributions live on every stage.
        return outputs.reshape(b, *x.shape[1:])[None], aux_acc[None]

    if extras_spec is not None:
        extras_axes = {a for dim in extras_spec for a in (
            dim if isinstance(dim, tuple) else (dim,)) if a is not None}
        if axis_name in extras_axes:
            raise ValueError(
                f"extras_spec {extras_spec} must not name the pipeline "
                f"axis {axis_name!r} (extras are not piped stage to "
                "stage; every stage holds the whole array)")
        if not extras_axes <= extra_axes:
            # out_specs claims y replicated over exactly x_spec's axes;
            # an extras-only manual axis would make each shard compute
            # a DIFFERENT y while check_vma=False suppresses the check
            # — reject instead of returning silently wrong outputs.
            raise ValueError(
                f"extras_spec {extras_spec} names axes "
                f"{sorted(extras_axes - extra_axes)} that x_spec "
                f"{x_spec} does not — activations must be manual over "
                "every axis the extras shard over")
    in_specs = (P(axis_name), x_spec) + (
        (extras_spec,) if extras_spec is not None else ())
    f = shard_map(run, mesh=mesh, axis_names={axis_name} | extra_axes,
                  in_specs=in_specs,
                  out_specs=(P(axis_name, *x_spec), P(axis_name)),
                  check_vma=False)

    def apply(stage_params, x, extras=None):
        if (extras is not None) != (extras_spec is not None):
            raise ValueError(
                "extras and extras_spec must be provided together "
                f"(extras_spec={'set' if extras_spec is not None else None},"
                f" extras={'given' if extras is not None else None})")
        args = (stage_params, x) + ((extras,) if extras is not None else ())
        ys, aux = f(*args)
        return ys[-1], aux.sum() / microbatches

    return apply
