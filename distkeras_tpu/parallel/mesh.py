"""Device mesh construction (L2' — replaces the reference's transport).

The reference's communication fabric is a hand-rolled star of TCP
sockets between Spark executors and a driver-side parameter server
(reference: distkeras/networking.py — connect/send_data/recv_data — and
distkeras/parameter_servers.py).  The TPU-native equivalent is a
``jax.sharding.Mesh`` over the device grid: collectives (psum /
all-gather / reduce-scatter) are emitted by XLA from sharding
annotations and ride the ICI torus, with DCN used automatically across
pod slices.  There is deliberately *no* user-level transport code in
this package — deleting the pickle-over-TCP hot path is the point
(SURVEY.md §3.2 identifies it as the reference's scalability
bottleneck).

Multi-host: call :func:`initialize_multihost` once per host process
before building a mesh; ``jax.devices()`` then spans the whole pod and
the same MeshSpec code path produces a global mesh.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
from jax.sharding import Mesh


# Canonical axis names, in mesh order.  data = batch (DP replicas),
# model = tensor parallelism, pipeline/seq/expert reserved for the wider
# parallelism surface (PP/SP/EP) layered on the same mesh.
AXES = ("data", "model", "pipeline", "seq", "expert")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape.  ``-1`` on ``data`` means "all remaining devices".

    Only axes with size > 1 consume devices; every axis is always present
    in the mesh so PartitionSpecs can name them unconditionally.
    """

    data: int = -1
    model: int = 1
    pipeline: int = 1
    seq: int = 1
    expert: int = 1

    def resolve(self, n_devices: int) -> tuple[int, ...]:
        fixed = self.model * self.pipeline * self.seq * self.expert
        if n_devices % fixed:
            raise ValueError(
                f"{n_devices} devices not divisible by model*pipeline*seq*expert={fixed}")
        data = self.data if self.data != -1 else n_devices // fixed
        total = data * fixed
        if total != n_devices:
            raise ValueError(
                f"MeshSpec {self} needs {total} devices, have {n_devices}")
        return (data, self.model, self.pipeline, self.seq, self.expert)


def make_mesh(spec: MeshSpec | None = None, devices=None) -> Mesh:
    """Build a ``jax.sharding.Mesh`` from a :class:`MeshSpec`.

    Device order follows ``jax.devices()`` which JAX already orders for
    ICI locality on TPU; the innermost mesh axes get the nearest
    neighbours, so put the highest-bandwidth-hungry axis (model) after
    data when both are >1.
    """
    spec = spec or MeshSpec()
    devices = np.asarray(devices if devices is not None else jax.devices())
    shape = spec.resolve(devices.size)
    return Mesh(devices.reshape(shape), AXES)


def local_device_count() -> int:
    return jax.local_device_count()


def initialize_multihost(coordinator_address: str | None = None,
                         num_processes: int | None = None,
                         process_id: int | None = None) -> None:
    """Join a multi-host JAX runtime (one call per host process).

    Replaces the reference's process-management inheritance from Spark
    (SURVEY.md §5: Spark executors host the workers).  On TPU pods the
    hosts coordinate through ``jax.distributed``; afterwards
    ``jax.devices()`` is global and every mesh built here spans the pod.

    No-op when running single-process (the common dev/test case).
    """
    if num_processes is None or num_processes <= 1:
        return
    enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def enable_cpu_collectives() -> None:
    """Select the gloo backend for cross-process CPU collectives.

    Without it any cross-process psum on the CPU backend dies with
    "Multiprocess computations aren't implemented on the CPU backend".
    Must run BEFORE ``jax.distributed.initialize``; accelerator
    backends ignore the option."""
    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return int(mesh.shape[axis])


def global_batch(arr, sharding):
    """Host batch -> device batch across the (possibly multi-host) mesh.

    Single-process: plain ``device_put`` under the sharding.
    Multi-process SPMD (the Spark-executor analogue, SURVEY.md §5):
    every process holds only ITS rows (its ``Dataset.shard``), so the
    global array is assembled from the process-local slab — each host's
    rows land on its own devices and the collectives do the rest.  The
    single shared definition: the trainer family and LMTrainer both
    route batches through here.
    """
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_process_local_data(sharding, arr)


def equal_across_hosts(local_count: int, what: str) -> int:
    """Assert every process computed the same ``local_count``; returns it.

    The ONE definition of the lockstep-safety check the multi-process
    paths share (streaming rounds, eval shard sizes, device-resident
    usable windows): a host that would run more collective iterations
    than its peers deadlocks the mesh, so the imbalance must raise on
    EVERY host — the allgather here is itself collective, but it runs
    before the loop, while all processes still agree.  No-op (no
    collective) single-process.
    """
    if jax.process_count() == 1:
        return local_count
    import numpy as np
    from jax.experimental import multihost_utils

    counts = [int(c) for c in multihost_utils.process_allgather(
        np.asarray(local_count, np.int64))]
    if len(set(counts)) != 1:
        raise ValueError(
            f"unequal {what} across processes: {counts} — every host "
            "must contribute the same count or the collectives "
            "deadlock; pad or trim the per-host shards")
    return local_count


def per_host_rows(global_bs: int, what: str = "global batch") -> int:
    """Rows each process feeds per global batch: ``global_bs /
    process_count``, validated to divide evenly (shared by the
    streaming, eval-chunk, and device-resident staging geometry)."""
    pcount = jax.process_count()
    if global_bs % pcount:
        raise ValueError(
            f"{what} {global_bs} (batch_size x num_workers) must "
            f"divide by the process count ({pcount})")
    return global_bs // pcount
