"""Local-replica trainers: AEASGD, EAMSGD, DOWNPOUR, Averaging, Ensemble.

Reference parity: distkeras/trainers.py::AEASGD / EAMSGD / DOWNPOUR /
AveragingTrainer / EnsembleTrainer + the corresponding workers
(distkeras/workers.py) and the DeltaParameterServer that holds the
"center variable" (distkeras/parameter_servers.py).

Unlike ADAG (which maps to plain gradient accumulation), these
algorithms *genuinely maintain divergent per-replica parameters* between
synchronizations — that is their published math (EASGD: Zhang et al.
2015; DOWNPOUR: Dean et al. 2012; see PAPERS.md).  The TPU-native
construction keeps that: each device on the mesh's ``data`` axis holds
its own full parameter/optimizer state (a *stacked* pytree sharded on
the leading replica axis), runs ``communication_window`` local steps
inside a ``lax.scan``, and then executes the algorithm's
synchronization as an explicit collective inside ``shard_map`` —
``psum``/``pmean`` over the ICI where the reference pickled whole
weight vectors through one TCP socket per worker (SURVEY.md §3.2's
scalability bottleneck).

Synchronization rules (SURVEY.md §7.4):
  * AEASGD — elastic: x_i -= a·(x_i − x̃);  x̃ += a·Σ_i(x_i − x̃), a = rho·lr
  * EAMSGD — AEASGD with Nesterov momentum on the local steps
  * DOWNPOUR — commit mean delta and pull: x̃ += mean_i(x_i − x̃); x_i = x̃
  * Averaging — x̃ = mean_i(x_i) once per epoch; x_i = x̃
  * Ensemble — no synchronization at all; k independent models
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.models.adapter import TrainState
from distkeras_tpu.parallel.mesh import equal_across_hosts
from distkeras_tpu.trainers.distributed import DistributedTrainer

# A sync rule: (local_tv, center_tv, axis_name) -> (new_local_tv, new_center_tv)
SyncFn = Callable


def _easgd_sync(alpha: float):
    def sync(tv, center, axis):
        diff = jax.tree.map(lambda x, c: x - c, tv, center)
        new_tv = jax.tree.map(lambda x, d: x - alpha * d, tv, diff)
        new_center = jax.tree.map(
            lambda c, d: c + alpha * jax.lax.psum(d, axis), center, diff)
        return new_tv, new_center
    return sync


def _downpour_sync(tv, center, axis):
    new_center = jax.tree.map(
        lambda c, x: c + jax.lax.pmean(x - c, axis), center, tv)
    return new_center, new_center


def _averaging_sync(tv, center, axis):
    mean = jax.tree.map(lambda x: jax.lax.pmean(x, axis), tv)
    return mean, mean


def _no_sync(tv, center, axis):
    return tv, center


class ReplicaTrainer(DistributedTrainer):
    """Shared machinery: stacked per-replica state + shard_map round.

    One jitted "round" consumes ``[n_replicas, window, batch, ...]`` of
    data: every replica scans its ``window`` microbatches locally, then
    the subclass's sync rule runs as a collective.  The whole round —
    local steps *and* synchronization — is a single XLA program.

    ``device_data=True`` stages each replica's consumption stream in
    its own device's HBM once (P("data") over the replica axis, same
    stream layout as ADAG._fit_device_data_multihost); each round then
    ships only a replicated ``[window * batch]`` index block and the
    round's shard_map gathers locally before the unchanged scan+sync —
    data order is bit-for-bit the streaming path's (parity-tested).
    """

    sync_fn: SyncFn = staticmethod(_no_sync)
    _supports_device_data = True

    def __init__(self, keras_model, loss="categorical_crossentropy", **kw):
        plan = kw.get("plan")
        if kw.pop("fsdp", False) or (
                plan is not None and getattr(plan, "fsdp_axis", None)):
            raise ValueError(
                f"{type(self).__name__} cannot use FSDP: each replica "
                "holds intentionally divergent full weights (that is the "
                "algorithm), so there is no single parameter set to "
                "scatter. Use ADAG/DynSGD with fsdp=True for "
                "memory-sharded data parallelism.")
        if kw.pop("zero1", False) or kw.pop("zero", 0) or (
                plan is not None and (getattr(plan, "zero1", False)
                                      or getattr(plan, "zero", 0))):
            raise ValueError(
                f"{type(self).__name__} cannot use zero1/zero=: each "
                "replica runs its own full optimizer on intentionally "
                "divergent weights (that is the algorithm), so there is "
                "no single update to shard. Use ADAG/DynSGD with zero= "
                "for the sharded stages.")
        super().__init__(keras_model, loss=loss, **kw)

    # ------------------------------------------------------------ state

    def _stack_state(self, states: list[TrainState]) -> TrainState:
        """Stack k host-side TrainStates into one [k, ...] pytree."""
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    def _n_local(self) -> int:
        """Replicas this process owns (all of them single-process)."""
        return self.num_workers // jax.process_count()

    def _replica_states(self) -> TrainState:
        """The *local* replica stack ``[n_local, ...]``; single-process
        that is the whole thing, multi-process each host builds only its
        slice (assembled into the global array by :meth:`_put`)."""
        base = self.adapter.init_state()
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (self._n_local(),) + a.shape),
            base)

    def _put(self, stacked: TrainState, center_tv):
        repl_sh = NamedSharding(self.mesh, P("data"))
        rep = NamedSharding(self.mesh, P())
        if jax.process_count() == 1:
            stacked = jax.tree.map(
                lambda a: jax.device_put(a, repl_sh), stacked)
            return stacked, jax.device_put(center_tv, rep)
        # Multi-process: each host contributes its local replicas' slab;
        # the global [n, ...] array spans all hosts' devices.  The
        # center variable is replicated from identical local copies.
        n = self.num_workers
        stacked = jax.tree.map(
            lambda a: jax.make_array_from_process_local_data(
                repl_sh, np.asarray(a), (n,) + tuple(a.shape[1:])), stacked)
        center_tv = jax.tree.map(
            lambda a: jax.make_array_from_process_local_data(
                rep, np.asarray(a), tuple(a.shape)), center_tv)
        return stacked, center_tv

    def _eval_state_view(self, pytree):
        if isinstance(pytree, dict):  # mid-fit round pytree
            # Evaluate the center variable (the algorithm's product);
            # aux state (BatchNorm stats) from replica 0.  The slice is
            # compiled with replicated output, same as the export path:
            # an eager a[0] cannot read non-addressable shards in the
            # multi-process runtime (and all hosts reach here in
            # lockstep, so the collective is safe).
            if getattr(self, "_eval_slice0", None) is None:
                self._eval_slice0 = jax.jit(
                    lambda s: jax.tree.map(lambda a: a[0], s),
                    out_shardings=NamedSharding(self.mesh, P()))
            return pytree["center_tv"], self._eval_slice0(
                pytree["stacked"].ntv)
        return super()._eval_state_view(pytree)

    # ------------------------------------------------------------ round

    def _make_round(self, window: int, indexed: bool = False):
        train_step = self.adapter.make_train_step()
        sync_fn = self.sync_fn
        mesh = self.mesh
        B = self.batch_size

        def scan_and_sync(stacked, center_tv, xs, ys):
            # Per-device views: stacked leaves [1, ...], xs [w, B, ...].
            local = jax.tree.map(lambda a: a[0], stacked)

            def micro(st, batch):
                x, y = batch
                st2, loss = train_step(st, x, y)
                return st2, loss

            local, losses = jax.lax.scan(micro, local, (xs, ys))
            new_tv, new_center = sync_fn(local.tv, center_tv, "data")
            local = local.replace(tv=new_tv)
            mean_loss = jax.lax.pmean(jnp.mean(losses), "data")
            return (jax.tree.map(lambda a: a[None], local), new_center,
                    mean_loss)

        def local_round(stacked, center_tv, xs, ys):
            return scan_and_sync(stacked, center_tv, xs[0], ys[0])

        def local_round_indexed(stacked, center_tv, Xb, Yb, idx):
            # Xb is THIS replica's staged consumption stream; idx is the
            # replicated block-local offset vector (identical per
            # replica), so the gather is purely device-local.
            shape = lambda a: (window, B) + a.shape[1:]
            xs = jnp.take(Xb, idx, axis=0).reshape(shape(Xb))
            ys = jnp.take(Yb, idx, axis=0).reshape(shape(Yb))
            return scan_and_sync(stacked, center_tv, xs, ys)

        data_specs = ((P("data"), P("data"), P())
                      if indexed else (P("data"), P("data")))
        sharded = shard_map(
            local_round_indexed if indexed else local_round, mesh=mesh,
            in_specs=(P("data"), P()) + data_specs,
            out_specs=(P("data"), P(), P()),
            check_vma=False,
        )
        return jax.jit(sharded, donate_argnums=(0, 1))

    # ------------------------------------------------------------ fit

    def _round_stream(self, dataset: Dataset, window: int):
        """Yield this host's [n_local, w, B, ...] stacks per epoch.

        Single-process that is the full [n, w, B, ...] round; in the
        multi-process runtime each host streams its ``Dataset.shard``
        to its local replicas (replica ``h * n_local + i`` trains on
        host h's i-th slab — document/construct shards accordingly when
        exact replica assignment matters).
        """
        n = self._n_local()
        for _ in range(self.num_epoch):
            for xs, ys in dataset.batches(
                    self.batch_size, features_col=self.features_col,
                    label_col=self.label_col, window=n * window):
                # [n*w, B, ...] -> [n, w, B, ...]
                yield (xs.reshape((n, window) + xs.shape[1:]),
                       ys.reshape((n, window) + ys.shape[1:]))

    def _index_rounds(self, dataset: Dataset, window: int):
        """Device-resident analogue of :meth:`_round_stream`: stage each
        replica's consumption stream in HBM once (stream layout: host
        rows ``[rounds, n_local, w*B, ...]`` transposed to
        ``[n_local, rounds*w*B, ...]``, sharded P("data") so device i's
        contiguous shard is replica i's stream), then yield one
        ``(X, Y, idx)`` per round where idx is a replicated block-local
        offset vector — the rows streaming would feed, in order."""
        n_local = self._n_local()
        rows = n_local * window * self.batch_size
        usable = len(dataset) - len(dataset) % rows
        rounds = usable // rows
        wb = window * self.batch_size

        def layout(col):
            a = np.asarray(col[:usable])
            a = a.reshape((rounds, n_local, wb) + a.shape[1:])
            a = np.moveaxis(a, 1, 0)
            return np.ascontiguousarray(a.reshape((usable,) + a.shape[3:]))

        sh = NamedSharding(self.mesh, P("data"))
        rep = NamedSharding(self.mesh, P())
        X = self._global_batch(layout(dataset[self.features_col]), sh)
        Y = self._global_batch(layout(dataset[self.label_col]), sh)
        multi = jax.process_count() > 1
        for _ in range(self.num_epoch):
            for r in range(rounds):
                idx = np.arange(r * wb, (r + 1) * wb, dtype=np.int32)
                # Replicated blocks need the explicit global shape
                # (every host holds the identical copy; _global_batch
                # would concatenate hosts' rows) — same idiom as
                # ADAG._fit_device_data_multihost's index blocks.
                yield (X, Y,
                       jax.make_array_from_process_local_data(
                           rep, idx, idx.shape) if multi
                       else jax.device_put(idx, rep))

    def _window(self, dataset: Dataset) -> int:
        return self.communication_window

    def _fit(self, dataset: Dataset):
        pcount = jax.process_count()
        if pcount > 1 and self.num_workers % pcount:
            raise ValueError(
                f"num_workers={self.num_workers} must divide by the "
                f"process count ({pcount}): each host owns an equal "
                "share of the replica stack")
        window = self._window(dataset)
        stacked = self._replica_states()
        center_tv = self.adapter.init_state().tv
        stacked, center_tv = self._put(stacked, center_tv)
        round_fn = self._make_round(window, indexed=self.device_data)
        batch_sh = NamedSharding(self.mesh, P("data"))

        def globalize(a):
            if pcount == 1:
                return a
            return jax.make_array_from_process_local_data(
                batch_sh, a, (self.num_workers,) + tuple(a.shape[1:]))

        # Lockstep safety: unequal round counts deadlock the sync
        # collective (one shared definition — mesh.equal_across_hosts).
        rows = self.batch_size * self._n_local() * window
        equal_across_hosts((len(dataset) // rows) * self.num_epoch,
                           f"round counts ({rows}-row windows)")

        restored, start = self._restore_or(
            {"stacked": stacked, "center_tv": center_tv})
        stacked, center_tv = restored["stacked"], restored["center_tv"]
        if self.device_data:
            rounds_iter = self._index_rounds(dataset, window)
        else:
            rounds_iter = ((globalize(xs), globalize(ys))
                           for xs, ys in self._round_stream(dataset, window))
        losses, rnd = [], 0
        for args in rounds_iter:
            rnd += 1
            if rnd <= start:
                continue
            stacked, center_tv, loss = round_fn(
                stacked, center_tv, *args)
            losses.append(loss)
            self._checkpoint({"stacked": stacked, "center_tv": center_tv}, rnd)
            self._eval_hook({"stacked": stacked, "center_tv": center_tv}, rnd)
        if losses or not start:  # resumed-past-the-end runs skip straight to export
            self._require_steps(
                losses, self.batch_size * self._n_local() * window,
                len(dataset))
            self._record(losses)
            self._checkpoint({"stacked": stacked, "center_tv": center_tv},
                             rnd, final=True)
        self._final_stacked = stacked  # kept for ensemble export
        # Export the center variable; aux state (BatchNorm stats etc.)
        # taken from replica 0.  The slice is compiled with replicated
        # output so every host can materialize it (an eager a[0] cannot
        # read non-addressable shards in the multi-process runtime).
        first = jax.jit(lambda s: jax.tree.map(lambda a: a[0], s),
                        out_shardings=NamedSharding(self.mesh, P()))(stacked)
        return first.replace(tv=center_tv)


class AEASGD(ReplicaTrainer):
    """Asynchronous Elastic Averaging SGD, synchronous-elastic form.

    Reference parity: distkeras/trainers.py::AEASGD (rho,
    communication_window, learning_rate).  The elastic coefficient is
    a = rho * learning_rate, as in the reference workers' elastic force.
    """

    def __init__(self, keras_model, communication_window: int = 32,
                 rho: float = 5.0, learning_rate: float = 0.01, **kw):
        if callable(learning_rate):
            raise ValueError(
                "AEASGD/EAMSGD need a scalar learning_rate: the elastic "
                "coefficient alpha = rho * learning_rate is part of the "
                "algorithm's fixed-point math (reference elastic force), "
                "not just an optimizer step size, so an optax schedule "
                "has no single value to derive it from. Use a scalar "
                "here, or ADAG/DOWNPOUR/SingleTrainer for scheduled LR.")
        super().__init__(keras_model, learning_rate=learning_rate, **kw)
        self.communication_window = communication_window
        self.rho = rho
        alpha = rho * learning_rate
        n = self.num_workers
        if alpha * n >= 1.0:
            # Keep the center update contractive; the reference's async
            # form hides this with staleness, the sync form must not blow up.
            clamped = 0.9 / n
            warnings.warn(
                f"AEASGD elastic coefficient rho*learning_rate = {alpha:g} "
                f"violates the synchronous stability bound "
                f"rho*learning_rate*num_workers < 1 (num_workers={n}); "
                f"clamping to {clamped:g}. Lower rho or learning_rate to "
                "run the requested coefficient (see docs/algorithms.md).",
                stacklevel=2)
            alpha = clamped
        self.alpha = alpha
        self.sync_fn = _easgd_sync(alpha)


class EAMSGD(AEASGD):
    """Elastic Averaging Momentum SGD.

    Reference parity: distkeras/trainers.py::EAMSGD — AEASGD plus
    Nesterov momentum on the local worker updates (SURVEY.md §3.3).
    """

    def __init__(self, keras_model, communication_window: int = 32,
                 rho: float = 5.0, learning_rate: float = 0.01,
                 momentum: float = 0.9, **kw):
        import optax

        kw.setdefault("worker_optimizer",
                      optax.sgd(learning_rate, momentum=momentum,
                                nesterov=True))
        super().__init__(keras_model,
                         communication_window=communication_window,
                         rho=rho, learning_rate=learning_rate, **kw)
        self.momentum = momentum


class DOWNPOUR(ReplicaTrainer):
    """DOWNPOUR SGD, synchronous form.

    Reference parity: distkeras/trainers.py::DOWNPOUR — workers
    accumulate local updates for ``communication_window`` batches, then
    commit the delta and pull the center (SURVEY.md §3.3).  Synchronous
    semantics: all replicas commit at once, the center advances by the
    *mean* delta, and replicas restart from the new center; per-replica
    optimizer state (the reference's worker-local Adagrad etc.) persists
    across windows.
    """

    sync_fn = staticmethod(_downpour_sync)

    def __init__(self, keras_model, communication_window: int = 5, **kw):
        kw.setdefault("worker_optimizer", "adagrad")
        super().__init__(keras_model, **kw)
        self.communication_window = communication_window


class AveragingTrainer(ReplicaTrainer):
    """Model averaging: independent epoch training, then weight mean.

    Reference parity: distkeras/trainers.py::AveragingTrainer (workers
    train on their partition; the driver averages all resulting weight
    sets).  Here the average is a ``pmean`` once per epoch.
    """

    sync_fn = staticmethod(_averaging_sync)

    def __init__(self, keras_model, **kw):
        super().__init__(keras_model, **kw)

    def _window(self, dataset: Dataset) -> int:
        # One sync per epoch: window = batches each replica owns per epoch.
        w = len(dataset) // (self.batch_size * self.num_workers)
        if w < 1:
            raise ValueError("dataset too small for one batch per replica")
        return w


class EnsembleTrainer(ReplicaTrainer):
    """Train k independent models in parallel; return all of them.

    Reference parity: distkeras/trainers.py::EnsembleTrainer
    (num_models).  Each replica slot trains its own independently
    initialized model on its own data stream; there is no collective in
    the round at all.  ``train()`` returns a *list* of Keras models.
    """

    sync_fn = staticmethod(_no_sync)

    def __init__(self, keras_model, num_models: int | None = None, **kw):
        window = kw.pop("communication_window", 8)
        if kw.get("eval_every"):
            raise ValueError(
                "EnsembleTrainer has no single model to evaluate "
                "mid-training (its members are intentionally "
                "independent); evaluate the returned models with "
                "ModelPredictor + AccuracyEvaluator instead")
        if num_models is not None:
            kw.setdefault("num_workers", num_models)
        super().__init__(keras_model, **kw)
        self.num_models = self.num_workers
        self.communication_window = window

    def train(self, dataset, features_col=None, label_col=None,
              eval_dataset=None):
        if eval_dataset is not None:
            raise ValueError(
                "EnsembleTrainer returns k independent models; evaluate "
                "them individually (ModelPredictor + AccuracyEvaluator) "
                "rather than through eval_dataset")
        return super().train(dataset, features_col=features_col,
                             label_col=label_col)

    def _replica_states(self) -> TrainState:
        # Independent initializations per member, derived from the
        # trainer seed for reproducibility.  Seeds are keyed on the
        # *global* member index, so a multi-process run initializes the
        # same ensemble as a single-process one.
        states = []
        original = self.adapter.model.get_weights()
        host = jax.process_index()
        nl = self._n_local()
        for i in range(host * nl, (host + 1) * nl):
            seed = None if self.seed is None else self.seed + i
            self.adapter.model.set_weights(_reinit_weights(original, seed))
            states.append(self.adapter.init_state())
        self.adapter.model.set_weights(original)
        return self._stack_state(states)

    def _export(self, state) -> list:
        # Single-process: every shard is addressable, slice eagerly
        # (holds one member at a time).  Multi-process: replicate the
        # stack once (compiled all-gather) so every host can
        # materialize every member — the per-device cost is the price
        # of returning all k models on all hosts.
        full = self._final_stacked
        if jax.process_count() > 1:
            full = jax.jit(lambda s: s,
                           out_shardings=NamedSharding(self.mesh, P()))(full)
        models = []
        for i in range(self.num_workers):
            st = jax.tree.map(lambda a: a[i], full)
            models.append(self.adapter.export_model(st))
        return models


def _reinit_weights(weights, seed=None):
    """Fresh glorot-ish reinitialization for matrices; 1-D weights
    (biases, BatchNorm gamma/beta, ...) keep their original init — zeroing
    them would kill normalization layers (gamma must stay at ones)."""
    rng = np.random.default_rng(seed)
    out = []
    for w in weights:
        if w.ndim >= 2:
            fan_in, fan_out = w.shape[-2], w.shape[-1]
            limit = np.sqrt(6.0 / (fan_in + fan_out))
            out.append(rng.uniform(-limit, limit, w.shape).astype(w.dtype))
        else:
            out.append(np.array(w, copy=True))
    return out
