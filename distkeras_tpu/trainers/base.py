"""Trainer base + SingleTrainer (reference parity: distkeras/trainers.py).

API contract kept from the reference: construct with a Keras model,
loss, optimizer and knobs; ``train(dataset) -> trained keras model``;
``training_time`` attribute records the wall clock of the run
(reference: Trainer.train records training_time; SURVEY.md §5 notes it
is the reference's only perf signal).  ``history`` additionally records
per-step losses — strictly more observability than the reference.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from distkeras_tpu import obs
from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.models.adapter import ModelAdapter
from distkeras_tpu.resilience import chaos
from distkeras_tpu.resilience.chaos import Preempted
from distkeras_tpu.utils.profiling import StepTimer


def normalize_zero_args(zero, zero1: bool, zero_bucket_mb,
                        zero1_bucket_mb):
    """Reconcile the ``zero=`` stage knob with its deprecated PR-2
    aliases — ONE definition for both trainer families
    (``DistributedTrainer`` and ``LMTrainer``), so the alias semantics
    can never drift between them.  Returns
    ``(zero, zero1, zero_bucket_mb)`` with ``zero1 == (zero == 1)``.
    """
    if zero is None:
        zero = 1 if zero1 else 0
    elif zero1 and zero != 1:
        raise ValueError(
            f"zero1=True is the deprecated alias of zero=1 and "
            f"cannot combine with zero={zero}; pass zero= alone")
    if zero not in (0, 1, 2, 3):
        raise ValueError(
            f"zero must be 0 (off), 1, 2 or 3, got {zero!r}")
    if zero_bucket_mb is not None and zero1_bucket_mb is not None:
        raise ValueError(
            "pass only one of zero_bucket_mb / zero1_bucket_mb "
            "(the latter is the deprecated alias)")
    if zero_bucket_mb is None:
        zero_bucket_mb = zero1_bucket_mb
    return zero, zero == 1, zero_bucket_mb


class CheckpointingBase:
    """Checkpoint/resume plumbing shared across the whole trainer family.

    The Keras trainers (:class:`Trainer` subclasses) and the flagship
    :class:`~distkeras_tpu.trainers.lm.LMTrainer` persist training state
    through the same orbax-backed machinery so the user contract —
    ``checkpoint_dir`` / ``checkpoint_every`` / ``max_checkpoints`` /
    ``resume`` — is uniform, the way the reference keeps one contract
    across its trainer family (reference: distkeras/trainers.py base
    class).
    """

    def _setup_checkpointing(self, *, checkpoint_dir: str | None,
                             checkpoint_every: int, max_checkpoints: int,
                             resume: bool, shuffle: bool,
                             seed: int | None,
                             backend: str = "auto") -> None:
        self.checkpoint_every = checkpoint_every
        self.resume = resume
        self.checkpoint_dir = checkpoint_dir
        self.max_checkpoints = max_checkpoints
        self.checkpoint_backend = backend
        # Set by a resilience.Supervisor (or any orchestrator): when
        # this Event is set, the next round boundary forces a final
        # synchronous checkpoint and raises Preempted — the graceful
        # half of a preemption.
        self.preempt_event = None
        self._ckpt = None
        self._last_saved_round = 0
        if resume and shuffle and seed is None:
            raise ValueError(
                "resume=True with shuffle=True needs a fixed seed: resume "
                "skips the first N rounds of the stream, which only lands on "
                "the right data if the permutation is reproducible")
        if (resume or checkpoint_every) and not checkpoint_dir:
            raise ValueError(
                "resume/checkpoint_every need a checkpoint_dir — without one "
                "nothing is restored or written")

    def _open_checkpoints(self) -> None:
        """Open the per-run checkpoint manager (closed by _close_)."""
        self._last_saved_round = 0
        if not self.checkpoint_dir:
            return
        from distkeras_tpu.checkpoint import CheckpointManager

        # Opened per run and closed on exit so orbax's async machinery
        # doesn't outlive the training it serves.
        self._ckpt = CheckpointManager(
            self.checkpoint_dir, max_to_keep=self.max_checkpoints,
            backend=self.checkpoint_backend)
        if not self.resume and self._ckpt.latest_step() is not None:
            self._ckpt.close()
            self._ckpt = None
            raise ValueError(
                f"checkpoint_dir {self.checkpoint_dir!r} already holds "
                "checkpoints; pass resume=True to continue from them or "
                "point at a fresh directory (orbax refuses to overwrite "
                "an existing step)")

    def _close_checkpoints(self) -> None:
        if self._ckpt is not None:
            self._ckpt.close()
            self._ckpt = None

    def _restore_or(self, pytree):
        """Return (pytree, start_round): latest checkpoint if resuming.

        Resume semantics: deterministic data order; the first
        ``start_round`` rounds of the batch stream are skipped so the
        restored state continues exactly where the checkpoint left off.
        """
        if not (self._ckpt and self.resume):
            return pytree, 0
        step = self._ckpt.latest_step()
        if step is None:
            return pytree, 0
        valid = self._ckpt.latest_valid_step()
        if valid != step:
            # Torn latest (host died mid-save on a store without
            # atomic rename): resume from the newest step that passes
            # the integrity check instead of crashing inside restore —
            # the same selection rule the cluster-consistent restart
            # applies across hosts.
            import warnings

            from distkeras_tpu.resilience.cluster import (
                trim_to_consistent)

            warnings.warn(
                f"checkpoint step {step} under "
                f"{self.checkpoint_dir!r} is torn/partial; resuming "
                f"from the latest valid step {valid} instead",
                stacklevel=2)
            obs.event("checkpoint.torn", step=step, fallback=valid)
            # Drop the torn steps: the resumed run will pass their
            # rounds again, and both backends refuse to overwrite a
            # step directory that (half-)exists.  One trimming rule,
            # shared with the cluster driver's pre-epoch trim.
            trim_to_consistent([self._ckpt.directory])
            if valid is None:
                return pytree, 0
            step = valid
        with obs.span("checkpoint.restore", step=step):
            restored = self._ckpt.restore(pytree, step)
        return restored, step

    def attach_publisher(self, publisher, every: int = 1):
        """Wire a :class:`~distkeras_tpu.serving.publish.
        SnapshotPublisher` into the round loop: every ``every`` rounds
        (and on the final round) the trainer publishes its current
        weights as snapshot version ``round_idx`` — the trainer side
        of the live train→serve weight push (docs/serving_guide.md).

        Publishing is independent of checkpointing: a trainer with no
        ``checkpoint_dir`` still publishes.  The snapshot version IS
        the round index, so versions are monotone across a resumed
        run for free.  Returns ``self`` for chaining."""
        if every < 1:
            raise ValueError(f"every must be >= 1, got {every}")
        self._publisher = publisher
        self._publish_every = int(every)
        self._last_published = 0
        return self

    def _publish_tree(self, pytree):
        """The weights to publish, extracted from the round-loop state.
        Subclasses override to unwrap their carry (and un-view ZeRO-3
        shard views); the base publishes the state as-is."""
        return pytree

    def _maybe_publish(self, pytree, round_idx: int,
                       final: bool = False) -> None:
        pub = getattr(self, "_publisher", None)
        if pub is None or round_idx == self._last_published:
            return
        if final or round_idx % self._publish_every == 0:
            with obs.span("publish.snapshot", step=round_idx):
                pub.publish(self._publish_tree(pytree), round_idx)
            self._last_published = round_idx

    def _checkpoint(self, pytree, round_idx: int, final: bool = False) -> None:
        """Persist training state after round ``round_idx`` (1-based).

        Blocks until the save is durable: the round loop donates state
        buffers into the next step, so an in-flight async write must not
        alias them.  States at dist-keras scale write in milliseconds.
        """
        chaos.probe("train.round", step=round_idx)
        self._maybe_publish(pytree, round_idx, final)
        if self.preempt_event is not None and self.preempt_event.is_set():
            # Graceful preemption (SIGTERM via a Supervisor, or any
            # orchestrator flipping the event): persist THIS round's
            # state synchronously, then stop.  The resumed run replays
            # from here bit-for-bit — data order is round-indexed and
            # every RNG stream is keyed on the round counter.
            if self._ckpt is not None and round_idx != self._last_saved_round:
                with obs.span("checkpoint.save", step=round_idx,
                              preempt=True):
                    self._ckpt.save(pytree, round_idx, force=True)
                    self._ckpt.wait_until_finished()
                self._last_saved_round = round_idx
            obs.event("train.preempted", round=round_idx,
                      checkpointed=self._ckpt is not None)
            raise Preempted(
                f"preempted at round {round_idx}"
                + (" (state checkpointed)" if self._ckpt is not None
                   else " (no checkpoint_dir: round lost)"))
        if self._ckpt is None or round_idx == self._last_saved_round:
            return  # (final save right after a periodic one: already durable)
        periodic = self.checkpoint_every and round_idx % self.checkpoint_every == 0
        if final or periodic:
            with obs.span("checkpoint.save", step=round_idx):
                self._ckpt.save(pytree, round_idx, force=True)
                self._ckpt.wait_until_finished()
            self._last_saved_round = round_idx

    def _record_run_metrics(self) -> None:
        """End-of-run telemetry (obs, docs/observability.md): loss and
        timing gauges from state the run already computed host-side —
        never a per-step device sync, never an extra compiled program
        (the zero-overhead contract the obs smoke test pins)."""
        if obs.active() is None:
            return
        name = type(self).__name__
        obs.gauge("train.training_time_s", self.training_time,
                  trainer=name)
        hist = getattr(self, "history", None)
        if hist:
            obs.gauge("train.loss", hist[-1], trainer=name)
            obs.gauge("train.loss_mean", sum(hist) / len(hist),
                      trainer=name)
            obs.count("train.rounds", len(hist), trainer=name)
        for phase, st in self.step_timer.phase_stats().items():
            obs.gauge("train.phase_total_s", st["total_s"],
                      trainer=name, phase=phase)


class Trainer(CheckpointingBase):
    """Base trainer: owns the adapter and the train() bookkeeping."""

    def __init__(self, keras_model, loss="categorical_crossentropy",
                 worker_optimizer="sgd", learning_rate: float | None = None,
                 batch_size: int = 32, num_epoch: int = 1,
                 features_col: str = "features", label_col: str = "label",
                 shuffle: bool = False, seed: int | None = None,
                 checkpoint_dir: str | None = None, checkpoint_every: int = 0,
                 max_checkpoints: int = 3, resume: bool = False,
                 checkpoint_backend: str = "auto",
                 preprocess=None, metrics=(), eval_every: int = 0):
        self.adapter = ModelAdapter(
            keras_model, loss=loss, optimizer=worker_optimizer,
            learning_rate=learning_rate, preprocess=preprocess,
            metrics=metrics)
        # Mid-training evaluation: every ``eval_every`` rounds (and once
        # at the end) the trainer runs the adapter's eval fn over the
        # eval dataset passed to train(), appending
        # ``(round, {"loss": ..., metric...})`` to ``eval_history``.
        self.eval_every = eval_every
        self.eval_history: list[tuple[int, dict]] = []
        self._eval_batch = None
        self._eval_chunks = None   # multi-process: pre-staged global chunks
        self._eval_fn = None
        self.batch_size = batch_size
        self.num_epoch = num_epoch
        self.features_col = features_col
        self.label_col = label_col
        self.shuffle = shuffle
        self.seed = seed
        self.training_time: float = 0.0
        self.history: list[float] = []
        # Per-run phase observability (utils/profiling.StepTimer): the
        # distributed trainers populate "h2d" (host staging + transfer
        # dispatch) and "step" (jitted dispatch) so an input-bound run
        # reads differently from a compute-bound one without a profile.
        self.step_timer = StepTimer()
        # Checkpoint/resume (SURVEY.md §5: the reference has none; here
        # any trainer can persist its full training state via orbax).
        self._setup_checkpointing(
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            max_checkpoints=max_checkpoints, resume=resume, shuffle=shuffle,
            seed=seed, backend=checkpoint_backend)

    # -- subclass hook -----------------------------------------------------
    def _fit(self, dataset: Dataset):  # pragma: no cover
        raise NotImplementedError

    def train(self, dataset: Dataset, features_col: str | None = None,
              label_col: str | None = None,
              eval_dataset: Dataset | None = None):
        """Train and return a fresh Keras model with the learned weights.

        (EnsembleTrainer returns a list of models via its ``_export``.)
        ``eval_dataset`` feeds the ``eval_every`` hook (see __init__);
        passing one without ``eval_every`` evaluates once, at the end.
        """
        if features_col:
            self.features_col = features_col
        if label_col:
            self.label_col = label_col
        if self.shuffle:
            dataset = dataset.shuffle(self.seed)
        self.eval_history = []
        self._eval_batch = None
        self._eval_chunks = None
        if eval_dataset is not None:
            if jax.process_count() > 1:
                self._stage_eval_chunks(eval_dataset)
            elif len(eval_dataset) == 0:
                raise ValueError("eval_dataset is empty")
            else:
                self._eval_batch = (eval_dataset[self.features_col],
                                    eval_dataset[self.label_col])
            self._eval_fn = jax.jit(self.adapter.make_eval_fn())
        elif self.eval_every:
            raise ValueError(
                "eval_every is set but train() got no eval_dataset")
        # Per-run observability: phase stats describe THIS run only
        # (explicit reset — reuse across train() calls must not blend
        # runs), and the whole run is one obs span.
        self.step_timer.reset()
        t0 = time.perf_counter()
        self._open_checkpoints()
        try:
            with obs.span("train.run", trainer=type(self).__name__):
                state = self._fit(dataset)
                self._eval_hook(state, rnd=None, final=True)
                jax.block_until_ready(state.tv)
        finally:
            self._close_checkpoints()
        self.training_time = time.perf_counter() - t0
        self._record_run_metrics()
        return self._export(state)

    # -- evaluation hook ---------------------------------------------------
    def _stage_eval_chunks(self, eval_dataset: Dataset) -> None:
        """Multi-process eval: pre-stage the (host-local) eval shard as
        globally-sharded chunks of exactly the training microbatch
        geometry, mirroring LMTrainer's eval-chunk plumbing.

        Each host contributes ``global_bs / process_count`` rows per
        chunk (``_global_batch`` assembles the global array from the
        process-local slabs); the jitted eval fn then computes the
        global mean with compiler-inserted collectives and returns it
        replicated, so every host records identical eval_history.  The
        collective cadence requires every host to pass an eval shard
        with the SAME row count (checked up front); the tail remainder
        that doesn't fill a chunk is dropped, as in training.

        Only the host-side slabs are kept here; each global chunk is
        assembled on device when an eval round actually fires
        (_eval_hook) — pinning the whole eval set in HBM for the run
        would cut into training memory, the thing the single-process
        path's mini-batching exists to protect.
        """
        from distkeras_tpu.parallel.mesh import (equal_across_hosts,
                                                  per_host_rows)

        mesh = getattr(self, "mesh", None)
        if mesh is None:
            raise ValueError(
                "eval_dataset in the multi-process runtime needs a mesh "
                "trainer (the distributed/elastic family or LMTrainer); "
                "SingleTrainer has no cross-host eval plane")
        pcount = jax.process_count()
        feed = per_host_rows(self.batch_size * self.num_workers,
                             what="eval-chunk global batch")
        equal_across_hosts(len(eval_dataset), "eval shard sizes")
        usable = len(eval_dataset) - len(eval_dataset) % feed
        if usable == 0:
            raise ValueError(
                f"eval_dataset holds {len(eval_dataset)} rows per host "
                f"but one eval chunk needs {feed} "
                "(batch_size x num_workers / process_count)")
        if usable < len(eval_dataset):
            import warnings

            # The single-process path mini-batches ALL rows, so a
            # ragged shard silently diverges from that run's metrics
            # unless the caller is told (advisor round-4).
            warnings.warn(
                f"multi-process eval uses {usable} of "
                f"{len(eval_dataset)} eval rows per host (chunks of "
                f"{feed}); the {len(eval_dataset) - usable}-row tail is "
                "excluded from eval metrics on every host — pad or trim "
                "the shard to a multiple of the chunk size for "
                "single-process-identical numbers", stacklevel=3)
        x = np.asarray(eval_dataset[self.features_col])
        y = np.asarray(eval_dataset[self.label_col])
        sh = self._batch_sharding(leading_window=False)
        self._eval_chunks = (
            [(x[j:j + feed], y[j:j + feed], feed * pcount)
             for j in range(0, usable, feed)], sh)

    def _eval_state_view(self, pytree):
        """(tv, ntv) of the evaluable model inside a fit-loop pytree."""
        return pytree.tv, pytree.ntv

    def _eval_hook(self, pytree, rnd, final: bool = False) -> None:
        """Record eval metrics at round ``rnd``; the end-of-training
        call records round -1 (always runs when an eval set exists)."""
        if self._eval_batch is None and self._eval_chunks is None:
            return
        if not final and not (self.eval_every and rnd % self.eval_every == 0):
            return
        tv, ntv = self._eval_state_view(pytree)
        sums, n = {}, 0
        if self._eval_chunks is not None:
            # Multi-process: host slabs are assembled into globally-
            # sharded chunks only when an eval round fires; the eval
            # outputs are replicated scalars (global means via the
            # compiled collectives), identical on every host.
            slabs, sh = self._eval_chunks
            for xb, yb, rows in slabs:
                part = self._eval_fn(tv, ntv,
                                     self._global_batch(xb, sh),
                                     self._global_batch(yb, sh))
                for k, v in part.items():
                    sums[k] = sums.get(k, 0.0) + float(v) * rows
                n += rows
        else:
            x, y = self._eval_batch
            # Mini-batch the eval set (at the training batch size) so a
            # large eval split never materializes all activations at
            # once; at most two compiled shapes (full + one remainder).
            bs = min(self.batch_size, len(x))
            for i in range(0, len(x), bs):
                xb, yb = x[i:i + bs], y[i:i + bs]
                part = self._eval_fn(tv, ntv, xb, yb)
                for k, v in part.items():
                    sums[k] = sums.get(k, 0.0) + float(v) * len(xb)
                n += len(xb)
        out = {k: v / n for k, v in sums.items()}
        self.eval_history.append((-1 if final else rnd, out))

    def _export(self, state):
        return self.adapter.export_model(state)

    # -- helpers -----------------------------------------------------------
    def _epoch_stream(self, dataset: Dataset, window: int | None = None):
        """Yield (x, y) batches across all epochs."""
        for _ in range(self.num_epoch):
            ds = dataset
            yield from ds.batches(
                self.batch_size, features_col=self.features_col,
                label_col=self.label_col, drop_remainder=True, window=window)

    def _record(self, losses) -> None:
        self.history.extend(float(l) for l in losses)

    def _require_steps(self, losses, rows_needed: int, n_rows: int) -> None:
        """Refuse to silently return an untrained model.

        Every trainer needs at least ``rows_needed`` rows to form one
        step; with fewer, the batch stream is empty and training would
        be a no-op the user can't distinguish from success.
        """
        if not losses:
            raise ValueError(
                f"dataset has {n_rows} rows but one training step needs "
                f"{rows_needed} (batch_size x num_workers x window); "
                "reduce batch_size/communication_window/num_workers or "
                "provide more data")


class SingleTrainer(Trainer):
    """Single-device training: one jitted step, a Python loop over batches.

    Reference parity: distkeras/trainers.py::SingleTrainer +
    distkeras/workers.py::SingleTrainerWorker (one partition, sequential
    ``train_on_batch`` loop — SURVEY.md §3.1).  Here the step is one XLA
    program; the loop merely feeds batches and retires device losses
    without forcing a sync every step.

    ``steps_per_call`` > 1 scans that many optimizer updates inside one
    XLA call (adapter.make_multi_train_step), amortizing host dispatch —
    the dominant cost for small models.  Checkpoint granularity becomes
    ``steps_per_call`` steps; a round = one call; like the windowed
    distributed trainers, each epoch drops its tail remainder of up to
    ``steps_per_call * batch_size - 1`` rows (shapes must stay static).

    ``device_data=True`` stages the dataset columns in device memory
    once and feeds each round an int32 index block instead of batch
    payloads (adapter.make_indexed_train_step): after the one-time
    staging transfer, only ~4 bytes/sample/epoch cross the
    host->device link.  The right mode whenever the dataset fits in
    HBM (CIFAR-scale and far beyond) — the host link is the input
    pipeline's narrow point.
    Identical math and data order to the streaming path.
    """

    def __init__(self, keras_model, loss="categorical_crossentropy", *,
                 steps_per_call: int = 1, device_data: bool = False, **kw):
        # steps_per_call is keyword-only so the parent's positional
        # contract (keras_model, loss, ...) is preserved.
        super().__init__(keras_model, loss=loss, **kw)
        if steps_per_call < 1:
            raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
        self.steps_per_call = steps_per_call
        self.device_data = device_data

    def _fit(self, dataset: Dataset):
        spc = self.steps_per_call
        state = self.adapter.init_state()
        state, start = self._restore_or(state)
        if start and int(state.step) != start * spc:
            raise ValueError(
                f"checkpoint at round {start} holds optimizer step "
                f"{int(state.step)}, but steps_per_call={spc} implies "
                f"{start * spc}: the checkpoint was written under a "
                "different steps_per_call — resume with the original "
                "value (data skipping is counted in rounds)")
        if self.device_data:
            step = jax.jit(self.adapter.make_indexed_train_step(spc),
                           donate_argnums=0)
            X = jax.device_put(dataset[self.features_col])
            Y = jax.device_put(dataset[self.label_col])
            n = len(dataset)
            rows = self.batch_size * spc

            def stream():
                for _ in range(self.num_epoch):
                    for i in range(0, n - (n % rows), rows):
                        yield (X, Y,
                               np.arange(i, i + rows, dtype=np.int32)
                               .reshape(spc, self.batch_size))
            stream = stream()
        elif spc == 1:
            step = jax.jit(self.adapter.make_train_step(), donate_argnums=0)
            stream = self._epoch_stream(dataset)
        else:
            step = jax.jit(self.adapter.make_multi_train_step(spc),
                           donate_argnums=0)
            stream = self._epoch_stream(dataset, window=spc)
        losses, rnd = [], start
        for rnd, args in enumerate(stream, 1):
            if rnd <= start:
                continue
            state, loss = step(state, *args)
            # Device array (scalar, or [spc] when scanning); no sync here.
            losses.append(loss)
            self._checkpoint(state, rnd)
            self._eval_hook(state, rnd)
        if start and not losses:  # resumed past the end: nothing left to do
            return state
        self._require_steps(losses, self.batch_size * spc, len(dataset))
        self._record(np.concatenate([np.atleast_1d(l) for l in losses]))
        self._checkpoint(state, rnd, final=True)
        return state
