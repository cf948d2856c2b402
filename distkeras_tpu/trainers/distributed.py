"""Synchronous data-parallel trainers: ADAG and DynSGD.

Reference parity: distkeras/trainers.py::ADAG / DynSGD +
distkeras/workers.py::ADAGWorker / DynSGDWorker +
distkeras/parameter_servers.py (ADAG/DynSGD parameter servers).

Semantic mapping (SURVEY.md §7.4): the reference's workers accumulate
updates for ``communication_window`` batches, then commit the
accumulated delta to a central parameter server and pull fresh weights.
In bulk-synchronous SPMD that cadence is *gradient accumulation*: each
DP replica scans ``window`` microbatches accumulating gradients, the
mean gradient is combined across replicas by the compiler-inserted
all-reduce (the batch is sharded over the mesh ``data`` axis), and one
optimizer update applies it.  The pickle-over-TCP parameter-server hot
path (SURVEY.md §3.2) has no equivalent here — XLA collectives over ICI
do the exchange.

DynSGD's only difference from ADAG was staleness-scaled learning rate
``lr/(tau+1)``; under synchronous execution staleness tau == 0, so
DynSGD degenerates to ADAG exactly (SURVEY.md §7.4).  The class is kept
for API parity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.parallel.mesh import (MeshSpec, equal_across_hosts,
                                          make_mesh, per_host_rows,
                                          global_batch as mesh_global_batch)
from distkeras_tpu.parallel.sharding import (ShardingPlan, Zero1Plan,
                                              dp_plan, fsdp_plan,
                                              zero1_plan)
from distkeras_tpu.trainers.base import Trainer


class DistributedTrainer(Trainer):
    """Base for mesh trainers: builds the mesh and sharding plumbing.

    Subclasses that implement the device-resident data plane set
    ``_supports_device_data = True``; everyone else rejects the knob at
    construction.

    ``num_workers`` (reference kwarg) = number of data-parallel replicas
    = size of the mesh's ``data`` axis.  Defaults to all visible
    devices.  A :class:`ShardingPlan` may add tensor parallelism on the
    ``model`` axis on top (something the reference cannot do at all).
    ``fsdp=True`` is shorthand for ``plan=fsdp_plan()``: weights and
    optimizer state scatter over the data axis (ZeRO-3) instead of
    replicating — identical training math, ~num_workers x less
    parameter memory per device.

    ``zero=`` selects a ZeRO sharding stage (docs/zero1.md; identical
    training math at every stage, pure-data meshes only):

    * ``zero=1`` shards only the *weight update*: parameters stay
      replicated (forward/backward untouched), the optimizer state
      scatters over the data axis, and each round's exchange becomes
      reduce-scatter(grads) -> per-replica shard update ->
      all-gather(update), in ~``zero_bucket_mb`` fusion buckets
      (parallel/collectives.py).  Unchanged communication volume,
      ~num_workers x less optimizer memory and update compute per
      device.  ``zero1=True`` is the deprecated alias.
    * ``zero=2`` additionally shards the GRADIENT ACCUMULATOR: each
      microbatch's bucketed reduce-scatter interleaves into the
      accumulation scan, so a replica only ever materializes its 1/n
      gradient shard and the per-round wire drops from ``window``
      all-reduces to ``window`` reduce-scatters + one all-gather.
    * ``zero=3`` additionally shards the PARAMETERS as chunk-major
      ``[n, cols]`` shard views with gather-on-use: the forward
      re-materializes them per fusion bucket just-in-time
      (collectives.gather_bucket) and the update runs entirely on the
      shard views — per-device param+grad+optimizer bytes all drop
      ~num_workers x.  Compare ``fsdp=True`` (the GSPMD
      dimension-sharded spelling, which composes with TP but leaves
      small/indivisible leaves replicated).

    **Gradient-exchange policy** (docs/lowcomm.md, ADAG/DynSGD only):
    ``merge_rule="adasum"`` replaces the mean-reduce with pairwise
    adaptive summation (arXiv 2006.02924); ``sync_every=H`` switches to
    local-SGD — H purely-local rounds per replica, then one
    momentum-aware parameter merge (1/H the collective frequency);
    ``compress="int8"``/``"topk"`` applies an error-feedback codec per
    fusion bucket (~4x fewer gradient wire bytes for int8, pinned in
    scripts/comm_budget.json).  ``compress="int8"`` composes with
    ``zero1=True`` by compressing the reduce-scatter leg.
    ``probe_metrics=True`` adds an in-graph grad-norm probe to the step
    (``probe_history``; zero extra compiled programs — the step is one
    program either way).
    """

    _supports_device_data = False
    _supports_exchange = False

    def __init__(self, keras_model, loss="categorical_crossentropy",
                 worker_optimizer="sgd", learning_rate: float | None = None,
                 batch_size: int = 32, num_epoch: int = 1,
                 num_workers: int | None = None, mesh=None,
                 plan: ShardingPlan | None = None, fsdp: bool = False,
                 zero: int | None = None,
                 zero1: bool = False, zero1_bucket_mb: float | None = None,
                 zero_bucket_mb: float | None = None,
                 device_data: bool = False, merge_rule: str = "mean",
                 sync_every: int = 1, compress=None,
                 topk_frac: float = 0.01, probe_metrics: bool = False,
                 **kw):
        super().__init__(keras_model, loss=loss,
                         worker_optimizer=worker_optimizer,
                         learning_rate=learning_rate, batch_size=batch_size,
                         num_epoch=num_epoch, **kw)
        from distkeras_tpu.trainers.base import normalize_zero_args

        zero, zero1, zero_bucket_mb = normalize_zero_args(
            zero, zero1, zero_bucket_mb, zero1_bucket_mb)
        if device_data and not self._supports_device_data:
            raise ValueError(
                f"device_data=True is not supported by "
                f"{type(self).__name__}; it is implemented for "
                "ADAG/DynSGD, the replica family (AEASGD/EAMSGD/"
                "DOWNPOUR/Averaging/Ensemble), SingleTrainer, and "
                "LMTrainer")
        self.device_data = device_data
        from distkeras_tpu.parallel.exchange import ExchangeConfig

        exchange = ExchangeConfig(
            merge_rule=merge_rule, sync_every=sync_every,
            compress=compress, topk_frac=topk_frac,
            # Under zero1 x int8 the exchange's bucket layout IS the
            # zero1 layout, so the one bucket knob governs both.
            **({} if zero_bucket_mb is None
               else {"bucket_mb": zero_bucket_mb}))
        self.exchange = exchange
        self.probe_metrics = probe_metrics
        self.probe_history: list[dict] = []
        if (not exchange.is_default or probe_metrics) \
                and not self._supports_exchange:
            raise ValueError(
                f"{type(self).__name__} does not support the gradient-"
                "exchange options (merge_rule/sync_every/compress/"
                "probe_metrics); they are implemented for ADAG/DynSGD "
                "(and LMTrainer) — the replica family already has its "
                "own communication cadence")
        if not exchange.is_default:
            if device_data:
                raise ValueError(
                    "merge_rule/sync_every/compress do not compose with "
                    "device_data=True: the exchange layer computes "
                    "per-replica gradients in a shard_map the indexed "
                    "data plane does not route through")
            if fsdp or plan is not None:
                raise ValueError(
                    "merge_rule/sync_every/compress build their own "
                    "placement plan; they do not compose with fsdp=True "
                    "or an explicit plan=")
            if self.adapter.ntv_paths:
                raise ValueError(
                    "gradient-exchange options need a model without "
                    "non-trainable training state (BatchNorm running "
                    "stats, seeded Dropout): per-replica local updates "
                    "would diverge it — train such models with the "
                    "default synchronous exchange")
            if zero and not (zero == 1 and exchange.compress == "int8"
                             and exchange.sync_every == 1):
                raise ValueError(
                    "the ZeRO stages compose with zero=1 + "
                    "compress='int8' only (the chunked codec compresses "
                    "the reduce-scatter leg); adasum, local-SGD, codec "
                    "rules and stages 2/3 replace the exchange the "
                    "sharded update rides")
        if probe_metrics and exchange.sync_every > 1:
            raise ValueError(
                "probe_metrics with sync_every > 1 is not supported: "
                "the local-SGD period has no single per-step global "
                "gradient to probe")
        if probe_metrics and device_data:
            raise ValueError(
                "probe_metrics does not compose with device_data=True "
                "(the indexed data plane's scanned step has no probe "
                "output slot)")
        if sum((fsdp, bool(zero), plan is not None)) > 1:
            raise ValueError(
                "pass only one of plan=, fsdp=True, zero=/zero1=True — "
                "they are alternative placement policies for the same "
                "state")
        if zero_bucket_mb is not None and not zero:
            raise ValueError(
                "zero_bucket_mb/zero1_bucket_mb only apply with a ZeRO "
                "stage (the plan=zero1_plan(...)/zero3_plan(...) "
                "spellings carry their own bucket_mb)")
        if not exchange.is_default:
            from distkeras_tpu.parallel.sharding import ExchangePlan

            self.plan = ExchangePlan(exchange, zero1=zero1)
        else:
            from distkeras_tpu.parallel.sharding import zero3_plan

            self.plan = plan or (fsdp_plan() if fsdp
                                 else zero1_plan(zero_bucket_mb)
                                 if zero == 1
                                 else Zero1Plan(zero_bucket_mb)
                                 if zero == 2
                                 else zero3_plan(zero_bucket_mb)
                                 if zero == 3
                                 else dp_plan())
            # plan=zero1_plan()/zero3_plan() are the explicit spellings
            # of zero=1/zero=3: the plans' sharded layouts only exist
            # if the optimizer/step are wired to produce them.
            if not zero:
                if getattr(self.plan, "zero1", False):
                    zero, zero1 = 1, True
                elif getattr(self.plan, "zero", 0):
                    zero = int(self.plan.zero)
        if mesh is not None:
            self.mesh = mesh
        else:
            devices = jax.devices()
            n = num_workers or len(devices)
            if n > len(devices):
                raise ValueError(
                    f"num_workers={n} exceeds visible devices ({len(devices)}); "
                    "oversubscription is not supported — it would serialize "
                    "on-device anyway")
            self.mesh = make_mesh(MeshSpec(data=n), devices=devices[:n])
        self.num_workers = int(self.mesh.shape["data"])
        if not exchange.is_default:
            for ax, size in self.mesh.shape.items():
                if ax != "data" and int(size) > 1:
                    raise ValueError(
                        "merge_rule/sync_every/compress compose with the "
                        f"data axis only, but the mesh has {ax}="
                        f"{int(size)}")
        self.zero = zero
        self.zero1 = zero1
        self._zero_inner = None
        self._zero_bucket_mb = getattr(self.plan, "bucket_mb", None)
        if zero == 1 and exchange.compress == "int8":
            from distkeras_tpu.parallel.collectives import zero_validate
            from distkeras_tpu.parallel.exchange import exchange_optimizer

            zero_validate(self.mesh, worker_optimizer, stage=zero)
            self.adapter.optimizer = exchange_optimizer(
                self.adapter.optimizer, self.mesh, exchange, zero1=True,
                names=self.adapter.tv_paths)
        elif zero:
            from distkeras_tpu.parallel.collectives import zero1_enable

            # The shared enablement path: zero1_enable runs the
            # construction-time checks for this stage — a known
            # non-elementwise transform (LARS/LAMB trust ratios)
            # raises naming itself instead of silently diverging
            # inside the scattered update — then wraps AFTER the
            # adapter resolved the optimizer: the wrapper is a drop-in
            # GradientTransformation, so init_state and every
            # accum/train step builder pick it up unchanged.  For
            # stages 2/3 only its INIT half is consumed (shard-view
            # state); the zero accum step drives the raw inner update
            # on the scattered views directly (_zero_inner).
            self._zero_inner = self.adapter.optimizer
            self.adapter.optimizer = zero1_enable(
                self._zero_inner, self.mesh, spec=worker_optimizer,
                bucket_mb=self._zero_bucket_mb, stage=zero)
        elif exchange.needs_grad_exchange:
            from distkeras_tpu.parallel.exchange import exchange_optimizer

            self.adapter.optimizer = exchange_optimizer(
                self.adapter.optimizer, self.mesh, exchange,
                names=self.adapter.tv_paths)

    # ------------------------------------------------------------ helpers

    def _zero_view_state(self, state):
        """Stage 3: the persistent ``tv`` is the chunk-major shard-view
        layout (``[n, cols]`` per leaf) — converted ONCE here, before
        placement; the step trains on views end to end."""
        layout = self.adapter.zero_layout(self.num_workers,
                                          self._zero_bucket_mb)
        return state.replace(tv=layout.shard_views(list(state.tv)))

    def _zero_unview_state(self, state):
        """Inverse of :meth:`_zero_view_state` (gathers the scattered
        views): parameter-layout ``tv`` for eval/export."""
        layout = self.adapter.zero_layout(self.num_workers,
                                          self._zero_bucket_mb)
        return state.replace(tv=layout.unview(list(state.tv)))

    def _shard_state(self, state):
        if self.zero >= 3:
            state = self._zero_view_state(state)
        sh = self.plan.state_shardings(self.mesh, state, self.adapter.tv_paths)
        return jax.device_put(state, sh), sh

    def _eval_state_view(self, pytree):
        """Mid-train eval under stage 3 reads the params back out of
        the shard views (a gather per eval round, never per step)."""
        if self.zero >= 3:
            pytree = self._zero_unview_state(pytree)
        return pytree.tv, pytree.ntv

    def _export(self, state):
        if self.zero >= 3:
            state = self._zero_unview_state(state)
        return super()._export(state)

    def _publish_tree(self, state):
        """Live weight push: publish parameter-layout weights (one
        gather per bucket under stage 3, only on publish rounds —
        same cost note as mid-train eval)."""
        tv, ntv = self._eval_state_view(state)
        return {"tv": list(tv), "ntv": list(ntv)}

    def _batch_sharding(self, leading_window: bool,
                        leading_sync: bool = False):
        spec = (P(None, None, "data") if leading_sync
                else P(None, "data") if leading_window else P("data"))
        return NamedSharding(self.mesh, spec)

    def _stacked_local_vag(self):
        """``jax.value_and_grad`` replacement for the gradient-exchange
        configurations: per-replica gradients are computed inside a
        shard_map over ``data`` and returned STACKED (leading replica
        axis, sharded), for :func:`exchange_optimizer` to merge.  The
        loss is pmean'd for reporting.  The LM analogue is
        ``LMTrainer._stacked_local_value_and_grad``."""
        mesh = self.mesh

        def value_and_grad(loss, has_aux=True):
            vag = jax.value_and_grad(loss, has_aux=has_aux)

            def wrapped(tv, ntv, x, y):
                def body(tv, ntv, x, y):
                    (l, ntv2), g = vag(tv, ntv, x, y)
                    g = jax.tree.map(lambda v: v[None], g)
                    return (jax.lax.pmean(l, "data"), ntv2), g

                return shard_map(
                    body, mesh=mesh,
                    in_specs=(P(), P(), P("data"), P("data")),
                    out_specs=((P(), P()), P("data")),
                    check_vma=False)(tv, ntv, x, y)

            return wrapped

        return value_and_grad

    # Batch staging shares one definition with LMTrainer
    # (parallel.mesh.global_batch): process-local slab assembly
    # multi-process, device_put under the sharding single-process.
    _global_batch = staticmethod(mesh_global_batch)


class ADAG(DistributedTrainer):
    """Asynchronous Distributed Adaptive Gradients, synchronously.

    ``device_data=True`` stages the dataset in HBM (see
    _fit_device_data).

    Reference parity: distkeras/trainers.py::ADAG (the reference's own
    flagship algorithm, SURVEY.md §3.2).  ``communication_window`` maps
    to gradient-accumulation depth per global step.
    """

    _supports_device_data = True
    _supports_exchange = True

    def __init__(self, keras_model, communication_window: int = 12, **kw):
        super().__init__(keras_model, **kw)
        self.communication_window = communication_window

    def _accum_step_fn(self):
        """The (un-jitted) round step for this exchange configuration:
        local-SGD when ``sync_every > 1``, the stacked-local-gradient
        accumulation step when a merge rule/codec needs per-replica
        gradients, the ZeRO stage-2/3 scattered-accumulator step when
        ``zero >= 2``, the plain accumulation step otherwise."""
        ex = self.exchange
        w = self.communication_window
        if ex.sync_every > 1:
            return self.adapter.make_localsgd_accum_step(
                w, ex.sync_every, self.mesh, ex)
        if ex.needs_grad_exchange:
            return self.adapter.make_accum_train_step(
                w, value_and_grad=self._stacked_local_vag(),
                grad_axis_size=self.num_workers,
                probe=self.probe_metrics)
        if self.zero >= 2:
            return self.adapter.make_zero_accum_step(
                w, self.mesh, self._zero_inner, stage=self.zero,
                bucket_mb=self._zero_bucket_mb,
                probe=self.probe_metrics)
        return self.adapter.make_accum_train_step(
            w, probe=self.probe_metrics)

    def _jit_accum_step(self, state_sh, batch_sh):
        """THE jitted accumulation step of the streaming path — built
        here once so ``_fit`` and :meth:`traced_for_analysis` can never
        drift apart (the IR lint must audit the program that trains)."""
        return jax.jit(
            self._accum_step_fn(),
            in_shardings=(state_sh, batch_sh, batch_sh),
            out_shardings=(state_sh, NamedSharding(self.mesh, P())),
            donate_argnums=0,
        )

    def _jit_indexed_accum_step(self, state_sh, repl, idx_sh):
        """THE jitted step of the single-process device-resident data
        plane — shared by ``_fit_device_data`` and
        :meth:`traced_for_analysis` (same never-drift contract as
        :meth:`_jit_accum_step`).  Under ``zero >= 2`` the indexed
        gather wraps the scattered-accumulator step, so device_data
        and the ZeRO stages compose."""
        accum = (self._accum_step_fn() if self.zero >= 2 else None)
        return jax.jit(
            self.adapter.make_indexed_accum_train_step(
                self.communication_window, accum=accum),
            in_shardings=(state_sh, repl, repl, idx_sh),
            out_shardings=(state_sh, repl),
            donate_argnums=0,
        )

    def traced_for_analysis(self, dataset: Dataset):
        """Trace targets for the IR lint (analysis/ir_lint.py): the
        REAL jitted step this configuration would train with —
        streaming, or the device-resident indexed step under
        ``device_data=True`` (single-process form; the multi-host
        device_data program is a distinct shard_map build not yet
        covered) — plus example argument shapes derived from
        ``dataset`` exactly as the feed loop would shape them.
        Nothing executes and nothing is materialized (state is
        ``eval_shape`` structs) — the lint only traces/lowers."""
        from distkeras_tpu.analysis.ir_lint import TraceSpec

        w = self.communication_window
        H = self.exchange.sync_every
        state = jax.eval_shape(self.adapter.init_state)
        pbytes = int(sum(np.prod(v.shape) * v.dtype.itemsize
                         for v in jax.tree.leaves(state.tv)))
        if self.zero >= 3:
            state = jax.eval_shape(self._zero_view_state, state)
        state_sh = self.plan.state_shardings(self.mesh, state,
                                             self.adapter.tv_paths)
        X = dataset[self.features_col]
        Y = dataset[self.label_col]
        name = type(self).__name__.lower()
        variant = f"zero{self.zero}" if self.zero else "dp"
        if not self.exchange.is_default:
            label = self.exchange.label()
            variant = f"zero1_{label}" if self.zero1 else label
        global_bs = self.batch_size * self.num_workers
        if self.device_data:
            repl = NamedSharding(self.mesh, P())
            idx_sh = NamedSharding(self.mesh, P(None, "data"))
            step = self._jit_indexed_accum_step(state_sh, repl, idx_sh)
            args = (state,
                    jax.ShapeDtypeStruct(X.shape, X.dtype),
                    jax.ShapeDtypeStruct(Y.shape, Y.dtype),
                    jax.ShapeDtypeStruct((w, global_bs), np.int32))
            variant += "_device_data"
        else:
            batch_sh = self._batch_sharding(leading_window=True,
                                            leading_sync=H > 1)
            step = self._jit_accum_step(state_sh, batch_sh)
            lead = (H, w) if H > 1 else (w,)
            args = (state,
                    jax.ShapeDtypeStruct(lead + (global_bs,)
                                         + X.shape[1:], X.dtype),
                    jax.ShapeDtypeStruct(lead + (global_bs,)
                                         + Y.shape[1:], Y.dtype))
        return [TraceSpec(name=f"{name}_{variant}/accum_step", fn=step,
                          args=args, donate_argnums=(0,),
                          params_bytes=pbytes)]

    def _fit(self, dataset: Dataset):
        if self.device_data:
            return self._fit_device_data(dataset)
        w = self.communication_window
        H = self.exchange.sync_every
        state = self.adapter.init_state()
        state, state_sh = self._shard_state(state)
        batch_sh = self._batch_sharding(leading_window=True,
                                        leading_sync=H > 1)

        step = self._jit_accum_step(state_sh, batch_sh)

        # Global batch = num_workers * batch_size rows per microbatch;
        # one jitted call consumes `window` microbatches (x sync_every
        # local rounds under local-SGD).  Each process feeds its share
        # of the global batch from its dataset shard; the balance check
        # keeps hosts from deadlocking the all-reduce
        # (mesh.equal_across_hosts: raise-before-loop, on every host).
        feed_bs = per_host_rows(self.batch_size * self.num_workers)
        equal_across_hosts(len(dataset) // (feed_bs * w * H),
                           f"step counts ({feed_bs * w * H}-row windows)")

        def stream():
            for _ in range(self.num_epoch):
                for xs, ys in dataset.batches(
                        feed_bs, features_col=self.features_col,
                        label_col=self.label_col, window=w * H):
                    if H > 1:
                        # [H*w, feed, ...] -> [H, w, feed, ...]: the
                        # first w microbatches are local round 1 — the
                        # same rows, in the same order, the synchronous
                        # path would consume.
                        xs = xs.reshape((H, w) + xs.shape[1:])
                        ys = ys.reshape((H, w) + ys.shape[1:])
                    with self.step_timer.phase("h2d"):
                        args = (self._global_batch(xs, batch_sh),
                                self._global_batch(ys, batch_sh))
                    yield args

        return self._run_rounds(state, step, stream(), feed_bs * w * H,
                                dataset)

    def _run_rounds(self, state, step, rounds, rows_per_round, dataset):
        """ONE round-loop driver for the streaming and device-resident
        paths: resume skipping, loss/checkpoint/eval bookkeeping, and
        the end-of-run guards must not drift between them."""
        losses, probes, rnd = [], [], 0
        state, start = self._restore_or(state)
        for args in rounds:
            rnd += 1
            if rnd <= start:
                continue
            with self.step_timer.phase("step"):
                state, out = step(state, *args)
            if self.probe_metrics:
                loss, aux = out
                probes.append(aux)
            else:
                loss = out
            losses.append(loss)
            self._checkpoint(state, rnd)
            self._eval_hook(state, rnd)
        if start and not losses:
            return state
        self._require_steps(losses, rows_per_round, len(dataset))
        self._record(losses)
        self._record_probes(probes, state)
        self._checkpoint(state, rnd, final=True)
        return state

    def _record_probes(self, probes, state) -> None:
        """Retire the in-graph probe scalars (one device->host sync at
        END of run, never per step) and the exchange layer's residual
        diagnostic into obs."""
        if probes:
            self.probe_history = [
                {k: float(v) for k, v in p.items()} for p in probes]
            from distkeras_tpu import obs

            last = self.probe_history[-1]
            for k, v in last.items():
                obs.gauge(f"train.{k}", v, trainer=type(self).__name__)
        if self.exchange.compress is not None:
            from distkeras_tpu import obs
            from distkeras_tpu.parallel.exchange import residual_norm_of

            rn = residual_norm_of(state.opt_state)
            if rn is not None:
                obs.gauge("exchange.residual_norm", rn)
                self.residual_norm = rn


    def _fit_device_data(self, dataset: Dataset):
        """Device-resident data plane for the distributed flagship.

        The dataset columns are staged in HBM ONCE, replicated on the
        mesh; each round ships only a [window, global_batch] int32
        index block, sharded over the ``data`` axis, and every replica
        gathers its own rows on device — the distributed form of
        SingleTrainer's ``device_data`` (the streaming path is capped
        by the host-to-device link; not measured on a directly attached
        chip).  Training math is EXACTLY the streaming path's (same
        accum step fed the same rows in the same order —
        exactness-tested).

        Multi-process meshes take :meth:`_fit_device_data_multihost`:
        per-host shard-local staging (each host's rows live only on its
        own devices) with replica-local gathers under shard_map — no
        row is ever duplicated or shipped cross-host.
        """
        if jax.process_count() > 1:
            return self._fit_device_data_multihost(dataset)
        w = self.communication_window
        state = self.adapter.init_state()
        state, state_sh = self._shard_state(state)
        repl = NamedSharding(self.mesh, P())
        idx_sh = NamedSharding(self.mesh, P(None, "data"))

        step = self._jit_indexed_accum_step(state_sh, repl, idx_sh)
        X = jax.device_put(dataset[self.features_col], repl)
        Y = jax.device_put(dataset[self.label_col], repl)
        global_bs = self.batch_size * self.num_workers
        rows = global_bs * w
        n = len(dataset)

        def index_blocks():
            for _ in range(self.num_epoch):
                for i in range(0, n - (n % rows), rows):
                    idx = np.arange(i, i + rows, dtype=np.int32).reshape(
                        w, global_bs)
                    with self.step_timer.phase("h2d"):
                        idx_dev = jax.device_put(idx, idx_sh)
                    yield (X, Y, idx_dev)

        return self._run_rounds(state, step, index_blocks(), rows,
                                dataset)

    def _fit_device_data_multihost(self, dataset: Dataset):
        """Device-resident data plane across hosts (round-3 verdict:
        the single-process-only ValueError cut against the framework's
        distributed-first identity).

        Each host stages ITS ``Dataset.shard`` in HBM once, laid out so
        every replica's consumption stream is CONTIGUOUS in its own
        shard of the global array: the host's usable rows, viewed as
        ``[chunks, local_replicas, batch]``, are transposed to
        ``[local_replicas, chunks * batch]`` before staging under
        ``P("data")`` — device ``l`` of this host then holds exactly
        the rows streaming would feed it, in consumption order.  Per
        round only one replicated ``[window, batch]`` index block
        crosses the link, and a ``shard_map`` gathers each replica's
        microbatch rows from its LOCAL block (a sharded-``X`` gather
        under plain jit would allgather the dataset every step).  The
        gathered global batch re-enters the same accum step as the
        streaming path with the same sharding, so the training math
        and data order are EXACTLY the streaming multi-process run's
        (replica ``(h, l)`` sees host h's rows
        ``chunk * feed + l * batch + k`` either way) — parity-tested in
        tests/test_deploy.py.
        """
        w = self.communication_window
        pcount = jax.process_count()
        feed_bs = per_host_rows(self.batch_size * self.num_workers)
        n_local_dev = self.num_workers // pcount
        bs = self.batch_size
        n = len(dataset)
        usable = equal_across_hosts(
            n - n % (feed_bs * w),
            f"usable row counts ({feed_bs * w}-row windows)")
        if usable == 0:
            raise ValueError(
                f"dataset shard has {n} rows but one training step needs "
                f"{feed_bs * w} per host; reduce "
                "batch_size/communication_window/num_workers or provide "
                "more data")
        chunks = usable // feed_bs             # multiple of w

        def stream_layout(col):
            # [chunks, L, bs, ...] -> [L, chunks*bs, ...]: device l's
            # contiguous block = its consumption stream.
            a = np.asarray(col[:usable])
            a = a.reshape((chunks, n_local_dev, bs) + a.shape[1:])
            a = np.moveaxis(a, 1, 0)
            return np.ascontiguousarray(
                a.reshape((usable,) + a.shape[3:]))

        data_sh = NamedSharding(self.mesh, P("data"))
        rep = NamedSharding(self.mesh, P())
        X = jax.make_array_from_process_local_data(
            data_sh, stream_layout(dataset[self.features_col]))
        Y = jax.make_array_from_process_local_data(
            data_sh, stream_layout(dataset[self.label_col]))

        state = self.adapter.init_state()
        state, state_sh = self._shard_state(state)
        accum = (self._accum_step_fn() if self.zero >= 2
                 else self.adapter.make_accum_train_step(w))
        mesh = self.mesh

        def local_gather(Xb, Yb, idx):
            # Xb [chunks*bs, ...]: THIS replica's stream; idx [w, bs]
            # replicated block-local offsets (identical per replica).
            shape = lambda a: (w, bs) + a.shape[1:]
            return (jnp.take(Xb, idx.reshape(-1), axis=0).reshape(
                        shape(Xb)),
                    jnp.take(Yb, idx.reshape(-1), axis=0).reshape(
                        shape(Yb)))

        gather = shard_map(
            local_gather, mesh=mesh,
            in_specs=(P("data"), P("data"), P()),
            out_specs=(P(None, "data"), P(None, "data")),
            check_vma=False)

        def step_fn(state, X, Y, idx):
            xs, ys = gather(X, Y, idx)
            return accum(state, xs, ys)

        step = jax.jit(
            step_fn,
            in_shardings=(state_sh, data_sh, data_sh, rep),
            out_shardings=(state_sh, NamedSharding(self.mesh, P())),
            donate_argnums=0,
        )

        def index_blocks():
            for _ in range(self.num_epoch):
                for r in range(chunks // w):
                    idx = np.arange(r * w * bs, (r + 1) * w * bs,
                                    dtype=np.int32).reshape(w, bs)
                    # device_put cannot target non-addressable devices;
                    # every host holds the identical block, so assemble
                    # the replicated global array from the local copy.
                    with self.step_timer.phase("h2d"):
                        idx_dev = jax.make_array_from_process_local_data(
                            rep, idx, idx.shape)
                    yield (X, Y, idx_dev)

        return self._run_rounds(state, step, index_blocks(), feed_bs * w,
                                dataset)


class DynSGD(ADAG):
    """Dynamic SGD.  Reference parity: distkeras/trainers.py::DynSGD.

    The reference scales each commit's learning rate by 1/(tau+1) where
    tau is the update staleness (DynSGDParameterServer).  Synchronous
    execution has tau == 0 identically, so DynSGD == ADAG here; kept as
    a distinct class for API parity (SURVEY.md §7.4).
    """
