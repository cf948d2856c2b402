"""Stateless functional view of a Keras 3 model (JAX backend).

This is the L1' substrate from SURVEY.md §7.1.  The reference keeps a
*stateful* Keras model inside each Spark worker and mutates it with
``model.train_on_batch`` (reference: distkeras/workers.py).  On TPU the
idiomatic unit is a *pure function over pytrees*: we extract the model's
variables once, and every train/predict step is

    loss, (tv, ntv, opt_state) = step(tv, ntv, opt_state, batch)

built from ``model.stateless_call`` — fully traceable, so the whole
epoch compiles to one XLA program per shape, and ``jax.sharding``
annotations on the pytrees drive data/tensor parallelism with collectives
inserted by the compiler (this replaces the reference's
parameter-server pull/commit protocol, distkeras/parameter_servers.py).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.ops.losses import resolve_loss
from distkeras_tpu.ops.optimizers import resolve_optimizer
from distkeras_tpu.utils.serialization import (
    deserialize_keras_model,
    serialize_keras_model,
)


@flax.struct.dataclass
class TrainState:
    """Pure pytree holding everything a train step needs.

    ``tv``/``ntv`` are the trainable / non-trainable variable values, in
    the order Keras reports them.  ``opt_state`` is the optax state over
    ``tv``.  ``step`` is the global step counter (device scalar, so the
    whole state lives on-device between steps).
    """

    tv: Any
    ntv: Any
    opt_state: Any
    step: jnp.ndarray


class ModelAdapter:
    """Wraps a Keras 3 model into stateless apply / train-step builders.

    One adapter instance owns the (traced-once) Keras object; all actual
    compute flows through pure functions that close over the model's
    *structure* but take variables as explicit pytree arguments.
    """

    def __init__(self, keras_model, loss="categorical_crossentropy",
                 optimizer="sgd", learning_rate: float | None = None,
                 metrics: Sequence[str] = (),
                 preprocess: Callable | None = None):
        import keras  # deferred so KERAS_BACKEND is already forced

        if keras.backend.backend() != "jax":  # pragma: no cover
            raise RuntimeError(
                "distkeras_tpu requires the Keras JAX backend, but keras is "
                "running on %r. Import distkeras_tpu before keras, or set "
                "KERAS_BACKEND=jax." % keras.backend.backend())
        self.model = keras_model
        if not keras_model.built:
            raise ValueError(
                "Keras model must be built (call it once or pass an Input "
                "layer) before wrapping in ModelAdapter")
        self.loss_fn = resolve_loss(loss)
        self.optimizer = resolve_optimizer(optimizer, learning_rate)
        self.metrics = tuple(metrics)
        unknown = [m for m in self.metrics if m != "accuracy"]
        if unknown:  # fail at construction, not after a whole run
            raise ValueError(
                f"unknown metric(s) {unknown}; known: ['accuracy']")
        # On-device input transform, traced into every step/predict
        # program (e.g. ``lambda x: x.astype("float32") / 255``).  Lets
        # the host ship the smallest wire dtype — uint8 pixels are 4x
        # fewer h2d bytes than the normalized f32 — and XLA fuses the
        # expansion into the first consumer.  The reference normalizes
        # host-side in Spark transformers (reference:
        # distkeras/transformers.py MinMaxTransformer), which quadruples
        # its wire traffic; on TPU the link is the scarce resource.
        self.preprocess = preprocess
        # Variable paths, for sharding rules keyed on names.
        self.tv_paths = [v.path for v in keras_model.trainable_variables]
        self.ntv_paths = [v.path for v in keras_model.non_trainable_variables]

    # ---------------------------------------------------------------- state

    def init_state(self) -> TrainState:
        """Snapshot the Keras variables into a fresh TrainState.

        A real copy, not ``asarray``'s alias: the train loops donate
        their state buffers, so an aliasing snapshot would consume the
        Keras variables on the first step and a second ``train`` on the
        same trainer (the Supervisor's retry path) would read deleted
        arrays."""
        tv = [jnp.array(v.value, copy=True)
              for v in self.model.trainable_variables]
        ntv = [jnp.array(v.value, copy=True)
               for v in self.model.non_trainable_variables]
        return TrainState(
            tv=tv,
            ntv=ntv,
            opt_state=self.optimizer.init(tv),
            step=jnp.zeros((), jnp.int32),
        )

    def write_back(self, state: TrainState) -> None:
        """Copy trained values from a TrainState back into the Keras model."""
        for var, val in zip(self.model.trainable_variables, state.tv):
            var.assign(np.asarray(val))
        for var, val in zip(self.model.non_trainable_variables, state.ntv):
            var.assign(np.asarray(val))

    def export_model(self, state: TrainState):
        """Return a *new* Keras model holding the trained weights.

        Mirrors the reference trainers returning a fresh deserialized
        model to the driver (distkeras/trainers.py Trainer.train).

        When the adapter has a ``preprocess`` hook the exported Keras
        model does NOT contain it (it is a jax transform, not a layer):
        callers must apply the same transform to inputs — or predict
        through :meth:`make_predict_fn` / ModelPredictor built from
        this adapter, which do.  A warning marks the hazard.
        """
        if self.preprocess is not None:
            import warnings

            warnings.warn(
                "export_model: the trained weights expect inputs "
                "transformed by this adapter's preprocess hook, but the "
                "exported Keras model does not embed it. Apply the same "
                "transform before model.predict, or run inference "
                "through the adapter's predict fn.", UserWarning,
                stacklevel=2)
        self.write_back(state)
        return deserialize_keras_model(serialize_keras_model(self.model))

    # ---------------------------------------------------------------- fns

    def stateless_apply(self, tv, ntv, x, training: bool = False):
        """Pure forward pass: returns (outputs, updated_ntv)."""
        if self.preprocess is not None:
            x = self.preprocess(x)
        out, ntv2 = self.model.stateless_call(tv, ntv, x, training=training)
        return out, ntv2

    def make_loss_fn(self) -> Callable:
        """Pure ``f(tv, ntv, x, y) -> (loss, ntv')`` for value_and_grad.

        Rematerialization note: checkpointing this whole function would
        be a peak-memory no-op (the backward's recompute materializes
        every residual at once); useful remat needs sub-function
        granularity, which requires model structure — the functional
        transformer does it per block (models/transformer.py
        TransformerConfig.remat).
        """
        model, loss_fn, pre = self.model, self.loss_fn, self.preprocess

        def compute_loss(tv, ntv, x, y):
            if pre is not None:
                x = pre(x)
            preds, ntv2 = model.stateless_call(tv, ntv, x, training=True)
            return loss_fn(y, preds), ntv2

        return compute_loss

    def make_train_step(self) -> Callable:
        """Build ``step(state, x, y) -> (state', loss)`` (not yet jitted).

        The caller decides how to jit/shard it — SingleTrainer jits it
        plain; distributed trainers wrap it with shardings over a mesh.
        """
        compute_loss = self.make_loss_fn()
        optimizer = self.optimizer

        def train_step(state: TrainState, x, y):
            grad_fn = jax.value_and_grad(compute_loss, has_aux=True)
            (loss, ntv2), grads = grad_fn(state.tv, state.ntv, x, y)
            updates, opt_state = optimizer.update(grads, state.opt_state, state.tv)
            tv = jax.tree.map(lambda p, u: p + u, state.tv, updates)
            return TrainState(tv=tv, ntv=ntv2, opt_state=opt_state,
                              step=state.step + 1), loss

        return train_step

    def make_accum_train_step(self, window: int,
                              value_and_grad: Callable | None = None,
                              grad_axis_size: int | None = None,
                              probe: bool = False) -> Callable:
        """Build a gradient-accumulation step over ``window`` microbatches.

        ``step(state, xs, ys)`` with ``xs: [window, B, ...]`` scans the
        microbatches, accumulating gradients, then applies one optimizer
        update on the mean gradient.  This is the synchronous semantics of
        the reference's ``communication_window`` commit cadence
        (distkeras/workers.py: workers accumulate for N batches then
        commit to the parameter server) — see SURVEY.md §7.4.

        ``value_and_grad`` (default ``jax.value_and_grad``) is the
        gradient-construction hook, same contract as the transformer's
        (models/transformer.make_train_step): it receives the loss fn
        and must return a ``(loss, aux), grads``-shaped callable.  The
        distributed trainers' gradient-exchange configurations pass a
        shard_map-local construction that returns STACKED per-replica
        gradients (leading axis ``grad_axis_size``) for the exchange
        optimizer to merge (parallel/exchange.py).

        ``probe=True``: the step returns ``(state, (loss, aux))`` with
        ``aux = {"grad_norm": ...}`` computed in-graph (the opt-in
        diagnostics probe; same program count — the trainers declare
        the compile-budget delta, which is zero extra programs).
        """
        compute_loss = self.make_loss_fn()
        optimizer = self.optimizer
        vag = (jax.value_and_grad if value_and_grad is None
               else value_and_grad)

        def train_step(state: TrainState, xs, ys):
            grad_fn = vag(compute_loss, has_aux=True)
            if grad_axis_size is None:
                zero = jax.tree.map(jnp.zeros_like, state.tv)
            else:
                zero = jax.tree.map(
                    lambda v: jnp.zeros((grad_axis_size,) + v.shape,
                                        v.dtype), state.tv)

            def micro(carry, batch):
                g_acc, ntv, loss_acc = carry
                x, y = batch
                (loss, ntv2), grads = grad_fn(state.tv, ntv, x, y)
                g_acc = jax.tree.map(jnp.add, g_acc, grads)
                return (g_acc, ntv2, loss_acc + loss), None

            (g_sum, ntv2, loss_sum), _ = jax.lax.scan(
                micro, (zero, state.ntv, jnp.zeros(())), (xs, ys))
            grads = jax.tree.map(lambda g: g / window, g_sum)
            updates, opt_state = optimizer.update(grads, state.opt_state, state.tv)
            tv = jax.tree.map(lambda p, u: p + u, state.tv, updates)
            out_state = TrainState(tv=tv, ntv=ntv2, opt_state=opt_state,
                                   step=state.step + 1)
            loss = loss_sum / window
            if probe:
                import optax

                return out_state, (loss,
                                   {"grad_norm": optax.global_norm(grads)})
            return out_state, loss

        return train_step

    def zero_layout(self, n: int, bucket_mb: float | None = None):
        """The ZeRO fusion-bucket layout of this model's trainable
        variables (shapes/dtypes only — nothing materializes).  The ONE
        geometry the stage-2/3 step builders, the trainers' view
        conversion, and the sharding plans share, so an accumulator
        bucket and its optimizer-state mirror can never disagree."""
        from distkeras_tpu.parallel.collectives import (
            DEFAULT_BUCKET_MB, Zero1Layout)

        structs = [jax.ShapeDtypeStruct(tuple(v.shape), np.dtype(v.dtype))
                   for v in self.model.trainable_variables]
        return Zero1Layout.for_tree(
            structs, n,
            DEFAULT_BUCKET_MB if bucket_mb is None else bucket_mb)

    def make_zero_accum_step(self, window: int, mesh, inner,
                             stage: int, bucket_mb: float | None = None,
                             probe: bool = False) -> Callable:
        """The gradient-accumulation step for ZeRO stages 2 and 3
        (docs/zero1.md): same contract as :meth:`make_accum_train_step`
        — ``step(state, xs, ys)`` scanning ``window`` microbatches —
        but the gradient accumulator is the SCATTERED fusion-bucket
        layout: each microbatch's gradient is packed per bucket and
        reduce-scattered INTO the accumulation scan (the
        ``collectives.scatter`` constraint on the carry), so a replica
        only ever persists its ``1/n`` gradient shard.  The update
        then runs on the shard views directly via ``inner`` (the
        UNWRAPPED optax transform, whose state the trainers init over
        shard views).

        Stage 2 keeps parameters replicated and all-gathers the update
        (RS-per-microbatch + one AG — *less* wire than the per-
        microbatch all-reduce it replaces).  Stage 3 additionally takes
        ``state.tv`` as ``[n, cols]`` shard views and re-materializes
        parameters per fusion bucket just-in-time inside the loss
        (``collectives.gather_bucket``: all-gather forward, reduce-
        scatter backward); the update output IS the new view state — no
        parameter all-gather leg at all.
        """
        from distkeras_tpu.parallel.collectives import (all_gather,
                                                        gather_bucket,
                                                        scatter)

        if stage not in (2, 3):
            raise ValueError(f"stage must be 2 or 3, got {stage}")
        n = int(mesh.shape["data"])
        layout = self.zero_layout(n, bucket_mb)
        compute_loss = self.make_loss_fn()

        def loss_of_views(v, ntv, x, y):
            with jax.named_scope("zero3/param_gather"):
                buckets = [gather_bucket(b, mesh)
                           for b in layout.pack_views(v)]
            return compute_loss(layout.unpack(buckets), ntv, x, y)

        def train_step(state: TrainState, xs, ys):
            grad_fn = jax.value_and_grad(
                loss_of_views if stage >= 3 else compute_loss,
                has_aux=True)
            scope = ("zero3/grad_accum" if stage >= 3
                     else "zero2/accum_scatter")

            def micro(carry, batch):
                bks, ntv, loss_acc = carry
                x, y = batch
                (loss, ntv2), g = grad_fn(state.tv, ntv, x, y)
                g_bks = (layout.pack_views(g) if stage >= 3
                         else layout.pack(g))
                with jax.named_scope(scope):
                    bks = [scatter(a + b, mesh)
                           for a, b in zip(bks, g_bks)]
                return (bks, ntv2, loss_acc + loss), None

            (bks, ntv2, loss_sum), _ = jax.lax.scan(
                micro, (layout.zero_buckets(), state.ntv, jnp.zeros(())),
                (xs, ys))
            g_views = layout.views_from_buckets(
                [b / window for b in bks])
            p_views = (state.tv if stage >= 3
                       else layout.shard_views(state.tv))
            with jax.named_scope(f"zero{stage}/update"):
                u_views, opt_state = inner.update(
                    g_views, state.opt_state, p_views)
            if stage >= 3:
                tv = jax.tree.map(lambda p, u: p + u, state.tv, u_views)
            else:
                with jax.named_scope("zero2/all_gather"):
                    u_buckets = [all_gather(b, mesh)
                                 for b in layout.pack_views(u_views)]
                tv = jax.tree.map(lambda p, u: p + u, state.tv,
                                  layout.unpack(u_buckets))
            out_state = TrainState(tv=tv, ntv=ntv2, opt_state=opt_state,
                                   step=state.step + 1)
            loss = loss_sum / window
            if probe:
                import optax

                return out_state, (loss,
                                   {"grad_norm": optax.global_norm(
                                       g_views)})
            return out_state, loss

        return train_step

    def make_localsgd_accum_step(self, window: int, sync_every: int,
                                 mesh, config, axis: str = "data"
                                 ) -> Callable:
        """Local-SGD over the accumulation step (parallel/exchange.py):
        ``step(state, xs, ys)`` with ``xs: [sync_every, window, GB, ...]``
        runs, per replica INSIDE a shard_map over ``axis``,
        ``sync_every`` purely-local rounds (each a ``window``-microbatch
        accumulation + local optimizer update on this replica's batch
        shard), then ONE cross-replica merge: parameter deltas by the
        configured rule (mean / adasum) and floating optimizer-state
        leaves averaged (the momentum-aware sync).  Collective
        frequency drops to 1/``sync_every`` of the synchronous step's.

        Loss reported is the cross-replica mean of the per-replica mean
        losses over the period.  Requires a model whose non-trainable
        variables do not update cross-batch (BatchNorm is rejected by
        the trainers): a replica-local ntv update would diverge.
        """
        from jax import shard_map as smap
        from jax.sharding import PartitionSpec as P

        from distkeras_tpu.parallel.exchange import (merge_local_params,
                                                     sync_local_tree)

        compute_loss = self.make_loss_fn()
        optimizer = self.optimizer
        n = int(mesh.shape[axis])

        def train_step(state: TrainState, xs, ys):
            def local_run(tv0, ntv0, opt0, xs, ys):
                grad_fn = jax.value_and_grad(compute_loss, has_aux=True)

                def local_round(carry, batch):
                    tv, ntv, opt = carry
                    xw, yw = batch          # [window, b_local, ...]
                    zero = jax.tree.map(jnp.zeros_like, tv)

                    def micro(c, b):
                        g_acc, ntv_c, loss_acc = c
                        x, y = b
                        (loss, ntv2), g = grad_fn(tv, ntv_c, x, y)
                        return (jax.tree.map(jnp.add, g_acc, g), ntv2,
                                loss_acc + loss), None

                    (g_sum, ntv2, loss_sum), _ = jax.lax.scan(
                        micro, (zero, ntv, jnp.zeros(())), (xw, yw))
                    grads = jax.tree.map(lambda g: g / window, g_sum)
                    u, opt = optimizer.update(grads, opt, tv)
                    tv = jax.tree.map(lambda p, q: p + q, tv, u)
                    return (tv, ntv2, opt), loss_sum / window

                (tv, ntv, opt), losses = jax.lax.scan(
                    local_round, (tv0, ntv0, opt0), (xs, ys))
                tv = merge_local_params(tv0, tv, config, axis, n)
                opt = sync_local_tree(opt, config, axis, n)
                ntv = sync_local_tree(ntv, config, axis, n)
                return tv, ntv, opt, jax.lax.pmean(
                    jnp.mean(losses), axis)

            tv, ntv, opt, loss = smap(
                local_run, mesh=mesh,
                in_specs=(P(), P(), P(), P(None, None, axis),
                          P(None, None, axis)),
                out_specs=(P(), P(), P(), P()),
                check_vma=False)(list(state.tv), list(state.ntv),
                                 state.opt_state, xs, ys)
            return TrainState(tv=tv, ntv=ntv, opt_state=opt,
                              step=state.step + sync_every), loss

        return train_step

    def make_multi_train_step(self, n_steps: int) -> Callable:
        """Build ``step(state, xs, ys) -> (state', losses)`` running
        ``n_steps`` *optimizer updates* in one XLA call.

        ``xs: [n_steps, B, ...]`` — one minibatch per scanned step (NOT
        gradient accumulation; compare make_accum_train_step, which
        takes one update over its window).  Amortizes per-call host
        dispatch, which dominates for small models (the reference pays
        a py4j+pickle round trip per batch — reference:
        distkeras/workers.py; here even the jit dispatch can be folded
        away).  Returns the per-step losses ``[n_steps]``.
        """
        train_step = self.make_train_step()

        def multi(state: TrainState, xs, ys):
            def body(state, batch):
                state, loss = train_step(state, *batch)
                return state, loss

            return jax.lax.scan(body, state, (xs, ys))

        return multi

    def make_indexed_train_step(self, n_steps: int) -> Callable:
        """Build ``step(state, X, Y, idx) -> (state', losses)`` for
        device-resident datasets.

        ``X``/``Y`` are the *whole* dataset staged in HBM (ship them
        once, in their wire dtype — uint8 pixels cost 4x less than f32
        and ``preprocess`` expands on device); ``idx: [n_steps, B]``
        selects each scanned step's minibatch with an on-device gather.
        Per window only the tiny index block crosses the host->device
        link, so epoch shuffling costs ~nothing no matter how slow the
        link is.  This inverts the reference's data plane — Spark ships
        every batch to the worker as pickled rows (reference:
        distkeras/workers.py iterating mapPartitions) — into the
        TPU-native form: data parked in HBM, the program comes to it.
        """
        train_step = self.make_train_step()

        def window(state: TrainState, X, Y, idx):
            if idx.shape[0] != n_steps:
                raise ValueError(
                    f"index block carries {idx.shape[0]} steps but this "
                    f"program was built for n_steps={n_steps}; the step "
                    "counter and checkpoint-round bookkeeping depend on "
                    "them agreeing")

            def body(st, ix):
                st, loss = train_step(
                    st, jnp.take(X, ix, axis=0), jnp.take(Y, ix, axis=0))
                return st, loss

            return jax.lax.scan(body, state, idx)

        return window

    def make_indexed_accum_train_step(self, window: int,
                                      accum: Callable | None = None
                                      ) -> Callable:
        """``make_accum_train_step`` over a device-resident dataset:
        ``step(state, X, Y, idx)`` with ``idx: [window, GB]`` gathers
        each microbatch from the staged ``X``/``Y`` on device, then
        accumulates exactly like the streaming accum step.  The
        distributed trainers' device_data path (per round, only the
        index block crosses the link; the mesh gathers its own rows).
        ``accum`` overrides the wrapped accumulation step (the ZeRO
        stage-2/3 trainers pass :meth:`make_zero_accum_step`'s)."""
        accum = accum if accum is not None \
            else self.make_accum_train_step(window)

        def step(state: TrainState, X, Y, idx):
            if idx.shape[0] != window:
                raise ValueError(
                    f"index block carries {idx.shape[0]} microbatches "
                    f"but this program accumulates window={window}")
            xs = jnp.take(X, idx.reshape(-1), axis=0).reshape(
                (*idx.shape, *X.shape[1:]))
            ys = jnp.take(Y, idx.reshape(-1), axis=0).reshape(
                (*idx.shape, *Y.shape[1:]))
            return accum(state, xs, ys)

        return step

    def make_eval_fn(self) -> Callable:
        """Pure ``f(tv, ntv, x, y) -> {"loss": ..., metric...}``.

        Inference-mode loss plus every metric named in ``metrics``
        (currently ``"accuracy"``: argmax match for multiclass logits,
        0.5-threshold for a single binary logit).  The trainers jit this
        for their ``eval_every`` hook — the reference's only mid-train
        signal is the worker-side loss history (reference:
        distkeras/workers.py yielding training histories).
        """
        model, loss_fn, pre = self.model, self.loss_fn, self.preprocess
        names = self.metrics

        def class_labels(y, preds):
            """Integer class per row from sparse, one-hot, or [N,1]
            binary labels — explicit, so no shape ever broadcasts to
            [N, N] garbage (same hazard ops/losses.py _align guards)."""
            if y.ndim == preds.ndim and y.shape[-1] == preds.shape[-1] > 1:
                return y.argmax(-1)  # one-hot
            if y.ndim == preds.ndim and y.shape[-1] == 1:
                y = y[..., 0]  # [N, 1] binary/sparse
            if y.ndim != preds.ndim - 1:
                raise ValueError(
                    f"label shape {y.shape} incompatible with prediction "
                    f"shape {preds.shape} for accuracy")
            return y.astype(jnp.int32)

        def evaluate(tv, ntv, x, y):
            if pre is not None:
                x = pre(x)
            preds, _ = model.stateless_call(tv, ntv, x, training=False)
            out = {"loss": loss_fn(y, preds)}
            if "accuracy" in names:  # names validated in __init__
                labels = class_labels(y, preds)
                if preds.shape[-1] == 1:
                    hit = (preds[..., 0] > 0).astype(jnp.int32) == labels
                else:
                    hit = preds.argmax(-1) == labels
                out["accuracy"] = jnp.mean(hit.astype(jnp.float32))
            return out

        return evaluate

    def make_predict_fn(self) -> Callable:
        """Pure ``f(tv, ntv, x) -> outputs`` (inference mode)."""
        model, pre = self.model, self.preprocess

        def predict(tv, ntv, x):
            if pre is not None:
                x = pre(x)
            out, _ = model.stateless_call(tv, ntv, x, training=False)
            return out

        return predict
