"""LMTrainer: the transformer flagship under the trainer-family API.

The reference's trainer family stops at Keras Sequential models fed by
`train_on_batch` (reference: distkeras/trainers.py); the TPU rebuild's
flagship is the functional transformer (models/transformer.py), and
this class gives it the same user contract as every other trainer —
``LMTrainer(cfg, ...).train(dataset) -> params`` with ``history`` and
``training_time`` — while exposing the full parallelism surface through
two knobs:

- ``mesh``: any MeshSpec mesh; the ``data`` axis shards the batch, a
  ``model`` axis applies Megatron TP (transformer.tp_rules), a ``seq``
  axis switches attention to the ring implementation, an ``expert``
  axis shards MoE experts, and a ``pipeline`` axis pipelines the trunk.
- ``microbatches``: GPipe depth when the mesh has a pipeline axis.

Dataset contract: one column of token rows ``[N, seq_len + 1]`` (inputs
plus the shifted targets, as lm_loss expects).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from distkeras_tpu import obs
from distkeras_tpu.data.dataset import Dataset
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.parallel.mesh import (AXES, make_mesh,
                                          global_batch as mesh_global_batch)
from distkeras_tpu.parallel.ring import make_ring_attention
from distkeras_tpu.parallel.sharding import ShardingPlan
from distkeras_tpu.trainers.base import CheckpointingBase
from distkeras_tpu.utils.profiling import StepTimer


_OPTS = {
    "adam": optax.adam,
    "adamw": optax.adamw,
    "sgd": optax.sgd,
}

# Staging more than this fraction of reported device memory fails fast
# (the rest of the step still needs activations/params/moments).
_STAGING_FRACTION = 0.8
# With no backend memory report (CPU), only an absurd estimate warns.
_STAGING_SANITY_BYTES = 8 << 30


def _device_bytes_limit():
    """Per-device memory budget in bytes, or None on a backend that
    reports none (``memory_stats()`` is None on CPU).  A TPU reports
    one, and a failing ``memory_stats()`` there raises.  Module-level
    so tests can monkeypatch a tiny budget to exercise the staging
    guard."""
    stats = jax.local_devices()[0].memory_stats()
    return None if stats is None else stats["bytes_limit"]


def _with_ema(opt, decay: float):
    """Wrap an optax transform so its state carries a Polyak/EMA shadow
    of the parameters: ``state = (inner_state, ema_params)``.

    The shadow updates with the POST-step parameters each optimizer
    step (``apply_updates`` on the incoming params — the same value the
    train step is about to adopt).  Living inside the optimizer state
    means checkpoint/resume and the params-like positional sharding
    rule (_state_shardings) cover it for free; LMTrainer exposes it as
    ``.ema_params`` after training.
    """
    def init(params):
        return opt.init(params), jax.tree.map(jnp.asarray, params)

    def update(grads, state, params=None, **kw):
        inner, shadow = state
        updates, inner = opt.update(grads, inner, params, **kw)
        stepped = optax.apply_updates(params, updates)
        shadow = jax.tree.map(
            lambda s, q: decay * s + (1.0 - decay) * q, shadow, stepped)
        return updates, (inner, shadow)

    return optax.GradientTransformation(init, update)


def _make_zero_step(cfg: tfm.TransformerConfig, inner, mesh, layout,
                    stage: int, grad_accum: int, probe: bool):
    """The ZeRO stage-2/3 train step for the pure-DP LM
    (docs/zero1.md): the gradient accumulator is the SCATTERED fusion-
    bucket layout — each microbatch's bucketed reduce-scatter
    interleaves into the accumulation loop (``collectives.scatter`` on
    the carry), so a replica only ever materializes its 1/n gradient
    shard — and the update runs on the shard views via ``inner`` (the
    raw optax chain, whose state the trainer inits over views).

    Stage 2 keeps ``params`` replicated and all-gathers the update;
    stage 3 takes ``params`` AS the ``[n, cols]`` shard-view tree,
    re-materializes full parameters per fusion bucket just-in-time
    inside the loss (``collectives.gather_bucket``: all-gather forward,
    reduce-scatter backward) and returns the updated views — no
    parameter all-gather leg at all.
    """
    from distkeras_tpu.parallel.collectives import (all_gather,
                                                    gather_bucket,
                                                    scatter)

    dropping = cfg.dropout > 0
    scope = "zero3/grad_accum" if stage >= 3 else "zero2/accum_scatter"

    def loss_of_views(v, tok, rng, seg):
        buckets = [gather_bucket(b, mesh) for b in layout.pack_views(v)]
        full = layout.unpack(buckets)
        return tfm.lm_loss(full, tok, cfg, None, None, rng, None, seg)

    def loss_full(p, tok, rng, seg):
        return tfm.lm_loss(p, tok, cfg, None, None, rng, None, seg)

    def step(carry, tokens, dropout_rng=None, segment_ids=None):
        params, opt_state = carry
        if dropping and dropout_rng is None:
            raise ValueError(
                f"cfg.dropout={cfg.dropout} but the train step got no "
                "dropout_rng (LMTrainer threads the rng automatically)")
        rng = dropout_rng if dropping else None
        grad_fn = jax.value_and_grad(
            loss_of_views if stage >= 3 else loss_full)
        acc = layout.zero_buckets()
        loss = jnp.zeros((), jnp.float32)
        for i in range(grad_accum):
            tok = tokens[i] if grad_accum > 1 else tokens
            seg = (None if segment_ids is None
                   else segment_ids[i] if grad_accum > 1
                   else segment_ids)
            ri = (jax.random.fold_in(rng, i)
                  if rng is not None and grad_accum > 1 else rng)
            li, gi = grad_fn(params, tok, ri, seg)
            g_bks = (layout.pack_views(gi) if stage >= 3
                     else layout.pack(gi))
            with jax.named_scope(scope):
                acc = [scatter(a + b, mesh) for a, b in zip(acc, g_bks)]
            loss = loss + li
        g_views = layout.views_from_buckets(
            [b / grad_accum for b in acc])
        p_views = params if stage >= 3 else layout.shard_views(params)
        with jax.named_scope(f"zero{stage}/update"):
            u_views, opt_state = inner.update(g_views, opt_state,
                                              p_views)
        if stage >= 3:
            params = jax.tree.map(lambda p, u: p + u, params, u_views)
        else:
            with jax.named_scope("zero2/all_gather"):
                u_buckets = [all_gather(b, mesh)
                             for b in layout.pack_views(u_views)]
            params = jax.tree.map(lambda p, u: p + u, params,
                                  layout.unpack(u_buckets))
        loss = loss / grad_accum
        if probe:
            return (params, opt_state), (
                loss, {"grad_norm": optax.global_norm(g_views)})
        return (params, opt_state), loss

    return step


def _make_localsgd_step(cfg: tfm.TransformerConfig, optimizer, mesh,
                        config):
    """Local-SGD train step for the pure-DP LM (docs/lowcomm.md):
    ``step((params, opt), tokens[H, B, S+1])`` runs, per replica inside
    a shard_map over ``data``, ``H = config.sync_every`` purely-local
    optimizer steps on this replica's batch shards, then ONE
    cross-replica merge — parameter deltas by the configured rule
    (mean / adasum per fusion bucket) and floating optimizer-state
    leaves averaged (momentum-aware).  1/H the collective frequency of
    the synchronous step; pinned by the collective census."""
    from distkeras_tpu.parallel.exchange import (merge_local_params,
                                                 sync_local_tree)

    def step(carry, tokens, dropout_rng=None, segment_ids=None):
        if dropout_rng is not None or segment_ids is not None:
            raise ValueError(
                "sync_every > 1 does not support dropout or packed "
                "segments (replica-local loss)")
        params, opt_state = carry
        n_data = int(mesh.shape["data"])

        def local_run(params, opt_state, tokens):
            grad_fn = jax.value_and_grad(tfm.lm_loss)

            def local_step(c, tok):
                p, s = c
                loss, g = grad_fn(p, tok, cfg, None, None, None, None,
                                  None)
                u, s = optimizer.update(g, s, p)
                p = jax.tree.map(lambda a, b: a + b, p, u)
                return (p, s), loss

            (p, s), losses = jax.lax.scan(
                local_step, (params, opt_state), tokens)
            with jax.named_scope("exchange/localsgd_sync"):
                p = merge_local_params(params, p, config, "data", n_data)
                s = sync_local_tree(s, config, "data", n_data)
                loss = jax.lax.pmean(jnp.mean(losses), "data")
            return (p, s), loss

        return shard_map(local_run, mesh=mesh,
                         in_specs=(P(), P(), P(None, "data", None)),
                         out_specs=((P(), P()), P()),
                         check_vma=False)(params, opt_state, tokens)

    return step


class LMTrainer(CheckpointingBase):
    """Train a causal transformer LM over a device mesh.

    Carries the full trainer-family contract: ``history`` /
    ``training_time``, ``shuffle`` (+ ``seed``), and orbax
    checkpoint/resume through ``checkpoint_dir`` / ``checkpoint_every``
    / ``max_checkpoints`` / ``resume`` — the same knobs as
    :class:`~distkeras_tpu.trainers.base.Trainer` (reference keeps one
    uniform contract across its family, distkeras/trainers.py).
    A checkpoint round is one optimizer step.

    ``device_data=True`` stages the token rows in HBM ONCE (int32 —
    cheap relative to activations), sharded over the ``data`` axis in
    consumption-stream layout; each step then ships only a tiny
    replicated index block and gathers its batch on device
    (_stage_stream).  This is the distributed/flagship form of
    SingleTrainer's device-resident input plane (the host link caps
    streaming); composes with fsdp/TP/ring/pipeline
    meshes and grad_accum/segments because the gather feeds the
    unchanged train step inside the same jitted program.  Data order
    is bit-for-bit the streaming path's (parity-tested).

    ``zero=1|2|3``: ZeRO sharding stages (docs/zero1.md; identical
    training math, pure-DP meshes only, ~``zero_bucket_mb`` fusion
    buckets).  Stage 1 (alias ``zero1=True``) shards the weight
    update: reduce-scatter(grads) -> each replica updates its shard ->
    all-gather(update); optimizer memory (adam moments, the EMA
    shadow) and update FLOPs drop ~data-axis x at unchanged comm
    volume.  Stage 2 additionally shards the gradient accumulator —
    each microbatch's bucketed reduce-scatter interleaves into the
    ``grad_accum`` loop, so a replica only materializes its 1/n
    gradient shard.  Stage 3 additionally holds the PARAMETERS as
    chunk-major ``[n, cols]`` shard views with bucket-granular
    gather-on-use (collectives.gather_bucket) and updates the views in
    place — per-device param+grad+opt bytes all drop ~data-axis x.
    ``fsdp=True`` is the GSPMD dimension-sharded ZeRO-3 alternative
    when TP composition matters.

    **Gradient-exchange policy** (docs/lowcomm.md; pure-DP meshes, no
    dropout/MoE/segments): ``merge_rule="adasum"`` merges replica
    gradients by pairwise adaptive summation instead of the mean
    (arXiv 2006.02924); ``sync_every=H`` switches to local-SGD — H
    purely-local optimizer steps then one momentum-aware parameter
    merge, 1/H the collective frequency (the WAN-tolerant mode for the
    cluster substrate); ``compress="int8"``/``"topk"`` applies an
    error-feedback codec per fusion bucket (~4x fewer gradient wire
    bytes for int8, pinned by the collective census).
    ``compress="int8"`` composes with ``zero1=True`` by compressing
    the reduce-scatter leg.  ``probe_metrics=True`` adds an in-graph
    grad-norm probe (``probe_history``; zero extra compiled programs).

    ``ema_decay``: maintain a Polyak/EMA average of the weights inside
    the optimizer state (decay per optimizer step); after ``train``,
    ``self.ema_params`` holds the servable averaged tree.  Composes
    with the mesh/checkpoint/accum features because the shadow is just
    more optimizer state.  Not offered on LoRATrainer (its optax.masked
    re-wrap would shadow a MaskedNode-laden packed tree; the servable
    artifact there is the merged tree ``train`` already returns).
    """

    @property
    def ema_params(self):
        """EMA weight tree from the last ``train`` call (requires
        ``ema_decay``); None before training."""
        if not self._ema:
            raise ValueError("ema_params requires ema_decay= on the "
                             "constructor")
        return self._ema_params

    def __init__(self, cfg: tfm.TransformerConfig, optimizer="adamw",
                 learning_rate: float = 3e-4, weight_decay: float | None = None,
                 batch_size: int = 8,
                 num_epoch: int = 1, mesh=None, rules=None,
                 microbatches: int | None = None, fsdp: bool = False,
                 zero: int | None = None,
                 zero1: bool = False, zero1_bucket_mb: float | None = None,
                 zero_bucket_mb: float | None = None,
                 device_data: bool = False,
                 grad_accum: int = 1, grad_clip_norm: float | None = None,
                 merge_rule: str = "mean", sync_every: int = 1,
                 compress=None, topk_frac: float = 0.01,
                 probe_metrics: bool = False,
                 tokens_col: str = "tokens", seed: int = 0,
                 shuffle: bool = False, eval_every: int = 0,
                 profile_dir: str | None = None, profile_steps: int = 3,
                 checkpoint_dir: str | None = None, checkpoint_every: int = 0,
                 max_checkpoints: int = 3, resume: bool = False,
                 checkpoint_backend: str = "auto",
                 ema_decay: float | None = None):
        self.cfg = cfg
        # (fused_qkv: the sharding rules name wq / wk / wv / wo.)
        tfm.reject_extended(cfg, "LMTrainer", allow=(
            "ffn_gated", "tie_head", "post_norms"))
        from distkeras_tpu.trainers.base import normalize_zero_args

        zero, zero1, zero_bucket_mb = normalize_zero_args(
            zero, zero1, zero_bucket_mb, zero1_bucket_mb)
        if not callable(learning_rate) and learning_rate <= 0:
            raise ValueError(
                f"learning_rate must be positive, got {learning_rate}")
        if weight_decay is not None and optimizer != "adamw":
            raise ValueError(
                "weight_decay only applies to optimizer='adamw' (pass a "
                "prebuilt optax transform for anything more exotic); "
                f"got optimizer={optimizer!r}")
        if hasattr(optimizer, "init"):  # prebuilt optax GradientTransformation
            self.optimizer = optimizer
        elif callable(optimizer):  # optax factory: optax.lion etc.
            self.optimizer = optimizer(learning_rate)
        elif optimizer == "adamw" and weight_decay is not None:
            # Standard masking: RMSNorm scales are excluded from decay
            # (decaying a normalization gain toward 0 fights the
            # parameterization, not overfitting).
            def decay_mask(params):
                def leaf(path, _):
                    name = jax.tree_util.keystr(path, simple=True,
                                                separator="/")
                    return not name.endswith("_scale")
                return jax.tree_util.tree_map_with_path(leaf, params)

            self.optimizer = optax.adamw(
                learning_rate, weight_decay=weight_decay, mask=decay_mask)
        else:
            try:
                self.optimizer = _OPTS[optimizer](learning_rate)
            except KeyError:
                raise ValueError(
                    f"unknown optimizer {optimizer!r}; known: {sorted(_OPTS)} "
                    "(or pass an optax factory / GradientTransformation)")
        if grad_clip_norm is not None:
            if grad_clip_norm <= 0:
                raise ValueError(
                    f"grad_clip_norm must be positive, got {grad_clip_norm}")
            self.optimizer = optax.chain(
                optax.clip_by_global_norm(grad_clip_norm), self.optimizer)
        if ema_decay is not None:
            if not 0.0 < ema_decay < 1.0:
                raise ValueError(
                    f"ema_decay must be in (0, 1), got {ema_decay}")
            # The shadow rides INSIDE the optimizer state, so
            # checkpointing, resume, and the params-like sharding rule
            # all cover it with zero extra machinery.
            self.optimizer = _with_ema(self.optimizer, ema_decay)
        self._ema = ema_decay is not None
        self._ema_params = None
        if grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        self.grad_accum = grad_accum
        if eval_every < 0:
            raise ValueError(f"eval_every must be >= 0, got {eval_every}")
        # Optional XLA profile of a few steady-state steps (skips round
        # 1, which is compile): utils/profiling.trace around rounds
        # [2, 2 + profile_steps); view in TensorBoard/Perfetto.
        if profile_steps < 1:
            raise ValueError(
                f"profile_steps must be >= 1, got {profile_steps}")
        self.profile_dir = profile_dir
        self.profile_steps = profile_steps
        self.batch_size = batch_size
        self.num_epoch = num_epoch
        self.mesh = mesh if mesh is not None else make_mesh()
        self.fsdp = fsdp
        self.device_data = device_data
        self.plan = ShardingPlan(
            rules=tfm.tp_rules() if rules is None else rules,
            fsdp_axis="data" if fsdp else None)
        self.tokens_col = tokens_col
        self.seed = seed
        self.shuffle = shuffle
        self.history: list[float] = []
        self.eval_every = eval_every
        # [(round, {"loss", "perplexity"})]; loss here is pure NLL (no
        # MoE aux), so exp(loss) is honest perplexity.
        self.eval_history: list[tuple[int, dict]] = []
        self.training_time: float = 0.0
        # Same phase observability as the Keras trainer family: "h2d"
        # = host staging + transfer dispatch, "step" = jitted dispatch.
        self.step_timer = StepTimer()
        self._setup_checkpointing(
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            max_checkpoints=max_checkpoints, resume=resume, shuffle=shuffle,
            seed=seed, backend=checkpoint_backend)

        missing = [a for a in AXES if a not in self.mesh.shape]
        if missing:
            raise ValueError(
                f"mesh is missing axes {missing}: LMTrainer needs the "
                f"canonical axis set {AXES} (build the mesh with "
                "parallel.mesh.make_mesh / MeshSpec, which always carries "
                "all five, sized 1 when unused)")
        n_pipe = int(self.mesh.shape["pipeline"])
        n_seq = int(self.mesh.shape["seq"])
        n_model = int(self.mesh.shape["model"])
        if (n_model > 1 and rules is None and cfg.n_kv_heads is not None
                and cfg.kv_heads % n_model):
            raise ValueError(
                f"GQA with Megatron TP: n_kv_heads={cfg.kv_heads} must "
                f"divide by the mesh model axis ({n_model}) — the default "
                "tp_rules shard K/V projections over their head "
                "dimension. Use more KV heads, a smaller model axis, or "
                "custom rules.")
        if cfg.dropout > 0 and n_pipe > 1:
            raise ValueError(
                "cfg.dropout > 0 cannot compose with a pipeline axis > 1: "
                "the pipeline's tick schedule is compiled without a "
                "per-microbatch rng stream (TransformerConfig.dropout). "
                "Train with dropout on a dp/tp/sp/fsdp mesh, or drop the "
                "regularizer under PP.")
        if fsdp and n_pipe > 1:
            raise ValueError(
                "fsdp=True cannot compose with a pipeline axis > 1: the "
                "pipelined trunk runs in a manual shard_map over "
                "{pipeline, seq} whose in_specs take the stage-stacked "
                "parameters whole. Shard memory across pipeline stages "
                "instead (that is what PP does), or drop the pipeline axis.")
        if microbatches is not None and n_pipe <= 1:
            raise ValueError(
                "microbatches only applies with a pipeline mesh axis > 1 "
                f"(mesh has pipeline={n_pipe})")
        self.microbatches = microbatches or (2 * n_pipe if n_pipe > 1 else 1)

        self.zero = zero
        self.zero1 = zero1
        self._zero_inner = None
        self._zero_layout_cache = None
        if zero_bucket_mb is not None and not zero:
            raise ValueError(
                "zero_bucket_mb/zero1_bucket_mb only apply with a "
                "ZeRO stage (zero=/zero1=True)")
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
        if not exchange.is_default:
            pure_dp = (n_model == 1 and n_seq == 1 and n_pipe == 1
                       and int(self.mesh.shape["expert"]) == 1
                       and not fsdp and not cfg.num_experts)
            if not pure_dp:
                raise ValueError(
                    "merge_rule/sync_every/compress compose with the "
                    "pure data-parallel mesh only (no model/seq/"
                    "pipeline/expert axes, no fsdp, no MoE): the "
                    "exchange layer computes per-replica gradients in "
                    "a shard_map over the data axis")
            if cfg.dropout > 0:
                raise ValueError(
                    "merge_rule/sync_every/compress do not compose "
                    "with cfg.dropout > 0: the dropout mask stream is "
                    "a global-batch quantity a replica-local loss "
                    "would draw differently")
            if device_data:
                raise ValueError(
                    "merge_rule/sync_every/compress do not compose "
                    "with device_data=True: the staged data plane "
                    "does not route through the local-gradient "
                    "shard_map")
            if zero and not (zero == 1 and exchange.compress == "int8"
                             and exchange.sync_every == 1):
                raise ValueError(
                    "the ZeRO stages compose with zero=1 + "
                    "compress='int8' only (the chunked codec compresses "
                    "the reduce-scatter leg); adasum, local-SGD, codec "
                    "rules and stages 2/3 replace the exchange the "
                    "sharded update rides")
            if exchange.sync_every > 1 and grad_accum > 1:
                raise ValueError(
                    "sync_every > 1 with grad_accum > 1 is not "
                    "supported: the local-SGD period already scans "
                    "sync_every microbatches per call")
        if probe_metrics and exchange.sync_every > 1:
            raise ValueError(
                "probe_metrics with sync_every > 1 is not supported: "
                "the local-SGD period has no single per-step global "
                "gradient to probe")
        if probe_metrics and device_data:
            raise ValueError(
                "probe_metrics does not compose with device_data=True "
                "(the staged-stream step has no probe output slot)")
        if zero:
            if fsdp:
                raise ValueError(
                    f"zero={zero} (chunk-major ZeRO) and fsdp=True "
                    "(the GSPMD dimension-sharded ZeRO-3 spelling) are "
                    "exclusive: they are alternative placements for "
                    "the same state")
            from distkeras_tpu.parallel.collectives import (
                DEFAULT_BUCKET_MB, zero1_enable, zero_validate)

            self._zero_bucket_mb = (DEFAULT_BUCKET_MB
                                    if zero_bucket_mb is None
                                    else zero_bucket_mb)
            # Satellite contract: the elementwise-compatibility check
            # runs at construction for EVERY stage — a known
            # non-elementwise transform (LARS/LAMB trust ratios) raises
            # naming itself instead of silently diverging inside the
            # scattered update.  Also rejects non-pure-DP meshes.
            # (Stage 1 runs it through zero1_enable, the shared
            # enablement path; stages 2/3 validate here and init over
            # views without a wrapper.)
            if zero != 1:
                zero_validate(self.mesh, optimizer, stage=zero)
            if zero == 1 and exchange.compress == "int8":
                from distkeras_tpu.parallel.exchange import (
                    exchange_optimizer)

                # zero1 x int8-EF: the exchange optimizer both shards
                # the update AND compresses the reduce-scatter leg.
                zero_validate(self.mesh, optimizer, stage=zero)
                self.optimizer = exchange_optimizer(
                    self.optimizer, self.mesh, exchange, zero1=True)
            elif zero == 1:
                # Wrap LAST, outside clip/EMA/weight-decay chains: the
                # whole chain then runs on shard views (the EMA shadow
                # and adam moments scatter too — the memory win covers
                # them all).
                self.optimizer = zero1_enable(
                    self.optimizer, self.mesh, spec=optimizer,
                    bucket_mb=self._zero_bucket_mb)
            else:
                # Stages 2/3 drive the raw chain on shard views from
                # inside the step (_make_zero_step); the trainer inits
                # its state over views directly, so no wrapper at all.
                self._zero_inner = self.optimizer
        elif exchange.needs_grad_exchange:
            from distkeras_tpu.parallel.exchange import exchange_optimizer

            self.optimizer = exchange_optimizer(
                self.optimizer, self.mesh, exchange)

        # segments (packed sequences) ride EVERY trunk: the default
        # flash attention, the ring (seq-axis) path — make_ring_attention
        # rotates the KV-side segment shard with its K/V — and the
        # pipelined trunk (per-microbatch segment slices ride the
        # pipeline as make_pipeline extras).
        if n_pipe > 1:
            # PP x SP: the pipeline shard_map goes manual over
            # {pipeline, seq} and runs the ring attention body per stage.
            # The head runs outside the pipeline, so with cfg.ce_chunks
            # the loss takes the trunk's hidden states (hidden_fn) and
            # chunks the vocab head exactly like the un-pipelined path.
            chunked = cfg.ce_chunks > 1
            def fwd(p, t, seg=None):
                return tfm.apply_pipelined(
                    p, t, cfg, self.mesh, microbatches=self.microbatches,
                    seq_axis="seq" if n_seq > 1 else None,
                    return_hidden=chunked, segment_ids=seg)
            # _forward_nll calls fwd(params, inputs, seg) so the trunk
            # masks attention, not just the loss.
            fwd.handles_segments = True
            self._fwd_kw = {"hidden_fn" if chunked else "apply_fn": fwd}
        elif n_seq > 1:
            ring = make_ring_attention(self.mesh, causal=True,
                                       window=cfg.attention_window)
            self._fwd_kw = {"attention_fn": ring}
        else:
            self._fwd_kw = {}
        # Replicated-DP (pure data mesh, replicated params): build the
        # gradient inside a shard_map so the tied embedding's two
        # cotangent contributions (lookup scatter + unembed dot) are
        # summed LOCALLY before one explicit per-leaf pmean — the
        # compiler-inserted exchange otherwise all-reduces them
        # separately (the graph lint's `comm-redundant-ar` finding:
        # 2x the embedding bytes on the wire every step).  Scoped to
        # exactly the configs where the exchange is the plain gradient
        # all-reduce: any sharded-param/sharded-update plan (fsdp,
        # zero1, TP/SP/PP axes) and MoE keep the compiler-inserted
        # collectives.
        dp_local_grads = (n_model == 1 and n_seq == 1 and n_pipe == 1
                          and int(self.mesh.shape["expert"]) == 1
                          and not fsdp and not zero
                          and not cfg.num_experts)
        if exchange.needs_grad_exchange:
            # Exchange configurations (adasum / EF codecs, zero1 x int8
            # included) feed the exchange optimizer STACKED per-replica
            # gradients instead of pmean'd ones.
            self._vag = self._stacked_local_value_and_grad()
        elif dp_local_grads:
            self._vag = self._dp_local_value_and_grad()
        else:
            self._vag = None
        # _fwd_kw captures the mesh-specific forward once; the step and
        # eval builders (and LoRATrainer's overrides) share it.
        if exchange.sync_every > 1:
            self._step_builder = lambda opt: _make_localsgd_step(
                cfg, opt, self.mesh, exchange)
        elif zero >= 2:
            self._step_builder = lambda opt: _make_zero_step(
                cfg, opt, self.mesh, self._layout(), stage=zero,
                grad_accum=grad_accum, probe=self.probe_metrics)
        else:
            self._step_builder = lambda opt: tfm.make_train_step(
                cfg, opt, grad_accum=grad_accum,
                value_and_grad=self._vag, probe=self.probe_metrics,
                **self._fwd_kw)
        self._nll_fn = lambda p, t, seg=None: tfm.lm_nll(
            p, t, cfg,
            segment_ids=seg,
            **self._fwd_kw)
        if zero >= 3:
            # Eval/serve read the params back out of the shard views:
            # gather per fusion bucket (jit-native all-gather), then
            # the unchanged nll — one gather per eval chunk, never per
            # train step.
            from distkeras_tpu.parallel.collectives import gather_bucket

            base_nll = self._nll_fn

            def nll_views(v, t, seg=None):
                layout = self._layout()
                full = layout.unpack(
                    [gather_bucket(b, self.mesh)
                     for b in layout.pack_views(v)])
                return base_nll(full, t, seg)

            self._nll_fn = nll_views

    @property
    def _feed_block(self) -> int:
        """Leading microbatch-block size of the fed token array: the
        grad-accum depth, or the local-SGD period (mutually exclusive
        by construction); 1 = a flat [B, S+1] batch."""
        return (self.grad_accum if self.grad_accum > 1
                else self.exchange.sync_every)

    def _layout(self):
        """The ZeRO fusion-bucket layout of this config's parameter
        tree (shapes only — eval_shape, nothing materializes); one
        geometry shared by the step builder, the view conversion, the
        eval gather and the sharding rules."""
        if self._zero_layout_cache is None:
            from distkeras_tpu.parallel.collectives import Zero1Layout

            shapes = jax.eval_shape(
                lambda: tfm.init_params(jax.random.key(self.seed),
                                        self.cfg))
            self._zero_layout_cache = Zero1Layout.for_tree(
                shapes, int(self.mesh.shape["data"]),
                self._zero_bucket_mb)
        return self._zero_layout_cache

    def _publish_tree(self, carry):
        """Live weight push: the carry is ``(params, opt_state)``;
        publish the params in parameter layout (one gather per bucket
        under stage 3, only on publish rounds)."""
        params, _ = carry
        if self.zero >= 3:
            params = self._layout().unview(params)
        return params

    def _dp_local_value_and_grad(self):
        """``jax.value_and_grad`` replacement for the replicated-DP
        configuration (see __init__): gradients are computed per
        replica inside a ``shard_map`` over the ``data`` axis — so
        autodiff's add of the tied embedding's two contributions is a
        LOCAL op — and exchanged with ONE explicit ``pmean`` per leaf.
        Identical math to the compiler-inserted all-reduce (the global
        batch mean's gradient is the mean of equal-sized shard
        gradients), at exactly parameter-bytes of all-reduce payload.

        Dropout and packed-segment runs fall back to the compiler-
        inserted exchange at trace time: the dropout mask stream and
        the valid-target count are *global-batch* quantities that a
        replica-local loss would compute differently.
        """
        mesh = self.mesh

        def value_and_grad(loss):
            vag = jax.value_and_grad(loss)

            def wrapped(params, tokens, cfg, attention_fn, apply_fn,
                        rng, hidden_fn, segment_ids=None):
                if rng is not None or segment_ids is not None:
                    return vag(params, tokens, cfg, attention_fn,
                               apply_fn, rng, hidden_fn, segment_ids)

                def local_grads(p, t):
                    l, g = vag(p, t, cfg, attention_fn, apply_fn,
                               None, hidden_fn, None)
                    def pm(x):
                        return jax.lax.pmean(x, "data")
                    return pm(l), jax.tree.map(pm, g)

                return shard_map(local_grads, mesh=mesh,
                                 in_specs=(P(), P("data", None)),
                                 out_specs=(P(), P()),
                                 check_vma=False)(params, tokens)

            return wrapped

        return value_and_grad

    def _stacked_local_value_and_grad(self):
        """``jax.value_and_grad`` replacement for the gradient-exchange
        configurations (parallel/exchange.py): per-replica gradients
        are computed inside a ``shard_map`` over ``data`` and returned
        STACKED — global ``[n, *leaf]`` sharded ``P("data")`` — for the
        exchange optimizer to merge (adasum / EF codecs; the
        compiler's pmean never runs).  The loss is pmean'd for
        reporting.  Dropout and packed segments are rejected at
        construction/train time, so the trace-time guard here is
        belt-and-braces."""
        mesh = self.mesh

        def value_and_grad(loss):
            vag = jax.value_and_grad(loss)

            def wrapped(params, tokens, cfg, attention_fn, apply_fn,
                        rng, hidden_fn, segment_ids=None):
                if rng is not None or segment_ids is not None:
                    raise ValueError(
                        "gradient-exchange configurations do not "
                        "support dropout or packed segments "
                        "(replica-local loss)")

                def local_grads(p, t):
                    l, g = vag(p, t, cfg, attention_fn, apply_fn,
                               None, hidden_fn, None)
                    g = jax.tree.map(lambda v: v[None], g)
                    return jax.lax.pmean(l, "data"), g

                return shard_map(local_grads, mesh=mesh,
                                 in_specs=(P(), P("data", None)),
                                 out_specs=(P(), P("data")),
                                 check_vma=False)(params, tokens)

            return wrapped

        return value_and_grad

    # ------------------------------------------------------------------

    @staticmethod
    def _put_global(tree, shardings):
        """Host pytree -> mesh-placed pytree, multi-process safe.

        Single process: plain ``device_put``.  Multi-process SPMD (the
        mesh spans hosts): every process holds the identical full host
        array (same-seeded parameter init), so each leaf is assembled
        per-shard via ``make_array_from_callback`` — ``device_put``
        cannot target non-addressable devices.  Per-host *data* (token
        batches, eval chunks) goes through :meth:`_global_batch`
        instead.
        """
        if jax.process_count() == 1:
            return jax.device_put(tree, shardings)

        def put(x, sh):
            x = np.asarray(x)
            return jax.make_array_from_callback(x.shape, sh,
                                                lambda idx: x[idx])

        return jax.tree.map(put, tree, shardings)

    # Per-step token blocks and eval chunks route through the shared
    # parallel.mesh.global_batch (one definition of the process-local
    # slab assembly for the whole trainer family).
    _global_batch = staticmethod(mesh_global_batch)

    def _guard_staged_bytes(self, n_rows: int, width: int,
                            with_segments: bool) -> None:
        """Fail fast when ``device_data=True`` would stage more HBM
        than the devices have, instead of surfacing as a raw XLA
        allocation error deep inside ``_global_batch`` (round-6 fix).

        The staged stream is int32 ``[rows, seq+1]`` sharded over the
        ``data`` axis (doubled when segments ride along), so each
        device persists ``rows * width * 4 / local_devices`` bytes for
        the whole run.  Backends that report a budget
        (``memory_stats``) get a hard error above
        ``_STAGING_FRACTION``; budget-less backends only warn past an
        absolute sanity bound.
        """
        n_local = int(self.mesh.shape["data"]) // jax.process_count()
        per_dev = (n_rows * width * 4 * (2 if with_segments else 1)
                   // max(n_local, 1))
        limit = _device_bytes_limit()
        msg = (f"device_data=True would stage "
               f"{per_dev / 2**20:.1f} MiB of token rows per device"
               + (" (segments included)" if with_segments else ""))
        if limit is not None and per_dev > _STAGING_FRACTION * limit:
            raise ValueError(
                f"{msg}, over {int(_STAGING_FRACTION * 100)}% of the "
                f"{limit / 2**20:.1f} MiB device budget — train with "
                "device_data=False (the streaming fallback), shard the "
                "corpus across more hosts, or trim the dataset")
        if limit is None and per_dev > _STAGING_SANITY_BYTES:
            import warnings

            warnings.warn(
                f"{msg}; this backend reports no memory budget, but "
                "that figure rarely fits — device_data=False streams "
                "from host instead", stacklevel=3)

    def _stage_stream(self, rows, steps):
        """Host token rows (consumption order) -> ONE device-resident
        int32 array sharded over the ``data`` axis, laid out so each
        device's shard is exactly its own consumption stream,
        contiguous — the LM form of ADAG._fit_device_data_multihost's
        stream layout.  Device ``(h, d)``'s stream position
        ``(step, accum, k)`` holds host h's row
        ``step*rows_per_step + accum*local_bs + d*sub + k`` — precisely
        the row the streaming path's ``_global_batch`` would place on
        that device — so an on-device ``take`` of a replicated index
        block reproduces streaming data order bit-for-bit.
        """
        n_proc = jax.process_count()
        n_data = int(self.mesh.shape["data"])
        n_local_dev = n_data // n_proc
        sub = self.batch_size // n_data
        a = np.asarray(rows, np.int32)
        a = a.reshape((steps, self.grad_accum, n_local_dev, sub)
                      + a.shape[1:])
        a = np.moveaxis(a, 2, 0)
        a = np.ascontiguousarray(a.reshape((len(rows),) + a.shape[4:]))
        return self._global_batch(a, NamedSharding(self.mesh,
                                                   P("data", None)))

    def _replicated(self, a):
        """Small replicated host array -> mesh.  NOT _global_batch:
        a replicated sharding must keep the local shape as the global
        shape (every host holds the identical copy), where the shared
        helper would concatenate hosts' rows."""
        return self._put_global(a, NamedSharding(self.mesh, P()))

    def init_params(self):
        params = tfm.init_params(jax.random.key(self.seed), self.cfg)
        return self._put_global(
            params, self.plan.tree_shardings(self.mesh, params))

    def _state_shardings(self, params, opt_state):
        """Sharding trees for (params, opt_state): subtrees of the
        optimizer state mirroring the params structure (adam mu/nu,
        momentum buffers) take the params' shardings; everything else
        (step counters) is replicated.

        Under the ZeRO stages the optimizer state instead holds
        ``[n, cols]`` shard views and takes the shared shard-view rule
        (``parallel/rules.py``); at stage 3 ``params`` is itself the
        view tree and scatters ``P("data", None)`` per leaf.
        """
        if self.zero >= 3:
            from distkeras_tpu.parallel.rules import (
                zero3_param_shardings)

            psh = zero3_param_shardings(params, self.mesh)
        else:
            psh = self.plan.tree_shardings(self.mesh, params)
        rep = NamedSharding(self.mesh, P())
        if self.exchange.needs_grad_exchange:
            # Exchange state: error-feedback residuals shard over
            # their replica axis (and shard views under zero1 x int8);
            # inner moments replicate like the (pure-DP) params.
            from distkeras_tpu.parallel.exchange import (
                exchange_state_shardings)

            return psh, exchange_state_shardings(
                params, opt_state, self.mesh, zero1=self.zero1)
        if self.zero:
            from distkeras_tpu.parallel.collectives import (
                zero1_state_shardings)

            return psh, zero1_state_shardings(params, opt_state,
                                              self.mesh)
        p_def = jax.tree.structure(params)

        def params_like(x):
            return jax.tree.structure(x) == p_def

        osh = jax.tree.map(lambda x: psh if params_like(x) else rep,
                           opt_state, is_leaf=params_like)
        return psh, osh

    def _build_carry_and_step(self, params):
        """Committed carry + THE jitted step for this configuration:
        ``(params, opt_state, psh, osh, step, step_sh, tok_sh)`` —
        ``train()``'s construction, also reached by ``bench_suite.py
        zero_stages`` so the bench times the exact program users train.

        Optimizer state must be *committed* to the mesh: fresh eager
        arrays are uncommitted (jit may reshard them freely) but the
        checkpoint-restore template takes each leaf's sharding
        literally, so adam's scalar count would come back pinned to
        one device while params span the mesh — an invalid mix.  Built
        under jit with explicit out_shardings (structure from
        eval_shape): eager optax init on params spanning
        non-addressable devices would fail multi-process.
        """
        if self.zero >= 2:
            # Stages 2/3 run the raw chain on shard views: the state
            # inits over the view tree (scattered moments), and at
            # stage 3 the persistent params themselves convert to the
            # ``[n, cols]`` view layout here — the carry trains as
            # views end to end.
            layout = self._layout()

            def init_views(p):
                return self.optimizer.init(layout.shard_views(p))

            opt_shapes = jax.eval_shape(init_views, params)
            carry_struct = (jax.eval_shape(layout.shard_views, params)
                            if self.zero >= 3 else params)
            psh, osh = self._state_shardings(carry_struct, opt_shapes)
            opt_state = jax.jit(init_views, out_shardings=osh)(params)
            if self.zero >= 3:
                params = jax.jit(layout.shard_views,
                                 out_shardings=psh)(params)
        else:
            opt_shapes = jax.eval_shape(self.optimizer.init, params)
            psh, osh = self._state_shardings(params, opt_shapes)
            opt_state = jax.jit(self.optimizer.init,
                                out_shardings=osh)(params)
        step, step_sh, tok_sh = self._jit_train_step(psh, osh)
        return params, opt_state, psh, osh, step, step_sh, tok_sh

    def _jit_train_step(self, psh, osh):
        """Build THE jitted optimizer step for this configuration —
        ``train`` and :meth:`traced_for_analysis` share this one
        construction so the IR lint audits the program that trains,
        never a reimplementation.  Returns ``(step, step_sh, tok_sh)``
        (the fed block's and the flat token rows' shardings)."""
        tok_sh = NamedSharding(self.mesh, P("data", None))
        # With accumulation (or a local-SGD period) the fed block is
        # [accum|sync_every, B, S+1]: the microbatch axis leads, batch
        # still shards over data.
        step_sh = (tok_sh if self._feed_block == 1
                   else NamedSharding(self.mesh, P(None, "data", None)))
        rep = NamedSharding(self.mesh, P())
        jit_kw = {}
        if int(self.mesh.shape["pipeline"]) == 1:
            # Pin the carry layout so XLA keeps the plan's placement
            # (scattered params under FSDP, Megatron splits under TP)
            # across steps instead of resharding at its own whim.
            # The pipelined trunk is exempt: its manual shard_map
            # governs placement internally.  rng and segment slots
            # are always present positionally (None when unused —
            # an empty pytree binds no sharding).
            if self.device_data:
                # The staged stream shares the token sharding: both
                # are [rows, S+1] split over the data axis.
                in_sh = ((psh, osh), tok_sh, rep, rep, tok_sh)
            else:
                in_sh = ((psh, osh), step_sh, rep, step_sh)
            jit_kw = dict(in_shardings=in_sh,
                          out_shardings=((psh, osh), rep))
        if self.device_data:
            # HBM-resident data plane: the staged stream stays on
            # device; each step ships only a replicated [accum, sub]
            # index block and a shard_map gathers every device's
            # rows from its OWN shard (a plain take on the sharded
            # array would all-gather the dataset each step).  The
            # gather fuses into the same XLA program as the step.
            inner = self._step_builder(self.optimizer)
            accum = self.grad_accum

            def local_take(xb, idx):
                g = jnp.take(xb, idx.reshape(-1), axis=0)
                return g.reshape(idx.shape + xb.shape[1:])

            gather = shard_map(
                local_take, mesh=self.mesh,
                in_specs=(P("data", None), P()),
                out_specs=(P(None, "data", None) if accum > 1
                           else P("data", None)),
                check_vma=False)

            def dd_step(carry, X, idx, rng, Seg):
                tok = gather(X, idx)
                seg = None if Seg is None else gather(Seg, idx)
                return inner(carry, tok, rng, seg)

            step = jax.jit(dd_step, donate_argnums=0, **jit_kw)
        else:
            step = jax.jit(self._step_builder(self.optimizer),
                           donate_argnums=0, **jit_kw)
        return step, step_sh, tok_sh

    def traced_for_analysis(self, seq_len: int | None = None,
                            n_rows: int | None = None):
        """Trace targets for the IR lint (analysis/ir_lint.py): the
        jitted train step this configuration executes, with example
        argument shapes for one optimizer round (``seq_len`` defaults
        to ``cfg.max_len``).  Under ``device_data=True`` the staged
        stream's aval depends on the corpus size — pass
        ``n_rows=len(tokens)`` to trace the exact program a concrete
        ``train(tokens)`` call compiles (default: one step's rows).
        Nothing executes and nothing is materialized — state is shape
        structs (``jax.eval_shape``), so a production-size trainer can
        be linted without touching HBM; the lint only traces and
        lowers."""
        from distkeras_tpu.analysis.ir_lint import TraceSpec

        seq = self.cfg.max_len if seq_len is None else seq_len
        params = jax.eval_shape(
            lambda: tfm.init_params(jax.random.key(self.seed),
                                    self.cfg))
        pbytes = int(sum(np.prod(v.shape) * v.dtype.itemsize
                         for v in jax.tree.leaves(params)))
        if self.zero >= 2:
            layout = self._layout()
            opt_state = jax.eval_shape(
                lambda p: self.optimizer.init(layout.shard_views(p)),
                params)
            if self.zero >= 3:
                params = jax.eval_shape(layout.shard_views, params)
        else:
            opt_state = jax.eval_shape(self.optimizer.init, params)
        psh, osh = self._state_shardings(params, opt_state)
        step, _, _ = self._jit_train_step(psh, osh)
        rng = (jax.random.key(self.seed + 0x5eed)
               if self.cfg.dropout > 0 else None)
        name = type(self).__name__.lower()
        variant = (f"zero{self.zero}" if self.zero
                   else "fsdp" if self.fsdp else "dp")
        if not self.exchange.is_default:
            label = self.exchange.label()
            variant = f"zero1_{label}" if self.zero1 else label
        # Shapes are the GLOBAL avals the jitted step consumes — the
        # same for every process count (multi-process hosts each feed
        # a block that _global_batch assembles into these).
        if self.device_data:
            n_data = int(self.mesh.shape["data"])
            sub = self.batch_size // n_data
            rows_per_step = self.batch_size * self.grad_accum
            rows = (rows_per_step if n_rows is None
                    else n_rows - n_rows % rows_per_step)
            X = jax.ShapeDtypeStruct((rows, seq + 1), jnp.int32)
            idx = jax.ShapeDtypeStruct(
                (self.grad_accum, sub) if self.grad_accum > 1
                else (sub,), jnp.int32)
            args = ((params, opt_state), X, idx, rng, None)
        else:
            block = self._feed_block
            shape = ((block, self.batch_size, seq + 1) if block > 1
                     else (self.batch_size, seq + 1))
            args = ((params, opt_state),
                    jax.ShapeDtypeStruct(shape, jnp.int32), rng, None)
        return [TraceSpec(name=f"{name}_{variant}/train_step", fn=step,
                          args=args, donate_argnums=(0,),
                          params_bytes=pbytes)]

    def train(self, dataset: Dataset | np.ndarray, params=None,
              eval_tokens: np.ndarray | None = None,
              segments: np.ndarray | None = None,
              eval_segments: np.ndarray | None = None):
        """Train over the token rows; returns the trained params pytree.

        ``eval_tokens [M, seq+1]`` (with ``eval_every``) runs a held-out
        NLL/perplexity evaluation every ``eval_every`` optimizer steps
        and once at the end (round -1) into ``eval_history``; fed in
        ``batch_size`` chunks, dropping a remainder of up to
        ``batch_size - 1`` rows (static shapes, one compiled program).

        ``segments`` (with optional ``eval_segments``): packed-sequence
        segment ids aligned with the rows (data/packing.pack_documents)
        — attention stays within-document and the loss skips boundary/
        padding targets.  Works on every mesh: data/model/fsdp/expert,
        the ``seq`` (ring) axis, and pipeline meshes (per-microbatch
        segment slices ride the pipeline).

        Multi-process: BOTH ``dataset`` and ``eval_tokens`` are this
        host's shard (e.g. ``rows[process_index::process_count]``), and
        every host must pass the same row counts — each eval chunk is
        ``batch_size / process_count`` local rows assembled into one
        global batch, so feeding the full set on every host would
        evaluate each row ``process_count`` times.
        """
        tokens = (dataset if isinstance(dataset, np.ndarray)
                  else dataset[self.tokens_col])
        if tokens.ndim != 2:
            raise ValueError(f"tokens must be [N, seq+1], got {tokens.shape}")
        if segments is not None:
            if segments.shape != tokens.shape:
                raise ValueError(
                    f"segments must align with the token rows "
                    f"{tokens.shape}, got {segments.shape}")
        if eval_segments is not None and segments is None:
            raise ValueError("eval_segments without segments — pack "
                             "train and eval the same way")
        if segments is not None and not self.exchange.is_default:
            raise ValueError(
                "packed segments do not compose with merge_rule/"
                "sync_every/compress: the valid-target count is a "
                "global-batch quantity a replica-local loss would "
                "compute differently")
        # Multi-process SPMD: every process runs this same loop over its
        # OWN rows (feed tokens[process_index::process_count] or
        # Dataset.shard) — all hosts must pass the same row count or
        # their step counts diverge and the collectives deadlock.
        n_proc = jax.process_count()
        n_data = int(self.mesh.shape["data"])
        n_seq = int(self.mesh.shape["seq"])
        seq_len = tokens.shape[1] - 1
        if n_seq > 1 and seq_len % n_seq:
            raise ValueError(
                f"sequence length {seq_len} (token rows carry seq+1 = "
                f"{tokens.shape[1]} positions) must divide by the mesh seq "
                f"axis ({n_seq}) for ring attention to shard it")
        global_bs = self.batch_size
        # The pipelined path splits each per-data-shard batch into
        # microbatches; without a pipeline axis only data divides it.
        divisor = n_data * (self.microbatches
                            if int(self.mesh.shape["pipeline"]) > 1 else 1)
        if global_bs % divisor:
            raise ValueError(
                f"batch_size={global_bs} must divide by data axis ({n_data})"
                + (f" x microbatches ({self.microbatches})"
                   if divisor != n_data else ""))
        if n_proc > 1 and n_data % n_proc:
            raise ValueError(
                f"multi-process training needs the data axis ({n_data}) to "
                f"divide by the process count ({n_proc}) so every host "
                "feeds its own devices' shards")
        if self.shuffle:
            # Same permutation contract as Dataset.shuffle; the row
            # gather runs through the native threaded loader when built.
            from distkeras_tpu.native import gather_rows

            perm = np.random.default_rng(self.seed).permutation(len(tokens))
            tokens = gather_rows(tokens, perm)  # gather_rows coerces to C-order
            if segments is not None:
                segments = gather_rows(segments, perm)

        self.eval_history = []
        if self.eval_every and eval_tokens is None:
            raise ValueError("eval_every is set but train() got no "
                             "eval_tokens")
        if eval_tokens is not None:
            if (eval_tokens.ndim != 2
                    or eval_tokens.shape[1] != tokens.shape[1]):
                raise ValueError(
                    f"eval_tokens must be [M, {tokens.shape[1]}] like the "
                    f"training rows, got {eval_tokens.shape}")
            if (eval_segments is not None
                    and eval_segments.shape != eval_tokens.shape):
                raise ValueError(
                    f"eval_segments must align with eval_tokens "
                    f"{eval_tokens.shape}, got {eval_segments.shape}")
            if len(eval_tokens) < global_bs // n_proc:
                raise ValueError(
                    f"eval_tokens has {len(eval_tokens)} rows; one eval "
                    f"batch needs {global_bs // n_proc} per process")

        # Per-run phase stats (and obs spans) describe THIS run only.
        self.step_timer.reset()
        t0 = time.perf_counter()
        # Fail fast on a bad checkpoint_dir before paying parameter
        # init and mesh placement.
        self._open_checkpoints()
        profiling = False
        try:
            if params is None:
                params = self.init_params()
            (params, opt_state, psh, osh, step, step_sh,
             tok_sh) = self._build_carry_and_step(params)
            dropping = self.cfg.dropout > 0
            # Dropout stream keyed on the optimizer round: resume from a
            # checkpoint replays the identical mask sequence.
            drop_base = (jax.random.key(self.seed + 0x5eed)
                         if dropping else None)

            eval_fn = None
            if eval_tokens is not None:
                from distkeras_tpu.utils.misc import nll_to_perplexity

                nll = jax.jit(self._nll_fn)
                eval_bs = global_bs // n_proc  # rows per process
                n_eval = len(eval_tokens) - (len(eval_tokens) % eval_bs)
                # Stage the eval chunks once; every eval round reuses
                # the device arrays instead of re-paying the transfer.
                eval_chunks = [
                    self._global_batch(
                        np.asarray(eval_tokens[j:j + eval_bs], np.int32),
                        tok_sh)
                    for j in range(0, n_eval, eval_bs)]
                eval_seg_chunks = eval_weights = None
                if eval_segments is not None:
                    eval_seg_chunks, eval_weights = [], []
                    for j in range(0, n_eval, eval_bs):
                        seg = np.asarray(eval_segments[j:j + eval_bs],
                                         np.int32)
                        gseg = self._global_batch(seg, tok_sh)
                        eval_seg_chunks.append(gseg)
                        # Packed chunks carry different VALID-target
                        # counts; each chunk's mean NLL must be
                        # weighted by its count or the corpus mean is
                        # biased toward padding-heavy tail chunks.
                        # Counted on the assembled GLOBAL chunk (not
                        # the host-local shard): nll() returns the
                        # global mean, and every process must weight
                        # it identically or multi-host eval_history
                        # desynchronizes.
                        eval_weights.append(int(jnp.sum(
                            (gseg[:, 1:] == gseg[:, :-1])
                            & (gseg[:, :-1] != 0))))

                def eval_fn(carry, rnd):
                    ps = carry[0]
                    if eval_seg_chunks is None:
                        mean = sum(float(nll(ps, c))
                                   for c in eval_chunks) / len(eval_chunks)
                    else:
                        tot = sum(w * float(nll(ps, c, sc))
                                  for c, sc, w in zip(
                                      eval_chunks, eval_seg_chunks,
                                      eval_weights))
                        mean = tot / max(sum(eval_weights), 1)
                    self.eval_history.append(
                        (rnd, {"loss": mean,
                               "perplexity": nll_to_perplexity(mean)}))

                if self.profile_dir and self.eval_every:
                    # Pre-compile the eval nll so an eval round landing
                    # inside the profiler capture window records eval
                    # *execution*, not its first-call XLA compile (the
                    # trace contract is steady-state work only).  With
                    # eval_every=0 no eval can land in the window.
                    jax.block_until_ready(
                        nll(params, eval_chunks[0]))

            carry, losses, probes = (params, opt_state), [], []
            # Multi-process: ``tokens`` holds only this host's rows, so
            # each step consumes 1/n_proc of the global row count and
            # the global batch is assembled shard-wise (_global_batch).
            # A local-SGD period (sync_every) consumes a block exactly
            # like grad_accum does — one leading microbatch axis.
            blk = self._feed_block
            rows_per_step = global_bs * blk // n_proc
            n_rows = len(tokens) - (len(tokens) % rows_per_step)
            if not n_rows:
                raise ValueError(
                    f"dataset has {len(tokens)} rows; one step needs "
                    f"{rows_per_step} (batch_size x grad_accum"
                    + (f" / {n_proc} processes)" if n_proc > 1 else ")"))
            X_dev = seg_dev = None
            if self.device_data:
                steps_pe = n_rows // rows_per_step
                self._guard_staged_bytes(n_rows, tokens.shape[1],
                                         segments is not None)
                X_dev = self._stage_stream(tokens[:n_rows], steps_pe)
                if segments is not None:
                    seg_dev = self._stage_stream(segments[:n_rows],
                                                 steps_pe)
            carry, start = self._restore_or(carry)
            rnd = 0
            # Profile rounds relative to the first *executed* round
            # (resume skips rnd <= start): one warm round for compile,
            # then profile_steps captured rounds.
            prof_start = start + 2
            for _ in range(self.num_epoch):
                for i in range(0, n_rows, rows_per_step):
                    rnd += 1
                    if rnd <= start:
                        continue
                    if self.device_data:
                        sub = global_bs // n_data
                        s = i // rows_per_step
                        flat = np.arange(s * self.grad_accum * sub,
                                         (s + 1) * self.grad_accum * sub,
                                         dtype=np.int32)
                        idx = (flat.reshape(self.grad_accum, sub)
                               if self.grad_accum > 1 else flat)
                        with self.step_timer.phase("h2d"):
                            step_args = (X_dev, self._replicated(idx))
                    else:
                        block = np.asarray(tokens[i:i + rows_per_step],
                                           np.int32)
                        seg_batch = None
                        if segments is not None:
                            seg_block = np.asarray(
                                segments[i:i + rows_per_step], np.int32)
                            if self.grad_accum > 1:
                                seg_block = seg_block.reshape(
                                    self.grad_accum, global_bs // n_proc,
                                    seg_block.shape[1])
                            seg_batch = self._global_batch(seg_block,
                                                           step_sh)
                        if blk > 1:
                            block = block.reshape(blk,
                                                  global_bs // n_proc,
                                                  block.shape[1])
                        with self.step_timer.phase("h2d"):
                            step_args = (self._global_batch(block,
                                                            step_sh),)
                    if self.profile_dir and rnd == prof_start:
                        jax.profiler.start_trace(self.profile_dir)
                        profiling = True
                    rng = (jax.random.fold_in(drop_base, rnd)
                           if dropping else None)
                    with self.step_timer.phase("step"):
                        if self.device_data:
                            carry, out = step(carry, *step_args, rng,
                                              seg_dev)
                        else:
                            carry, out = step(carry, *step_args, rng,
                                              seg_batch)
                    if self.probe_metrics:
                        loss, probe_aux = out
                        probes.append(probe_aux)
                    else:
                        loss = out
                    if (profiling
                            and rnd >= prof_start - 1 + self.profile_steps):
                        # Flush async device work ONCE, when the profile
                        # window closes — not a per-iteration sync.
                        jax.block_until_ready(loss)  # dkt: ignore[hot-sync]
                        jax.profiler.stop_trace()
                        profiling = False
                    losses.append(loss)
                    self._checkpoint(carry, rnd)
                    if (eval_fn is not None and self.eval_every
                            and rnd % self.eval_every == 0):
                        eval_fn(carry, rnd)
            if profiling:  # run shorter than the requested capture
                jax.block_until_ready(losses[-1])
                jax.profiler.stop_trace()
                profiling = False
            elif self.profile_dir and rnd < prof_start:
                import warnings

                warnings.warn(
                    f"profile_dir is set but the run executed only "
                    f"{max(0, rnd - start)} round(s); the trace skips the "
                    f"compile round and starts at round {prof_start - start}"
                    " — no profile was written. Train on more data or more "
                    "epochs to capture one.", stacklevel=2)
            if losses:
                self._checkpoint(carry, rnd, final=True)
            if eval_fn is not None and not (
                    self.eval_history and self.eval_history[-1][0] == rnd):
                eval_fn(carry, -1)  # final state not already evaluated
        finally:
            if profiling:  # exception mid-capture: close the profiler
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
            self._close_checkpoints()
        params, opt_state = carry
        if self.zero >= 3:
            # The carry trained as shard views; hand the user back a
            # params-layout tree (one gather per bucket, end of run).
            params = self._layout().unview(params)
        if self._ema:
            # Under a grad-exchange wrapper the state nests one level
            # deeper: (ema_state, ExchangeState).
            ema_src = (opt_state[0] if self.exchange.needs_grad_exchange
                       else opt_state)
            self._ema_params = ema_src[1]
            if self.zero:
                # The shadow rode the optimizer state as scattered
                # shard views; hand the user back a params-layout tree.
                self._ema_params = self._layout().unview(
                    self._ema_params)
        jax.block_until_ready(jax.tree.leaves(params)[0])
        self.history = [float(l) for l in losses]
        # Probe scalars and the exchange residual diagnostic retire in
        # ONE device->host pass at end of run, never per step.
        if probes:
            self.probe_history = [
                {k: float(v) for k, v in p.items()} for p in probes]
            for k, v in self.probe_history[-1].items():
                obs.gauge(f"train.{k}", v, trainer=type(self).__name__)
        if self.exchange.compress is not None:
            from distkeras_tpu.parallel.exchange import residual_norm_of

            rn = residual_norm_of(opt_state)
            if rn is not None:
                obs.gauge("exchange.residual_norm", rn)
                self.residual_norm = rn
        self.training_time = time.perf_counter() - t0
        self._record_run_metrics()
        if obs.active() is not None:
            # Once a call, from the host's rows: the share of the causal
            # band's tiles each segmented attention kernel computes.
            from distkeras_tpu.ops.attention import (live_tile_share,
                                                     segment_tiles_for)

            inputs = (None if segments is None
                      else np.asarray(segments[:n_rows])[:, :seq_len])
            for kernel, tiles in segment_tiles_for(seq_len).items():
                share = 1.0 if inputs is None else live_tile_share(
                    inputs, *tiles, self.cfg.attention_window)
                obs.gauge("train.attn_live_tile_share", share,
                          trainer=type(self).__name__, kernel=kernel)
        return params


class LoRATrainer(LMTrainer):
    """Fine-tune a FROZEN pretrained base with LoRA adapters, under the
    exact LMTrainer contract (history, eval, shuffle, checkpoints,
    meshes, packing).

    ``base_params``: the pretrained tree (tfm.init_params layout, e.g.
    from ``dk.load_lm``).  The trained state is the packed
    ``(adapters, base)`` pair: the optimizer is wrapped in
    ``optax.masked`` so moments exist for the adapter leaves ONLY, the
    loss stop-gradients the base, and the step's base output aliases
    its input (donation keeps it in place).  ``train`` returns the
    MERGED servable params (``self.adapters`` keeps the raw delta —
    ship it with ``lora_merge`` for instant A/B of adapter versions).

    The merge runs inside the jitted step, so every LMTrainer mesh
    (TP, FSDP, ring, pipeline) and feature (grad_accum, segments,
    chunked CE) composes unchanged.  Checkpoints store the packed pair
    (base included — simple and correct; at LoRA scale the adapter
    delta is the only part that changes between steps).
    """

    def __init__(self, cfg: tfm.TransformerConfig, base_params,
                 lora_rank: int = 8, lora_alpha: float = 16.0,
                 lora_targets=("wq", "wv"), **kw):
        from distkeras_tpu.models.lora import (LoRAConfig, _validate,
                                               lora_mask, make_lora_loss)

        if base_params is None:
            raise ValueError(
                "LoRATrainer needs the pretrained base_params (load_lm "
                "or a trained LMTrainer tree) — LoRA over a random base "
                "is a sign the wrong trainer was picked")
        self.lora = LoRAConfig(rank=lora_rank, alpha=lora_alpha,
                               targets=tuple(lora_targets))
        _validate(cfg, self.lora)
        if kw.get("ema_decay") is not None:
            raise ValueError(
                "ema_decay is not supported on LoRATrainer: the "
                "adapter-masked optimizer state cannot shadow the "
                "frozen base; serve the merged tree train() returns "
                "(or EMA-average adapters outside the trainer)")
        if kw.get("zero1") or kw.get("zero"):
            raise ValueError(
                "zero1/zero= is not supported on LoRATrainer: the "
                "masked packed (adapters, base) state keeps moments "
                "only for the ~1000x-smaller adapter leaves, so there "
                "is nothing worth sharding — and the frozen base must "
                "stay whole for the in-step merge")
        if (kw.get("merge_rule", "mean") != "mean"
                or kw.get("sync_every", 1) != 1
                or kw.get("compress") is not None
                or kw.get("probe_metrics")):
            raise ValueError(
                "merge_rule/sync_every/compress/probe_metrics are not "
                "supported on LoRATrainer: the packed (adapters, base) "
                "gradient is ~1000x smaller than the base, so the "
                "exchange is never the bottleneck — and the builders "
                "here bypass the exchange-aware step construction")
        super().__init__(cfg, **kw)
        self.optimizer = optax.masked(self.optimizer, lora_mask)
        self._base_host = base_params
        self.adapters = None
        loss_fn = make_lora_loss(cfg, self.lora)
        fwd_kw = self._fwd_kw
        # Deliberately WITHOUT the parent's value_and_grad hook
        # (_dp_local_value_and_grad): the tied-embedding redundancy it
        # fixes cannot occur here — the base (embedding included) is
        # stop-gradiented, so its cotangent is a symbolic zero with no
        # all-reduce at all — while the shard_map path's per-leaf
        # pmean would ADD explicit collectives over the base-sized
        # zero gradient leaves the compiler currently elides.
        self._step_builder = lambda opt: tfm.make_train_step(
            cfg, opt, grad_accum=self.grad_accum, loss_fn=loss_fn,
            **fwd_kw)

        def nll(packed, t, seg=None):
            from distkeras_tpu.models.lora import lora_merge

            adapters, base = packed
            merged = lora_merge(base, adapters, cfg, self.lora)
            return tfm.lm_nll(
                merged, t, cfg,
                segment_ids=seg,
                **fwd_kw)

        self._nll_fn = nll

    def init_params(self):
        from distkeras_tpu.models.lora import lora_init

        adapters = lora_init(jax.random.key(self.seed + 1), self.cfg,
                             self.lora)
        # COPY the base into the packed state: the train loop donates
        # its carry (the base aliases through the step, which is the
        # point), so without a copy the first step would consume the
        # caller's buffers and a second train()/serve on the same base
        # would hit "Array has been deleted".
        base = jax.tree.map(lambda x: jnp.array(x, copy=True),
                            self._base_host)
        packed = (adapters, base)
        return self._put_global(
            packed, self.plan.tree_shardings(self.mesh, packed))

    def train(self, dataset, params=None, **kw):
        from distkeras_tpu.models.lora import lora_merge

        if params is not None:
            raise ValueError(
                "LoRATrainer builds its own (adapters, base) state from "
                "the constructor's base_params; to resume, use "
                "checkpoint_dir/resume like any trainer")
        packed = super().train(dataset, **kw)
        self.adapters, base = packed
        return lora_merge(base, self.adapters, self.cfg, self.lora)
