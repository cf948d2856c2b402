"""Functional transformer LM: the framework's flagship composite model.

The reference's deepest model is a one-layer LSTM (reference: examples,
IMDB config); this module is where the TPU rebuild goes past it — a
decoder-only transformer written as pure functions over a dict pytree,
designed so every parallelism axis of the device mesh applies:

- **data**: batch sharded via the batch PartitionSpec,
- **model** (TP): Megatron layout — QKV/FFN-in column-sharded,
  attn-out/FFN-out row-sharded (XLA inserts the psum/reduce-scatter),
- **seq** (SP): ring attention (distkeras_tpu.parallel.ring) when
  ``attention_fn`` is a ring wrapper; activations sharded [data, seq],
- **expert** (EP): Switch-style top-1 MoE FFN with capacity dropping;
  expert weights sharded over ``expert`` (XLA inserts the all-to-alls
  around the dispatch/combine einsums),
- **pipeline** (PP): the per-layer params are stacked [L, ...] so a
  contiguous slice of layers forms a stage
  (distkeras_tpu.parallel.pipeline consumes ``block_apply``).

No flax: parameters are plain nested dicts so sharding rules regex over
key-paths (parallel.sharding.ShardingPlan.tree_shardings) and the
driver's dry-run can jit the full train step with explicit
NamedShardings.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distkeras_tpu.ops.attention import flash_attention
from distkeras_tpu.ops.grouped import TILE_M as GROUP_TILE, grouped_matmul
from distkeras_tpu.ops.retention import log_gate, retention_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_len: int = 128
    # MoE: 0 experts = dense FFN.  With E > 0 every layer's FFN is a
    # top-k MoE with `capacity_factor` slack per expert: moe_top_k=1 is
    # Switch routing (combine weight = the raw top-1 probability),
    # moe_top_k=2 is GShard/Mixtral-style top-2 (combine weights =
    # top-k probabilities renormalized over the selected experts;
    # first choices take capacity priority over second choices).
    # Expert capacity scales with k: cap = capacity_factor * k * N / E
    # (capacity_factor stays "slack per assignment" at any k).
    num_experts: int = 0
    moe_top_k: int = 1
    capacity_factor: float = 1.25
    aux_loss_coef: float = 0.01
    dtype: str = "float32"  # activation/compute dtype (bfloat16 on TPU)
    # Rotary position embeddings (half-split rotation on q/k) instead of
    # the learned pos_emb table: position information becomes relative
    # inside attention, the standard long-context choice (no trained
    # table capping usable length at max_len — max_len still bounds the
    # decode KV cache).  Requires an even head_dim.
    rope: bool = False
    rope_theta: float = 10000.0
    # Residual dropout rate (embedding, attention output, FFN/MoE
    # output).  Active only when a dropout_rng is supplied (training);
    # inference and eval are always deterministic.  Not supported under
    # pipeline parallelism (the compiled tick schedule has no
    # per-microbatch rng stream) — LMTrainer rejects the combination.
    dropout: float = 0.0
    # Grouped-query attention: fewer K/V heads than Q heads (None =
    # n_heads = vanilla MHA; 1 = multi-query).  Shrinks the decode KV
    # cache and its HBM traffic by n_heads/n_kv_heads; K/V are repeated
    # to full heads for the attention kernels (training compute
    # unchanged, the cache is the win).
    n_kv_heads: int | None = None
    # Rematerialize each block in the backward pass (jax.checkpoint):
    # activation memory drops from O(layers) to O(1) blocks at ~1/3 more
    # FLOPs — the standard long-context/deep-model trade on TPU, where
    # HBM, not MXU, is the usual ceiling.
    remat: bool = False
    # Selective remat (only with remat=True): which intermediates the
    # backward may keep instead of recomputing.  None = recompute
    # everything (max memory saving); "dots" saves matmul outputs
    # (recompute only the cheap elementwise work — most of the no-remat
    # speed at a fraction of its memory); "dots_no_batch" saves only
    # matmuls without batch dims (weight-stationary contractions).
    remat_policy: str | None = None
    # Vocab-head cross-entropy chunking (training/eval loss only).
    # With ce_chunks > 1 the loss computes the [tokens, vocab] logits in
    # ce_chunks sequential slices, each rematerialized in the backward,
    # so the full [B, S, V] f32 logits never materialize in HBM — at
    # vocab 32k, seq 1k, batch 8 that is ~1 GB of f32 written + re-read
    # several times per step on the unchunked path.  Pure optimization:
    # loss and gradients are exact (per-slice logsumexp), sampling and
    # predict paths are untouched (they need one position's logits
    # only).  0/1 = off.
    ce_chunks: int = 0
    # Sliding-window (local) attention: each position attends its last
    # `attention_window` positions (self included) instead of the full
    # causal past — compute per token drops from O(L) to O(window) in
    # the flash kernels (dead blocks skipped), the standard local-
    # attention long-context trade.  None = full causal attention.
    # Composes with rope/GQA/remat/ce_chunks, the KV-cached decode, and
    # ring attention (global-position masking per hop).
    attention_window: int | None = None
    # z-loss (ST-MoE eq. 6): z_loss_coef * mean(logsumexp(logits)^2)
    # added to the TRAINING loss only.  Keeps the softmax normalizer
    # near 0 so bf16 logits stay in range over long runs — the standard
    # stability regularizer for large-vocab LMs.  Excluded from lm_nll
    # (eval perplexity stays a pure model-quality number).  Typical:
    # 1e-4.  Works on every head path, including chunked CE.
    z_loss_coef: float = 0.0
    # --- the EXTENDED block (any of the five below off its default) ---
    # Gated feed-forward (SwiGLU): (silu(h·w1) * (h·w3))·w2, three
    # matrices (w1 the gate, w3 the up projection, w2 down) instead of
    # gelu(h·w1)·w2.
    ffn_gated: bool = False
    # Untied output head: logits = hidden · head^T with its own
    # ``head [V, D]`` leaf instead of ``tok_emb``.
    tie_head: bool = True
    # A norm on each sublayer's OUTPUT as well as its input (four norms
    # a layer): x = x + rms(attn(rms(x))); x = x + rms(ffn(rms(x))).
    # The third placement, "only": on the output and NOT on the input
    # (two norms a layer): x = x + rms(attn(x)); x = x + rms(ffn(x)).
    post_norms: bool | str = False
    # The attention projections as MATRICES: q, k and v side by side in
    # one ``attn/wqkv [L, D, (heads + 2 kv_heads) * head_dim]`` (one
    # product a layer) and ``attn/wo [L, heads * head_dim, D]``, in
    # place of wq / wk / wv ``[L, D, heads, head_dim]`` and wo ``[L,
    # heads, head_dim, D]``.  On the TPU a [D, heads, head_dim] leaf is
    # tiled over (heads, head_dim) and a decode step re-lays it out
    # before every product: 3.6 ms of a 46.7 ms step and 1.2 GB of
    # temporaries at 48 layers of 16 heads x 128 (chip, PR 27).  A
    # layout, not a model: both give the same function of the same
    # numbers.  The sharding rules, LoRA and int8 weights know the
    # split layout only.
    fused_qkv: bool = False
    # Looped stack: the SAME ``n_layers`` layers' weights are applied
    # ``n_passes`` times, the final norm after every pass (the next
    # pass starts from it), an exit gate D -> 1 beside the head.  The
    # KV cache holds one plane per (pass, layer): n_passes * n_layers.
    # Served, every pass runs and the last pass's logits are the
    # model's (exit threshold 1); :func:`apply_passes` returns every
    # pass's logits and the exit distribution.
    n_passes: int = 1
    # --- the TYPED stack: a layer is a pair (attention kind,
    # feed-forward kind) read from two per-layer lists, and the keys
    # below say what the kinds mean.  ``apply`` and the serving path
    # (``generate``, ``ContinuousBatcher`` with ``hot_swap`` and
    # ``prefill_chunk``) run it; every other path rejects these keys by
    # name (:func:`reject_extended`).  The parameter tree groups the
    # layers by kind (``layers/<attention>.<ffn>/...``, each group
    # stacked on its own leading axis), and a run of consecutive layers
    # of one kind is one scan.  A stack whose lists name full attention
    # and the dense feed-forward everywhere is today's stack: the lists
    # are dropped (``__post_init__``) and it compiles today's programs.
    # ``layer_types``: "window" | "full" | "retention" | "latent" per
    # layer; a
    # window layer attends its last ``sliding_window`` positions (self
    # included) — unlike ``attention_window``, which windows the WHOLE
    # stack and makes the serving lanes roll.  Served, a window layer's
    # K/V plane is a ring of ``sliding_window`` slots beside the full
    # layers' ``max_len``-slot planes.  A retention layer is power
    # retention of degree 2 (``ops/retention.py``): no softmax, weights
    # ``(q . k / sqrt d)^2`` under a per-K/V-head gate ``attn/wg [D,
    # kv_heads]``, ``attn/bg``; served, its plane is a float32 STATE of
    # fixed size, whatever the position.
    layer_types: tuple | None = None
    sliding_window: int | None = None
    # ``ffn_types``: "dense" | "sparse" per layer.  A sparse layer is
    # the ROUTED feed-forward (:func:`moe_ffn`): a router over all
    # ``num_experts``, ``moe_top_k`` experts a token, no capacity and
    # no dropped token, computed for the experts HELD here —
    # ``moe_held`` (their ids; None = all) — each of width ``moe_d_ff``
    # and the dense form, plus ``moe_shared`` shared experts that every
    # token takes.  Its router: scores sigmoid(x·wg), the choice by
    # score + a selection bias, weights = the chosen scores over their
    # sum, times ``moe_route_scale``.
    ffn_types: tuple | None = None
    moe_held: tuple | None = None
    moe_d_ff: int | None = None
    moe_shared: int = 0
    moe_route_scale: float = 1.0
    # Kinds of layer that rotate q and k (None: every layer, where
    # ``rope``): a model may rotate in its window layers only.
    rope_layer_types: tuple | None = None
    # The width of a head where it is not d_model / n_heads (read it
    # as ``head_dim``); RMSNorm over each head of q and k before the
    # rotation (``attn/q_scale``, ``attn/k_scale`` [head_dim]); the
    # epsilon of every RMSNorm.
    d_head: int | None = None
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # A LATENT layer (``layer_types`` "latent": multi-head latent
    # attention): the queries through a low-rank pair ``attn/wq_a [D,
    # q_lora_rank]`` (an RMSNorm, ``q_a_scale``) and ``wq_b``, to
    # ``n_heads`` heads of ``qk_nope_head_dim + qk_rope_head_dim``; ONE
    # joint projection ``wkv_a [D, kv_lora_rank + qk_rope_head_dim]`` to
    # a latent (an RMSNorm, ``kv_a_scale``) and a rotary key that every
    # head shares; ``wkv_b [kv_lora_rank, heads * (qk_nope_head_dim +
    # v_head_dim)]`` rebuilds a head's keys and values from the latent.
    # The rotation is of the ``qk_rope_head_dim`` columns alone, scores
    # are over ``1 / sqrt(qk_nope_head_dim + qk_rope_head_dim)``.
    # Served, the layer's plane holds the latent and the rotated key of
    # a position — one row of ``latent_width`` values, no heads axis —
    # and a decode step attends it in the ABSORBED form (``wkv_b``
    # folded into the queries and the output).  Where the source pairs
    # the rotary columns ``(2i, 2i + 1)`` (``rope_interleave``), the
    # tree holds them de-interleaved (evens, then odds) and rotates
    # halves split as every other layer does — a layout of the
    # weights, no option: the scores are the same.  Such a stack's
    # layers are all latent (its feed-forwards may differ).
    q_lora_rank: int | None = None
    kv_lora_rank: int | None = None
    qk_nope_head_dim: int | None = None
    qk_rope_head_dim: int | None = None
    v_head_dim: int | None = None

    def __post_init__(self):
        # Lists arrive from JSON as lists; a config is a static jit
        # argument and has to hash.
        put = lambda k, v: object.__setattr__(self, k, v)
        for k in ("layer_types", "ffn_types", "moe_held",
                  "rope_layer_types"):
            v = getattr(self, k)
            if v is not None:
                put(k, tuple(v))
        if self.layer_types is None and self.ffn_types is None:
            return
        # One list given: the other names today's kind everywhere.
        if self.layer_types is None:
            put("layer_types", ("full",) * self.n_layers)
        if self.ffn_types is None:
            put("ffn_types", ("dense",) * self.n_layers)
        if (set(self.layer_types) == {"full"}
                and set(self.ffn_types) == {"dense"}):
            put("layer_types", None)
            put("ffn_types", None)

    @property
    def typed(self) -> bool:
        """Whether the layers differ in kind (the typed stack)."""
        return self.layer_types is not None

    @property
    def layer_kinds(self) -> tuple:
        """``(attention kind, feed-forward kind)`` of every layer."""
        return tuple(zip(self.layer_types, self.ffn_types))

    @property
    def layer_runs(self) -> tuple:
        """The typed stack as runs of consecutive layers of one kind:
        ``(group, first index within the group, layers)`` — one scan
        each over a slice of the group's stacked leaves."""
        runs, seen = [], {}
        for kind in self.layer_kinds:
            g = ".".join(kind)
            at = seen.get(g, 0)
            seen[g] = at + 1
            if runs and runs[-1][0] == g:
                runs[-1][2] += 1
            else:
                runs.append([g, at, 1])
        return tuple(tuple(r) for r in runs)

    @property
    def extended(self) -> bool:
        """Whether this config uses a switch above.  ``block_apply``
        (so ``apply`` and ``lm_loss``) and the cached decode body every
        plain call takes (``generate._chunk_in_place``) implement them;
        every other path — :func:`reject_extended` — knows the base
        block only and says so."""
        return self.n_passes != 1 or any(
            getattr(self, k) != base
            for k, base in {**_SWITCHES, **_TYPED_KEYS}.items())

    @property
    def kv_planes(self) -> int:
        """(k, v) planes of ``max_len`` slots in the decode cache: one
        per pass and layer; in a typed stack one per full layer."""
        if self.typed:
            return self.layer_types.count("full")
        return self.n_passes * self.n_layers

    @property
    def kv_ring_planes(self) -> int:
        """Ring planes of the decode cache: one per window layer of a
        typed stack."""
        return self.layer_types.count("window") if self.typed else 0

    @property
    def state_planes(self) -> int:
        """State planes of the decode cache: one per retention layer
        of a typed stack."""
        return self.layer_types.count("retention") if self.typed else 0

    @property
    def latent_planes(self) -> int:
        """Latent planes of the decode cache: one per latent layer of
        a typed stack."""
        return self.layer_types.count("latent") if self.typed else 0

    @property
    def latent_width(self) -> int:
        """Values a position holds in a latent plane: the latent and
        the shared rotary key, rounded up to whole lane tiles of 128
        (the slab's rows are what the kernels copy; an unpadded row of
        576 would be stored as 640 by the TPU's tiled layout anyway)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def rope_dim(self) -> int:
        """Columns the rotation turns: the head, or a latent stack's
        ``qk_rope_head_dim``."""
        return self.qk_rope_head_dim if self.latent_planes else self.head_dim

    @property
    def head_dim(self) -> int:
        if self.d_head is not None:
            return self.d_head
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def expert_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def experts_held(self) -> tuple:
        """Ids of the routed experts whose weights are here."""
        return (tuple(range(self.num_experts)) if self.moe_held is None
                else self.moe_held)

    @property
    def kv_heads(self) -> int:
        kv = self.n_kv_heads if self.n_kv_heads is not None else self.n_heads
        if not 1 <= kv <= self.n_heads or self.n_heads % kv:
            raise ValueError(
                f"n_kv_heads={kv} must divide n_heads={self.n_heads}")
        return kv


# The extended block's switches and their defaults (the base block).
_SWITCHES = {"ffn_gated": False, "tie_head": True, "post_norms": False,
             "fused_qkv": False}
# The typed stack's keys and theirs: a path that takes the switches
# above (the training loss) still rejects these by name.
_TYPED_KEYS = {"layer_types": None, "ffn_types": None,
               "sliding_window": None, "moe_held": None,
               "moe_d_ff": None, "moe_shared": 0, "moe_route_scale": 1.0,
               "rope_layer_types": None, "d_head": None, "qk_norm": False,
               "norm_eps": 1e-6, "q_lora_rank": None, "kv_lora_rank": None,
               "qk_nope_head_dim": None, "qk_rope_head_dim": None,
               "v_head_dim": None}
# A latent layer's shape keys: all of them, or none.
_LATENT_KEYS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim")


def reject_extended(cfg: "TransformerConfig", path: str,
                    allow: tuple = ()) -> None:
    """THE validation site for what the extended block does not run:
    every path that was not made to work with it (and tested) calls
    this with its own name and raises for an extended configuration.
    ``allow`` names the switches the path does take — what shares
    ``block_apply`` lacks only the pass loop, and a looped stack is
    never allowed (training the last pass alone would be a different
    objective under the model's name)."""
    on = [k for k, base in _SWITCHES.items()
          if getattr(cfg, k) != base and k not in allow]
    on += [k for k, base in _TYPED_KEYS.items() if getattr(cfg, k) != base]
    if cfg.n_passes > 1 or on:
        # The kinds no other path knows, by name.
        for planes, kind in ((cfg.state_planes, "retention"),
                             (cfg.latent_planes, "latent")):
            if planes:
                on[on.index("layer_types")] = f"layer_types: '{kind}'"
        what = (f"a looped stack (n_passes={cfg.n_passes})"
                if cfg.n_passes > 1 else
                f"the extended block ({' / '.join(on)})")
        raise ValueError(
            f"{path} does not support {what}: it runs through "
            "apply / apply_passes, generate and ContinuousBatcher "
            "(monolithic lanes, chunked prefill) only")


_REMAT_POLICIES = {
    None: None,
    "dots": "checkpoint_dots",
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
}


def _validate_remat_policy(cfg: "TransformerConfig",
                           require_remat: bool = True) -> None:
    """Single enforcement point for the remat knobs.

    ``require_remat=True`` (init_params) also rejects a policy with
    remat=False — a config *built* that way is a mistake.  Wrap time
    passes False: ``dataclasses.replace(cfg, remat=False)`` on a
    training config is the natural way to run eval/inference, and the
    leftover policy is simply inert there.
    """
    if cfg.remat_policy is None:
        return
    if cfg.remat_policy not in _REMAT_POLICIES:
        raise ValueError(
            f"unknown remat_policy {cfg.remat_policy!r}; "
            f"known: {sorted(k for k in _REMAT_POLICIES if k)} or None")
    if require_remat and not cfg.remat:
        raise ValueError(
            "remat_policy is set but remat=False — the policy only "
            "selects what a rematerialized backward may save; enable "
            "remat=True (or drop the policy)")


def _remat_block(cfg: "TransformerConfig", moe_dense_routing: bool = False):
    """``block_apply`` wrapped per cfg.remat / cfg.remat_policy.

    ``moe_dense_routing`` is bound OUTSIDE the checkpoint wrapper (a
    plain-Python partial, not a traced argument): a bool passed through
    ``jax.checkpoint`` would become a tracer and break the block's
    Python-level routing branch.
    """
    # Unknown names are rejected even with remat=False (typos must not
    # pass silently); only the remat-required pairing check is relaxed
    # (an inert leftover policy is fine at eval time).
    _validate_remat_policy(cfg, require_remat=False)
    fn = (functools.partial(block_apply, moe_dense_routing=True)
          if moe_dense_routing else block_apply)
    if not cfg.remat:
        return fn
    name = _REMAT_POLICIES[cfg.remat_policy]
    policy = getattr(jax.checkpoint_policies, name) if name else None
    return jax.checkpoint(fn, static_argnums=(2, 3), policy=policy)


def _dense_init(rng, shape, fan_in):
    return jax.random.normal(rng, shape, jnp.float32) / math.sqrt(fan_in)


def init_params(rng, cfg: TransformerConfig):
    """Build the parameter pytree.  Per-layer params are stacked on a
    leading [n_layers] axis (scan/pipeline-friendly: one tree, L-major).
    """
    if not 0.0 <= cfg.dropout < 1.0:
        raise ValueError(f"dropout must be in [0, 1), got {cfg.dropout}")
    if cfg.ce_chunks < 0:
        raise ValueError(f"ce_chunks must be >= 0, got {cfg.ce_chunks}")
    if cfg.z_loss_coef < 0:
        raise ValueError(
            f"z_loss_coef must be >= 0, got {cfg.z_loss_coef} (a negative "
            "coefficient would silently disable the regularizer)")
    if cfg.attention_window is not None and cfg.attention_window < 1:
        raise ValueError(
            f"attention_window must be >= 1, got {cfg.attention_window}")
    if cfg.num_experts and not 1 <= cfg.moe_top_k <= cfg.num_experts:
        raise ValueError(
            f"moe_top_k={cfg.moe_top_k} must be in [1, num_experts="
            f"{cfg.num_experts}]")
    if cfg.n_passes < 1:
        raise ValueError(f"n_passes must be >= 1, got {cfg.n_passes}")
    if cfg.post_norms not in (False, True, "only"):
        raise ValueError(
            f"post_norms must be False, True or 'only', got "
            f"{cfg.post_norms!r}")
    if cfg.typed:
        _validate_typed(cfg)
    elif any(getattr(cfg, k) is not None for k in _LATENT_KEYS):
        raise ValueError(
            f"{_LATENT_KEYS} shape a latent layer: name the layers "
            "(layer_types: 'latent')")
    elif cfg.num_experts:
        reject_extended(cfg, "a MoE feed-forward (num_experts > 0) "
                        "without ffn_types (the capacity dispatch)")
    _validate_remat_policy(cfg)
    keys = jax.random.split(rng, 12)
    d, f, h, hd = cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.head_dim
    kv = cfg.kv_heads

    def group(gkey, L, sparse=None, retention=False, latent=False):
        """``L`` layers of one kind, stacked on a leading axis; the
        whole stack where the layers do not differ (``sparse`` None:
        the old capacity-dispatch experts where ``num_experts``)."""
        gk = keys if gkey is None else jax.random.split(gkey, 12)

        def stack(key, shape, fan_in):
            return _dense_init(key, (L, *shape), fan_in)

        def latent_leaves():
            rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
            dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
            # The two inner norms' scales are drawn (1 +- 0.25; a
            # trained model's are learnt), so that leaving a norm out
            # of a computation shows: at 1 it would hardly, the
            # projections of a normed stream being near unit size.
            scale = lambda key, n: 1.0 + 0.25 * jax.random.normal(
                key, (L, n), jnp.float32)
            return {
                "wq_a": stack(gk[0], (d, rq), d),
                "q_a_scale": scale(jax.random.fold_in(gk[0], 1), rq),
                "wq_b": stack(gk[1], (rq, h * (dn + dr)), rq),
                "wkv_a": stack(gk[2], (d, rkv + dr), d),
                "kv_a_scale": scale(jax.random.fold_in(gk[2], 1), rkv),
                "wkv_b": stack(jax.random.fold_in(gk[2], 2),
                               (rkv, h * (dn + dv)), rkv),
                "wo": stack(gk[3], (h * dv, d), h * dv)}

        layers = {"attn": latent_leaves() if latent else {
            "wq": stack(gk[0], (d, h, hd), d),
            "wk": stack(gk[1], (d, kv, hd), d),
            "wv": stack(gk[2], (d, kv, hd), d),
            "wo": stack(gk[3], (h, hd, d), h * hd),
        }}
        if cfg.post_norms != "only":
            layers["ln1_scale"] = jnp.ones((L, d))
            layers["ln2_scale"] = jnp.ones((L, d))
        if cfg.fused_qkv and not latent:  # the same draws, as matrices
            a = layers["attn"]
            layers["attn"] = {
                "wqkv": jnp.concatenate(
                    [a[w].reshape(L, d, -1) for w in ("wq", "wk", "wv")],
                    axis=-1),
                "wo": a["wo"].reshape(L, -1, d)}
        if cfg.qk_norm:
            layers["attn"]["q_scale"] = jnp.ones((L, hd))
            layers["attn"]["k_scale"] = jnp.ones((L, hd))
        if retention:
            # The gate, one scalar a K/V head.  Its bias is drawn
            # around 6: g = sigmoid(6 +- 2) keeps a state for tens to
            # thousands of positions (a trained gate is learnt; at 0 a
            # state would be gone within a few positions and nothing
            # of the mechanism would show).
            layers["attn"]["wg"] = stack(jax.random.fold_in(gk[3], 1),
                                         (d, kv), d)
            layers["attn"]["bg"] = 6.0 + jax.random.normal(
                jax.random.fold_in(gk[3], 2), (L, kv), jnp.float32)
        if sparse:
            ef, held = cfg.expert_ff, len(cfg.experts_held)
            # Every expert a draw of its own, by its ID: a share holds
            # the numbers the whole model has for its experts.
            def experts(key, shape, fan_in):
                return jax.vmap(
                    lambda e: _dense_init(jax.random.fold_in(key, e),
                                          (L, *shape), fan_in),
                    out_axes=1)(jnp.asarray(cfg.experts_held))
            layers["moe"] = {
                "wg": stack(gk[4], (d, cfg.num_experts), d),
                # Gate and up side by side: one grouped product.
                "w13": experts(gk[5], (d, 2 * ef), d),
                "w2": experts(gk[6], (ef, d), ef),
            }
            assert layers["moe"]["w13"].shape[1] == held
            # The selection bias: drawn, so that leaving it out of a
            # computation shows.
            layers["moe"]["bias"] = 0.02 * jax.random.normal(
                gk[9], (L, cfg.num_experts), jnp.float32)
            if cfg.moe_shared:
                sf = cfg.moe_shared * ef
                layers["shared"] = {
                    "w1": stack(gk[7], (d, sf), d),
                    "w3": stack(jax.random.fold_in(gk[7], 1), (d, sf), d),
                    "w2": stack(gk[8], (sf, d), sf)}
        elif sparse is None and cfg.num_experts:
            layers["moe"] = {
                "wg": stack(gk[4], (d, cfg.num_experts), d),
                "w1": stack(gk[5], (cfg.num_experts, d, f), d),
                "w2": stack(gk[6], (cfg.num_experts, f, d), f),
            }
        else:
            layers["ffn"] = {
                "w1": stack(gk[7], (d, f), d),
                "w2": stack(gk[8], (f, d), f),
            }
            if cfg.ffn_gated:
                layers["ffn"]["w3"] = stack(jax.random.fold_in(gk[7], 1),
                                            (d, f), d)
        if cfg.post_norms:
            layers["ln1_post_scale"] = jnp.ones((L, d))
            layers["ln2_post_scale"] = jnp.ones((L, d))
        return layers

    if cfg.typed:
        kinds = cfg.layer_kinds
        layers = {
            ".".join(kind): group(
                jax.random.fold_in(keys[0], i), kinds.count(kind),
                sparse=kind[1] == "sparse",
                retention=kind[0] == "retention",
                latent=kind[0] == "latent")
            for i, kind in enumerate(dict.fromkeys(kinds))}
    else:
        layers = group(None, cfg.n_layers)
    params = {
        # Tied embedding/unembedding: std 1/sqrt(d) keeps initial logits
        # O(1) so the initial LM loss sits at ~ln(vocab).
        "tok_emb": _dense_init(keys[9], (cfg.vocab_size, d), d),
        "ln_f_scale": jnp.ones((d,)),
        "layers": layers,
    }
    if cfg.rope:
        if hd % 2:
            raise ValueError(
                f"rope needs an even head_dim, got {hd} "
                f"(d_model={d}, n_heads={h})")
    else:
        params["pos_emb"] = _dense_init(keys[10], (cfg.max_len, d), 1.0) * 0.02
    if not cfg.tie_head:
        params["head"] = _dense_init(keys[11], (cfg.vocab_size, d), d)
    if cfg.n_passes > 1:
        # Exit gate D -> 1, evaluated after every pass's final norm.
        params["exit_w"] = _dense_init(jax.random.fold_in(keys[11], 1),
                                       (d,), d)
        params["exit_b"] = jnp.zeros(())
    return params


def _validate_typed(cfg: "TransformerConfig") -> None:
    """What a typed stack's keys have to say together."""
    n = cfg.n_layers
    for name, kinds in (("layer_types", ("window", "full", "retention",
                                         "latent")),
                        ("ffn_types", ("dense", "sparse"))):
        got = getattr(cfg, name)
        if len(got) != n or set(got) - set(kinds):
            raise ValueError(
                f"{name} must name one of {kinds} for each of the "
                f"{n} layers, got {got}")
    if cfg.n_passes != 1 or cfg.attention_window is not None:
        raise ValueError(
            "a typed stack (layer_types / ffn_types) is neither looped "
            "(n_passes) nor windowed as a whole (attention_window: its "
            "window layers take sliding_window)")
    if "window" in cfg.layer_types:
        w = cfg.sliding_window
        if w is None or w < 1 or cfg.max_len % w:
            raise ValueError(
                f"window layers need a sliding_window >= 1 that divides "
                f"max_len={cfg.max_len} (a window layer's cache plane is "
                f"a ring of that many slots), got {w}")
    if "retention" in cfg.layer_types and (
            cfg.head_dim % 2 or cfg.n_heads // cfg.kv_heads > 5):
        raise ValueError(
            "retention layers need an even head_dim and at most 5 query "
            f"heads a K/V head, got head_dim={cfg.head_dim}, n_heads="
            f"{cfg.n_heads}, n_kv_heads={cfg.kv_heads}")
    given = [k for k in _LATENT_KEYS if getattr(cfg, k) is not None]
    if "latent" in cfg.layer_types:
        if (set(cfg.layer_types) != {"latent"} or len(given) != len(
                _LATENT_KEYS) or not cfg.rope or cfg.qk_rope_head_dim % 2
                or cfg.post_norms or cfg.qk_norm
                or cfg.rope_layer_types is not None):
            raise ValueError(
                "latent layers (layer_types: 'latent') make a stack of "
                f"their own, with every one of {_LATENT_KEYS} given, "
                "rope=True over an even qk_rope_head_dim, and no "
                "post_norms, qk_norm or rope_layer_types, got "
                f"layer_types={cfg.layer_types}, "
                + ", ".join(f"{k}={getattr(cfg, k)}" for k in _LATENT_KEYS))
    elif given:
        raise ValueError(
            f"{given} shape a latent layer (layer_types: 'latent'); the "
            "stack has none")
    if "sparse" in cfg.ffn_types:
        held = cfg.experts_held
        if not (1 <= cfg.moe_top_k <= cfg.num_experts) or not held or (
                len(set(held)) != len(held)
                or not all(0 <= e < cfg.num_experts for e in held)):
            raise ValueError(
                f"sparse layers need 1 <= moe_top_k <= num_experts and "
                f"moe_held distinct ids below num_experts, got top_k="
                f"{cfg.moe_top_k}, num_experts={cfg.num_experts}, "
                f"moe_held={cfg.moe_held}")
        if not cfg.ffn_gated:
            raise ValueError(
                "sparse layers hold gated experts: ffn_gated=True")
    if cfg.rope_layer_types is not None and not cfg.rope:
        raise ValueError("rope_layer_types needs rope=True")


def layer_rotates(cfg: "TransformerConfig", kind) -> bool:
    """Whether a layer of ``kind`` (None: an untyped stack's) rotates
    its q and k."""
    return cfg.rope and (kind is None or cfg.rope_layer_types is None
                         or kind[0] in cfg.rope_layer_types)


def layer_at(layers, cfg: "TransformerConfig", i: int):
    """``(layer i's parameters, its kind)`` out of the stacked tree;
    the kind is None where the layers do not differ."""
    if not cfg.typed:
        return jax.tree.map(lambda a: a[i], layers), None
    kind = cfg.layer_kinds[i]
    j = cfg.layer_kinds[:i].count(kind)
    return jax.tree.map(lambda a: a[j], layers[".".join(kind)]), kind


def tp_rules():
    """Megatron-layout PartitionSpecs over the ``model`` axis.

    Keyed on tree_shardings key-paths (leading [L] stack axis first for
    per-layer params).  Column-parallel in, row-parallel out: the only
    collective per block is one psum pair, inserted by XLA.
    """
    return [
        (r"attn/w[qkv]$", P(None, None, "model", None)),
        (r"attn/wo$", P(None, "model", None, None)),
        (r"ffn/w1$", P(None, None, "model")),
        (r"ffn/w2$", P(None, "model", None)),
        # MoE: experts over 'expert', their matmuls over 'model'.
        (r"moe/wg$", P()),
        (r"moe/w1$", P(None, "expert", None, "model")),
        (r"moe/w2$", P(None, "expert", "model", None)),
        (r"tok_emb$", P(None, "model")),
        (r"pos_emb$", P(None, "model")),
    ]


def _resolve_attention_fn(cfg: "TransformerConfig", attention_fn,
                          segment_ids=None):
    """ONE guard for the window/attention_fn pairing (apply_hidden and
    apply_pipelined share it).

    No fn: build the default windowed flash lambda (closing over
    ``segment_ids`` for packed sequences).  Custom fn: its
    ``handles_window`` attribute (set by make_ring_attention; set it
    yourself on hand-rolled fns) must equal ``cfg.attention_window`` in
    BOTH directions — a band applied on one side only would silently
    diverge training from the KV-cached decode, which follows cfg.
    """
    if cfg.typed:
        if attention_fn is not None or segment_ids is not None:
            raise ValueError(
                "a typed stack (layer_types / ffn_types) runs its own "
                "attention by layer kind: no attention_fn, no "
                "segment_ids")
        by_window = lambda window: lambda q, k, v: flash_attention(
            q, k, v, True, window=window)
        # A retention layer's attention needs the layer's gate, which
        # no ``fn(q, k, v)`` takes: ``_attention_block`` runs it.
        # (Nor a latent layer's, whose heads are rebuilt from a latent:
        # ``latent_attention``.)
        return {"window": by_window(cfg.sliding_window),
                "full": by_window(None), "retention": None, "latent": None}
    if attention_fn is None:
        return lambda q, k, v: flash_attention(
            q, k, v, True, window=cfg.attention_window,
            segment_ids=segment_ids)
    if segment_ids is not None:
        if getattr(attention_fn, "handles_segments", False):
            # make_ring_attention sets the attribute: the fn takes the
            # per-call segments itself (rotating the KV-side shard).
            base_fn = attention_fn
            attention_fn = lambda q, k, v: base_fn(
                q, k, v, segment_ids=segment_ids)
            attention_fn.handles_window = getattr(base_fn,
                                                  "handles_window", None)
        else:
            raise ValueError(
                "segment_ids with this custom attention_fn is not "
                "supported: the packed-document mask must be applied "
                "inside the attention implementation (set "
                "fn.handles_segments = True and accept a segment_ids "
                "kwarg, as make_ring_attention does) — or drop the "
                "custom fn / unpack the batch")
    fn_window = getattr(attention_fn, "handles_window", None)
    if fn_window != cfg.attention_window:
        raise ValueError(
            f"attention window mismatch: cfg.attention_window="
            f"{cfg.attention_window} but the supplied attention_fn "
            f"implements window={fn_window} (fn.handles_window). Build "
            "the fn with the same window (make_ring_attention(..., "
            "window=...) sets the attribute; set it yourself on custom "
            "fns) or align the config — a one-sided band silently "
            "diverges training from the KV-cached decode")
    return attention_fn


def _check_len(s: int, cfg: TransformerConfig) -> None:
    # RoPE has no trained position table: any training length is valid
    # (max_len only sizes the decode KV cache, models/generate.py).
    if not cfg.rope and s > cfg.max_len:
        raise ValueError(
            f"sequence length {s} exceeds max_len={cfg.max_len} (note "
            "lm_loss feeds tokens[:, :-1], so token arrays may carry "
            "max_len + 1 positions)")


def _dropout(x, rate: float, key):
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0).astype(x.dtype)


# Sublayer scopes.  Every operation of a forward, decode or training
# program sits in one ``jax.named_scope`` of this small vocabulary
# (here and in models/generate.py), so that a device profile names the
# work by what the MODEL calls it and not by what the compiler fused it
# into; backward and rematerialised operations inherit the path.  A
# scope is metadata only: it changes no compiled program.
#   embed      token/position embedding, rotary angles
#   norm       every RMSNorm
#   attn_proj  the q/k/v/out products and the rotary rotation
#   attn       scores, softmax, values (the flash kernels sit here)
#   kv_slab    cutting a layer's K/V out of the cache slab, and putting
#              it back (decode and chunked prefill)
#   mlp        the feed-forward (or MoE) block; inside it, in a sparse
#              layer of a typed stack: moe_route (scores, choice,
#              weights), moe_experts (the held experts' grouped
#              products, sorted in and gathered back), moe_shared
#   head       unembedding (tied or not) and cross-entropy
#   loop_exit  what sits between two passes of a looped stack: the
#              final norm after every pass and the exit gate
SCOPES = ("embed", "norm", "attn_proj", "attn", "kv_slab", "mlp", "head",
          "loop_exit")
MOE_SCOPES = ("moe_route", "moe_experts", "moe_shared")
# Inside ``attn``, in a retention layer: ret_gate (the gate's product
# and log-sigmoid), ret_state (the state's decay-and-add and, in a
# decode step, its query: the kernel ``ret_state_step`` sits here),
# ret_chunk (a chunk's own pairs and its query of the state before it;
# on the TPU the kernel ``ret_chunk_fwd``, the new state included).
RET_SCOPES = ("ret_gate", "ret_state", "ret_chunk")
# In a latent layer: under ``attn_proj``, mla_q (the low-rank query
# pair, its norm, the rotation) and mla_kv (the joint projection, the
# latent's norm, the shared key's rotation); under ``attn``, mla_absorb
# (``wkv_b`` folded into the queries and, after the attention, into its
# output — the served path; the kernel ``mla_decode_fwd`` sits in
# ``attn`` beside it).
MLA_SCOPES = ("mla_q", "mla_kv", "mla_absorb")


def _rms_norm(x, scale, eps=1e-6, scope="norm"):
    with jax.named_scope(scope):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def rope_angles(positions, head_dim: int, theta: float):
    """Rotation angles ``[..., head_dim/2]`` for integer positions."""
    half = head_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    return positions.astype(jnp.float32)[..., None] * inv


def rope_rotate(x, ang):
    """Half-split rotary rotation of the last dim of ``x`` by ``ang``
    (broadcastable to ``x[..., :half]``); f32 math, input dtype out."""
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(x.dtype)


def _attention_block(lp, x, attention_fn, rope_ang=None, kv_groups=1,
                     return_kv=False, eps=1e-6):
    with jax.named_scope("attn_proj"):
        q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
        k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
        if "q_scale" in lp:  # qk_norm: over each head, before rotating
            q = _rms_norm(q, lp["q_scale"], eps)
            k = _rms_norm(k, lp["k_scale"], eps)
        if rope_ang is not None:
            q, k = rope_rotate(q, rope_ang), rope_rotate(k, rope_ang)
    kv = (k, v)  # post-rope, pre-GQA-expansion: the decode cache layout
    with jax.named_scope("attn"):
        if "wg" in lp:  # a retention layer: the attention form, plain
            with jax.named_scope("ret_gate"):
                logg = log_gate(x, lp["wg"], lp["bg"])
            out = retention_attention(q, k, v, logg).astype(x.dtype)
        elif kv_groups > 1:  # GQA: expand shared K/V heads for the kernel
            out = attention_fn(q, jnp.repeat(k, kv_groups, axis=2),
                               jnp.repeat(v, kv_groups, axis=2))
        else:
            out = attention_fn(q, k, v)
    with jax.named_scope("attn_proj"):
        out = jnp.einsum("bshk,hkd->bsd", out, lp["wo"])
    return (out, kv) if return_kv else out


def latent_qkv(attn, h, rope_ang, cfg: TransformerConfig):
    """A latent layer's projections of the normed stream ``h [B, T,
    D]``: ``(q_nope [B, T, H, nope], q_pe [B, T, H, rope], c [B, T,
    kv_lora_rank], k_pe [B, T, rope])`` — the queries through their
    low-rank pair, the latent after its norm, the shared rotary key;
    ``q_pe`` and ``k_pe`` rotated by ``rope_ang [B | 1, T, 1,
    rope / 2]``, halves split: the tree holds the rotary columns of
    ``wq_b`` and ``wkv_a`` DE-INTERLEAVED (evens, then odds) where the
    source pairs ``(2i, 2i + 1)`` — a layout of the weights: the
    scores are the same.  ONE definition for the expanded form
    (:func:`latent_attention`) and the served, absorbed one
    (``generate._chunk_in_place``)."""
    eps = cfg.norm_eps
    dn, dr, rkv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla_q"):
        cq = _rms_norm(jnp.einsum("btd,dr->btr", h, attn["wq_a"]),
                       attn["q_a_scale"], eps)
        q = jnp.einsum("btr,rk->btk", cq, attn["wq_b"])
        q = q.reshape(q.shape[:2] + (cfg.n_heads, dn + dr))
        q_nope, q_pe = q[..., :dn], rope_rotate(q[..., dn:], rope_ang)
    with jax.named_scope("mla_kv"):
        ckv = jnp.einsum("btd,dr->btr", h, attn["wkv_a"])
        c = _rms_norm(ckv[..., :rkv], attn["kv_a_scale"], eps)
        k_pe = rope_rotate(ckv[..., rkv:], rope_ang[:, :, 0])
    return q_nope, q_pe, c, k_pe


def latent_kv_b(attn, cfg: TransformerConfig):
    """``wkv_b`` with the heads split out: ``(keys' [kv_lora_rank, H,
    nope], values' [kv_lora_rank, H, v])``."""
    w = attn["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def latent_attention(attn, h, rope_ang, cfg: TransformerConfig):
    """A latent layer's attention over ``h [B, T, D]`` in the EXPANDED
    form, no cache: every head's keys and values rebuilt from the
    latent, the shared rotary key beside each head's, causal softmax
    in float32 over the materialised scores (the no-cache path; served,
    the layer keeps the latent and attends it absorbed).  ``[B, T, H *
    v_head_dim]`` before the output projection."""
    with jax.named_scope("attn_proj"):
        q_nope, q_pe, c, k_pe = latent_qkv(attn, h, rope_ang, cfg)
        wk, wv = latent_kv_b(attn, cfg)
        with jax.named_scope("mla_kv"):
            k_nope = jnp.einsum("bsr,rhn->bshn", c, wk)
            v = jnp.einsum("bsr,rhv->bshv", c, wv)
    with jax.named_scope("attn"):
        f32 = dict(preferred_element_type=jnp.float32)
        score = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope, **f32)
                 + jnp.einsum("bthr,bsr->bhts", q_pe, k_pe, **f32)
                 ) / math.sqrt(q_nope.shape[-1] + q_pe.shape[-1])
        t = h.shape[1]
        score = jnp.where(jnp.tril(jnp.ones((t, t), bool)), score, -1e30)
        out = jnp.einsum("bhts,bshv->bthv",
                         jax.nn.softmax(score, axis=-1).astype(v.dtype), v)
    return out.reshape(out.shape[:2] + (-1,))


def _moe_gates(probs, cfg: TransformerConfig):
    """Top-k expert choice shared by every routing path.

    Returns ``(gates [..., k], expert [..., k])``: k=1 keeps the raw
    top-1 probability as the combine weight (Switch semantics — the
    router gradient flows through the gate magnitude); k>1 renormalizes
    the top-k probabilities over the selected experts (GShard/Mixtral).
    ONE definition so capacity, dense, and decode routing cannot drift.
    """
    gates, expert = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_top_k > 1:
        gates = gates / gates.sum(axis=-1, keepdims=True)
    return gates, expert


def _moe_block(lp, x, cfg: TransformerConfig):
    """Top-k MoE with capacity dropping (Switch at k=1, GShard at k=2).

    Tokens flatten to [N, D]; the dispatch/combine einsums carry the
    expert axis, which the EP sharding rules place on the mesh
    ``expert`` axis — XLA emits the all-to-alls.  Dropped assignments
    contribute 0 (the residual connection keeps the token's stream;
    with k>1 a token's other choice may still land).  First choices
    take capacity priority over second choices (choice-major cumsum) —
    GShard's sequential assignment.  Returns (out, aux_loss).
    """
    b, s, d = x.shape
    n = b * s
    e = cfg.num_experts
    k_sel = cfg.moe_top_k
    # Capacity per expert scales with k so capacity_factor keeps
    # meaning "slack per assignment" (t5x convention).
    cap = max(1, int(cfg.capacity_factor * k_sel * n / e))
    flat = x.reshape(n, d)

    router = jnp.einsum("nd,de->ne", flat.astype(jnp.float32), lp["wg"])
    probs = jax.nn.softmax(router, axis=-1)
    gates, expert = _moe_gates(probs, cfg)          # [N, k] each
    one_hot = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # [N, k, E]

    # Load-balancing aux loss (Switch Transformer eq. 4) on FIRST
    # choices — reduces exactly to Switch at k=1, and first-choice
    # density is the balance that matters at any k.
    density = one_hot[:, 0].mean(axis=0)
    density_proxy = probs.mean(axis=0)
    aux = jnp.sum(density * density_proxy) * e * cfg.aux_loss_coef

    # Choice-major flattening: all first choices claim slots before any
    # second choice competes.
    oh_cm = one_hot.transpose(1, 0, 2).reshape(k_sel * n, e)
    pos = jnp.cumsum(oh_cm, axis=0) * oh_cm  # 1-based slot, [kN, E]
    keep = (pos <= cap).astype(jnp.float32) * oh_cm
    slot_oh = jax.nn.one_hot((pos - 1.0).astype(jnp.int32), cap,
                             dtype=jnp.float32) * keep[..., None]  # [kN,E,C]
    slot_k = slot_oh.reshape(k_sel, n, e, cap)

    # Dispatch sums over choices: a token picked by both its choices
    # (different experts — top_k indices are distinct) lands in both.
    xe = jnp.einsum("knec,nd->ecd", slot_k, flat.astype(jnp.float32))
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, lp["w1"]))
    ye = jnp.einsum("ecf,efd->ecd", h, lp["w2"])
    # Combine weights ride the slot one-hots: choice k of token n
    # contributes gates[n, k] iff its assignment survived capacity.
    comb = slot_k * gates.T.reshape(k_sel, n)[:, :, None, None]
    out = jnp.einsum("ecd,knec->nd", ye, comb)
    return out.astype(x.dtype).reshape(b, s, d), aux


def _moe_dense_block(lp, x, cfg: TransformerConfig):
    """Capacity-FREE top-k MoE over [B, S, D] — the batched twin of
    _decode_step's per-token branch (models/generate.py): every expert
    runs on every token (E x compute) and the router's picks are
    gathered.  Used by generate.prefill so prefilled and sequential
    prompt processing match exactly; training keeps :func:`_moe_block`
    (capacity dispatch).  Unselected experts are zero-masked BEFORE the
    combine so a non-finite value in an unpicked expert cannot poison
    the token (0 * inf is NaN; where() is not).
    """
    dtype = x.dtype
    router = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), lp["wg"])
    probs = jax.nn.softmax(router, axis=-1)
    gates, expert = _moe_gates(probs, cfg)               # [B, S, k]
    # Per-expert combined weight: top_k indices are distinct, so this
    # sums each selected expert's gate into its slot.
    sel = jnp.einsum("bske,bsk->bse",
                     jax.nn.one_hot(expert, cfg.num_experts,
                                    dtype=jnp.float32), gates)
    h1 = jax.nn.gelu(jnp.einsum("bsd,edf->bsef", x,
                                lp["w1"].astype(dtype)))
    y_all = jnp.einsum("bsef,efd->bsed", h1, lp["w2"].astype(dtype))
    y_all = jnp.where(sel[..., None] > 0, y_all, 0.0)
    return jnp.einsum("bsed,bse->bsd", y_all, sel.astype(y_all.dtype)
                      ).astype(dtype)


def moe_route(moe, h, cfg: TransformerConfig):
    """The router of a sparse layer over ``h [N, D]``: ``(expert [N, k]
    ids among all ``num_experts``, weight [N, k] float32)``.  Float32
    throughout: a choice is a comparison of near-equal scores."""
    with jax.named_scope("moe_route"):
        score = jax.nn.sigmoid(jnp.einsum(
            "nd,de->ne", h.astype(jnp.float32),
            moe["wg"].astype(jnp.float32)))
        # The bias chooses; the weights are of the raw scores.
        _, expert = jax.lax.top_k(score + moe["bias"].astype(jnp.float32),
                                  cfg.moe_top_k)
        picked = jnp.take_along_axis(score, expert, axis=-1)
        return expert, (cfg.moe_route_scale * picked
                        / picked.sum(axis=-1, keepdims=True))


def moe_held_experts(moe, h, expert, gates, cfg: TransformerConfig,
                     layer=None):
    """The HELD experts' part of a sparse layer for ``h [N, D]`` routed
    to ``expert [N, k]`` with ``gates [N, k]``: ``(out [N, D] float32,
    local [N, k])`` — ``local`` is an assignment's index among the held
    experts, or their number where its expert is not here (what absent
    experts would add is left out).

    One path for a prefill chunk and a decode step: the N·k
    assignments are sorted by held expert (those of absent experts
    last) into rows grouped by expert, one grouped product a
    projection over the held experts' stacked weights
    (``ops.grouped.grouped_matmul``: on the TPU a kernel that walks
    only the row tiles the groups fill), and each token gathers its k
    rows back and sums them under its gates.  No capacity: the row
    count is the assignments', so no token is dropped whatever the
    routing.

    ``layer`` (traced int32): ``moe["w13"]`` and ``moe["w2"]`` are a
    GROUP's stacked leaves ``[layers, held, ...]`` and this is layer
    ``layer`` of it — the products then run over all ``layers * held``
    groups with every other layer's empty.  A layer's experts cut out
    of the stack inside a scan would be copied before a grouped
    product could read them (1.2 GB a layer at the benchmark's sizes:
    AOT, PR 31); the whole stack is handed over as it lies."""
    with jax.named_scope("moe_experts"):
        n, k = expert.shape
        held = cfg.experts_held
        e = len(held)
        table = np.full((cfg.num_experts,), e, np.int32)
        table[list(held)] = np.arange(e, dtype=np.int32)
        local = jnp.asarray(table)[expert]                    # [N, k]
        key = local.reshape(-1)
        order = jnp.argsort(key)
        sizes = jnp.sum(key[:, None] == jnp.arange(e)[None, :], axis=0,
                        dtype=jnp.int32)
        # Every group starts on a row tile of the kernel, so a group of
        # up to a tile of rows is ONE visit of its expert's weights
        # wherever it lies: packed end to end, a group that straddles a
        # tile boundary is read twice, and how many do follows the
        # routing (a 512-token chunk's ~512 held rows made 19 or 20
        # visits by the seed's draw: serve_tok_s 1.8 % apart in two
        # clusters; chip, PR 31).  The row count stays a static bound:
        # all N*k assignments and a tile of slack a group.
        # A group WITHOUT rows keeps one tile (of zero rows): every held
        # expert's weights are read in every product, so the product's
        # time follows the shapes and not the routing.  Skipped, an
        # expert no token reached made a step faster by its 75 MB, and
        # how many are reached is the router's skew: under seeded random
        # weights one layer's products took 0.71 of another's in one
        # run, and the same requests ran 0.9 to 5.7 % faster by the
        # seed (6,979-7,282 tokens/s over 12 seeds; 6,865-6,934 with
        # the tile kept; chip, PR 31) — where a share of a deployment
        # sees its experts reached by every chip's tokens.
        tile = GROUP_TILE
        padded = jnp.maximum(-(-sizes // tile), 1) * tile
        first = jnp.cumsum(sizes) - sizes          # in the sorted order
        base = jnp.cumsum(padded) - padded         # in the padded rows
        n_rows = -(-n * k // tile) * tile + e * tile
        row = jnp.arange(n_rows)
        group = jnp.minimum(jnp.searchsorted(base + padded, row,
                                             side="right"), e - 1)
        within = row - base[group]
        live = within < sizes[group]
        src = order[jnp.clip(first[group] + within, 0, n * k - 1)] // k
        rows = jnp.where(live[:, None], h[src], 0)
        w13, w2 = moe["w13"], moe["w2"]
        if layer is not None:
            padded = jax.lax.dynamic_update_slice(
                jnp.zeros((w13.shape[0] * e,), jnp.int32), padded,
                (layer * e,))
            w13, w2 = (a.reshape((-1,) + a.shape[2:]) for a in (w13, w2))
        up = grouped_matmul(rows, w13.astype(h.dtype), padded)
        f = up.shape[-1] // 2
        y = grouped_matmul(jax.nn.silu(up[:, :f]) * up[:, f:],
                           w2.astype(h.dtype), padded)
        # Each assignment's row back (an absent expert's: any row,
        # selected away), summed over a token's k under its gates.
        slot = jnp.minimum(key, e - 1)
        at = base[slot] + jnp.argsort(order) - first[slot]
        y = jnp.where((key < e)[:, None], y[at].astype(jnp.float32), 0.0)
        out = (y * gates.reshape(-1)[:, None]).reshape(n, k, -1).sum(axis=1)
        return out, local


def moe_ffn(lp, h, cfg: TransformerConfig, with_routes: bool = False,
            stacked=None):
    """The routed feed-forward of a typed stack's sparse layer over
    ``h [..., D]``: the held experts' part for the tokens routed to
    them plus the shared expert, in ``h``'s dtype.  ``with_routes``:
    also ``local [..., k]`` of :func:`moe_held_experts` (the serving
    engine's counters).  ``stacked = (w13, w2, layer)``: the experts'
    weights as their group's whole stack and this layer's index in it
    (``lp["moe"]`` then holds the router alone)."""
    flat = h.reshape(-1, h.shape[-1])
    expert, gates = moe_route(lp["moe"], flat, cfg)
    moe, layer = lp["moe"], None
    if stacked is not None:
        moe, layer = {"w13": stacked[0], "w2": stacked[1]}, stacked[2]
    out, local = moe_held_experts(moe, flat, expert, gates, cfg, layer)
    if "shared" in lp:
        with jax.named_scope("moe_shared"):
            out = out + ffn_apply(lp["shared"], flat, cfg)
    out = out.astype(h.dtype).reshape(h.shape)
    if with_routes:
        return out, local.reshape(h.shape[:-1] + local.shape[-1:])
    return out


def final_norm(x, params, cfg: TransformerConfig):
    """The stack's one final norm; in a looped stack it runs after
    every pass and belongs to what sits between two passes."""
    return _rms_norm(x, params["ln_f_scale"], cfg.norm_eps,
                     scope="loop_exit" if cfg.n_passes > 1 else "norm")


def split_qkv(qkv, cfg: TransformerConfig):
    """``[..., (heads + 2 kv_heads) * head_dim]`` — the fused
    projection's output, or the ``wqkv`` matrix — cut into q, k and v
    with the heads split out: ``[..., heads | kv_heads, head_dim]``."""
    hd = cfg.head_dim
    cut = (cfg.n_heads * hd, (cfg.n_heads + cfg.kv_heads) * hd)
    return tuple(a.reshape(a.shape[:-1] + (-1, hd))
                 for a in jnp.split(qkv, cut, axis=-1))


def ffn_apply(ffn, h, cfg: TransformerConfig):
    """The dense feed-forward over ``h [..., D]``: gelu(h·w1)·w2, or
    gated (silu(h·w1) * (h·w3))·w2 — ONE definition for
    :func:`block_apply` and ``generate._chunk_in_place``."""
    y = jnp.einsum("...d,df->...f", h, ffn["w1"])
    if cfg.ffn_gated:
        y = jax.nn.silu(y) * jnp.einsum("...d,df->...f", h, ffn["w3"])
    else:
        y = jax.nn.gelu(y)
    return jnp.einsum("...f,fd->...d", y, ffn["w2"])


def block_apply(layer_params, x, cfg: TransformerConfig,
                attention_fn: Callable, rope_ang=None, drop_key=None,
                return_kv=False, moe_dense_routing=False, kind=None):
    """One transformer block (pre-norm).  ``kind``: a typed stack's
    ``(attention kind, feed-forward kind)`` of this layer — the caller
    hands the attention function and the rotation that go with it
    (:func:`_trunk`); a sparse layer is :func:`moe_ffn`.  Returns
    (x, aux_loss), or (x, aux_loss, (k, v)) with ``return_kv`` (post-rope, kv-heads-only —
    the decode-cache layout; generate.prefill consumes it so there is
    exactly ONE definition of the block body to keep in sync).
    ``moe_dense_routing`` swaps the MoE FFN for the capacity-free
    decode-parity :func:`_moe_dense_block` (prefill's inference
    semantics); aux comes back 0 on that path.

    ``rope_ang`` and ``drop_key`` are *traced array* arguments (not
    closures) so the remat wrapper's static_argnums stay (2, 3) — a
    callable closing over traced values would leak tracers through
    jax.checkpoint.  ``drop_key`` non-None enables residual dropout.
    """
    eps, pre = cfg.norm_eps, cfg.post_norms != "only"
    h = _rms_norm(x, layer_params["ln1_scale"], eps) if pre else x
    attn_w = layer_params["attn"]
    kv = None
    if kind is not None and kind[0] == "latent":
        a = latent_attention(attn_w, h, rope_ang, cfg)
        with jax.named_scope("attn_proj"):
            a = jnp.einsum("btk,kd->btd", a, attn_w["wo"])
    else:
        if cfg.fused_qkv:  # matrices (init_params): heads split out here
            wq, wk, wv = split_qkv(attn_w["wqkv"], cfg)
            attn_w = {**attn_w, "wq": wq, "wk": wk, "wv": wv,
                      "wo": attn_w["wo"].reshape(-1, cfg.head_dim,
                                                 cfg.d_model)}
        a = _attention_block(attn_w, h, attention_fn, rope_ang,
                             kv_groups=cfg.n_heads // cfg.kv_heads,
                             return_kv=return_kv, eps=eps)
        if return_kv:
            a, kv = a
    if cfg.post_norms:
        a = _rms_norm(a, layer_params["ln1_post_scale"], eps)
    # The residual sums (and the dropout before them) go to the
    # sublayer whose output they take in: no operation of a block is
    # left outside the vocabulary.
    with jax.named_scope("attn_proj"):
        if drop_key is not None:
            a = _dropout(a, cfg.dropout, jax.random.fold_in(drop_key, 0))
        x = x + a
    h = _rms_norm(x, layer_params["ln2_scale"], eps) if pre else x
    with jax.named_scope("mlp"):
        aux = jnp.zeros((), jnp.float32)
        if kind is not None and kind[1] == "sparse":
            y = moe_ffn(layer_params, h, cfg)
        elif kind is not None or not cfg.num_experts:
            y = ffn_apply(layer_params["ffn"], h, cfg)
        elif moe_dense_routing:
            y = _moe_dense_block(layer_params["moe"], h, cfg)
        else:
            y, aux = _moe_block(layer_params["moe"], h, cfg)
    if cfg.post_norms:
        y = _rms_norm(y, layer_params["ln2_post_scale"], eps)
    with jax.named_scope("mlp"):
        if drop_key is not None:
            y = _dropout(y, cfg.dropout, jax.random.fold_in(drop_key, 1))
        out = x + y
    return (out, aux, kv) if return_kv else (out, aux)


def _trunk(params, tokens, cfg: TransformerConfig,
           attention_fn: Callable | None = None, dropout_rng=None,
           moe_dense_routing: bool = False, segment_ids=None):
    """The trunk of :func:`apply_hidden`: ``(hiddens, aux)`` with one
    final-norm hidden ``[B, S, D]`` per pass of the stack (a list of
    ``cfg.n_passes``; one for every unlooped config)."""
    attention_fn = _resolve_attention_fn(cfg, attention_fn, segment_ids)
    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    _check_len(s, cfg)
    with jax.named_scope("embed"):
        x = params["tok_emb"][tokens].astype(dtype)
        rope_ang = None
        if cfg.rope:
            rope_ang = rope_angles(jnp.arange(s), cfg.rope_dim,
                                   cfg.rope_theta)[None, :, None, :]
        else:
            x = x + params["pos_emb"][:s][None].astype(dtype)
    dropping = cfg.dropout > 0 and dropout_rng is not None
    if dropping:
        # fold_in index n_layers: disjoint from the per-layer keys 0..L-1.
        x = _dropout(x, cfg.dropout,
                     jax.random.fold_in(dropout_rng, cfg.n_layers))

    aux_total = jnp.zeros((), jnp.float32)

    block = _remat_block(cfg, moe_dense_routing=moe_dense_routing)

    # Python loop (not scan): attention_fn may close over shard_map /
    # pallas calls whose tracing under scan complicates sharding; layer
    # counts at this framework's scale compile fine unrolled.
    hiddens = []
    for r in range(cfg.n_passes):
        for i in range(cfg.n_layers):
            lp, kind = layer_at(params["layers"], cfg, i)
            # Pass 0 keeps the keys 0..L-1 it always had (L is the
            # embedding's); later passes continue past them.
            drop_key = (jax.random.fold_in(
                dropout_rng, r * (cfg.n_layers + 1) + i) if dropping
                else None)
            if kind is None:
                x, aux = block(lp, x, cfg, attention_fn, rope_ang,
                               drop_key)
            else:  # a typed stack: attention and rotation by kind
                x, aux = block_apply(
                    lp, x, cfg, attention_fn[kind[0]],
                    rope_ang if layer_rotates(cfg, kind) else None,
                    drop_key, kind=kind)
            aux_total = aux_total + aux
        # The ONE final norm, after every pass: the next pass starts
        # from the normed stream.
        x = final_norm(x, params, cfg)
        hiddens.append(x)
    return hiddens, aux_total


def apply_hidden(params, tokens, cfg: TransformerConfig,
                 attention_fn: Callable | None = None, dropout_rng=None,
                 moe_dense_routing: bool = False, segment_ids=None):
    """Trunk forward: tokens [B, S] int32 -> final-norm hidden [B, S, D]
    (of the LAST pass where the stack is looped: what is served).

    Everything in :func:`apply` except the unembedding matmul; the
    chunked cross-entropy path consumes the hidden states directly so
    the full-vocab logits never materialize.  Returns (hidden, aux).

    ``moe_dense_routing=True`` scores MoE configs with the capacity-FREE
    dense routing that :func:`~distkeras_tpu.models.generate.generate`
    and ``prefill`` use — the *inference semantics* (aux comes back 0).
    Evaluating a trained MoE this way agrees exactly with the KV-cached
    decode at ANY capacity factor; the default (training capacity
    dispatch) diverges for every token the router would capacity-drop.
    No-op for dense configs.

    ``segment_ids [B, S]`` int32 (packed sequences, data/packing.py):
    attention is masked to within-segment pairs; 0 marks padding.
    With ``rope=True`` the packed forward is EXACT vs running each
    document alone — rotary scores depend only on within-document
    relative distance, which a uniform position shift preserves.  With
    a learned position table, packed documents see shifted rows
    (standard packing behavior; prefer rope for packed training).
    """
    hiddens, aux = _trunk(params, tokens, cfg, attention_fn, dropout_rng,
                          moe_dense_routing, segment_ids)
    return hiddens[-1], aux


def head_table(params, cfg: TransformerConfig):
    """The output head's ``[V, D]`` table: ``tok_emb`` when tied."""
    return params["tok_emb"] if cfg.tie_head else params["head"]


def _unembed(hidden, params, cfg: TransformerConfig):
    """Unembedding head: hidden [B, S, D] -> f32 logits [B, S, V].

    The single definition of the head — apply, apply_pipelined and the
    materialized loss branch all call it, so the 'chunked CE matches
    materialized logits' invariant has one site to stay in sync with.
    """
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("head"):
        logits = jnp.einsum("bsd,vd->bsv", hidden,
                            head_table(params, cfg).astype(dtype))
        return logits.astype(jnp.float32)


def apply(params, tokens, cfg: TransformerConfig,
          attention_fn: Callable | None = None, dropout_rng=None,
          moe_dense_routing: bool = False, segment_ids=None):
    """Forward pass: tokens [B, S] int32 -> logits [B, S, V].

    ``attention_fn(q, k, v) -> out`` defaults to causal flash attention
    (Pallas on TPU); pass a ``make_ring_attention(...)`` wrapper for
    sequence parallelism.  ``dropout_rng`` non-None (with cfg.dropout
    > 0) enables training dropout; omit it for deterministic
    inference/eval.  ``moe_dense_routing=True`` selects the decode-
    parity capacity-free MoE routing; ``segment_ids`` masks packed
    sequences (see :func:`apply_hidden`).
    Returns (logits, aux_loss).
    """
    x, aux_total = apply_hidden(params, tokens, cfg, attention_fn,
                                dropout_rng, moe_dense_routing,
                                segment_ids)
    return _unembed(x, params, cfg), aux_total


def apply_passes(params, tokens, cfg: TransformerConfig,
                 attention_fn: Callable | None = None):
    """Every pass of a looped stack: ``(logits [R, B, S, V] f32,
    exit_probs [R, B, S] f32)``.

    After pass ``r`` the exit gate reads the normed stream:
    ``lambda_r = sigmoid(x_r · exit_w + exit_b)``.  The probability of
    leaving at pass ``r`` is ``p_r = lambda_r * prod_{j<r}(1 -
    lambda_j)`` and the last pass takes what is left, ``p_{R-1} =
    prod_{j<R-1}(1 - lambda_j)``, so the R values sum to 1.  Served at
    exit threshold 1, the model's logits are ``logits[-1]`` — what
    :func:`apply` returns and the engines compute; an adaptive exit (a
    pass count that differs by token) is not implemented."""
    if cfg.n_passes < 2:
        raise ValueError(
            f"apply_passes needs a looped stack (n_passes >= 2, got "
            f"{cfg.n_passes}): an unlooped config has no exit gate")
    hiddens, _ = _trunk(params, tokens, cfg, attention_fn)
    logits = jnp.stack([_unembed(h, params, cfg) for h in hiddens])
    with jax.named_scope("loop_exit"):
        lam = jax.nn.sigmoid(jnp.stack([
            jnp.einsum("bsd,d->bs", h.astype(jnp.float32),
                       params["exit_w"].astype(jnp.float32))
            for h in hiddens]) + params["exit_b"])
        stay = jnp.cumprod(1.0 - lam[:-1], axis=0)       # prod_{j<=r}
        before = jnp.concatenate([jnp.ones_like(lam[:1]), stay])
        probs = jnp.concatenate([lam[:-1] * before[:-1], before[-1:]])
    return logits, probs


@jax.named_scope("head")
def chunked_softmax_xent(hidden, emb, targets, n_chunks: int):
    """Mean softmax cross-entropy without materializing full logits.
    Returns ``(mean_nll, mean_lse_sq)`` — the second term is the z-loss
    statistic ``mean(logsumexp^2)`` (free here: the per-row logsumexp
    is already computed), consumed by ``lm_loss`` when
    ``cfg.z_loss_coef`` is set.

    ``hidden`` [B, S, D] (compute dtype), ``emb`` [V, D], ``targets``
    [B, S] int — target -1 marks an EXCLUDED position (loss masking:
    packed-sequence boundaries/padding, plus the internal chunk-pad
    rows) and the mean divides by the VALID count only.  A ``lax.scan``
    over the chunks computes each [N/n_chunks, V] logits
    slice, reduces it to its per-row ``logsumexp - target_logit``, and
    discards it.  ``jax.checkpoint`` on the body re-derives the slice in
    the backward, so peak HBM for the head is one slice fwd + bwd
    instead of the full [N, V] f32 logits (plus XLA's saved
    intermediates).  Exact — not an approximation: same per-row math as
    ``log_softmax`` + gather, chunking only reorders the reduction.
    """
    n_tok = targets.size
    d = hidden.shape[-1]
    h = hidden.reshape(n_tok, d)
    t = targets.reshape(n_tok).astype(jnp.int32)
    pad = (-n_tok) % n_chunks
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, d), h.dtype)])
        t = jnp.concatenate([t, jnp.full((pad,), -1, jnp.int32)])
    h = h.reshape(n_chunks, -1, d)
    t = t.reshape(n_chunks, -1)
    emb_c = emb.astype(hidden.dtype)

    def body(carry, sl):
        nll_total, z_total, n_valid = carry
        hc, tc = sl
        logits = jnp.einsum("cd,vd->cv", hc, emb_c).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(tc, 0)[:, None], axis=-1)[:, 0]
        valid = tc >= 0
        nll = jnp.where(valid, lse - tgt, 0.0)
        z = jnp.where(valid, jnp.square(lse), 0.0)
        return (nll_total + nll.sum(), z_total + z.sum(),
                n_valid + valid.sum()), None

    (total, z_total, n_valid), _ = jax.lax.scan(
        jax.checkpoint(body),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
         jnp.zeros((), jnp.int32)), (h, t))
    denom = jnp.maximum(n_valid, 1).astype(jnp.float32)
    return total / denom, z_total / denom


def apply_pipelined(params, tokens, cfg: TransformerConfig, mesh,
                    microbatches: int, attention_fn: Callable | None = None,
                    axis_name: str = "pipeline", seq_axis: str | None = None,
                    return_hidden: bool = False, segment_ids=None):
    """Forward pass with the layer trunk pipelined over ``axis_name``.

    Embedding and the head run outside the pipeline (they change shape);
    the residual trunk — whose stacked [L, ...] params slice naturally
    into ``n_stages`` contiguous stages — runs under
    parallel.pipeline.make_pipeline.  MoE aux loss flows through the
    pipeline (stage outputs carry ``(activation, aux)``), averaged over
    microbatches so it sits on the same scale as :func:`apply` — note
    expert capacity applies per *microbatch* under PP, so routing can
    drop slightly differently than the un-pipelined forward.

    For PP x SP, pass ``seq_axis="seq"``: the pipeline's shard_map goes
    manual over {pipeline, seq} and each stage runs the raw
    :func:`~distkeras_tpu.parallel.ring.ring_attention` body on its
    sequence shard — one composed shard_map, which (unlike a nested
    shard_map) transposes cleanly under AD.  MoE routing/capacity then
    applies per sequence shard.

    ``segment_ids [B, S]`` (packed sequences): every stage masks
    attention to within-document pairs — the per-microbatch segment
    slice rides the pipeline as make_pipeline ``extras`` (each stage
    indexes the microbatch it is processing), sharded over ``seq_axis``
    under PP x SP so the ring body receives its local shard.  Only the
    default-flash and seq_axis attention paths carry segments (a custom
    attention_fn raises, as in :func:`apply_hidden`).

    Returns (logits, aux).
    """
    import functools

    from distkeras_tpu.parallel.pipeline import make_pipeline

    reject_extended(cfg, "apply_pipelined")
    segmented = segment_ids is not None
    if segmented and attention_fn is not None:
        raise ValueError(
            "segment_ids with a custom attention_fn is not supported "
            "under the pipeline — use the default flash path or "
            "seq_axis (see apply_hidden's guard)")
    x_spec = P()
    extras_spec = P() if segmented else None
    ring_seq = seq_axis is not None and int(mesh.shape[seq_axis]) > 1
    if ring_seq:
        if attention_fn is not None:
            raise ValueError(
                "pass either attention_fn or seq_axis, not both: under "
                "seq_axis the pipeline installs the ring attention body "
                "itself")
        from distkeras_tpu.parallel.ring import ring_attention

        attention_fn = functools.partial(ring_attention, axis_name=seq_axis,
                                         causal=True,
                                         window=cfg.attention_window)
        x_spec = P(None, seq_axis)
        if segmented:
            extras_spec = P(None, None, seq_axis)
    elif not segmented:
        attention_fn = _resolve_attention_fn(cfg, attention_fn)
    n_stages = int(mesh.shape[axis_name])
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible into {n_stages} stages")
    per_stage = cfg.n_layers // n_stages

    dtype = jnp.dtype(cfg.dtype)
    b, s = tokens.shape
    _check_len(s, cfg)
    with jax.named_scope("embed"):
        x = params["tok_emb"][tokens].astype(dtype)
        if not cfg.rope:
            x = x + params["pos_emb"][:s][None].astype(dtype)

    stage_params = jax.tree.map(
        lambda a: a.reshape(n_stages, per_stage, *a.shape[1:]),
        params["layers"])

    block = _remat_block(cfg)

    seq_sharded = x_spec != P()

    def stage_fn(lp, u, seg=None):
        rope_ang = None
        if cfg.rope:
            # Positions must be *global*: under PP x SP this body runs
            # on a sequence shard, so offset by the shard's ring index.
            l_loc = u.shape[1]
            start = (jax.lax.axis_index(seq_axis) * l_loc
                     if seq_sharded else 0)
            rope_ang = rope_angles(start + jnp.arange(l_loc), cfg.head_dim,
                                   cfg.rope_theta)[None, :, None, :]
        if seg is None:
            att = attention_fn
        elif ring_seq:
            # The ring body with this microbatch's LOCAL segment shard.
            att = functools.partial(attention_fn, segment_ids=seg)
        else:
            # ONE definition of the default segmented flash path —
            # shared with apply_hidden via the resolver.
            att = _resolve_attention_fn(cfg, None, seg)
        aux_stage = jnp.zeros((), jnp.float32)
        for i in range(per_stage):
            li = jax.tree.map(lambda a: a[i], lp)
            u, aux = block(li, u, cfg, att, rope_ang)
            aux_stage = aux_stage + aux
        return u, aux_stage

    pipe = make_pipeline(stage_fn, mesh, microbatches, axis_name,
                         x_spec=x_spec, extras_spec=extras_spec)
    if segmented:
        if segment_ids.shape != tokens.shape:
            raise ValueError(
                f"segment_ids must align with tokens {tokens.shape}, "
                f"got {segment_ids.shape}")
        seg_mb = jnp.asarray(segment_ids, jnp.int32).reshape(
            microbatches, b // microbatches, s)
        x, aux_total = pipe(stage_params, x, seg_mb)
    else:
        x, aux_total = pipe(stage_params, x)
    x = _rms_norm(x, params["ln_f_scale"])
    if return_hidden:
        # The head runs outside the pipeline, so the chunked-CE loss can
        # consume the hidden states directly (lm_loss hidden_fn).
        return x, aux_total
    return _unembed(x, params, cfg), aux_total


def _forward_nll(params, tokens, cfg: TransformerConfig,
                 attention_fn: Callable | None,
                 apply_fn: Callable | None, dropout_rng=None,
                 hidden_fn: Callable | None = None,
                 moe_dense_routing: bool = False,
                 segment_ids=None):
    """(mean next-token NLL, aux) — shared by train loss and eval.

    Three forward routes:

    - ``apply_fn(params, inputs) -> (logits, aux)``: caller-materialized
      logits (legacy custom-forward hook); full log_softmax head.
    - ``hidden_fn(params, inputs) -> (hidden, aux)``: caller supplies
      final-norm hidden states (e.g. ``apply_pipelined`` with
      ``return_hidden=True``); the head honors ``cfg.ce_chunks``.
    - neither: the default :func:`apply_hidden` trunk; the head honors
      ``cfg.ce_chunks``.

    ``segment_ids [B, S+1]`` (aligned with ``tokens``, packed
    sequences): attention is segment-masked on the default trunk, and
    the loss EXCLUDES targets that cross a document boundary or sit in
    padding (segment 0) — the mean divides by the valid count.  A
    custom apply_fn/hidden_fn with ``handles_segments = True`` is
    called as ``fn(params, inputs, seg)`` so its forward can mask
    attention too (LMTrainer's pipelined fwd does); without the
    attribute it gets only the loss masking.
    """
    if apply_fn is not None and hidden_fn is not None:
        raise ValueError("pass apply_fn or hidden_fn, not both")
    reject_extended(cfg, "lm_loss / lm_nll (the training and evaluation "
                    "loss)", allow=tuple(_SWITCHES))
    targets = tokens[:, 1:]
    valid = None
    seg_in = None
    if segment_ids is not None:
        if segment_ids.shape != tokens.shape:
            raise ValueError(
                f"segment_ids must align with tokens {tokens.shape}, "
                f"got {segment_ids.shape}")
        seg_in = segment_ids[:, :-1]
        # A target is trainable iff it continues its input's document
        # (same nonzero segment) — boundary and pad targets are dead.
        valid = ((segment_ids[:, 1:] == seg_in) & (seg_in != 0))
        targets = jnp.where(valid, targets, -1)
    zc = cfg.z_loss_coef

    @jax.named_scope("head")
    def full_head(logits, aux):
        # z-loss rides in aux (training-only, like the MoE penalty —
        # lm_nll drops aux, so eval perplexity stays pure).
        logp = jax.nn.log_softmax(logits, axis=-1)
        per_tok = -jnp.take_along_axis(
            logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
        if valid is None:
            nll = per_tok.mean()
        else:
            denom = jnp.maximum(valid.sum(), 1)
            nll = jnp.where(valid, per_tok, 0.0).sum() / denom
        if zc > 0:
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            if valid is None:
                aux = aux + zc * jnp.square(lse).mean()
            else:
                denom = jnp.maximum(valid.sum(), 1)
                aux = aux + zc * (jnp.where(valid, jnp.square(lse), 0.0)
                                  .sum() / denom)
        return nll, aux

    def call_custom(fn, *args):
        if seg_in is not None and getattr(fn, "handles_segments", False):
            return fn(*args, seg_in)
        return fn(*args)

    if apply_fn is not None:
        logits, aux = call_custom(apply_fn, params, tokens[:, :-1])
        return full_head(logits, aux)
    if hidden_fn is None:
        hidden_fn = lambda p, t: apply_hidden(p, t, cfg, attention_fn,
                                              dropout_rng,
                                              moe_dense_routing,
                                              seg_in)
    hidden, aux = call_custom(hidden_fn, params, tokens[:, :-1])
    if cfg.ce_chunks > 1:
        nll, z_mean = chunked_softmax_xent(hidden, head_table(params, cfg),
                                           targets, cfg.ce_chunks)
        if zc > 0:
            aux = aux + zc * z_mean
        return nll, aux
    return full_head(_unembed(hidden, params, cfg), aux)


def lm_loss(params, tokens, cfg: TransformerConfig,
            attention_fn: Callable | None = None,
            apply_fn: Callable | None = None, dropout_rng=None,
            hidden_fn: Callable | None = None, segment_ids=None):
    """Next-token cross-entropy (+ MoE aux), mean over the trainable
    targets (all B*(S-1) positions, or the within-document subset when
    ``segment_ids`` marks packed sequences — see :func:`_forward_nll`).

    ``apply_fn(params, inputs) -> (logits, aux)`` defaults to
    :func:`apply`; pass ``hidden_fn`` (e.g. a closure over
    :func:`apply_pipelined` with ``return_hidden=True``) to train a
    custom trunk under the ``cfg.ce_chunks`` head.
    """
    if dropout_rng is not None and (apply_fn is not None
                                    or hidden_fn is not None):
        raise ValueError(
            "dropout_rng only threads through the default apply(); "
            "a custom apply_fn/hidden_fn (e.g. the pipelined trunk) "
            "must take its own rng — pipeline parallelism does not "
            "support dropout (see TransformerConfig.dropout)")
    nll, aux = _forward_nll(params, tokens, cfg, attention_fn, apply_fn,
                            dropout_rng, hidden_fn,
                            segment_ids=segment_ids)
    return nll + aux


def lm_nll(params, tokens, cfg: TransformerConfig,
           attention_fn: Callable | None = None,
           apply_fn: Callable | None = None,
           hidden_fn: Callable | None = None,
           moe_dense_routing: bool = False, segment_ids=None):
    """Mean next-token NLL *without* the MoE aux regularizer — the
    evaluation quantity (``exp`` of it is perplexity; the router load
    penalty is a training device, not model quality).

    ``moe_dense_routing=True`` evaluates MoE configs with the decode-
    parity capacity-free routing (see :func:`apply_hidden`) — the right
    lens for "what perplexity will the served model show": identical to
    the KV-cached decode at any capacity factor.  Only affects the
    default trunk (a custom apply_fn/hidden_fn controls its own
    routing)."""
    return _forward_nll(params, tokens, cfg, attention_fn, apply_fn,
                        hidden_fn=hidden_fn,
                        moe_dense_routing=moe_dense_routing,
                        segment_ids=segment_ids)[0]


def make_train_step(cfg: TransformerConfig, optimizer,
                    attention_fn: Callable | None = None,
                    apply_fn: Callable | None = None,
                    grad_accum: int = 1,
                    hidden_fn: Callable | None = None,
                    loss_fn: Callable | None = None,
                    value_and_grad: Callable | None = None,
                    probe: bool = False):
    """``step((params, opt_state), tokens) -> ((params', opt_state'), loss)``.

    Pure; callers jit it with NamedShardings (see __graft_entry__ and
    the trainers).  With ``grad_accum > 1``, ``tokens`` is
    ``[grad_accum, B, S+1]``: gradients accumulate over the microbatches
    and one optimizer update applies their mean — the memory lever for
    batch sizes whose activations do not fit HBM (the LM analogue of
    the Keras family's ``communication_window``, SURVEY.md §7.4).  The
    microbatch loop is unrolled, not scanned: attention_fn may close
    over shard_map/pallas calls whose tracing under scan complicates
    sharding (same reason apply() unrolls its layer loop).

    ``loss_fn`` (default :func:`lm_loss`) must share lm_loss's
    signature; a custom hook reinterprets the differentiated "params"
    tree (e.g. models/lora's (adapters, base) packing, which merges
    before calling lm_loss).

    ``value_and_grad`` (default ``jax.value_and_grad``) is the
    gradient-construction hook: it receives the loss fn and must
    return a callable with ``jax.value_and_grad``'s calling
    convention.  LMTrainer's replicated-DP configuration passes a
    shard_map-local construction here that sums the tied embedding's
    two gradient contributions *before* the cross-replica exchange
    (trainers/lm.py ``_dp_local_value_and_grad``) — XLA's CPU
    partitioner otherwise all-reduces them separately.

    ``probe=True``: the step returns ``(carry, (loss, aux))`` with
    ``aux = {"grad_norm": ...}`` computed in-graph — LMTrainer's
    opt-in diagnostics probe (same program count either way; under the
    stacked-local-gradient exchange the norm is over the stacked
    per-replica tree).
    """
    dropping = cfg.dropout > 0
    if value_and_grad is None:
        value_and_grad = jax.value_and_grad

    def step(carry, tokens, dropout_rng=None, segment_ids=None):
        params, opt_state = carry
        grad_fn = value_and_grad(loss_fn if loss_fn is not None
                                 else lm_loss)
        if dropping and dropout_rng is None:
            raise ValueError(
                f"cfg.dropout={cfg.dropout} but the train step got no "
                "dropout_rng: pass step(carry, tokens, rng) or training "
                "silently runs unregularized (LMTrainer threads the rng "
                "automatically)")
        rng = dropout_rng if dropping else None
        if grad_accum == 1:
            loss, grads = grad_fn(params, tokens, cfg, attention_fn,
                                  apply_fn, rng, hidden_fn, segment_ids)
        else:
            # NOTE: a stacked-local value_and_grad returns [n, *leaf]
            # gradients; the zeros_like(params) accumulator broadcasts
            # against them on the first add, so accumulation works for
            # both layouts.
            grads = jax.tree.map(jnp.zeros_like, params)
            loss = jnp.zeros((), jnp.float32)
            for i in range(grad_accum):
                ri = jax.random.fold_in(rng, i) if rng is not None else None
                li, gi = grad_fn(params, tokens[i], cfg, attention_fn,
                                 apply_fn, ri, hidden_fn,
                                 None if segment_ids is None
                                 else segment_ids[i])
                grads = jax.tree.map(jnp.add, grads, gi)
                loss = loss + li
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            loss = loss / grad_accum
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        if probe:
            import optax

            return (params, opt_state), (
                loss, {"grad_norm": optax.global_norm(grads)})
        return (params, opt_state), loss

    return step
