"""Training driver: ``LMTrainer.train()``, the users' entry point.

Every ``train()`` call builds a new ``jit`` (tracing, lowering and a
load from the compile cache before its first step), and steps are
dispatched asynchronously, so a host stamp per step means nothing.
The window therefore sits inside ONE call:

1. a short first call (``warm_steps``) fills the compile cache and
   gives the step time (set-up);
2. the measured call gets as many rows as fill ``--seconds`` at that
   step time, plus ``lead`` steps at the front;
3. the rows are handed over as ``FencedRows``: when the trainer slices
   the rows of step ``lead``, the slice first enqueues a trivial
   computation and blocks on it — the device runs in order, so every
   earlier step has finished — and stamps the window's start;
4. the end is stamped when ``train()`` returns, which blocks on the
   parameters.  Tokens of the steps between the stamps ÷ the seconds
   between them.

A step callback in the trainer would make the fence unnecessary
(PERF.md, list for the ``tracing`` issue).
"""

from __future__ import annotations

import json
import math
import os
import time


class FencedRows:
    """An array-like the trainer slices once per step.  It answers
    ``rows["tokens"]`` with itself, so it passes as a dataset."""

    def __init__(self, rows, rows_per_step, fence_at, clock, meter=None):
        self.rows = rows
        self.meter = meter
        self.programs_at_fence = None
        self.ndim, self.shape = rows.ndim, rows.shape
        self.rows_per_step = rows_per_step
        self.fence_at = fence_at          # step index whose slice fences
        self.clock = clock
        self.t_fence = None

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, key):
        if isinstance(key, str):
            return self
        if isinstance(key, slice) and key.start is not None:
            step = key.start // self.rows_per_step
            if step == self.fence_at and self.t_fence is None:
                import jax
                import jax.numpy as jnp

                jax.block_until_ready(jnp.zeros((), jnp.int32) + 1)
                self.t_fence = self.clock()
                if self.meter is not None:
                    self.programs_at_fence = self.meter.programs
        return self.rows[key]


def run(ctx):
    import jax
    import jax.numpy as jnp

    import distkeras_tpu as dk
    from distkeras_tpu import obs
    from distkeras_tpu.models import transformer as tfm

    conf, mix = ctx.conf, ctx.mix
    cfg = tfm.TransformerConfig(**conf["transformer_config"])
    spec = conf["trainer"]
    chips = int(ctx.cell["chips"])
    if chips != int(spec["chips"]):
        raise SystemExit(f"cell asks for {chips} chips, the configuration "
                         f"is laid out for {spec['chips']}")
    rows_per_step = int(spec["rows_per_chip"]) * chips
    clock = time.perf_counter
    mesh = dk.make_mesh(dk.MeshSpec(data=chips), devices=ctx.devices)
    gen = ctx.module("traffic", mix["generator"])
    lead = 2
    warm_steps = int(mix.get("warm_steps", 3))

    def trainer(**extra):
        return getattr(dk, spec["class"])(
            cfg, batch_size=rows_per_step, mesh=mesh, seed=0,
            **spec["kwargs"], **extra)

    probe = trainer()
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    shardings = probe.plan.tree_shardings(mesh, shapes)
    init = jax.jit(lambda key: tfm.init_params(key, cfg),
                   out_shardings=shardings)
    # Weights on the device from the seed, in one jitted call, placed
    # as the trainer's plan places them.  train() donates them, so each
    # call (and the reference) gets a fresh, identical tree.
    make_params = lambda: init(jax.random.key(ctx.seed % (2 ** 31)))

    # ------------------------------------------------ set-up: warm call
    rows, segs = gen.make(mix, ctx.seed, cfg.vocab_size,
                          rows_per_step * warm_steps)
    warm = FencedRows(rows, rows_per_step, 1, clock)
    t0 = clock()
    probe.train(warm, params=make_params(), segments=segs)
    t_warm = clock()
    step_s = (t_warm - warm.t_fence) / (warm_steps - 1)
    n_steps = max(lead + 2, lead + math.ceil(ctx.seconds / step_s))
    rows, segs = gen.make(mix, ctx.seed, cfg.vocab_size,
                          rows_per_step * n_steps)
    targets = gen.target_tokens(segs)

    # ------------------------------------------------------ measured call
    extra = {}
    prof_dir = os.path.join(ctx.scratch, "profile")
    prof_steps = int(ctx.cell.get("trace_steps", 4))
    trace_path = os.path.join(ctx.scratch, "obs_events.jsonl")
    if ctx.trace:
        extra = dict(profile_dir=prof_dir, profile_steps=prof_steps)
        obs.enable(trace_path=trace_path)
    try:
        tr = trainer(**extra)
        fenced = FencedRows(rows, rows_per_step, lead, clock, ctx.meter)
        params0 = make_params()
        jax.block_until_ready(params0)
        t_call = clock()
        trained = tr.train(fenced, params=params0, segments=segs)
        t_end = clock()
    finally:
        if ctx.trace:
            obs.disable()
    t_begin = fenced.t_fence
    setup_s = t_begin - ctx.t_process
    # The step's program is requested once, at step 0, before the
    # fence; anything requested after it compiled inside the window.
    programs_in_window = ctx.meter.programs - fenced.programs_at_fence
    window_s = t_end - t_begin
    steps = n_steps - lead
    tokens = int(targets[rows_per_step * lead:].sum())
    history = [float(v) for v in tr.history]
    timer = tr.step_timer.phases
    del trained, params0
    notes = {"window_s": window_s, "steps": steps, "tokens": tokens,
             "step_s_warm_call": step_s, "step_s": window_s / steps,
             "rows_per_step": rows_per_step,
             "programs_in_window": programs_in_window,
             "packing_fill": float(targets.mean() / mix["seq_len"]),
             "setup": {"to_warm_call_s": t0 - ctx.t_process,
                       "warm_call_s": t_warm - t0,
                       "rows_s": t_call - t_warm,
                       "call_to_fence_s": t_begin - t_call},
             "loss_first_last": [history[0], history[-1]]}

    # ---------------------------------------------------- correctness
    import reference

    check = reference.check_training(
        ctx, make_params, rows[:rows_per_step], segs[:rows_per_step],
        history, ctx.devices[0])
    notes["reference"] = check
    bad_steps = sum(1 for v in history if not math.isfinite(v))
    correct = bool(check["ok"] and bad_steps == 0 and tokens > 0
                   and programs_in_window == 0)
    with open(os.path.join(ctx.scratch, f"losses_seed{ctx.seed}.json"),
              "w") as f:
        json.dump({"workload": ctx.cell["name"], "seed": ctx.seed,
                   "losses": history}, f)

    e2e = {"setup_s": (setup_s, "s"),
           "train_tok_s_chip": (tokens / window_s / chips, "tokens/s/chip")}
    record = {"correct": correct, "attempted": len(history),
              "failed": bad_steps, "end_to_end": e2e, "notes": notes,
              "kind": "train", "window": (t_begin, t_end),
              "tokens": tokens, "window_s": window_s, "chips": chips,
              "step_timer": {k: list(v) for k, v in timer.items()},
              "conf": conf, "peaks": ctx.peaks, "mix": mix,
              "window_segments": segs[rows_per_step * lead:],
              "rows_per_step": rows_per_step}
    if ctx.trace:
        import trace_reduce

        record["trace"] = trace_reduce.summarize(
            prof_dir, n_devices=chips,
            dump=os.path.join(ctx.scratch, "trace_listing.json"))
        # The trainer profiles rounds 2 .. 1 + profile_steps.
        lo, hi = rows_per_step, rows_per_step * (1 + prof_steps)
        record["traced_segments"] = segs[lo:hi]
        record["traced_steps"] = prof_steps
    with open(os.path.join(ctx.scratch, "last_run_notes.json"), "w") as f:
        json.dump(notes, f, indent=1, default=str)
    return record
