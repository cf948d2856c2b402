"""Serving driver for a stack of selective state-space (Mamba) layers
among full-attention layers (a lane's cache is, an ssm layer, a
fixed-size float32 state and the conv's last inputs; an attention
layer, K/V slots): ``serve_engine``'s loop, unchanged, with that
model's own plain reference.

``serve_engine.run`` decides ``correct`` through ``import reference``.
Jamba's block is not what ``reference.py`` computes, so this driver
puts ``reference_jamba`` where that import looks and calls the accepted
driver (the way ``serve_retention.py`` does it, seating watch
included): the warm-up, the ramp and the timed window are its own.
The check wants a request whose lane had an EARLIER OCCUPANT (a stale
state is not masked by position as stale slots are), so the loop's
seating is written down for the reference
(``reference_jamba.LANE_HISTORY``).  And where the cell names
``ssm_state_tol`` it wants the states the engine HOLDS when the run
ends (``reference_jamba.check_states``: a state's rounding the logits
may not tell, the state itself can), so the loop is kept for the
reference too (``serve_latent.py``'s watch).

A program that has no ssm layer cannot build the configuration: the
run stops at once with a ``ValueError`` that names the layer kind,
before any weight is made.

The same two switches in the environment as ``serve_moe.py``, for
showing that the comparison is tight (``benchmarks/tests/
test_ssm_cell.py``; the chip runs that set its limits).  Both act after the timed
window, on the finished requests the harness itself samples:

``REFERENCE_FAULT=<name>``     the reference that decides ``correct``
                               is computed WRONG (one of
                               ``reference_jamba.FAULTS``): the run
                               has to come out not ``correct``.
``REFERENCE_CONTROLS=1``       after the check that decides, the same
                               sample is held to every faulty
                               reference; each verdict goes under
                               ``notes.reference.controls`` of the
                               run's record (``last_run_notes.json``).
"""

import os
import sys
import time
import types


def _program_has_ssm(conf):
    """The program's config of this model, or a ``ValueError`` naming
    the layer kind it lacks."""
    from distkeras_tpu.models import transformer as tfm

    try:
        cfg = tfm.TransformerConfig(**conf["transformer_config"])
    except TypeError as err:
        raise ValueError(
            "this program has no layer kind 'ssm' (layer_types: 'ssm', "
            f"the selective state-space mixer): {err}") from None
    if not getattr(cfg, "ssm_planes", 0):
        raise ValueError("this program has no layer kind 'ssm' "
                         "(layer_types: 'ssm')")
    return cfg


def _reference(loops):
    import reference_jamba as ref

    fault = os.environ.get("REFERENCE_FAULT") or None
    controls = os.environ.get("REFERENCE_CONTROLS") == "1"

    def check_serving(ctx, params, finished):
        loop = loops[-1] if loops else None
        # The states are read off lanes that DECODE.  At a toy size a
        # run can end with every lane admitting: the loop goes on,
        # after the window and outside every number, until one has its
        # first token.
        while loop and loop.outstanding() and not any(
                r.tokens for r in loop.by_lane.values()):
            loop.step(time.perf_counter)
        out = ref.check_serving(ctx, params, finished, fault=fault,
                                loop=loop)
        if controls:
            keep = ("ok", "worst_gap_to_best_logit",
                    "mean_gap_to_best_logit", "argmax_of_reference",
                    "tokens")
            out["controls"] = {}
            for f in ref.FAULTS[1:]:
                got = ref.check_serving(ctx, params, finished, fault=f,
                                        loop=loop)
                out["controls"][f] = {
                    **{k: v for k, v in got.items() if k in keep},
                    "ssm_state_err": got.get("states", {}).get(
                        "ssm_state_err")}
        return out

    shim = types.ModuleType("reference")
    shim.check_serving = check_serving
    return shim, ref


def run(ctx):
    _program_has_ssm(ctx.conf)
    serve_engine = ctx.module("drivers", "serve_engine")
    loops = []
    (mine, ref), theirs = _reference(loops), sys.modules.get("reference")
    ref.LANE_HISTORY.clear()
    ctx.module("drivers", "serve_latent")._watch_loop(serve_engine, loops)
    ctx.module("drivers", "serve_retention")._watch_seating(
        serve_engine, ref.LANE_HISTORY)
    sys.modules["reference"] = mine
    try:
        return serve_engine.run(ctx)
    finally:
        if theirs is None:
            del sys.modules["reference"]
        else:
            sys.modules["reference"] = theirs
