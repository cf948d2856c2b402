"""Serving driver for a stack of latent-attention layers (a lane's
cache is one row of latent a token, no K or V of any head) over routed
experts: ``serve_engine``'s loop, unchanged, with that model's own
plain reference.

``serve_engine.run`` decides ``correct`` through ``import reference``.
JoyAI-LLM-Flash's block is not what ``reference.py`` computes, so this
driver puts ``reference_joyai`` where that import looks and calls the
accepted driver (the way ``serve_retention.py`` does it, seating watch
included): the warm-up, the ramp and the timed window are its own.
The check wants a request whose lane had an EARLIER OCCUPANT (its
stale rows past the new prompt must stay masked), so the loop's
seating is written down for the reference
(``reference_joyai.LANE_HISTORY``).  And it wants the rows the engine
HOLDS when the run ends (``reference_joyai.check_rows``: the logits
cannot tell a cache's rounding from the routers' near-ties, the rows
can), so the loop itself is kept for the reference too.

The same two switches in the environment as ``serve_moe.py``, for
showing that the comparison is tight
(``benchmarks/tests/test_latent_cell.py``; the builder's chip
runs).  Both act after the timed window, on the finished requests the
harness itself samples:

``REFERENCE_FAULT=<name>``     the reference that decides ``correct``
                               is computed WRONG (one of
                               ``reference_joyai.FAULTS``): the run
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


def _watch_loop(serve_engine, loops):
    """``Loop.__init__`` as it is, and the loop kept: the run's own is
    the last."""
    init = getattr(serve_engine.Loop, "_init", serve_engine.Loop.__init__)

    def __init__(self, engine, sample):
        init(self, engine, sample)
        loops.append(self)

    serve_engine.Loop._init, serve_engine.Loop.__init__ = init, __init__


def _reference(loops):
    import reference_joyai as ref

    fault = os.environ.get("REFERENCE_FAULT") or None
    controls = os.environ.get("REFERENCE_CONTROLS") == "1"

    def check_serving(ctx, params, finished):
        loop = loops[-1] if loops else None
        # The rows are read off lanes that DECODE (their tokens say how
        # many rows must be there).  At a toy size a run can end with
        # every lane admitting: the loop goes on, after the window and
        # outside every number, until one has its first token.
        while loop and loop.outstanding() and not any(
                r.tokens for r in loop.by_lane.values()):
            loop.step(time.perf_counter)
        out = ref.check_serving(ctx, params, finished, fault=fault, loop=loop)
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
                    "latent_row_err": got.get("rows", {}).get(
                        "latent_row_err")}
        return out

    shim = types.ModuleType("reference")
    shim.check_serving = check_serving
    return shim, ref


def run(ctx):
    serve_engine = ctx.module("drivers", "serve_engine")
    loops = []
    (mine, ref), theirs = _reference(loops), sys.modules.get("reference")
    ref.LANE_HISTORY.clear()
    _watch_loop(serve_engine, loops)
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
