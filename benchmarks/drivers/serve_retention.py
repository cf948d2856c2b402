"""Serving driver for a stack of power-retention layers (a lane's
cache is a fixed-size state, not keys and values): ``serve_engine``'s
loop, unchanged, with that model's own plain reference.

``serve_engine.run`` decides ``correct`` through ``import reference``.
Brumby's block is not what ``reference.py`` computes, so this driver
puts ``reference_brumby`` where that import looks and calls the
accepted driver (the way ``serve_moe.py`` does it): the warm-up, the
ramp and the timed window are its own.  One thing more: the accepted
loop knows which request sits in which lane and keeps no history of
it, and the check wants a request whose lane had an EARLIER OCCUPANT
(a stale state is not masked by position as stale slots are) — so the
loop's seating is watched and written down for the reference
(``reference_brumby.LANE_HISTORY``).

The same two switches in the environment as ``serve_moe.py``, for
showing that the comparison is tight
(``benchmarks/tests/test_retention_cell.py``; the builder's chip
runs).  Both act after the timed window, on the finished requests the
harness itself samples:

``REFERENCE_FAULT=<name>``     the reference that decides ``correct``
                               is computed WRONG (one of
                               ``reference_brumby.FAULTS``): the run
                               has to come out not ``correct``.
``REFERENCE_CONTROLS=1``       after the check that decides, the same
                               sample is held to every faulty
                               reference; each verdict goes under
                               ``notes.reference.controls`` of the
                               run's record (``last_run_notes.json``).
"""

import os
import sys
import types


def _reference():
    import reference_brumby as ref

    fault = os.environ.get("REFERENCE_FAULT") or None
    controls = os.environ.get("REFERENCE_CONTROLS") == "1"

    def check_serving(ctx, params, finished):
        out = ref.check_serving(ctx, params, finished, fault=fault)
        if controls:
            keep = ("ok", "worst_gap_to_best_logit",
                    "mean_gap_to_best_logit", "argmax_of_reference",
                    "tokens")
            out["controls"] = {
                f: {k: v for k, v in ref.check_serving(
                    ctx, params, finished, fault=f).items() if k in keep}
                for f in ref.FAULTS if f}
        return out

    shim = types.ModuleType("reference")
    shim.check_serving = check_serving
    return shim, ref


def _watch_seating(serve_engine, history):
    """``Loop._seat`` as it is, and after it every lane's newcomer
    appended to the lane's history."""
    seat = serve_engine.Loop._seat

    def _seat(self, before):
        seat(self, before)
        for lane, r in self.by_lane.items():
            held = history.setdefault(lane, [])
            if not held or held[-1] is not r:
                held.append(r)

    serve_engine.Loop._seat = _seat


def run(ctx):
    serve_engine = ctx.module("drivers", "serve_engine")
    (mine, ref), theirs = _reference(), sys.modules.get("reference")
    ref.LANE_HISTORY.clear()
    _watch_seating(serve_engine, ref.LANE_HISTORY)
    sys.modules["reference"] = mine
    try:
        return serve_engine.run(ctx)
    finally:
        if theirs is None:
            del sys.modules["reference"]
        else:
            sys.modules["reference"] = theirs
