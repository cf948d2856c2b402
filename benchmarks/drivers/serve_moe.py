"""Serving driver for the typed stack (window and full attention mixed,
routed experts of which a share is held): ``serve_engine``'s loop,
unchanged, with that model's own plain reference.

``serve_engine.run`` decides ``correct`` through ``import reference``.
K-EXAONE's share is not what ``reference.py`` computes, so this driver
puts ``reference_kexaone`` where that import looks and calls the
accepted driver (the way ``serve_looped.py`` does it): the warm-up,
the ramp and the timed window are its own.

The same two switches in the environment as ``serve_looped.py``, for
showing that the comparison is tight
(``benchmarks/tests/test_moe_cell.py``; the builder's chip runs).  Both
act after the timed window, on the finished requests the harness
itself samples:

``REFERENCE_FAULT=<name>``     the reference that decides ``correct``
                               is computed WRONG (one of
                               ``reference_kexaone.FAULTS``): the run
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
    import reference_kexaone as ref

    fault = os.environ.get("REFERENCE_FAULT") or None
    controls = os.environ.get("REFERENCE_CONTROLS") == "1"
    if fault is None and not controls:
        return ref

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
    return shim


def run(ctx):
    serve_engine = ctx.module("drivers", "serve_engine")
    mine, theirs = _reference(), sys.modules.get("reference")
    sys.modules["reference"] = mine
    try:
        return serve_engine.run(ctx)
    finally:
        if theirs is None:
            del sys.modules["reference"]
        else:
            sys.modules["reference"] = theirs
