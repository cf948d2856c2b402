#!/usr/bin/env python
"""Render an obs trace (JSONL) as a human-readable run report.

Phase breakdown (span totals/means/percentiles and share of the run's
wall span), latency histograms (bucket-interpolated p50/p95/p99),
counters/gauges, and the point-event timeline (chaos faults,
supervisor attempts, admission rejects) — reconstructed entirely from
one trace file written by ``distkeras_tpu.obs`` (docs/observability.md).

Usage::

    python scripts/obs_report.py run.jsonl
    python scripts/obs_report.py new.jsonl --compare base.jsonl
    python scripts/obs_report.py run.jsonl --json   # the report dict
    python scripts/obs_report.py --merge host0.jsonl host1.jsonl ...
    python scripts/obs_report.py serve.jsonl --request 3
    python scripts/obs_report.py serve.jsonl --max-len 8192
    python scripts/obs_report.py router.jsonl replica*.jsonl --request 7

``--compare BASE`` prints a regression diff of NEW (the positional
trace) against BASE instead of the full report — per-phase total/mean
deltas, latency percentile deltas, counter drift.

``--request ID`` renders ONE serving request's waterfall instead:
submit -> queue wait -> admission (chunked-prefill spans included) ->
per-step token emissions with inter-token gaps -> finish, filtered
from the round-11 per-request ``request_id`` trace propagation.  With
SEVERAL traces (round 13) the records are wall-clock aligned first
and the waterfall follows a fleet-wide router id across processes:
the routing decision, any re-route hop, and each replica's engine
stages render as one story.

``--max-len N`` (the serving engine's cache slots a lane, which no
trace record carries) turns "serving rounds"' attended positions into
the share of the slab an admission program's attention still reads:
1.0 on the dense path, about the live prefix's share on the bounded
one.

``--merge`` takes SEVERAL per-host traces (a multi-host run writes one
file per host per attempt) and renders ONE cross-host event timeline,
wall-clock aligned through each trace's meta anchor and tagged with
run id + host — how a coordinated cluster restart's fault/recovery
sequence reads as a single story (``--json`` emits it as one JSON
object per line, machine-readable; scripts/chaos_suite.py --cluster
prints exactly this).

Pure host-side file parsing: no jax import, safe anywhere.
"""

import argparse
import importlib
import json
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_report_module():
    """Import distkeras_tpu.obs.report WITHOUT executing the package
    root's ``__init__`` (which imports jax/keras and the whole
    framework): register stub parent packages whose ``__path__``
    points at the real directories, then import the stdlib-only obs
    submodules through them.  Keeps this script runnable on a host
    with no jax installed — it only parses JSONL files."""
    for name, path in (
            ("distkeras_tpu", os.path.join(REPO, "distkeras_tpu")),
            ("distkeras_tpu.obs",
             os.path.join(REPO, "distkeras_tpu", "obs")),
            # obs/metrics.py (and friends) import the lock wrappers
            # from utils.locks — stdlib-only, but the utils package
            # root is NOT (it pulls the framework), so it gets a stub
            # parent too.
            ("distkeras_tpu.utils",
             os.path.join(REPO, "distkeras_tpu", "utils"))):
        if name not in sys.modules:
            mod = types.ModuleType(name)
            mod.__path__ = [path]
            sys.modules[name] = mod
    return importlib.import_module("distkeras_tpu.obs.report")


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="+",
                    help="obs JSONL trace(s); several only with --merge")
    ap.add_argument("--compare", metavar="BASE",
                    help="diff TRACE against this earlier trace "
                         "instead of printing the full report")
    ap.add_argument("--merge", action="store_true",
                    help="merge per-host traces into one cross-host "
                         "event timeline (wall-clock aligned)")
    ap.add_argument("--request", type=int, metavar="ID", default=None,
                    help="render one serving request's waterfall "
                         "(submit/admit/chunks/emits/finish) instead "
                         "of the full report")
    ap.add_argument("--max-len", type=int, metavar="N", default=None,
                    help="the engine's cache slots a lane: prints the "
                         "admission programs' attended share")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of text "
                         "(with --merge: one timeline entry per line)")
    ap.add_argument("--max-events", type=int, default=None,
                    help="timeline rows to print "
                         "(default 60; 200 with --merge)")
    args = ap.parse_args(argv)

    report = _load_report_module()

    if args.merge:
        rep = report.merge_traces(args.trace)
        if args.json:
            for e in rep["timeline"]:
                print(json.dumps(e, default=str))
        else:
            print(report.render_merged(
                rep, max_events=args.max_events
                if args.max_events is not None else 200))
        return 0
    if len(args.trace) != 1 and args.request is None:
        ap.error("several traces need --merge or --request")
    if args.request is not None:
        # Several traces: the cross-process fleet case (a routed
        # request's story spans the router's trace and each replica's)
        # — records are wall-clock aligned before the waterfall.
        records = report.merged_records(args.trace)
        wf = report.request_waterfall(records, args.request)
        if args.json:
            print(json.dumps(wf, indent=1, default=str))
        else:
            print(report.render_waterfall(wf))
        return 0 if wf.get("found") else 1
    rep = report.load_report(args.trace[0], args.max_len)
    if args.compare:
        base = report.load_report(args.compare, args.max_len)
        if args.json:
            print(json.dumps({"base": base, "new": rep}, indent=1,
                             default=str))
        else:
            print(report.render_compare(base, rep))
        return 0
    if args.json:
        print(json.dumps(rep, indent=1, default=str))
    else:
        print(report.render_report(
            rep, max_events=args.max_events
            if args.max_events is not None else 60))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
