"""Reader over the device trace: the events of one line (``ops`` =
"XLA Ops", ``modules`` = "XLA Modules") whose label matches a pattern.

args: ``line``, ``pattern`` and ``stat``:
  ``median_ms``      median duration of a matching event
  ``ms_per_kilo``    summed duration / (sum of ``field`` over the obs
                     spans named in ``per_obs_spans`` that started while
                     the profiler ran / 1000)
  ``exposed_share``  % of the traced window in which a matching
                     operation ran on a device and no other did
"""

import statistics

import trace_reduce as reduce


def read(record, args):
    trace = record.get("trace")
    if not trace:
        return None
    events = trace["events"]
    if args["stat"] == "exposed_share":
        sec = reduce.exposed(events, args["pattern"], record["chips"])
        if sec is None or not trace["window_s"]:
            return None
        return 100.0 * sec / trace["window_s"]
    durs = reduce.matching(events, args["pattern"], args.get("line", "ops"))
    if not durs:
        return None
    if args["stat"] == "median_ms":
        return 1e3 * statistics.median(durs)
    if args["stat"] == "ms_per_kilo":
        lo, hi = record["profile_window"]
        n = sum(rec["fields"].get(args["field"], 0)
                for rec in record.get("obs_events", ())
                if rec.get("kind") == "span"
                and rec["name"] in args["per_obs_spans"]
                and lo <= rec["t0"] < hi)
        return 1e3 * sum(durs) / (n / 1000.0) if n else None
    raise ValueError(f"unknown stat {args['stat']!r}")
