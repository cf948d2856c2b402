"""Reader over what the engine says it dispatched: every launch is
named and numbered by the span around it (``serving.step`` /
``serving.admit`` / ``serving.admit_chunk`` carry ``program``, the name
the device trace gives the launch; ``serving.step`` its ``seq``), and
``serving.collect`` says which launch it read (``seq``).  So the
device's time is found by the program's own word, not by a pattern
over names ``jax.jit`` made up, and the two clocks meet on device
events every trace has — no frame of the Python tracer is read
(``events["host"]`` is never touched).

args: ``stat``:

``decode_ms``          median device time of an execution of a program
                       some ``serving.step`` span declared;
``admit_ms_per_ktok``  device time of the programs the admission spans
                       declared / their ``bucket`` summed over the
                       spans that started while the profiler ran / 1000;
``host_share``         % — over the decoding rounds of the window that
                       began after the profiler stopped: median
                       ``host_ms`` of ``serving.round`` (its time less
                       what its reads waited for the device) / median
                       period (one round's start to the next's, the
                       caller's time between two ``step()``s
                       included).  Near 100: the host sets the pace;
``dispatch_lead_ms``   through the clock join, median over the profiled
                       decode rounds of: device start of launch ``seq``
                       - end of its ``serving.step`` span — how long a
                       launch sat in the device's queue, the margin by
                       which the host is hidden (~0.1: the device waited
                       for the host).  Read while the Python tracer
                       slows the host: it understates.

**The clock join** (``join``).  The decode programs' executions on the
first device, in order, are laid against every run of as many
consecutive ``seq`` (at most one execution at either edge may find no
read); an alignment's offsets are ``device end - collect end`` over the
pairs whose ``wait_ms`` shows the host was blocked in the read (> 0.2
ms): there the read returns a near-constant readback latency after the
program ends.  The alignment whose offsets scatter least (mean distance
from their median) is kept; profile time = program time x 1e9 +
``offset_ns``, short of the truth by that latency.  From the other
side every pair has to satisfy ``device start >= serving.step start``
(``bound_us``: the least slack), and — the device runs launches in the
order they were made — as many admission programs have to stand between
two decode executions as admission spans between their two
``serving.step`` spans (one launch a span: every engine with a
``serving.round``): a violated pair means a wrong alignment, or a
``seq`` that names another launch than it says, and there is no join.

With a join ``dispatch_lead_ms`` prints a ``note`` line: ``offset_ns``,
``scatter_us`` (and ``worst_us``, the largest distance; ``next_us``,
the runner-up alignment's scatter), ``bound_us``, ``pairs``,
``blocked``, and
``device_idle_s`` — the first device's idle gaps by the program span
whose self time covers them (``round``, ``pump``, ``emit_loop``,
``reap``, ``collect``, ``step``, ``admit``, ``admit_chunk``; ``caller``
between two ``step()``s): what ``breakdown.idle_gaps`` says with Python
frames, in the program's names; it sums to ``window_s - busy_s``.

None where the program says none of this (a program older than the
fields): the metric is left out, nothing raises.
"""

import bisect
import json
import re
import statistics

import trace_reduce

STEP, COLLECT, ROUND = "serving.step", "serving.collect", "serving.round"
ADMIT = ("serving.admit", "serving.admit_chunk")
BLOCKED_MS = 0.2


def serving_spans(record):
    return sorted((r for r in record.get("obs_events", ())
                   if r.get("kind") == "span"
                   and r["name"].startswith("serving.")),
                  key=lambda r: r["t0"])


def declared(spans, names):
    """The ``program`` values the spans named ``names`` carry."""
    return {s["fields"]["program"] for s in spans
            if s["name"] in names and "program" in s["fields"]}


def executions(record, programs):
    """``[(start_ns, dur_ns)]``, in order, of the first device's
    programs whose own name — what stands before the ``(id)`` of an
    "XLA Modules" event — is one of ``programs``: the whole name, not a
    part of it."""
    devices = ((record.get("trace") or {}).get("events") or {}).get(
        "devices") or {}
    if not devices or not programs:
        return []
    modules = devices[sorted(devices)[0]]["modules"]
    return sorted((s, d) for label, s, d in modules
                  if re.sub(r"\(\d+\)$", "", trace_reduce.op_name(label))
                  in programs)


def join(spans, runs, between=()):
    """The clock join (module docstring) of the decode executions
    ``runs`` with the spans; ``between``: the admission executions'
    starts, in order.  ``{"offset_ns", "scatter_us", "worst_us",
    "next_us", "bound_us", "pairs", "blocked", "matched": [(execution,
    step span, collect span)]}``, or None."""
    steps = {s["fields"]["seq"]: s for s in spans
             if s["name"] == STEP and "seq" in s["fields"]}
    reads = {s["fields"]["seq"]: s for s in spans
             if s["name"] == COLLECT and "seq" in s["fields"]}
    both = steps.keys() & reads.keys()
    if not runs or not both:
        return None
    ends = {k: (reads[k]["t0"] + reads[k]["dur"]) * 1e9 for k in both
            if reads[k]["fields"].get("wait_ms", 0.0) > BLOCKED_MS}
    last = len(runs) - 1
    found = []
    for first in range(min(both) - 1, max(both) - last + 2):
        # at most one execution at either edge may find no read
        if any(first + i not in both for i in range(1, last)):
            continue
        offs = [s + d - ends[first + i] for i, (s, d) in enumerate(runs)
                if first + i in ends]
        if len(offs) < 3:
            continue
        mid = statistics.median(offs)
        far = [abs(o - mid) for o in offs]
        found.append((statistics.fmean(far), max(far), mid, len(offs), first))
    if not found:
        return None
    found.sort()
    scatter, worst, offset, blocked, first = found[0]
    pairs = [(run, steps[first + i], reads[first + i])
             for i, run in enumerate(runs) if first + i in both]
    # From the other side: a launch runs after its dispatch began ...
    bound = min(s - (step["t0"] * 1e9 + offset) for (s, _), step, _ in pairs)
    if bound < 0:
        return None
    # ... and the device runs launches in the order they were made: as
    # many admission programs between two decode executions as
    # admission spans between their two ``serving.step`` spans.
    admits = [s["t0"] for s in spans
              if s["name"] in ADMIT and "program" in s["fields"]]
    count = lambda xs, lo, hi: (bisect.bisect_left(xs, hi)
                                - bisect.bisect_left(xs, lo))
    for (a, sa, _), (b, sb, _) in zip(pairs, pairs[1:]):
        if count(between, a[0], b[0]) != count(admits, sa["t0"], sb["t0"]):
            return None
    return {"offset_ns": offset, "scatter_us": scatter / 1e3,
            "worst_us": worst / 1e3,
            "next_us": found[1][0] / 1e3 if len(found) > 1 else None,
            "bound_us": bound / 1e3, "pairs": len(pairs),
            "blocked": blocked, "matched": pairs}


class SelfTime:
    """Whose self time an interval of program time is: a span's overlap
    less its children's.  One thread's spans nest, so the top-level
    ones are disjoint and in order."""

    def __init__(self, spans):
        ids = {s["id"] for s in spans}
        self.children = {}
        for s in spans:
            self.children.setdefault(s["parent"], []).append(s)
        self.top = [s for s in spans if s["parent"] not in ids]
        self.starts = [s["t0"] for s in self.top]

    def _own(self, s, lo, hi, out):
        ov = min(hi, s["t0"] + s["dur"]) - max(lo, s["t0"])
        if ov <= 0:
            return 0.0
        own = ov - sum(self._own(c, lo, hi, out)
                       for c in self.children.get(s["id"], ()))
        key = s["name"].split(".", 1)[1]
        out[key] = out.get(key, 0.0) + own
        return ov

    def add(self, lo, hi, out):
        covered = 0.0
        i = max(0, bisect.bisect_right(self.starts, lo) - 1)
        while i < len(self.top) and self.top[i]["t0"] < hi:
            covered += self._own(self.top[i], lo, hi, out)
            i += 1
        out["caller"] = out.get("caller", 0.0) + (hi - lo) - covered


def device_idle(record, spans, offset_ns):
    """The first device's idle gaps — between the pieces of the union
    of its operations' intervals, as ``trace_reduce.reduce`` counts its
    busy time — by covering span, seconds, largest first."""
    devices = record["trace"]["events"]["devices"]
    dev = devices[sorted(devices)[0]]
    _, gaps = trace_reduce.union_length(
        [(s, s + d) for _, s, d in dev["ops"] or dev["modules"] if d > 0])
    own, out = SelfTime(spans), {}
    for s, e in gaps:
        own.add((s - offset_ns) / 1e9, (e - offset_ns) / 1e9, out)
    return dict(sorted(((k, v) for k, v in out.items() if v > 0),
                       key=lambda kv: -kv[1]))


def joined(record, spans):
    """The join of this record, and its ``note`` line."""
    found = join(spans, executions(record, declared(spans, (STEP,))),
                 [s for s, _ in executions(record, declared(spans, ADMIT))])
    if found:
        note = {k: v for k, v in found.items() if k != "matched"}
        note["device_idle_s"] = device_idle(record, spans,
                                           found["offset_ns"])
        print(json.dumps({"note": "span_dispatch", **note}), flush=True)
    return found


def host_share(record, spans):
    lo, hi = record["window"]
    cut = (record.get("profile_window") or (lo, lo))[1]
    rounds = [s for s in spans if s["name"] == ROUND
              and max(lo, cut) <= s["t0"] < hi
              and not s["fields"].get("idle") and "host_ms" in s["fields"]]
    if len(rounds) < 2:
        return None
    period = statistics.median(b["t0"] - a["t0"]
                               for a, b in zip(rounds, rounds[1:]))
    host = statistics.median(r["fields"]["host_ms"] for r in rounds)
    return 100.0 * host / (1e3 * period)


def read(record, args):
    spans = serving_spans(record)
    stat = args["stat"]
    if stat == "host_share":
        return host_share(record, spans)
    if stat == "decode_ms":
        runs = executions(record, declared(spans, (STEP,)))
        return 1e-6 * statistics.median(d for _, d in runs) if runs else None
    if stat == "admit_ms_per_ktok":
        runs = executions(record, declared(spans, ADMIT))
        lo, hi = record.get("profile_window") or (0.0, 0.0)
        n = sum(s["fields"].get("bucket", 0) for s in spans
                if s["name"] in ADMIT and lo <= s["t0"] < hi)
        return (1e-6 * sum(d for _, d in runs) / (n / 1000.0)
                if runs and n else None)
    if stat == "dispatch_lead_ms":
        found = joined(record, spans)
        if not found:
            return None
        return 1e-6 * statistics.median(
            s - ((step["t0"] + step["dur"]) * 1e9 + found["offset_ns"])
            for (s, _), step, _ in found["matched"])
    raise ValueError(f"unknown stat {stat!r}")
