"""Reader for the host's share of a serving round: the **step gap**,
from the end of one round's ``serving.step`` span (the decode step's
tokens are on the host) to the start of the next dispatching span
(``serving.admit``, ``serving.admit_chunk`` or ``serving.step``).  In
that time the device has nothing queued.

The metric is the median gap, in ms, over the rounds of the measured
window that began AFTER the profiler stopped: while it runs, the
Python tracer slows exactly the code the gap is made of.

On an earlier ``note`` line the reader prints what explains the number:

``split_ms``            the mean gap by the span the host was in — the
                        self time of ``emit_loop``, ``reap``, ``pump``,
                        ``round`` (inside ``serving.round`` but in no
                        child), and ``caller`` (between two ``step()``
                        calls: the benchmark's driver) — it sums to the
                        mean gap;
``profiled``            the same over the profiler's stretch: the
                        difference is the Python tracer's own effect;
``anchor``              the offset between the program's clock
                        (``perf_counter``) and the profile's, from
                        pairing the ``_dispatch_step`` frames the
                        Python tracer recorded with as many consecutive
                        ``serving.step`` spans, and its scatter;
``device_idle_s``       through that anchor, the device's idle gaps by
                        the span that covers them — to set beside
                        ``breakdown.idle_gaps``, which asks the Python
                        tracer's frame at each gap's middle.

args: none.  Returns None where the program records no
``serving.round`` span (a program older than the span).
"""

import bisect
import json
import re
import statistics

import trace_reduce

DISPATCH = ("serving.admit", "serving.admit_chunk", "serving.step")
FRAME = re.compile(r"lanes\.py:\d+ _dispatch_step$")


class Spans:
    """The program's ``serving.*`` spans, for "whose self time is this
    interval": a span's overlap less its children's.  The spans of one
    thread nest, so the top-level ones are disjoint and in order."""

    def __init__(self, records):
        self.spans = sorted(
            (r for r in records if r.get("kind") == "span"
             and r["name"].startswith("serving.")), key=lambda r: r["t0"])
        ids = {s["id"] for s in self.spans}
        self.children = {}
        for s in self.spans:
            self.children.setdefault(s["parent"], []).append(s)
        self.top = [s for s in self.spans if s["parent"] not in ids]
        self.top_starts = [s["t0"] for s in self.top]

    def _own(self, s, lo, hi, out):
        ov = min(hi, s["t0"] + s["dur"]) - max(lo, s["t0"])
        if ov <= 0:
            return 0.0
        own = ov - sum(self._own(c, lo, hi, out)
                       for c in self.children.get(s["id"], ()))
        if own > 0:
            key = s["name"].split(".", 1)[1]
            out[key] = out.get(key, 0.0) + own
        return ov

    def split(self, lo, hi):
        """``{span name without "serving.": seconds}`` of ``[lo, hi]``
        by self time, and ``caller`` for what no span covers."""
        out, covered = {}, 0.0
        i = max(0, bisect.bisect_right(self.top_starts, lo) - 1)
        while i < len(self.top) and self.top[i]["t0"] < hi:
            covered += self._own(self.top[i], lo, hi, out)
            i += 1
        out["caller"] = (hi - lo) - covered
        return out


def step_gaps(spans):
    """``[(round's t0, gap start, gap end)]`` for every decode step
    that another dispatch follows."""
    by_id = {s["id"]: s for s in spans.spans}
    dispatch = [s for s in spans.spans if s["name"] in DISPATCH]
    out = []
    for sp, nxt in zip(dispatch, dispatch[1:]):
        rnd = by_id.get(sp["parent"])
        if sp["name"] == "serving.step" and rnd is not None \
                and rnd["name"] == "serving.round" \
                and nxt["t0"] > sp["t0"] + sp["dur"]:
            out.append((rnd["t0"], sp["t0"] + sp["dur"], nxt["t0"]))
    return out


def mean_split(spans, gaps):
    total = {}
    for _, lo, hi in gaps:
        for k, v in spans.split(lo, hi).items():
            total[k] = total.get(k, 0.0) + v
    return {k: 1e3 * v / len(gaps) for k, v in sorted(total.items())}


def anchor(spans, events):
    """``(offset_ns, scatter_ns, n)``: profile time = program time x
    1e9 + offset.  The Python tracer's ``_dispatch_step`` frames are
    the decode dispatches of the profiler's stretch, in order; they are
    laid against every run of as many consecutive ``serving.step``
    spans, and the run that agrees best (smallest scatter, the largest
    distance of a pair's offset from the median) is the pairing.  None
    without frames, or with more frames than spans."""
    frames = sorted(s for name, s, _ in events.get("host", ())
                    if FRAME.search(name))
    steps = [s["t0"] * 1e9 for s in spans.spans
             if s["name"] == "serving.step"]
    best = None
    for j in range(len(steps) - len(frames) + 1 if frames else 0):
        offs = [f - t for f, t in zip(frames, steps[j:])]
        mid = statistics.median(offs)
        scatter = max(abs(o - mid) for o in offs)
        if best is None or scatter < best[1]:
            best = (mid, scatter, len(offs))
    return best


def device_idle(spans, events, offset_ns):
    """The first device's idle gaps, in program time, by covering
    span."""
    devs = sorted(events.get("devices", ()))
    ops = events["devices"][devs[0]]["ops"] if devs else []
    _, gaps = trace_reduce.union_length(
        [(s, s + d) for lab, s, d in ops if d > 0
         and not trace_reduce.CONTAINER.match(trace_reduce.op_name(lab))])
    total = {}
    for s, e in gaps:
        for k, v in spans.split((s - offset_ns) / 1e9,
                                (e - offset_ns) / 1e9).items():
            total[k] = total.get(k, 0.0) + v
    return {k: v for k, v in sorted(total.items(), key=lambda kv: -kv[1])}


def read(record, args):
    spans = Spans(record.get("obs_events", ()))
    gaps = step_gaps(spans)
    if not gaps:
        return None
    lo, hi = record["window"]
    stretch = record.get("profile_window") or (lo, lo)
    clean = [g for g in gaps if max(lo, stretch[1]) <= g[0] < hi]
    traced = [g for g in gaps if stretch[0] <= g[0] < stretch[1]]
    note = {"note": "span_gaps", "rounds": len(clean)}
    if clean:
        note["split_ms"] = mean_split(spans, clean)
    if traced:
        note["profiled"] = {
            "rounds": len(traced),
            "median_ms": 1e3 * statistics.median(e - s for _, s, e in traced),
            "split_ms": mean_split(spans, traced)}
    events = (record.get("trace") or {}).get("events") or {}
    found = anchor(spans, events)
    if found:
        off, scatter, n = found
        note["anchor"] = {"offset_ns": off, "scatter_us": scatter / 1e3,
                          "pairs": n}
        note["device_idle_s"] = device_idle(spans, events, off)
    print(json.dumps(note), flush=True)
    if not clean:
        return None
    return 1e3 * statistics.median(e - s for _, s, e in clean)
