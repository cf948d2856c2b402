"""From a profiler trace (``.xplane.pb``) to numbers.

The reduction is in two halves so that it can be pinned by a test on a
small recorded trace (``benchmarks/tests/data/``):

``load_events(dir)``  reads the newest ``*.xplane.pb`` under a profiler
    directory with ``jax.profiler.ProfileData`` into plain dicts:
    ``{"devices": {plane: {"ops": [...], "modules": [...]}},
       "host": [...]}``, every event ``[label, start_ns, dur_ns]``.
``reduce(events, ...)``  computes, per device and averaged over the
    devices used: the busy time (the union of the intervals in which
    an operation ran), the traced window (first operation's start to
    last operation's end on any device), the time by operation, and the
    longest idle gaps by what the host's Python was doing in them.

A device plane is ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO operation and ``XLA Modules`` one per executed
program.  An event's label is its name followed by its string-valued
stats (``tf_op``, ``long_name``, ...).  On this runtime an operation's
name is its whole HLO instruction, ``%name.n = shape op(operands),
attributes``: ``op_name`` cuts the operation's own name out of it, and
that is what a reader's pattern is matched against.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Host frames worth naming in an idle gap: the program's and the
# benchmark's own files, innermost first.
HOST_FRAME = re.compile(
    r"(lanes|engine|admission|elastic|lm|prefetch|dataset|packing|"
    r"serve_engine|train_lm)\.py:\d+ (\w+)")


def newest_xplane(profile_dir):
    files = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return files[-1]


def _label(ev):
    parts = [ev.name]
    try:
        for k, v in ev.stats:
            if isinstance(v, str) and v and k in (
                    "tf_op", "long_name", "hlo_op", "name", "kernel_details",
                    "hlo_category"):
                parts.append(v)
    except Exception:       # a stat the binding cannot decode: name only
        pass
    return " | ".join(parts)


def load_events(profile_dir):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(newest_xplane(profile_dir))
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in pd.planes:
        lines = list(plane.lines)
        out["lines"][plane.name] = [ln.name for ln in lines]
        if DEVICE_PLANE.match(plane.name):
            dev = out["devices"].setdefault(plane.name,
                                            {"ops": [], "modules": []})
            for ln in lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(ln.name)
                if key:
                    dev[key] = [[_label(e), int(e.start_ns),
                                 int(e.duration_ns)] for e in ln.events]
        elif plane.name.startswith("/host:CPU"):
            # The main Python thread: the line with the most events
            # that carries Python frames ("$file.py:line fn").
            best = []
            for ln in lines:
                evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in ln.events if e.name.startswith("$")]
                if len(evs) > len(best):
                    best = evs
            out["host"] = best
    return out


def union_length(intervals):
    """Total length of the union of ``(start, end)`` intervals, and the
    gaps between its pieces as ``(start, end)``."""
    busy, gaps, cur_s, cur_e = 0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


class HostFrames:
    """The host's Python frames, for "which frame covers instant t"."""

    def __init__(self, host):
        import numpy as np

        self.names = [h[0] for h in host]
        self.start = np.asarray([h[1] for h in host], np.int64)
        self.dur = np.asarray([h[2] for h in host], np.int64)
        self.ours = np.asarray([bool(HOST_FRAME.search(n))
                                for n in self.names], bool)

    def label(self, t):
        """The innermost frame of the program or the benchmark that
        covers ``t``, else the innermost frame of any file."""
        import numpy as np

        if not self.names:
            return "host: no frame recorded"
        cover = (self.start <= t) & (t < self.start + self.dur)
        for mask in (cover & self.ours, cover):
            idx = np.nonzero(mask)[0]
            if idx.size:
                name = self.names[idx[np.argmin(self.dur[idx])]]
                m = HOST_FRAME.search(name)
                return (f"{m.group(1)}.py:{m.group(2)}" if m
                        else name.lstrip("$"))
        return "host: no frame recorded"


MOSAIC = 'custom_call_target="tpu_custom_call"'


def op_name(label):
    """An event's own name: ``fusion.12`` of ``%fusion.12 = f32[..]
    fusion(.., %all-gather.7), ..``, ``jit_step(123)`` of a program.
    The rest of an operation's label is the HLO instruction's text,
    which NAMES ITS OPERANDS: a pattern meant for the operation must
    not see them, or a product that reads a gathered weight counts as
    a collective."""
    return label.split(" = ")[0].split(" | ")[0].lstrip("%")


def op_group(label):
    """An operation's name without its instance number — ``fusion.12``
    and ``fusion.7`` are one row of the breakdown — from a label that
    is the whole HLO instruction (``%name.n = shape op(...)``).  A
    Mosaic (Pallas) kernel is marked as such: the compiler names it
    after the JAX scope it sits in (``checkpoint``, ``jvp__``)."""
    name = op_name(label)
    name = re.sub(r"[.\d]+$", "", name) or name
    return f"mosaic:{name}" if MOSAIC in label else name


def reduce(events, n_devices=None, top=10):
    devs = sorted(events["devices"])
    if n_devices:
        devs = devs[:n_devices]
    per, t_first, t_last = [], None, None
    for name in devs:
        ops = events["devices"][name]["ops"] or events["devices"][name]["modules"]
        if not ops:
            continue
        iv = [(s, s + d) for _, s, d in ops if d > 0]
        busy, gaps = union_length(iv)
        per.append((name, ops, busy, gaps))
        lo, hi = min(s for s, _ in iv), max(e for _, e in iv)
        t_first = lo if t_first is None else min(t_first, lo)
        t_last = hi if t_last is None else max(t_last, hi)
    if not per:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "by_device": {}}
    window = (t_last - t_first) / 1e9
    by_op, by_dev = {}, {}
    for name, ops, busy, gaps in per:
        by_dev[name] = busy / 1e9
        for label, _, d in ops:
            g = op_group(label)
            by_op[g] = by_op.get(g, 0.0) + d / 1e9 / len(per)
    # Idle gaps of the first device, by what the host was doing at
    # each gap's middle; only the longest few hundred are looked up.
    name, ops, busy, gaps = per[0]
    by_host = {}
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:300]
    host = HostFrames(events.get("host") or [])
    for s, e in longest:
        lab = host.label((s + e) // 2)
        by_host[lab] = by_host.get(lab, 0.0) + (e - s) / 1e9
    rest = sum(e - s for s, e in gaps) - sum(e - s for s, e in longest)
    if rest > 0:
        by_host["gaps not looked up (short)"] = rest / 1e9
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": sum(by_dev.values()) / len(by_dev), "window_s": window,
            "device_ops": rank(by_op), "idle_gaps": rank(by_host),
            "by_device": by_dev, "n_gaps": len(gaps)}


# Operations that only contain others (their bodies' operations are
# events of their own on the same line): not work that could hide a
# collective, so ``exposed`` leaves them out.
CONTAINER = re.compile(r"^(while|conditional|call)([.\d]*)$")


def matching(events, pattern, line="ops", text=False):
    """``[duration_s, ...]`` of the events of ``line`` that match
    ``pattern``, on the first device (every device runs the same
    program under SPMD).  The pattern is searched in the event's own
    name; ``text=True`` searches the whole label (an attribute of the
    instruction, such as a Mosaic kernel's ``custom_call_target``)."""
    rx = re.compile(pattern)
    devs = sorted(events["devices"])
    if not devs:
        return []
    return [d / 1e9 for label, _, d in events["devices"][devs[0]][line]
            if rx.search(label if text else op_name(label))]


def exposed(events, pattern, n_devices=None):
    """Seconds, averaged over the devices, in which an operation whose
    own name matches ``pattern`` ran and no other operation did."""
    rx = re.compile(pattern)
    devs = sorted(events["devices"])[:n_devices or None]
    total = []
    for name in devs:
        coll, other = [], []
        for lab, s, d in events["devices"][name]["ops"]:
            own = op_name(lab)
            if d <= 0 or CONTAINER.match(own):
                continue
            (coll if rx.search(own) else other).append((s, s + d))
        both, _ = union_length(coll + other)
        only_other, _ = union_length(other)
        total.append((both - only_other) / 1e9)
    return sum(total) / len(total) if total else None


def summarize(profile_dir, n_devices=None, dump=None):
    """``reduce`` of the newest trace under ``profile_dir``, with the
    events kept under ``"events"`` for the readers.  ``dump``: a path
    for a short listing of planes, lines and the heaviest labels (what
    a builder looks at before writing a reader's pattern)."""
    events = load_events(profile_dir)
    out = reduce(events, n_devices=n_devices)
    out["events"] = events
    if dump:
        listing = {"lines": events["lines"], "summary": {
            k: out[k] for k in ("busy_s", "window_s", "device_ops",
                                "idle_gaps", "by_device")}}
        for name, dev in list(events["devices"].items())[:1]:
            for key in ("ops", "modules"):
                agg = {}
                for label, _, d in dev[key]:
                    a = agg.setdefault(label[:300], [0, 0.0])
                    a[0] += 1
                    a[1] += d / 1e9
                listing[f"{name} {key}"] = sorted(
                    ([k, n, s] for k, (n, s) in agg.items()),
                    key=lambda r: -r[2])[:60]
        with open(dump, "w") as f:
            json.dump(listing, f, indent=1)
        save_sample(events, dump[:-5] + "_sample.json.gz")
    return out


def save_sample(events, path, span_ns=100_000_000):
    """A cut of a trace small enough to commit: every event of the
    device lines and of the host's Python thread that starts within
    ``span_ns`` of the first device operation."""
    starts = [e[1] for d in events["devices"].values() for e in d["ops"]]
    if not starts:
        return
    lo = min(starts)
    keep = lambda evs: [e for e in evs if lo <= e[1] < lo + span_ns]
    cut = {"devices": {n: {k: keep(v) for k, v in d.items()}
                       for n, d in events["devices"].items()},
           "host": [e for e in events["host"]
                    if e[1] < lo + span_ns and e[1] + e[2] > lo],
           "lines": events["lines"]}
    with gzip.open(path, "wt") as f:
        json.dump(cut, f)


def load_sample(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)
