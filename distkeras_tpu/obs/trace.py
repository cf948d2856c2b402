"""Structured event trace: nestable spans + point events as JSONL.

One run = one append-only JSONL file.  Every record carries a
monotonic timestamp ``t`` (``time.perf_counter`` — durations and
ordering are exact within the process), the run id, and the host
(``jax`` process index when available) / OS pid, so a multi-host run's
per-host files can be merged and a whole training or serving session
reconstructed — and *diffed* — offline (scripts/obs_report.py).

Record kinds:

``meta``   — first line: run id, host/pid, unix wall time anchor (maps
             monotonic ``t`` to wall clock), and the clock anchor
             ``perf_counter_ns`` / ``time_ns`` read back to back: the
             pair that places every ``t`` / ``t0`` of this file on the
             wall clock to the nanosecond — and so beside a
             ``jax.profiler`` trace, whose host plane also carries
             every span (see ``annotation`` below).
``event``  — a point in time: ``{"kind": "event", "name", "t",
             "fields": {...}}`` (chaos faults, supervisor attempts,
             admission rejects).
``span``   — a closed interval, written at END: ``{"kind": "span",
             "name", "t0", "dur", "id", "parent", "depth",
             "fields"}``.  Nesting is tracked per thread; ``parent``
             is the enclosing span's id (None at top level), so the
             tree reconstructs without begin/end pairing.
``metrics``— a full registry snapshot (the obs session appends one on
             close), so a trace file is self-contained for reports; it
             repeats the clock anchor, so the two clocks' drift over
             the run is the difference of the two pairs.

**Records stay in memory.**  Recording a span or an event appends one
dict to a list; nothing is serialised on the recording thread until
:meth:`EventTrace.flush`, :meth:`EventTrace.close`, or the buffer
passing ``FLUSH_RECORDS`` records or ``FLUSH_AGE_S`` seconds (checked
when a record arrives — no thread).  So the file, and with it
``/trace/tail``, lags the program by at most that much while records
keep arriving, and a crash loses at most one buffer; what reached the
file is whole lines, a parseable prefix.

Thread safety: one lock around the buffer and the file; span stacks
are thread-local.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
import uuid

from distkeras_tpu.utils.locks import TracedLock


def _host_index() -> int:
    """jax process index if jax is already initialized; 0 otherwise.
    Deliberately does NOT import/initialize a backend."""
    import sys

    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return jax.process_index()
        except Exception:
            return 0
    return 0


def _clock_anchor() -> dict:
    """The monotonic clock of every ``t`` / ``t0`` and the wall clock,
    read back to back (sub-microsecond apart)."""
    return {"perf_counter_ns": time.perf_counter_ns(),
            "time_ns": time.time_ns()}


class Span:
    """The context manager :meth:`EventTrace.span` returns, and the
    handle its ``with`` yields — carries the ids and accepts late
    fields (``span.fields["x"] = ...`` before exit)."""

    __slots__ = ("name", "id", "parent", "depth", "t0", "fields",
                 "_trace", "_ann")

    def __init__(self, trace, name, fields):
        self._trace = trace
        self.name = name
        self.fields = fields

    def __enter__(self):
        tr = self._trace
        st = tr._stack()
        self.parent = st[-1].id if st else None
        self.depth = len(st)
        self.id = next(tr._ids)
        st.append(self)
        ann = tr.annotation
        self._ann = ann(self.name) if ann is not None else None
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._trace._stack().pop()
        self._trace._write({"kind": "span", "name": self.name,
                            "t0": self.t0, "dur": t1 - self.t0,
                            "id": self.id, "parent": self.parent,
                            "depth": self.depth, "fields": self.fields},
                           t1)


class EventTrace:
    """JSONL trace writer (see module docstring for the record model).

    ``path``: output file (parent dirs created).  ``run_id`` defaults
    to a fresh ``uuid4`` hex prefix.  Records are buffered in memory
    and written by :meth:`flush`, by :meth:`close` (or leaving the
    ``with``), and whenever a record arrives to a buffer of
    ``FLUSH_RECORDS`` records or one older than ``FLUSH_AGE_S``: a
    crash loses at most that one buffer, and the file always holds
    whole lines.

    ``annotation``: ``name -> context manager`` entered around every
    span, or None.  The obs session sets it to
    ``jax.profiler.TraceAnnotation``, which puts each span on the
    profiler's host plane under the same name — this module itself
    never imports jax.
    """

    FLUSH_RECORDS = 4096
    FLUSH_AGE_S = 1.0

    def __init__(self, path: str, run_id: str | None = None):
        self.path = os.path.abspath(path)
        self.run_id = run_id or uuid.uuid4().hex[:12]
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        # "w", not "a": one run = one file (the module contract).
        # Reusing a path across runs must not blend two runs' records
        # — their monotonic clocks have different epochs, so a merged
        # file would report meaningless relative times.
        self._f = open(self.path, "w", encoding="utf-8")
        # Leaf lock: guards the buffer and the file handle; span
        # stacks are thread-local, not locked.
        self._lock = TracedLock("obs.trace")
        self._tls = threading.local()
        self._ids = itertools.count(1)   # next() is atomic in CPython
        self._buf: list[dict] = []
        self._buf_t0 = 0.0               # arrival of the oldest record
        self.annotation = None
        self.host = _host_index()
        self.pid = os.getpid()
        now = time.perf_counter()
        self._write({"kind": "meta", "run": self.run_id,
                     "host": self.host, "pid": self.pid,
                     "t": now, "time_unix": time.time(),
                     **_clock_anchor()}, now)
        self.flush()    # the file names its run from the first instant

    # ------------------------------------------------------------ write

    def _write(self, rec: dict, now: float) -> None:
        """Buffer one record (``now``: the ``perf_counter`` reading the
        caller already took); past the size or age bound, write the
        buffer out first."""
        with self._lock:
            buf = self._buf
            if not buf:
                self._buf_t0 = now
            buf.append(rec)
            if (len(buf) >= self.FLUSH_RECORDS
                    or now - self._buf_t0 >= self.FLUSH_AGE_S):
                self._flush_locked()

    def _flush_locked(self) -> None:
        buf, self._buf = self._buf, []
        if buf and not self._f.closed:
            self._f.write("".join(
                json.dumps(rec, default=str) + "\n" for rec in buf))
            self._f.flush()

    def flush(self) -> None:
        """Serialise and write what is buffered (and flush the file)."""
        with self._lock:
            self._flush_locked()

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    # -------------------------------------------------------------- API

    def event(self, name: str, **fields) -> None:
        """Record a point event now."""
        st = self._stack()
        now = time.perf_counter()
        self._write({"kind": "event", "name": name, "t": now,
                     "span": st[-1].id if st else None,
                     "fields": fields}, now)

    def span(self, name: str, **fields) -> Span:
        """Record a closed interval around the ``with`` block; nests
        per thread.  The record is made at exit (one line per span)."""
        return Span(self, name, fields)

    def metrics(self, snapshot: dict) -> None:
        """Append a full metrics-registry snapshot record (with the
        clock anchor read again: the drift since ``meta``)."""
        now = time.perf_counter()
        self._write({"kind": "metrics", "t": now, **_clock_anchor(),
                     "data": snapshot}, now)

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._flush_locked()
                self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def tail_trace(path: str, n: int, kinds=None) -> list[dict]:
    """The last ``n`` records of a trace file, with the same
    torn-final-line tolerance as :func:`read_trace` — safe against a
    LIVE writer (the telemetry server's ``/trace/tail`` calls this
    while the session is still appending; a half-flushed last line is
    dropped, never an error).  Reads a bounded window from the end of
    the file, not the whole trace.  ``kinds``: keep only these record
    kinds (e.g. ``("event",)``)."""
    if n <= 0:
        return []
    # Generous per-record bound: read enough tail bytes for n records
    # plus one potentially-torn leading line, growing if the window
    # started mid-file and yielded too few parseable lines.
    window = max(n * 512, 8192)
    records: list[dict] = []
    with open(path, "rb") as f:
        f.seek(0, os.SEEK_END)
        size = f.tell()
        while True:
            start = max(size - window, 0)
            f.seek(start)
            chunk = f.read(size - start).decode("utf-8", "replace")
            lines = chunk.splitlines()
            if start > 0 and lines:
                lines = lines[1:]  # first line may start mid-record
            records = []
            for line in lines:
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn live write (tail) — lenient here
                if kinds is None or rec.get("kind") in kinds:
                    records.append(rec)
            if len(records) >= n or start == 0:
                break
            window *= 4
    return records[-n:]


def read_trace(path: str) -> list[dict]:
    """Parse one JSONL trace file back into records (strict: a
    truncated final line — crashed writer — is tolerated, anything
    else malformed raises)."""
    records = []
    with open(path, encoding="utf-8") as f:
        lines = f.read().splitlines()
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        try:
            records.append(json.loads(line))
        except ValueError:
            if i == len(lines) - 1:
                break  # torn final write from a crashed run
            raise
    return records


__all__ = ["EventTrace", "Span", "read_trace", "tail_trace"]
