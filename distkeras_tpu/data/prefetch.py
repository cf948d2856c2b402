"""Background batch prefetching.

The reference overlaps input with compute for free — Spark executors
iterate their partition while the JVM fetches the next (reference:
workers.py consuming mapPartitions iterators).  Here the equivalent is
a small host-side pipeline: a daemon thread runs the batch iterator
(shuffle-gather, windows, dtype conversion) ``depth`` elements ahead of
the training loop, so batch preparation overlaps the device step that
jax dispatches asynchronously.
"""

from __future__ import annotations

import collections
import queue
import threading
from typing import Iterable, Iterator

from distkeras_tpu import obs


class DeviceFeed:
    """Stream host batches to the device, ``depth`` items in flight.

    ``jax.device_put`` is asynchronous: issuing the next window's
    transfer *before* the consumer executes on the current one lets the
    host->device copy ride under the device step.  Transfers are issued
    from the consuming thread: unlike :class:`Prefetcher` this is
    single-threaded lookahead, not a producer thread (a choice that has
    not been re-examined on a directly attached chip).

    Feed it window-stacked batches (``[steps_per_call, B, ...]`` pytrees
    of numpy arrays) and consume with a multi-step jitted call: one
    execution per window amortizes the per-dispatch overhead that
    dominates small-step training, and the next window's bytes stream
    while the scan runs.  Ship the smallest dtype you can (uint8 pixels,
    int32 tokens) and expand/normalize on device — the h2d link, not
    HBM, is the input pipeline's narrow point (see ModelAdapter's
    ``preprocess`` hook).
    """

    def __init__(self, source: Iterable, depth: int = 2, sharding=None):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._source = source
        self._depth = depth
        self._sharding = sharding

    def __iter__(self):
        import jax

        pending: collections.deque = collections.deque()
        for item in self._source:
            # device_put maps over pytrees itself and coalesces the
            # leaves into one batched transfer.  The obs span measures
            # *dispatch* wall time (the transfer itself rides under
            # the device step — that overlap is the point); the bytes
            # counter sizes the h2d stream exactly.
            if obs.active() is not None:
                obs.count("data.h2d.bytes",
                          sum(getattr(x, "nbytes", 0)
                              for x in jax.tree.leaves(item)))
                obs.count("data.h2d.items")
            with obs.span("data.h2d"):
                pending.append(jax.device_put(item, self._sharding)
                               if self._sharding is not None
                               else jax.device_put(item))
            if len(pending) > self._depth:
                yield pending.popleft()
        while pending:
            yield pending.popleft()


class Prefetcher:
    """Iterate ``source`` on a background thread, ``depth`` items ahead.

    Exceptions in the source re-raise in the consumer (once; the
    iterator is exhausted afterwards, like a generator).  Abandoning the
    iterator mid-stream is safe: ``close()`` — called by ``__del__`` and
    usable explicitly — unblocks and stops the producer thread.
    """

    _DONE = object()

    def __init__(self, source: Iterable, depth: int = 2):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._err: BaseException | None = None
        self._finished = False
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, args=(iter(source),),
            name="dkt-prefetch", daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Enqueue unless closed; False means stop producing."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _run(self, it: Iterator) -> None:
        try:
            for item in it:
                if not self._put(item):
                    return
        except BaseException as e:  # propagate to consumer
            self._err = e
        finally:
            self._put(self._DONE)

    def close(self) -> None:
        """Stop the producer and release buffered items.

        Also wakes any consumer already blocked in ``__next__`` (the
        drain below could otherwise swallow the producer's ``_DONE``
        sentinel and leave that consumer blocked forever).
        """
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._finished = True
        try:
            self._q.put_nowait(self._DONE)
        except queue.Full:
            pass  # a queued item will wake the consumer instead

    def __del__(self):  # pragma: no cover - GC timing
        self.close()

    def __iter__(self):
        return self

    def __next__(self):
        if self._finished:
            raise StopIteration
        while True:
            try:
                item = self._q.get(timeout=0.05)
                break
            except queue.Empty:
                if self._stop.is_set():
                    raise StopIteration from None
        # Buffer occupancy at consumption: a gauge pinned near 0 means
        # the producer can't keep up (input-bound run); near `depth`
        # means compute-bound.  qsize() takes the queue mutex, so it
        # is guarded — the disabled path must stay free.
        if obs.active() is not None:
            obs.gauge("data.prefetch.occupancy", self._q.qsize())
        if item is self._DONE:
            self._finished = True
            if self._err is not None:
                err, self._err = self._err, None
                raise err
            raise StopIteration
        return item
