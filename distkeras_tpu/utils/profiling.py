"""Tracing & step timing (SURVEY.md §5: reference has `training_time` only).

The reference's entire observability surface is one wall-clock number
recorded by ``Trainer.train`` (reference: distkeras/trainers.py) plus
whatever the Spark UI shows.  Here:

* :func:`trace` — context manager writing an XLA/TPU profile (HLO
  timelines, per-op HBM/MXU utilization) viewable in TensorBoard or
  Perfetto, via ``jax.profiler``.
* :class:`StepTimer` — cheap per-step wall-clock stats with correct
  device synchronization at the measurement boundaries only (never
  inside the loop, which would stall the TPU pipeline).

Named regions on the profile timeline are ``obs.span``: with a
telemetry session active every span is also a
``jax.profiler.TraceAnnotation`` of the same name (docs/observability.md).
"""

from __future__ import annotations

import contextlib
import statistics
import time

import jax

from distkeras_tpu import obs


@contextlib.contextmanager
def trace(logdir: str):
    """Profile everything in the block into ``logdir``.

    View with ``tensorboard --logdir`` (profile plugin) or upload the
    ``.trace.json.gz`` to Perfetto.
    """
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class StepTimer:
    """Wall-clock stats over repeated step calls.

    Usage::

        timer = StepTimer()
        with timer.round():           # sync boundary outside the loop
            for batch in batches:
                state, loss = step(state, *batch)
        timer.finalize(state)         # blocks, closes the open round
        timer.mean_step_s, timer.p50_round_s, timer.samples_per_sec(n)

    Device work is async: individual step dispatches return immediately,
    so per-call timing lies.  The timer therefore measures *rounds*
    (sync → work → sync) and divides by the step count you report.

    **Named phase counters** (``phase``/``phase_s``/``phase_stats``)
    accumulate host wall time per phase across the run — the
    distributed trainers record ``"h2d"`` (host-side batch staging +
    transfer dispatch) and ``"step"`` (the jitted
    reduce-scatter+update+gather dispatch), so an input-bound run is
    distinguishable from a compute-bound one without a profiler.  The
    *device-side* split of a step — reduce vs update vs gather — is by
    design not host-observable (overlap means those regions interleave
    on the timeline); the ZeRO-1 update tags them with
    ``jax.named_scope`` (``zero1/reduce_scatter``, ``zero1/update``,
    ``zero1/all_gather``) so :func:`trace` profiles show the overlap,
    and ``scripts/bench_suite.py zero1_update`` measures the update
    phase as a number.

    The timer is also the **span frontend of the obs subsystem**
    (``distkeras_tpu.obs``, docs/observability.md): with a telemetry
    session active, every ``phase`` block is recorded as a trace span
    ``{scope}.{name}`` and every closed round as a ``{scope}.round``
    event, so a whole run's phase timeline reconstructs offline via
    ``scripts/obs_report.py``.  Disabled (the default), the obs hooks
    are a module-attr ``is None`` check — the timer stays hot-loop
    cheap either way.

    State persists across rounds but NOT across runs: call
    :meth:`reset` at the start of each run (the trainers do, at the
    top of every ``train()``), so ``phase_stats`` always describes the
    run just measured instead of silently accumulating across
    ``train()`` calls.
    """

    def __init__(self, scope: str = "train"):
        self.scope = scope
        self.rounds: list[tuple[float, int]] = []  # (seconds, n_steps)
        self.phases: dict[str, tuple[float, int]] = {}  # name -> (s, calls)
        self._t0: float | None = None
        self._n = 0

    def reset(self) -> None:
        """Drop all recorded rounds and phase stats (fresh run).  Any
        open round is abandoned, not recorded."""
        self.rounds = []
        self.phases = {}
        self._t0 = None
        self._n = 0

    @contextlib.contextmanager
    def phase(self, name: str):
        """Accumulate host wall time under ``name`` (re-entrant safe to
        nest *different* names; never syncs the device — wrap dispatch
        sites, then ``finalize`` closes the round with one barrier).
        Doubles as an obs trace span when telemetry is enabled."""
        t0 = time.perf_counter()
        try:
            if obs.active() is None:  # keep the disabled path
                yield self           # allocation-free (no f-string)
            else:
                with obs.span(f"{self.scope}.{name}"):
                    yield self
        finally:
            dt = time.perf_counter() - t0
            s, c = self.phases.get(name, (0.0, 0))
            self.phases[name] = (s + dt, c + 1)
            if obs.active() is not None:
                # Per-phase latency histogram (e.g. ``train.step_s``):
                # what the rolling-window SLO engine diffs for a LIVE
                # step-time percentile, where the spans above only
                # reconstruct offline.
                obs.observe(f"{self.scope}.{name}_s", dt)

    def phase_s(self, name: str) -> float:
        """Total seconds accumulated under ``name`` (0.0 if unused)."""
        return self.phases.get(name, (0.0, 0))[0]

    def phase_stats(self) -> dict:
        """``{name: {"total_s", "calls", "mean_s"}}`` for every phase."""
        return {name: {"total_s": s, "calls": c,
                       "mean_s": s / c if c else 0.0}
                for name, (s, c) in self.phases.items()}

    @contextlib.contextmanager
    def round(self, n_steps: int = 0):
        self._t0 = time.perf_counter()
        self._n = n_steps
        yield self
        # finalize() closes the round after the caller syncs.

    def count(self, n: int = 1) -> None:
        self._n += n

    def finalize(self, *sync_refs) -> None:
        """Block on ``sync_refs`` (device arrays) and close the round."""
        if sync_refs:
            jax.block_until_ready(sync_refs)
        if self._t0 is not None:
            dur = time.perf_counter() - self._t0
            self.rounds.append((dur, self._n))
            obs.event(f"{self.scope}.round", dur_s=dur, n_steps=self._n)
            self._t0 = None
            self._n = 0

    # ------------------------------------------------------------- stats

    @property
    def total_s(self) -> float:
        return sum(s for s, _ in self.rounds)

    @property
    def total_steps(self) -> int:
        return sum(n for _, n in self.rounds)

    @property
    def mean_step_s(self) -> float:
        n = self.total_steps
        return self.total_s / n if n else 0.0

    @property
    def p50_round_s(self) -> float:
        return statistics.median(s for s, _ in self.rounds) if self.rounds else 0.0

    def samples_per_sec(self, samples_per_step: int) -> float:
        return (samples_per_step * self.total_steps / self.total_s
                if self.total_s else 0.0)
