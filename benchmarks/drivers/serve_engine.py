"""Serving driver: one engine on one chip, driven by a single-threaded
loop in the process that holds the chip.

    admit what is due (``enqueue``) -> ``step()`` -> stamp every token
    the step returned against the host clock -> repeat

``step()`` ends in ``np.asarray(tokens)``, so when it returns the
tokens exist on the host: the stamp after it is the instant a client
could have been sent them.  Open loop: a request is timed from the
instant it was DUE, not from when the loop got round to it; how late
the loop ran is reported on an earlier line.  Closed loop: a client
sends its next request as soon as its last one finished.

From the program the driver takes the engine (public surface only:
``enqueue``, ``pump``, ``step``, ``take``, ``free_lanes``, ``lanes``)
and, in a traced run, its ``obs`` event trace.  ``step()`` names lanes,
not requests, so the driver keeps its own lane table: the lanes that
left ``free_lanes()`` across an ``enqueue``/``pump`` went, lowest
first, to the requests admitted in FIFO order.  That is checked, not
trusted: every finished request's stamped tokens have to equal the
engine's own transcript, or the run is not ``correct``.
"""

from __future__ import annotations

import json
import os
import re
import time

import numpy as np


class Req:
    __slots__ = ("idx", "rid", "due", "prompt", "max_new", "stamps",
                 "tokens", "done", "status", "in_window")

    def __init__(self, idx, due, prompt, max_new):
        self.idx, self.due, self.prompt, self.max_new = (idx, due, prompt,
                                                         max_new)
        self.rid = None
        self.stamps, self.tokens = [], []
        self.done = False
        self.status = "queued"
        self.in_window = False


def percentile(values, q):
    """The q-th percentile, by linear interpolation (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


# An end-to-end tail a cell may list: ``ttft_p95_ms`` (from the instant a
# request was DUE to its first token) or ``itl_p90_ms`` (gap between
# consecutive tokens of one request), any percentile.
TAIL = re.compile(r"^(ttft|itl)_p(\d{1,2})_ms$")


def window_tokens(reqs, t_begin, t_end):
    """Tokens stamped in ``(t_begin, t_end]``, and the gaps between
    consecutive tokens of one request that END there."""
    n = sum(1 for r in reqs for s in r.stamps if t_begin < s <= t_end)
    gaps = [b - a for r in reqs for a, b in zip(r.stamps, r.stamps[1:])
            if t_begin < b <= t_end]
    return n, gaps


def first_token_times(reqs, worst):
    """Seconds from the instant each request was DUE to its first
    token, and how many failed.  A request that failed, was refused or
    did not finish counts as the worst: its first token took until
    ``worst`` (the end of the drain)."""
    ttft = [(r.stamps[0] if r.stamps else worst) - r.due for r in reqs]
    failed = sum(1 for r in reqs if not (r.done and r.status == "ok"))
    return ttft, failed


class Loop:
    """The lane table, the stamping and the bookkeeping shared by the
    warm-up, the ramp and the measured window."""

    def __init__(self, engine, sample):
        self.engine = engine
        self.sample = sample          # trace run: occupancy per step
        self.by_lane = {}
        self.waiting = []             # enqueued, not yet in a lane (FIFO)
        self.finished = []
        self.mismatch = 0
        self.live_positions = 0
        self.steps = 0
        self.busy_sum = 0
        self.kv_sum = 0
        self.samples = 0

    def outstanding(self):
        return len(self.by_lane) + len(self.waiting)

    def _seat(self, before):
        """Lanes that left the free set since ``before``: lowest first,
        to the waiting requests in FIFO order."""
        gone = sorted(set(before) - set(self.engine.free_lanes()))
        for lane in gone:
            self.by_lane[lane] = self.waiting.pop(0)

    def enqueue(self, r):
        before = self.engine.free_lanes()
        self.waiting.append(r)
        r.rid = self.engine.enqueue(r.prompt, r.max_new)
        self._seat(before)

    def step(self, clock):
        eng = self.engine
        before = eng.free_lanes()
        if before and self.waiting:
            eng.pump()
            self._seat(before)
        out = eng.step()
        t = clock()
        self.steps += 1
        for lane, toks in out.items():
            if not toks:
                continue
            r = self.by_lane[lane]
            if not r.tokens:
                # The whole prompt is in the cache now.  How far a
                # chunked prefill has got the public surface does not
                # say, so a prompt on its way counts nothing: the
                # sampled fill is a lower bound.
                self.live_positions += len(r.prompt)
            r.tokens.extend(toks)
            r.stamps.extend([t] * len(toks))
            self.live_positions += len(toks)
            if len(r.tokens) >= r.max_new:
                self._finish(lane, r)
        if self.sample:
            self.busy_sum += len(eng.running())
            self.kv_sum += self.live_positions
            self.samples += 1
        return t

    def _finish(self, lane, r):
        res = self.engine.take(r.rid)     # KeyError: not finished after all
        r.done = True
        r.status = res.status
        if (not res.ok or len(res.generated) != len(r.tokens)
                or (np.asarray(res.generated) != np.asarray(r.tokens)).any()):
            self.mismatch += 1
        del self.by_lane[lane]
        self.live_positions -= len(r.prompt) + len(r.tokens)
        self.finished.append(r)


def run(ctx):
    import jax
    import jax.numpy as jnp

    from distkeras_tpu import obs, serving
    from distkeras_tpu.models import transformer as tfm

    conf, mix = ctx.conf, ctx.mix
    cfg = tfm.TransformerConfig(**conf["transformer_config"])
    eng_spec = conf["engine"]
    dtype = jnp.dtype(conf["param_dtype"])
    clock = time.perf_counter

    # Weights: on the device, from the seed, in the type they are
    # served in, in one jitted call.
    def make_params(key):
        return jax.tree.map(lambda a: a.astype(dtype),
                            tfm.init_params(key, cfg))

    params = jax.jit(make_params)(jax.random.key(ctx.seed % (2 ** 31)))
    jax.block_until_ready(params)
    t_params = clock()

    trace_path = os.path.join(ctx.scratch, "obs_events.jsonl")
    if ctx.trace:
        obs.enable(trace_path=trace_path)
    try:
        engine = getattr(serving, eng_spec["class"])(
            params, cfg, **eng_spec["kwargs"])
        t_engine = clock()
        gen = ctx.module("traffic", mix["generator"]).make(
            mix, ctx.seed, cfg.vocab_size)
        loop = Loop(engine, sample=ctx.trace)

        # Warm-up, counted as set-up: one request per admission width
        # the mix reaches and one chunked prompt, run to the end.
        warm = [Req(-1 - i, None, p, n) for i, (p, n) in enumerate(
            gen.warmup(eng_spec["kwargs"].get("prompt_buckets", ()),
                       eng_spec["kwargs"].get("prefill_chunk")))]
        for r in warm:
            loop.enqueue(r)
        while loop.outstanding():
            loop.step(clock)

        # Ramp, counted as set-up too: the mix itself, so that the
        # window opens on a loaded engine and not on an empty one.
        clients = (None if gen.open_loop
                   else int(mix["clients_per_lane"]) * engine.lanes)
        ramp_s = float(mix.get("ramp_s", 0.0))
        reqs = []
        state = {"next": 0, "late": []}
        t_arrivals = clock()          # due times count from here

        def feed(now):
            if gen.open_loop:
                while True:
                    due, prompt, n = gen.get(state["next"])
                    if t_arrivals + due > now:
                        return t_arrivals + due
                    r = Req(state["next"], t_arrivals + due, prompt, n)
                    state["next"] += 1
                    reqs.append(r)
                    loop.enqueue(r)
                    state["late"].append(now - r.due)
            else:
                while loop.outstanding() < clients:
                    _, prompt, n = gen.get(state["next"])
                    r = Req(state["next"], now, prompt, n)
                    state["next"] += 1
                    reqs.append(r)
                    loop.enqueue(r)
            return None

        def drive(until):
            """Feed and step until the clock passes ``until``; returns
            the stamp of the last step."""
            t = clock()
            while t < until:
                nxt = feed(t)
                if loop.outstanding():
                    t = loop.step(clock)
                else:                          # open loop, idle engine
                    time.sleep(max(0.0, min(nxt, until) - clock()))
                    t = clock()
            return t

        drive(t_arrivals + ramp_s)

        # ---------------------------------------------- the window
        programs0 = ctx.meter.programs
        profiling = None
        t_begin = clock()
        setup_s = t_begin - ctx.t_process
        steps0, loop.busy_sum, loop.kv_sum, loop.samples = loop.steps, 0, 0, 0
        t_window_end = t_begin + ctx.seconds
        if ctx.trace:
            # A few seconds of the profiler in the middle of the
            # window; the end-to-end numbers come from the untraced run.
            prof_dir = os.path.join(ctx.scratch, "profile")
            span = min(float(ctx.cell.get("trace_seconds", 3.0)),
                       ctx.seconds / 2)
            drive(t_begin + min(1.0, ctx.seconds / 4))
            jax.profiler.start_trace(prof_dir)
            profiling = prof_dir
            t_prof = clock()
            drive(t_prof + span)
            profile_window = (t_prof, clock())
            jax.profiler.stop_trace()
        t_end = drive(t_window_end)
        programs_in_window = ctx.meter.programs - programs0

        # Requests due in the window get drain_s to finish (no new
        # arrivals); what is still unfinished then has failed.
        for r in reqs:
            r.in_window = t_begin <= r.due < t_window_end
        t_drain = clock() + float(mix.get("drain_s", 0.0))
        while (gen.open_loop and clock() < t_drain
               and any(r.in_window and not r.done for r in reqs)):
            loop.step(clock)
    finally:
        if ctx.trace:
            obs.disable()

    window_s = t_end - t_begin
    tokens_in_window, gaps = window_tokens(reqs, t_begin, t_end)
    mine = [r for r in reqs if r.in_window]
    tenth = window_s / 10
    notes = {"window_s": window_s, "steps": loop.steps - steps0,
             "tokens_in_window": tokens_in_window,
             "tok_s_by_tenth": [window_tokens(
                 reqs, t_begin + i * tenth, t_begin + (i + 1) * tenth)[0]
                 / tenth for i in range(10)],
             "requests_sent": len(reqs), "requests_in_window": len(mine),
             "finished": len(loop.finished),
             "programs_in_window": programs_in_window,
             "setup": {"params_s": t_params - ctx.t_process,
                       "engine_s": t_engine - t_params,
                       "warm_and_ramp_s": t_begin - t_engine}}
    e2e = {"setup_s": (setup_s, "s"),
           "serve_tok_s": (tokens_in_window / window_s, "tokens/s")}
    if gen.open_loop:
        ttft, failed = first_token_times(mine, worst=clock())
        notes["generator_late_ms"] = {
            "p50": 1e3 * percentile(state["late"], 50),
            "max": 1e3 * max(state["late"])} if state["late"] else None
        notes["ttft_ms"] = {"n": len(ttft), "p50": 1e3 * percentile(ttft, 50),
                            "p95": 1e3 * percentile(ttft, 95)}
        notes["itl_ms"] = {"n": len(gaps), "p50": 1e3 * percentile(gaps, 50),
                           "p95": 1e3 * percentile(gaps, 95)}
        # Due before the window closed and still without a first token
        # when it did: a backlog that grows with the window means the
        # rate is above what the engine sustains.
        notes["backlog_at_end"] = sum(
            1 for r in reqs if r.due < t_window_end
            and not (r.stamps and r.stamps[0] <= t_end))
        for name in ctx.cell["end_to_end"]:
            tail = TAIL.match(name)
            if tail:
                series = ttft if tail.group(1) == "ttft" else gaps
                e2e[name] = (1e3 * percentile(series, int(tail.group(2))),
                             "ms")
        attempted = len(mine)
    else:
        # Closed loop: a client waits for its reply, so every request
        # sent whose end fell in the window was attempted.
        ended = [r for r in loop.finished if r.idx >= 0
                 and t_begin < r.stamps[-1] <= t_end]
        attempted = len(ended)
        failed = sum(1 for r in ended if r.status != "ok")

    # ---------------------------------------------------- correctness
    import reference

    check = reference.check_serving(
        ctx, params, [r for r in loop.finished if r.idx >= 0])
    notes["reference"] = check
    correct = (check["ok"] and loop.mismatch == 0
               and programs_in_window == 0 and tokens_in_window > 0)
    notes["transcript_mismatches"] = loop.mismatch

    record = {"correct": correct, "attempted": attempted, "failed": failed,
              "end_to_end": e2e, "notes": notes, "kind": "serve",
              "window": (t_begin, t_end), "tokens_in_window": tokens_in_window,
              "lanes": engine.lanes, "max_len": cfg.max_len,
              "samples": {"n": loop.samples, "busy_sum": loop.busy_sum,
                          "kv_sum": loop.kv_sum},
              "conf": conf, "peaks": ctx.peaks, "chips": 1}
    if ctx.trace:
        from distkeras_tpu.obs import read_trace

        record["obs_events"] = read_trace(trace_path)
        import trace_reduce

        record["trace"] = trace_reduce.summarize(
            profiling, n_devices=1,
            dump=os.path.join(ctx.scratch, "trace_listing.json"))
        record["profile_window"] = profile_window
    with open(os.path.join(ctx.scratch, "last_run_notes.json"), "w") as f:
        json.dump(notes, f, indent=1, default=str)
    return record
