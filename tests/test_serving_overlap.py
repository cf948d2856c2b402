"""One decode round in flight: ``ContinuousBatcher.step()`` dispatches
round k and then reads round k-1, so a token is returned by the call
after the one that dispatched it — and every transcript still equals
its solo ``generate`` run, token for token, under everything that can
happen between a round's dispatch and its read.

``Sync`` is the order before the overlap (each round read by the call
that dispatched it), kept as the oracle where no solo run exists: a
weight swap in mid-stream.
"""

import jax
import numpy as np
import pytest

from distkeras_tpu import obs
from distkeras_tpu.obs import read_trace
from distkeras_tpu.resilience import FaultInjected, FaultPlan
from distkeras_tpu.serving import ContinuousBatcher, PagedBatcher
from helpers import generate, serve_cfg, toy_params

CFG = serve_cfg()


class Sync(ContinuousBatcher):
    _overlap = False


@pytest.fixture(scope="module")
def params():
    return toy_params(CFG)


def solo(params, prompt, n, **kw):
    return np.asarray(generate(params, np.asarray(prompt)[None], CFG,
                               n, **kw))[0]


def prompt_of(rng, p):
    return rng.integers(0, 64, (p,)).astype(np.int32)


class Watch:
    """Steps an engine and holds every call to the return contract:
    what ``step()`` returns is exactly what the transcripts grew by in
    that call (each emitted token is returned once, and only tokens
    that exist on the host)."""

    def __init__(self, eng):
        self.eng, self.seen, self.calls = eng, {}, 0

    def enqueue(self, prompt, n, **kw):
        rid = self.eng.enqueue(prompt, n, **kw)
        self.seen[rid] = len(prompt)
        return rid

    def submit(self, prompt, n, **kw):
        lane = self.eng.submit(prompt, n, **kw)
        assert lane is not None
        self.seen[self.eng.last_request_id] = len(prompt)
        return self.eng.last_request_id

    def step(self, n=1):
        out = self.eng.step(n)
        self.calls += 1
        grown = []
        for rid, had in self.seen.items():
            toks = self.eng.partial(rid).tokens.tolist()
            if len(toks) > had:
                grown.append(toks[had:])
                self.seen[rid] = len(toks)
        assert sorted(v for v in out.values() if v) == sorted(grown)
        return out

    def run(self, limit=200):
        while self.eng.running() or self.eng.queued:
            self.step()
            assert self.calls < limit
        return {rid: self.eng.partial(rid).tokens for rid in self.seen}


def eos_at(ref, p, j):
    """An eos token that ends ``ref`` (a solo greedy run of a prompt of
    ``p`` tokens) at generated token ``j`` or before, and where."""
    eos = int(ref[p + j])
    return eos, p + list(ref[p:]).index(eos) + 1


# ------------------------------------------------------------ the order


def test_a_token_is_returned_by_the_call_after_its_dispatch(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=2)
    p = prompt_of(rng, 5)
    lane = eng.submit(p, 3)
    ref = solo(params, p, 3)
    assert eng.step() == {}                    # round 1 dispatched
    assert eng.step() == {lane: [ref[5]]}      # round 2 out, round 1 read
    assert eng.step() == {lane: [ref[6]]}
    # The whole budget is dispatched: nothing to launch, the round still
    # unread comes back, and only now has the lane stopped running.
    assert eng.running() == [lane]
    assert eng.step() == {lane: [ref[7]]}
    assert eng.running() == [] and eng._inflight is None
    assert eng.step() == {}
    np.testing.assert_array_equal(eng.drain(lane), ref)


@pytest.mark.parametrize("kind", ["continuous", "paged"])
def test_order_of_the_two_hooks(params, rng, kind):
    """``_dispatch_step`` then ``_read_tokens``: the continuous engine
    launches round k+1 before it reads round k; the paged engine, whose
    dispatch grows page tables from the transcript, reads each round at
    once."""
    if kind == "paged":
        eng = PagedBatcher(params, CFG, lanes=2, block=8, n_blocks=9,
                           prompt_buckets=(8,))
    else:
        eng = ContinuousBatcher(params, CFG, lanes=2)
    log, rounds = [], []
    launch, read = eng._dispatch_step, eng._read_tokens

    def logged_launch(n):
        toks = launch(n)
        rounds.append(toks)
        log.append(("launch", len(rounds)))
        return toks

    def logged_read(toks):
        log.append(("read", 1 + [t is toks for t in rounds].index(True)))
        return read(toks)

    eng._dispatch_step, eng._read_tokens = logged_launch, logged_read
    p = prompt_of(rng, 4)
    lane = eng.submit(p, 4)
    while eng.running():
        eng.step()
    np.testing.assert_array_equal(eng.drain(lane), solo(params, p, 4))
    if kind == "paged":
        want = [(h, k) for k in (1, 2, 3, 4) for h in ("launch", "read")]
    else:
        want = [("launch", 1), ("launch", 2), ("read", 1), ("launch", 3),
                ("read", 2), ("launch", 4), ("read", 3), ("read", 4)]
    assert log == want


# ---------------------------------------------------------- transcripts


def test_greedy_staggered_lanes_match_solo(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=2, max_queue=4)
    w = Watch(eng)
    prompts = [prompt_of(rng, p) for p in (6, 3, 9, 4)]
    budgets = [10, 5, 4, 7]
    rids = [w.enqueue(prompts[0], budgets[0])]
    for _ in range(3):
        w.step()
    rids += [w.enqueue(p, n) for p, n in zip(prompts[1:], budgets[1:])]
    got = w.run()
    for rid, p, n in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(got[rid], solo(params, p, n))


def test_sampled_with_keys_matches_solo(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=2, temperature=0.8,
                            top_k=8, max_queue=2)
    w = Watch(eng)
    prompts = [prompt_of(rng, p) for p in (4, 7, 5)]
    keys = [jax.random.key(k) for k in (11, 12, 13)]
    rids = [w.enqueue(p, 6, key=k) for p, k in zip(prompts, keys)]
    got = w.run()
    for rid, p, k in zip(rids, prompts, keys):
        np.testing.assert_array_equal(
            got[rid], solo(params, p, 6, temperature=0.8, top_k=8, key=k))


def test_eos_in_mid_flight_drops_the_surplus_round(params, rng):
    """A lane that ends by eos has one more round of itself in flight:
    that round's row is dropped, as ``n > 1`` drops a window's
    surplus."""
    eng = ContinuousBatcher(params, CFG, lanes=2)
    w = Watch(eng)
    pa, pb = prompt_of(rng, 5), prompt_of(rng, 7)
    ref = solo(params, pa, 12)
    eos, end = eos_at(ref, 5, 3)
    ra = w.submit(pa, 12, eos_token=eos)
    rb = w.submit(pb, 9)
    got = w.run()
    np.testing.assert_array_equal(got[ra], ref[:end])
    assert got[ra][-1] == eos and end < len(ref)
    np.testing.assert_array_equal(got[rb], solo(params, pb, 9))


def test_mixed_step_windows_match_solo(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=2)
    w = Watch(eng)
    pa, pb = prompt_of(rng, 4), prompt_of(rng, 6)
    ra, rb = w.submit(pa, 11), w.submit(pb, 7)
    for n in (1, 3, 2, 1, 3, 2, 1, 3, 2):
        w.step(n)
    assert not eng.running()
    np.testing.assert_array_equal(eng.partial(ra).tokens,
                                  solo(params, pa, 11))
    np.testing.assert_array_equal(eng.partial(rb).tokens,
                                  solo(params, pb, 7))


def test_chunked_admission_into_a_lane_freed_the_call_before(params, rng):
    """A ends by eos while its next round is in flight; B's three
    admission chunks go into the lane at once.  They are ordered after
    that round on the device, and the round's row is A's, not B's."""
    eng = ContinuousBatcher(params, CFG, lanes=1, max_queue=2,
                            prefill_chunk=8, prompt_buckets=(8,))
    w = Watch(eng)
    pa, pb = prompt_of(rng, 4), prompt_of(rng, 21)
    ref = solo(params, pa, 10)
    eos, end = eos_at(ref, 4, 2)
    ra = w.enqueue(pa, 10, eos_token=eos)
    while eng.poll(ra) is None:
        w.step()
    assert eng._inflight is not None and eng.free_lanes() == [0]
    rb = w.enqueue(pb, 6)
    assert eng.free_lanes() == []               # B's first chunk has run
    assert w.step() == {}                       # A's surplus row: dropped
    got = w.run()
    np.testing.assert_array_equal(got[ra], ref[:end])
    np.testing.assert_array_equal(got[rb], solo(params, pb, 6))


def test_a_readmitted_lane_gets_nothing_of_its_old_round(params, rng):
    """A is evicted at its deadline, NOT done, with a round in flight,
    and B takes the lane before that round is read: the round's record
    names A's ``_Lane``, so B's transcript starts with B's tokens."""
    t = [0.0]
    eng = ContinuousBatcher(params, CFG, lanes=1, clock=lambda: t[0])
    w = Watch(eng)
    pa, pb = prompt_of(rng, 5), prompt_of(rng, 3)
    ra = w.submit(pa, 10, ttl=5.0)
    w.step()
    w.step()
    t[0] = 6.0
    w.step()                       # reads round 2, evicts A; round 3 is out
    res = eng.poll(ra)
    assert res.status == "timeout"
    np.testing.assert_array_equal(res.tokens, solo(params, pa, 10)[:7])
    assert eng._inflight is not None
    rb = w.submit(pb, 4)
    assert w.step() == {}          # round 3 was A's
    got = w.run()
    np.testing.assert_array_equal(got[rb], solo(params, pb, 4))


def test_swap_params_between_dispatch_and_read(params, rng):
    """The arguments of a dispatched program are captured: a round
    dispatched under version 0 returns version 0's token though the
    swap came before its read, and the stream continues as it does when
    every round is read at once."""
    v1 = toy_params(CFG, 1)
    p, q = prompt_of(rng, 5), prompt_of(rng, 4)

    def serve(cls):
        eng = cls(params, CFG, lanes=2, hot_swap=True)
        w = Watch(eng)
        ra = w.submit(p, 8)
        w.step()
        w.step()                                # round 2 out under v0
        eng.swap_params(v1, 1)
        rb = w.submit(q, 5)                     # wholly under v1
        return ra, rb, w.run()

    ra, rb, got = serve(ContinuousBatcher)
    sa, sb, want = serve(Sync)
    np.testing.assert_array_equal(got[ra], want[sa])
    np.testing.assert_array_equal(got[rb], want[sb])
    np.testing.assert_array_equal(got[ra][:7], solo(params, p, 8)[:7])
    assert (got[ra] != solo(params, p, 8)).any()
    np.testing.assert_array_equal(got[rb], solo(v1, q, 5))


def test_elastic_resize_reads_the_round_in_flight_first(params, rng):
    """A resize renumbers lanes, and the round in flight names them by
    their old numbers: it is read before the move, and the next
    ``step()`` returns its tokens under the lanes the requests hold
    then (``Watch`` checks it).  Up with a round pending, then down."""
    eng = ContinuousBatcher(params, CFG, lane_tiers=(1, 2, 4), max_queue=1,
                            scale_up_after=1, scale_down_after=2,
                            prompt_buckets=(8,))
    w = Watch(eng)
    prompts = [prompt_of(rng, p) for p in (4, 6, 3, 5, 4)]
    budgets = [14, 3, 9, 3, 12]
    rids = [w.enqueue(prompts[0], budgets[0])]
    w.step()
    w.step()
    assert eng._inflight is not None
    rids += [w.enqueue(p, n) for p, n in zip(prompts[1:], budgets[1:])]
    assert eng.lanes == 4 and eng._inflight is None
    assert [len(toks) for _, toks in eng._flushed] == [1]
    down = 0
    while eng.running() or eng.queued or eng.lanes > 1:
        before = eng.lanes
        w.step()
        down += eng.lanes < before
        assert w.calls < 100
    assert down == 2
    for rid, p, n in zip(rids, prompts, budgets):
        np.testing.assert_array_equal(eng.take(rid).tokens,
                                      solo(params, p, n))


def test_shutdown_drains_the_round_in_flight(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=2, max_queue=2)
    prompts = [prompt_of(rng, p) for p in (5, 3, 6)]
    rids = [eng.enqueue(p, 6) for p in prompts]
    eng.step()
    eng.step()
    assert eng._inflight is not None
    res = eng.shutdown()
    assert eng._inflight is None and not eng.running()
    for rid, p in zip(rids, prompts):
        assert res[rid].ok
        np.testing.assert_array_equal(res[rid].tokens, solo(params, p, 6))


@pytest.mark.parametrize("budget,status", [(10, "cancelled"), (3, "ok")])
def test_shutdown_at_max_steps_keeps_the_tokens_on_the_device(
        params, rng, budget, status):
    """``max_steps`` trips with a round unread: its tokens are in the
    cancelled request's partial transcript, and a request that this
    round finishes is ``ok``."""
    eng = ContinuousBatcher(params, CFG, lanes=1, max_queue=1)
    p = prompt_of(rng, 4)
    rid = eng.enqueue(p, budget)
    eng.step()
    eng.step()
    (res,) = eng.shutdown(max_steps=1).values()      # round 3 dispatched
    assert res.request_id == rid and res.status == status
    np.testing.assert_array_equal(res.tokens,
                                  solo(params, p, budget)[:4 + 3])
    assert eng._inflight is None and eng.free_lanes() == [0]


def test_a_dispatch_that_raises_keeps_the_unread_round(params, rng):
    eng = ContinuousBatcher(params, CFG, lanes=1)
    w = Watch(eng)
    p = prompt_of(rng, 5)
    rid = w.submit(p, 6)
    with FaultPlan().fail("serving.step", at=3):
        w.step()
        w.step()
        unread = eng._inflight
        with pytest.raises(FaultInjected):
            eng.step()
        assert eng._inflight is unread
        assert len(w.step()[0]) == 1            # round 2, a call late
        got = w.run()
    np.testing.assert_array_equal(got[rid], solo(params, p, 6))


# ------------------------------------------------------------ the trace


def test_rounds_say_overlapped_and_collect_says_its_wait(params, rng,
                                                         tmp_path):
    path = str(tmp_path / "t.jsonl")
    with obs.session(trace_path=path):
        eng = ContinuousBatcher(params, CFG, lanes=2)
        eng.submit(prompt_of(rng, 5), 3)
        for _ in range(5):
            eng.step()
    spans = [r for r in read_trace(path) if r["kind"] == "span"]
    rounds = [s for s in spans if s["name"] == "serving.round"]
    by_round = {r["id"]: [s["name"] for s in spans if s["parent"] == r["id"]]
                for r in rounds}
    steps, collects = (
        [("serving." + name) in by_round[r["id"]] for r in rounds]
        for name in ("step", "collect"))
    assert steps == [True, True, True, False, False]
    assert collects == [False, True, True, True, False]
    assert [bool(r["fields"].get("overlapped")) for r in rounds] == [
        False, True, True, False, False]
    assert [bool(r["fields"].get("idle")) for r in rounds] == [
        False, False, False, False, True]
    assert [r["fields"]["tokens"] for r in rounds] == [0, 1, 1, 1, 0]
    for s in spans:
        if s["name"] == "serving.collect":
            assert set(s["fields"]) == {"seq", "wait_ms"}
            assert 0 <= s["fields"]["wait_ms"] <= s["dur"] * 1e3 + 1e-6
