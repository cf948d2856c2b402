"""Round-12 paged KV: block-granular cache, per-lane page tables,
content-hash stem sharing, copy-on-write forks.

The exact-parity contract is tests/test_serving.py's: every request's
emitted tokens are bit-identical to the monolithic engine's and to
solo ``generate`` — the block slab, the page-table gather, stem
sharing, and CoW forks must all be invisible in the tokens.  On top of
that: allocator bookkeeping (refcounts, OOM backpressure, no leaked
blocks across any vacation path), pinned prefixes on the one slab,
and the ``kv_int8="prefill"`` tolerance pin.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import obs
from distkeras_tpu.serving import (BlockAllocator, ContinuousBatcher,
                                   PagedBatcher, QueueFull)
from distkeras_tpu.serving.paged import (KV_INT8_PREFILL_LOGIT_TOL,
                                         TRASH_BLOCK)
from helpers import generate, jgen, serve_cfg, toy_params

CFG = serve_cfg()
BLOCK = 8
MB = CFG.max_len // BLOCK


@pytest.fixture(scope="module")
def params():
    return toy_params(CFG)


def paged(params, lanes=2, n_blocks=None, **kw):
    kw.setdefault("prompt_buckets", (8,))
    if n_blocks is None:
        n_blocks = lanes * MB + 1
    return PagedBatcher(params, CFG, lanes=lanes, block=BLOCK,
                        n_blocks=n_blocks, **kw)


def run_to_done(eng, lane):
    while lane in eng.running():
        eng.step()
    return eng.drain(lane)


def solo(params, prompt, n, **kw):
    return np.asarray(generate(params, np.asarray(prompt)[None], CFG,
                               n, **kw))[0]


def assert_no_leak(eng):
    """Every block is back on the free list and no lane table points
    anywhere but trash — the no-block-leaked invariant."""
    st = eng.allocator.stats()
    assert st["used"] == 0 and st["free"] == st["capacity"], st
    assert (eng._tables_np == TRASH_BLOCK).all()
    assert all(not b for b in eng._lane_blocks)


# ---------------------------------------------------- allocator unit


def test_allocator_refcount_and_residency():
    a = BlockAllocator(n_blocks=5, block=8)   # blocks 1..4 usable
    assert a.capacity == 4
    b1, b2 = a.alloc(), a.alloc()
    assert a.refs_of(b1) == 1
    a.share(b1)
    assert a.refs_of(b1) == 2
    a.register(b1, b"h1")
    assert a.share_by_hash(b"h1") == b1
    assert a.refs_of(b1) == 3
    # Free down to zero: the block moves to the free list but stays
    # hash-resident, so a later request can revive it...
    for _ in range(3):
        a.free(b1)
    assert a.refs_of(b1) == 0
    assert a.stats()["free"] == 3
    assert a.share_by_hash(b"h1") == b1        # revived
    a.free(b1)
    # ...until the free list recycles it: alloc purges the hash.
    got = {a.alloc() for _ in range(4)}
    assert len(got) == 4
    assert a.alloc() is None                   # exhausted, no raise
    assert a.share_by_hash(b"h1") is None      # recycled -> purged
    with pytest.raises(ValueError, match="not live"):
        a.free(99)
    a.free(b2)
    with pytest.raises(ValueError, match="not live"):
        a.free(b2)                             # double free
    with pytest.raises(ValueError, match="not live"):
        a.share(b2)


def test_allocator_register_first_writer_wins():
    a = BlockAllocator(n_blocks=4, block=8)
    b1, b2 = a.alloc(), a.alloc()
    a.register(b1, b"h")
    a.register(b2, b"h")                       # identical content
    assert a.share_by_hash(b"h") == b1
    a.free(b1)
    a.free(b1)                                 # drop the shared ref
    a.free(b2)


# ------------------------------------------------------- parity


def test_paged_greedy_parity_staggered_and_lane_reuse(params, rng):
    """Staggered admission + lane reuse: bit parity with both the
    monolithic engine and solo generate, and zero blocks leaked."""
    pb = paged(params, lanes=2)
    cb = ContinuousBatcher(params, CFG, lanes=2, prompt_buckets=(8,))
    prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
               for n in (5, 12, 7)]
    outs = {}
    lp1, lc1 = pb.submit(prompts[0], 10), cb.submit(prompts[0], 10)
    pb.step(), cb.step()
    lp2, lc2 = pb.submit(prompts[1], 8), cb.submit(prompts[1], 8)
    outs[0] = (run_to_done(pb, lp1), run_to_done(cb, lc1))
    # Lane reuse: the third request lands on a vacated lane whose
    # stale blocks went back to the allocator.
    lp3, lc3 = pb.submit(prompts[2], 9), cb.submit(prompts[2], 9)
    outs[1] = (run_to_done(pb, lp2), run_to_done(cb, lc2))
    outs[2] = (run_to_done(pb, lp3), run_to_done(cb, lc3))
    for i, (op, oc) in outs.items():
        assert np.array_equal(op, oc), f"request {i} diverged"
        n = (10, 8, 9)[i]
        assert np.array_equal(op, solo(params, prompts[i], n))
    assert_no_leak(pb)


def test_paged_sampled_parity_per_request(params, rng):
    """Seeded-sampled parity through the per-request-sampling step —
    greedy and sampled requests mixed in one paged batch."""
    pb = paged(params, lanes=2, per_request_sampling=True,
               temperature=0.0)
    p1 = rng.integers(0, 64, (6,)).astype(np.int32)
    p2 = rng.integers(0, 64, (9,)).astype(np.int32)
    k = jax.random.key(11)
    l1 = pb.submit(p1, 8, key=k, temperature=0.9, top_p=0.9)
    l2 = pb.submit(p2, 8)                      # greedy default
    o1, o2 = run_to_done(pb, l1), run_to_done(pb, l2)
    assert np.array_equal(
        o1, solo(params, p1, 8, temperature=0.9, top_p=0.9, key=k))
    assert np.array_equal(o2, solo(params, p2, 8))
    assert_no_leak(pb)


def test_paged_kv_int8_exact_parity(params, rng):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        pb = paged(params, lanes=2, kv_int8=True)
    p = rng.integers(0, 64, (7,)).astype(np.int32)
    out = run_to_done(pb, pb.submit(p, 9))
    assert np.array_equal(
        out, solo(params, p, 9, kv_int8=True, use_prefill=False))
    assert_no_leak(pb)


def test_paged_chunked_prefill_parity(params, rng):
    """Chunked prefill on the paged slab: the long prompt's chunks
    land one per step while another lane decodes; tokens match the
    monolithic chunked engine (itself pinned to solo runs)."""
    pb = paged(params, lanes=2, prefill_chunk=8,
               prompt_buckets=(8, 16))
    ps = rng.integers(0, 64, (4,)).astype(np.int32)
    pl = rng.integers(0, 64, (22,)).astype(np.int32)
    ls = pb.submit(ps, 12)
    pb.step()
    ll = pb.submit(pl, 6)                      # parks, admits chunked
    assert ll in pb.running()
    assert np.array_equal(run_to_done(pb, ls), solo(params, ps, 12))
    assert np.array_equal(run_to_done(pb, ll), solo(params, pl, 6))
    assert_no_leak(pb)


# -------------------------------------------------- stem sharing


def test_stem_sharing_hit_refcounts_and_parity(params, rng):
    """Two requests sharing a 2-block stem: the second admission
    refcounts the first's blocks (no re-prefill), both match solo
    runs, and vacating one keeps the shared blocks alive for the
    other."""
    pb = paged(params, lanes=2, prompt_buckets=(4, 16))
    stem = rng.integers(0, 64, (16,)).astype(np.int32)
    t1 = rng.integers(0, 64, (3,)).astype(np.int32)
    t2 = rng.integers(0, 64, (3,)).astype(np.int32)
    pr1, pr2 = np.concatenate([stem, t1]), np.concatenate([stem, t2])
    l1 = pb.submit(pr1, 6)
    used_before = pb.allocator.stats()["used"]
    l2 = pb.submit(pr2, 6)
    st = pb.allocator.stats()
    assert st["shared"] == 2, st               # both stem blocks hit
    # The second admission allocated only its tail blocks, not the
    # stem's: 19 warm tokens = 3 blocks, 2 shared -> 1 fresh.
    assert st["used"] == used_before + 1, (used_before, st)
    assert pb._lane_blocks[l1][:2] == pb._lane_blocks[l2][:2]
    o1 = run_to_done(pb, l1)                   # vacates lane 1
    st = pb.allocator.stats()
    assert st["shared"] == 0                   # survivor holds refs 1
    assert all(pb.allocator.refs_of(b) == 1
               for b in pb._lane_blocks[l2])
    o2 = run_to_done(pb, l2)
    assert np.array_equal(o1, solo(params, pr1, 6))
    assert np.array_equal(o2, solo(params, pr2, 6))
    assert_no_leak(pb)
    # Residency outlives the requests: a third shared-stem request
    # revives the freed blocks by hash.
    l3 = pb.submit(pr1, 4)
    assert pb.allocator.stats()["resident_hashes"] >= 2
    assert np.array_equal(run_to_done(pb, l3), solo(params, pr1, 4))


def test_stem_sharing_miss_stays_private(params, rng):
    """Different stems: no hash hit, fully private block sets."""
    pb = paged(params, lanes=2, prompt_buckets=(4, 16))
    a = rng.integers(0, 64, (18,)).astype(np.int32)
    b = rng.integers(0, 64, (18,)).astype(np.int32)
    assert not np.array_equal(a[:BLOCK], b[:BLOCK])
    la, lb = pb.submit(a, 5), pb.submit(b, 5)
    assert pb.allocator.stats()["shared"] == 0
    assert not set(pb._lane_blocks[la]) & set(pb._lane_blocks[lb])
    assert np.array_equal(run_to_done(pb, la), solo(params, a, 5))
    assert np.array_equal(run_to_done(pb, lb), solo(params, b, 5))


def test_stem_sharing_waits_for_chunked_content(params, rng):
    """A chunk-admitting lane's blocks must not hash-hit before their
    content is dispatched: a same-stem request admitted while the
    first is still PARKED shares only the chunks already landed."""
    pb = paged(params, lanes=2, prefill_chunk=8,
               prompt_buckets=(8, 24))
    stem = rng.integers(0, 64, (24,)).astype(np.int32)
    p1 = np.concatenate([stem, rng.integers(0, 64, (1,)).astype(np.int32)])
    l1 = pb.submit(p1, 4)                      # parked: 24 warm = 3 chunks
    assert pb._lane_state[l1].chunks is not None
    # Only chunk 0 (8 tokens = 1 block) has landed -> 1 resident hash.
    p2 = np.concatenate([stem, rng.integers(0, 64, (2,)).astype(np.int32)])
    l2 = pb.submit(p2, 4)
    assert len(pb._lane_blocks[l2]) >= 3
    assert pb.allocator.stats()["shared"] == 1  # just the landed block
    assert np.array_equal(run_to_done(pb, l1), solo(params, p1, 4))
    assert np.array_equal(run_to_done(pb, l2), solo(params, p2, 4))
    assert_no_leak(pb)


def test_stem_hit_unbucketable_span_falls_back(params, rng):
    """Code-review regression: a resident stem hit whose unshared
    span fits NO bucket at the skip offset must fall back to less
    sharing (down to a full re-prefill), never fail a request that
    validated — and the surplus shared refs are handed back."""
    pb = paged(params, lanes=2, prompt_buckets=(8,))  # buckets {8, 32}
    stem = rng.integers(0, 64, (16,)).astype(np.int32)
    first = np.concatenate([stem,
                            rng.integers(0, 64, (2,)).astype(np.int32)])
    run_to_done(pb, pb.submit(first, 4))       # makes the stem resident
    # warm 25: skip=16 -> span 9 at offset 16 (no bucket fits),
    # skip=8 -> span 17 at offset 8 (32 doesn't fit) -> skip=0.
    prompt = np.concatenate([stem,
                             rng.integers(0, 64, (10,)).astype(np.int32)])
    hits0 = pb.stem_hit_blocks
    lane = pb.submit(prompt, 4)
    assert lane is not None
    assert pb.stem_hit_blocks == hits0          # all shares given back
    assert pb.allocator.stats()["shared"] == 0
    assert np.array_equal(run_to_done(pb, lane),
                          solo(params, prompt, 4))
    assert_no_leak(pb)
    # A shareable span that DOES fit still shares (the fallback is
    # not a blanket disable): warm 21 -> span 5 at offset 16, bucket 8
    # fits (16 + 8 <= 32).
    ok = np.concatenate([stem,
                         rng.integers(0, 64, (6,)).astype(np.int32)])
    lane = pb.submit(ok, 4)
    assert pb.stem_hit_blocks == hits0 + 2      # revived by hash
    assert np.array_equal(run_to_done(pb, lane), solo(params, ok, 4))
    assert_no_leak(pb)


def test_growth_window_does_not_overallocate_past_budget(params, rng):
    """Code-review regression: a step window larger than a lane's
    remaining budget must not allocate blocks for the discarded
    garbage positions (that turned window roundup into spurious OOM
    evictions)."""
    pb = paged(params, lanes=2, n_blocks=3, prompt_buckets=(8,),
               step_windows=(1, 8))            # 2 usable blocks
    p1 = rng.integers(0, 64, (8,)).astype(np.int32)
    p2 = rng.integers(0, 64, (8,)).astype(np.int32)
    l1, l2 = pb.submit(p1, 1), pb.submit(p2, 1)
    out = pb.step(8)                           # window >> budget
    assert set(out) == {l1, l2}
    assert np.array_equal(pb.drain(l1), solo(params, p1, 1))
    assert np.array_equal(pb.drain(l2), solo(params, p2, 1))
    assert not pb.results()                    # nobody was evicted
    assert_no_leak(pb)


# ----------------------------------------------- pinned prefixes


def test_pinned_prefix_on_slab_parity_and_residency(params, rng):
    """The pooled-prefix story on the paged slab: pin once, every
    matching prompt hash-hits the pinned blocks (zero prefix prefill
    work — asserted via block identity), parity is exact, and unpin
    releases exactly the pin's references."""
    pb = paged(params, lanes=2, prompt_buckets=(4, 16))
    prefix = rng.integers(0, 64, (17,)).astype(np.int32)  # rounds to 16
    pid = pb.pin_prefix(prefix)
    assert pb.pinned.length_of(pid) == 16
    pinned_blocks = list(pb.pinned.blocks_of(pid))
    assert pb.allocator.stats()["used"] == 2
    tail = rng.integers(0, 64, (4,)).astype(np.int32)
    full = np.concatenate([prefix[:16], tail])
    lane = pb.submit(full, 6)
    # The lane's first two blocks ARE the pinned blocks, refcounted.
    assert pb._lane_blocks[lane][:2] == pinned_blocks
    assert all(pb.allocator.refs_of(b) == 2 for b in pinned_blocks)
    assert np.array_equal(run_to_done(pb, lane), solo(params, full, 6))
    assert all(pb.allocator.refs_of(b) == 1 for b in pinned_blocks)
    pb.unpin_prefix(pid)
    assert pid not in pb.pinned
    assert_no_leak(pb)
    with pytest.raises(KeyError):
        pb.unpin_prefix(pid)


def test_pinned_prefix_validation(params, rng):
    pb = paged(params, lanes=1, prompt_buckets=(8,))
    with pytest.raises(ValueError, match="full block"):
        pb.pin_prefix(rng.integers(0, 64, (BLOCK - 1,)))
    with pytest.raises(ValueError, match="leave room"):
        pb.pin_prefix(rng.integers(0, 64, (CFG.max_len,)))
    tiny = paged(params, lanes=1, n_blocks=2, prompt_buckets=(8,))
    tiny.pin_prefix(rng.integers(0, 64, (BLOCK,)))
    with pytest.raises(RuntimeError, match="no free KV blocks"):
        tiny.pin_prefix(np.arange(BLOCK, dtype=np.int32))


def test_pin_prefix_rolls_back_on_dispatch_fault(params, rng):
    """Code-review regression: a failure AFTER pin_prefix staged its
    blocks (here the admit dispatch) must hand every staged reference
    back — the pin was never published."""
    pb = paged(params, lanes=2, prompt_buckets=(8,))

    def boom(*a, **kw):
        raise RuntimeError("injected pin fault")
    real, pb._admit = pb._admit, boom
    with pytest.raises(RuntimeError, match="injected pin fault"):
        pb.pin_prefix(rng.integers(0, 64, (16,)).astype(np.int32))
    assert len(pb.pinned) == 0
    assert_no_leak(pb)
    pb._admit = real
    pid = pb.pin_prefix(rng.integers(0, 64, (16,)).astype(np.int32))
    assert pb.allocator.stats()["used"] == 2   # engine still healthy
    pb.unpin_prefix(pid)
    assert_no_leak(pb)


# ------------------------------------------------------ CoW forks


def test_cow_fork_beam_parity(params, rng):
    """Beam-style fork: branch on an alternative token mid-decode;
    the source stays bit-exact with its solo run and the branch
    matches the solo run of its forced-token transcript.  Only the
    divergent tail block is fresh — all full blocks are shared."""
    pb = paged(params, lanes=3, prompt_buckets=(8,))
    p = rng.integers(0, 64, (6,)).astype(np.int32)
    src = pb.submit(p, 12)
    for _ in range(4):
        pb.step()
    st = pb._lane_state[src]
    alt = (st.tokens[-1] + 1) % CFG.vocab_size
    frontier = len(st.tokens) - 1
    f = pb.fork(src, token=alt)
    assert f is not None
    shared = pb._lane_blocks[src][:frontier // BLOCK]
    assert pb._lane_blocks[f][:len(shared)] == shared
    assert all(pb.allocator.refs_of(b) == 2 for b in shared)
    o_src, o_f = run_to_done(pb, src), run_to_done(pb, f)
    assert np.array_equal(o_src, solo(params, p, 12))
    forced = np.asarray(o_f[:len(p) + 4])      # prompt + 3 kept + alt
    assert forced[-1] == alt
    assert np.array_equal(o_f, solo(params, forced, 12 - 4))
    assert_no_leak(pb)


def test_cow_fork_speculative_rollback(params, rng):
    """Speculative checkpoint/rollback: fork an exact replica, let
    the source speculate ahead, reject it (evict), and the
    checkpoint lane continues to the solo-run answer."""
    pb = paged(params, lanes=3, prompt_buckets=(8,), clock=lambda: 0.0)
    p = rng.integers(0, 64, (6,)).astype(np.int32)
    src = pb.submit(p, 12)
    for _ in range(3):
        pb.step()
    st = pb._lane_state[src]
    ck = pb.fork(src, token=st.tokens[-1])     # exact replica
    for _ in range(2):                         # "speculate" on src
        pb.step()
    # Reject: evict the speculating lane; its private blocks free,
    # the checkpoint's shared blocks survive.
    used = pb.allocator.stats()["used"]
    st_src = pb._lane_state[src]
    pb._finish(st_src.request_id, st_src.tokens, "cancelled",
               st_src.prompt_len)
    pb._vacate(src)
    assert pb.allocator.stats()["used"] < used
    assert np.array_equal(run_to_done(pb, ck), solo(params, p, 12))
    assert_no_leak(pb)


def test_cow_fork_sampled_key_and_validation(params, rng):
    pb = paged(params, lanes=2, temperature=0.8, prompt_buckets=(8,))
    p = rng.integers(0, 64, (5,)).astype(np.int32)
    src = pb.submit(p, 6, key=jax.random.key(3))
    pb.step()
    f = pb.fork(src, token=pb._lane_state[src].tokens[-1],
                key=jax.random.key(9))
    o_src, o_f = run_to_done(pb, src), run_to_done(pb, f)
    assert np.array_equal(
        o_src, solo(params, p, 6, temperature=0.8,
                    key=jax.random.key(3)))
    # The fork replays the same transcript prefix under ITS key: its
    # continuation is the solo run of that prefix with the new key.
    kept = len(o_f) - 6 + 1                   # prompt + first emitted
    assert np.array_equal(
        o_f, np.asarray(generate(params, np.asarray(o_f[:kept])[None],
                                 CFG, 6 - 1, temperature=0.8,
                                 key=jax.random.key(9)))[0])
    with pytest.raises(ValueError, match="empty"):
        pb.fork(0 if src != 0 else 1, token=1)
    greedy = paged(params, lanes=2, prompt_buckets=(8,))
    g = greedy.submit(p, 4)
    with pytest.raises(ValueError, match="sampling engine"):
        greedy.fork(g, token=1, key=jax.random.key(0))
    with pytest.raises(ValueError, match="outside vocab"):
        greedy.fork(g, token=CFG.vocab_size)


def test_cow_fork_backpressure(params, rng):
    """No free lane -> None; no free block for the tail copy -> None
    with every staged share rolled back."""
    pb = paged(params, lanes=2, n_blocks=4, prompt_buckets=(8,))
    # Budgets fit one block each (no growth pressure in this test).
    p = rng.integers(0, 64, (3,)).astype(np.int32)
    a = pb.submit(p, 5)
    b = pb.submit(rng.integers(0, 64, (6,)).astype(np.int32), 2)
    pb.step()
    assert pb.fork(a, token=1) is None          # lanes full
    run_to_done(pb, b)                          # a still decoding
    # 3 usable blocks: a holds 1 (6 warm tokens) and will have grown;
    # drain the allocator with a pin so the tail copy cannot alloc.
    while pb.allocator.alloc() is not None:
        pass
    st = pb.allocator.stats()
    assert pb.fork(a, token=1) is None
    assert pb.allocator.stats() == st           # rollback exact
    # No result was fabricated for the declined forks.
    assert pb.last_request_id is None


# ------------------------------------- backpressure, OOM, eviction


def test_admission_oom_declines_then_queue_backpressure(params, rng):
    """Allocator exhausted at admission: bare submit declines (no
    lane occupied, nothing leaked), enqueue queues the request and
    admits it once blocks free; past the queue cap, QueueFull."""
    pb = paged(params, lanes=3, n_blocks=3, max_queue=1,
               prompt_buckets=(8,))                  # 2 usable blocks
    # 5-token prompts + 3 new = 8 total: exactly one block each, no
    # growth — admission pressure only.
    p1 = rng.integers(0, 64, (5,)).astype(np.int32)
    p2 = rng.integers(0, 64, (5,)).astype(np.int32)
    p3 = rng.integers(0, 64, (5,)).astype(np.int32)
    l1, l2 = pb.submit(p1, 3), pb.submit(p2, 3)
    assert l1 is not None and l2 is not None
    assert pb.submit(p3, 3) is None              # blocks dry, lane free
    assert len(pb.free_lanes()) == 1
    r3 = pb.enqueue(p3, 3)                       # queues instead
    assert pb.queued == 1
    with pytest.raises(QueueFull):
        pb.enqueue(p3, 3)
    run_to_done(pb, l1)
    run_to_done(pb, l2)                          # frees blocks; pumps
    while pb.poll(r3) is None:
        pb.step()
    res = pb.take(r3)
    assert res.ok
    assert np.array_equal(res.tokens, solo(params, p3, 3))
    assert_no_leak(pb)


def test_growth_oom_evicts_with_structured_error(params, rng):
    """A lane the allocator cannot grow mid-decode is evicted with a
    structured "error" result; its freed blocks let the other lane
    finish exactly."""
    pb = paged(params, lanes=2, n_blocks=4, prompt_buckets=(8,))
    # Two lanes, 3 usable blocks: both will outgrow block 1 and only
    # one second block exists.
    p1 = rng.integers(0, 64, (7,)).astype(np.int32)
    p2 = rng.integers(0, 64, (7,)).astype(np.int32)
    l1 = pb.submit(p1, 12)                     # grows past 8 tokens
    l2 = pb.submit(p2, 12)
    while pb.running():
        pb.step()
    results = pb.results()
    evicted = [r for r in results.values() if r.status == "error"]
    assert len(evicted) == 1
    assert "exhausted" in evicted[0].error
    survivor = l1 if pb._lane_state[l1] is not None else l2
    sp = p1 if survivor == l1 else p2
    assert np.array_equal(pb.drain(survivor), solo(params, sp, 12))
    assert_no_leak(pb)


def test_chaos_eviction_mid_growth_shared_blocks_survive(params, rng):
    """The chaos leg: a deadline-evicted lane mid-growth frees its
    PRIVATE blocks; the stem blocks it shared survive for the other
    lane, whose output stays bit-exact, and nothing leaks."""
    t = {"now": 0.0}
    pb = paged(params, lanes=2, prompt_buckets=(4, 16),
               clock=lambda: t["now"])
    stem = rng.integers(0, 64, (16,)).astype(np.int32)
    pr1 = np.concatenate([stem, rng.integers(0, 64, (3,)).astype(np.int32)])
    pr2 = np.concatenate([stem, rng.integers(0, 64, (3,)).astype(np.int32)])
    l1 = pb.submit(pr1, 10)
    l2 = pb.submit(pr2, 10, ttl=5.0)           # will expire mid-decode
    shared = pb._lane_blocks[l1][:2]
    assert pb._lane_blocks[l2][:2] == shared
    blocks_at_admission = len(pb._lane_blocks[l2])
    for _ in range(7):
        pb.step()                              # both grow past block 2
    assert len(pb._lane_blocks[l2]) > blocks_at_admission  # mid-growth
    victim_private = [b for b in pb._lane_blocks[l2]
                      if b not in shared]
    assert victim_private                      # it DID grow private
    t["now"] = 6.0
    pb.step()                                  # reap evicts l2
    assert pb._lane_state[l2] is None
    for b in victim_private:                   # private blocks freed
        assert pb.allocator.refs_of(b) == 0
    for b in shared:                           # shared survive
        assert pb.allocator.refs_of(b) == 1
    assert np.array_equal(run_to_done(pb, l1),
                          solo(params, pr1, 10))
    assert_no_leak(pb)


def test_abort_admission_releases_staged_blocks(params, rng):
    """A failure AFTER block staging (here: the admit dispatch
    itself) must roll the staged blocks back — no half-admitted lane,
    no leak."""
    pb = paged(params, lanes=2, prompt_buckets=(8,))
    p = rng.integers(0, 64, (9,)).astype(np.int32)

    def boom(*a, **kw):
        raise RuntimeError("injected admit fault")
    real_admit, pb._admit = pb._admit, boom
    with pytest.raises(RuntimeError, match="injected admit fault"):
        pb.submit(p, 4)
    assert_no_leak(pb)
    # Early validation failures (before staging) stay clean too.
    pb._admit = real_admit
    with pytest.raises(ValueError, match="key iff"):
        pb.submit(p, 4, key=jax.random.key(0))  # greedy engine + key
    assert_no_leak(pb)
    out = run_to_done(pb, pb.submit(p, 4))      # engine still healthy
    assert np.array_equal(out, solo(params, p, 4))


def test_shutdown_drains_and_frees(params, rng):
    pb = paged(params, lanes=2, max_queue=2, prompt_buckets=(8,))
    rids = [pb.enqueue(rng.integers(0, 64, (6,)).astype(np.int32), 5)
            for _ in range(4)]
    res = pb.shutdown()
    assert sorted(res) == sorted(rids)
    assert all(r.ok for r in res.values())
    assert_no_leak(pb)


# ------------------------------------------- kv_int8="prefill"


def test_kv_int8_prefill_admission_tolerance(params, rng):
    """The round-12 satellite, pinned: a prefill-BUILT int8 cache
    (full-precision in-chunk attention, quantized once) differs from
    the exact decode-built cache by a real but bounded amount —
    nonzero (it IS a different build) and under
    KV_INT8_PREFILL_LOGIT_TOL on the first decode step's logits."""
    prompt = rng.integers(0, 64, (1, 17)).astype(np.int32)
    warm = jnp.asarray(prompt[:, :-1])
    w = warm.shape[1]
    cache_d = jgen.init_cache(CFG, 1, kv_int8=True)
    _, cache_d = jgen._decode_chunk(params, cache_d, warm,
                               jnp.zeros((1,), jnp.int32), CFG,
                               uniform_pos=True)
    cache_p, _ = jgen.prefill(params, warm, CFG, last_logits=False,
                         kv_int8=True)
    pos = jnp.full((1,), w, jnp.int32)
    last = jnp.asarray(prompt[:, -1:])
    lg_d, _ = jgen._decode_chunk(params, cache_d, last, pos, CFG)
    lg_p, _ = jgen._decode_chunk(params, cache_p, last, pos, CFG)
    diff = float(jnp.max(jnp.abs(lg_d - lg_p)))
    assert 0.0 < diff < KV_INT8_PREFILL_LOGIT_TOL, diff


def test_kv_int8_prefill_engine_agreement(params, rng):
    """Engine level: kv_int8="prefill" admission serves tokens that
    track the exact decode-built engine closely (measured: identical
    on this seed; the bound leaves headroom) and the decode phase
    after admission stays the same compiled path."""
    p = rng.integers(0, 64, (9,)).astype(np.int32)
    outs = {}
    for mode in (True, "prefill"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            pb = paged(params, lanes=1, kv_int8=mode)
        outs[mode] = run_to_done(pb, pb.submit(p, 12))
        assert_no_leak(pb)
    agree = np.mean(np.asarray(outs[True]) == np.asarray(outs["prefill"]))
    assert agree >= 0.9, (agree, outs)


def test_kv_int8_prefill_validation(params):
    from distkeras_tpu.models.quant import quantize_params

    with pytest.raises(ValueError, match="full-precision"):
        PagedBatcher(quantize_params(params), CFG, block=BLOCK,
                     kv_int8="prefill")
    with pytest.raises(ValueError, match='kv_int8 must be'):
        PagedBatcher(params, CFG, block=BLOCK, kv_int8="decode")
    # Monolithic engines reject the string too instead of silently
    # truthy-coercing it into plain decode-built int8.
    with pytest.raises(ValueError, match="PagedBatcher"):
        ContinuousBatcher(params, CFG, kv_int8="prefill")


# -------------------------------------------------- validation, obs


def test_paged_constructor_validation(params):
    with pytest.raises(ValueError, match="divide max_len"):
        PagedBatcher(params, CFG, block=5)
    win = serve_cfg(attention_window=16)
    with pytest.raises(ValueError, match="full-cache"):
        PagedBatcher(params, win, block=8)
    with pytest.raises(ValueError, match="block must be >= 1"):
        PagedBatcher(params, CFG, block=0)
    with pytest.raises(ValueError, match="n_blocks"):
        PagedBatcher(params, CFG, block=8, n_blocks=1)


def test_paged_obs_gauges_and_fork_counter(params, rng):
    """The round-12 observability satellite: kv_blocks_used/free/
    shared gauges and the cow_forks counter flow through the standard
    registry (and therefore /metrics and the cluster federation)."""
    sess = obs.enable()
    try:
        pb = paged(params, lanes=3, prompt_buckets=(4, 16))
        stem = rng.integers(0, 64, (16,)).astype(np.int32)
        l1 = pb.submit(
            np.concatenate([stem,
                            rng.integers(0, 64, (3,)).astype(np.int32)]),
            6)
        l2 = pb.submit(
            np.concatenate([stem,
                            rng.integers(0, 64, (3,)).astype(np.int32)]),
            6)
        f = pb.fork(l1, token=int(pb._lane_state[l1].tokens[-1]))
        reg = sess.registry
        assert reg.gauge("serving.kv_blocks_used").value() > 0
        assert reg.gauge("serving.kv_shared_blocks").value() >= 2
        assert (reg.gauge("serving.kv_blocks_used").value()
                + reg.gauge("serving.kv_blocks_free").value()
                == pb.allocator.capacity)
        assert reg.counter("serving.cow_forks").value() == 1
        assert reg.counter("serving.stem_hit_blocks").value() >= 2
        for lane in (l1, l2, f):
            run_to_done(pb, lane)
        assert reg.gauge("serving.kv_blocks_used").value() == 0
        text = reg.render_text()
        assert "serving_kv_blocks_used" in text
        assert "serving_cow_forks" in text
    finally:
        obs.disable()


def test_paged_zero_steady_state_compiles(params, rng):
    """Construction compiles everything; a full serve cycle —
    admission (stem hit AND miss), decode, fork, drain — compiles
    nothing (the in-repo mirror of the serving_paged* compile-guard
    sessions)."""
    import jax.monitoring

    n = {"c": 0}

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            n["c"] += 1
    jax.monitoring.register_event_duration_secs_listener(listener)
    pb = paged(params, lanes=3, prompt_buckets=(8,))
    built = n["c"]
    stem = rng.integers(0, 64, (8,)).astype(np.int32)
    l1 = pb.submit(
        np.concatenate([stem, rng.integers(0, 64, (4,)).astype(np.int32)]), 6)
    l2 = pb.submit(
        np.concatenate([stem, rng.integers(0, 64, (4,)).astype(np.int32)]), 6)
    pb.step()
    f = pb.fork(l1, token=int(pb._lane_state[l1].tokens[-1]))
    for lane in (l1, l2, f):
        run_to_done(pb, lane)
    assert n["c"] == built, f"serve phase compiled {n['c'] - built}"
