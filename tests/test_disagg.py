"""Round-17 disaggregated prefill/decode: block shipping + streaming.

Codec half (jax-free): the :mod:`serving.disagg` wire format round-
trips bit-exactly (int8 leaves included) and refuses anything torn.
Engine half: ``export_blocks``/``import_blocks`` adopt by page-table
splice with allocator refcounts — warm blocks hash-hit with zero
device writes, backpressure rolls back every reference.  Fleet half:
a role-split Router serves BIT-EXACT tokens vs solo (greedy AND
seeded, chunked-prefill and kv_int8 variants), never decodes on the
prefill replica, skips transfers for warm stems, falls back on hop
failure without a caller-visible error, streams the first token long
before the terminal result, and renders the cross-replica hop in the
``--request`` waterfall.
"""

import time

import numpy as np
import pytest

from distkeras_tpu import obs
from distkeras_tpu.obs.report import request_waterfall
from distkeras_tpu.obs.trace import read_trace
from distkeras_tpu.serving import (EngineEndpoint, HttpReplica,
                                   InProcessReplica, PagedBatcher,
                                   Router)
from distkeras_tpu.serving.disagg import (BlockShipment,
                                          decode_shipment,
                                          encode_shipment)
from helpers import serve_cfg, toy_params

BLOCK = 8


@pytest.fixture(scope="module")
def engine_params():
    import jax


    cfg = serve_cfg()
    return toy_params(cfg), cfg


def _paged(params, cfg, **kw):
    kw.setdefault("prompt_buckets", (8,))
    kw.setdefault("max_queue", 8)
    kw.setdefault("n_blocks", 16)
    return PagedBatcher(params, cfg, lanes=2, block=BLOCK, **kw)


def _cold(rng, blocks=2, tail=1):
    return rng.integers(0, 64, (blocks * BLOCK + tail,)) \
        .astype(np.int32)


def _run(router, rids):
    deadline = time.monotonic() + 120.0
    while any(router.poll(r) is None for r in rids):
        router.step()
        assert time.monotonic() < deadline
    return [router.take(r) for r in rids]


def _count(sess, name):
    doc = sess.registry.snapshot().get(name)
    if not doc:
        return 0.0
    return sum(s["value"] for s in doc["series"])


# ----------------------------------------------------------- the codec


def _toy_shipment(n=2):
    rng = np.random.default_rng(7)
    blocks, hashes = [], []
    for k in range(n):
        blocks.append((
            rng.normal(size=(2, 1, BLOCK, 2, 4)).astype(np.float32),
            rng.integers(-127, 128, (2, 1, BLOCK, 2, 4))
               .astype(np.int8),
            rng.normal(size=(2, 1, BLOCK, 2, 1)).astype(np.float32)))
        hashes.append(bytes([k]) * 16)
    return BlockShipment(block=BLOCK, hashes=tuple(hashes),
                         blocks=tuple(blocks))


def test_codec_roundtrip_bit_exact_including_int8():
    ship = _toy_shipment()
    back = decode_shipment(encode_shipment(ship))
    assert back.block == ship.block
    assert back.hashes == ship.hashes
    assert back.span == 2 * BLOCK and len(back) == 2
    assert back.nbytes == ship.nbytes
    for got, want in zip(back.blocks, ship.blocks):
        for g, w in zip(got, want):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(g, w)


def test_codec_rejects_malformed():
    ship = _toy_shipment()
    data = encode_shipment(ship)
    with pytest.raises(ValueError, match="truncated"):
        decode_shipment(data[:3])
    with pytest.raises(ValueError, match="truncated"):
        decode_shipment(data[:40])
    with pytest.raises(ValueError, match="magic"):
        decode_shipment(data.replace(b"dkt-blocks", b"dkt-bogus!"))
    with pytest.raises(ValueError, match="payload"):
        decode_shipment(data[:-8])
    with pytest.raises(ValueError, match="empty"):
        encode_shipment(BlockShipment(block=BLOCK, hashes=(),
                                      blocks=()))
    with pytest.raises(ValueError, match="digests"):
        BlockShipment(block=BLOCK, hashes=(b"x",), blocks=())
    ragged = BlockShipment(
        block=BLOCK, hashes=ship.hashes,
        blocks=(ship.blocks[0], ship.blocks[1][:2]))
    with pytest.raises(ValueError, match="ragged"):
        encode_shipment(ragged)


# ------------------------------------------------- export/import/adopt


def test_export_import_refcounts_and_admission_hit(engine_params,
                                                   rng):
    from helpers import generate

    params, cfg = engine_params
    src, dst = _paged(params, cfg), _paged(params, cfg)
    prompt = _cold(rng, blocks=2, tail=1)
    ship = src.export_blocks(prompt)
    assert len(ship) == 2 and ship.block == BLOCK
    assert ship.span == 16 and ship.nbytes > 0
    # The wire format carries the engine's real leaves bit-exactly.
    back = decode_shipment(encode_shipment(ship))
    for got, want in zip(back.blocks, ship.blocks):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    base_used = dst.allocator.stats()["used"]
    imported = dst.import_blocks(ship)
    assert imported["blocks"] == 2 and imported["hits"] == 0
    assert dst.allocator.stats()["used"] == base_used + 2
    assert set(ship.hexes()) <= set(dst.residency()["stem_hashes"])
    # Re-import is pure refcounting: content already resident.
    again = dst.import_blocks(ship)
    assert again["hits"] == 2
    assert dst.allocator.stats()["used"] == base_used + 2

    # Admission hash-hits the adopted run: zero re-prefill, tokens
    # bit-exact vs solo.
    rid = dst.enqueue(prompt, 5)
    while dst.poll(rid) is None:
        dst.step()
    res = dst.take(rid)
    assert dst.stem_hit_blocks >= 2
    solo = np.asarray(generate(params, prompt[None], cfg, 5))[0]
    np.testing.assert_array_equal(res.tokens, solo)

    dst.unpin_prefix(imported["prefix_id"])
    dst.unpin_prefix(again["prefix_id"])
    assert dst.allocator.stats()["used"] == base_used


def test_import_backpressure_rolls_back_every_reference(
        engine_params, rng):
    params, cfg = engine_params
    src = _paged(params, cfg)
    small = _paged(params, cfg, n_blocks=9)      # capacity 8
    for _ in range(2):                           # pin 6 of 8 blocks
        small.pin_prefix(rng.integers(0, 64, (24,)).astype(np.int32))
    used = small.allocator.stats()["used"]
    assert used == 6
    ship = src.export_blocks(_cold(rng, blocks=3, tail=0))
    assert small.import_blocks(ship) is None     # 3 > 2 free
    assert small.allocator.stats()["used"] == used  # nothing leaked

    with pytest.raises(ValueError, match="paged at"):
        small.import_blocks(BlockShipment(
            block=4, hashes=ship.hashes, blocks=ship.blocks))
    with pytest.raises(ValueError, match="empty"):
        small.import_blocks(BlockShipment(block=BLOCK, hashes=(),
                                          blocks=()))


# --------------------------------------------------- the 2-stage fleet


class _NoDecode(InProcessReplica):
    """A prefill replica that fails the test if the router ever
    routes a DECODE request to it — role exclusivity."""

    def enqueue(self, *a, **kw):
        raise AssertionError(
            "decode request admitted on the prefill replica")


def _fleet(params, cfg, prefill_cls=InProcessReplica, **kw):
    pre, dec = _paged(params, cfg, **kw), _paged(params, cfg, **kw)
    router = Router([prefill_cls("pre", pre, role="prefill"),
                     InProcessReplica("dec", dec, role="decode")])
    router.refresh_residency()    # the planner reads `block` off it
    return router, pre, dec


def test_disagg_parity_greedy_and_role_exclusivity(engine_params,
                                                   rng):
    from helpers import generate

    params, cfg = engine_params
    router, pre, dec = _fleet(params, cfg, prefill_cls=_NoDecode)
    prompts = [_cold(rng, blocks=2, tail=t) for t in (1, 2, 3)]
    with obs.session() as sess:
        rids = [router.enqueue(p, 5) for p in prompts]
        results = _run(router, rids)
        assert _count(sess, "router.disagg_requests") == 3
        assert _count(sess, "router.disagg_fallbacks") == 0
    for res, p in zip(results, prompts):
        solo = np.asarray(generate(params, p[None], cfg, 5))[0]
        np.testing.assert_array_equal(res.tokens, solo)
    # Each adopted run hash-hit at admission on the decode side.
    assert dec.stem_hit_blocks >= 6
    # Import pins were handed back at terminal: the decode slab
    # drains to empty (no pins, no lanes).
    router.pump()
    assert dec.residency()["prefix_ids"] == []
    assert dec.allocator.stats()["used"] == 0


def test_disagg_parity_seeded_sampling(engine_params, rng):
    import jax

    from helpers import generate

    params, cfg = engine_params
    kw = dict(temperature=0.7, top_k=16)
    router, _pre, _dec = _fleet(params, cfg, **kw)
    prompts = [_cold(rng, blocks=2, tail=t) for t in (1, 2)]
    keys = [jax.random.key(11), jax.random.key(12)]
    rids = [router.enqueue(p, 5, key=k)
            for p, k in zip(prompts, keys)]
    for res, p, k in zip(_run(router, rids), prompts, keys):
        solo = np.asarray(
            generate(params, p[None], cfg, 5, key=k, **kw))[0]
        np.testing.assert_array_equal(res.tokens, solo)


def test_disagg_parity_chunked_prefill(engine_params, rng):
    from helpers import generate

    params, cfg = engine_params
    router, _pre, _dec = _fleet(params, cfg, prefill_chunk=8)
    prompt = _cold(rng, blocks=2, tail=2)
    with obs.session() as sess:
        (res,) = _run(router, [router.enqueue(prompt, 5)])
        assert _count(sess, "router.disagg_requests") == 1
    solo = np.asarray(generate(params, prompt[None], cfg, 5))[0]
    np.testing.assert_array_equal(res.tokens, solo)


def test_disagg_parity_kv_int8(engine_params, rng):
    """int8 blocks ride the wire as-is: a disaggregated kv_int8
    request matches the SAME-config solo engine bit-exactly (int8
    decode is its own numeric contract, so the reference is the solo
    engine, not f32 generate)."""
    params, cfg = engine_params
    prompt = _cold(rng, blocks=2, tail=1)
    solo_eng = _paged(params, cfg, kv_int8=True)
    lane = solo_eng.enqueue(prompt, 5)
    while solo_eng.poll(lane) is None:
        solo_eng.step()
    ref = solo_eng.take(lane).tokens

    router, _pre, _dec = _fleet(params, cfg, kv_int8=True)
    with obs.session() as sess:
        (res,) = _run(router, [router.enqueue(prompt, 5)])
        assert _count(sess, "router.disagg_requests") == 1
    np.testing.assert_array_equal(res.tokens, ref)


def test_warm_stems_skip_the_transfer(engine_params, rng):
    from helpers import generate

    params, cfg = engine_params
    router, _pre, _dec = _fleet(params, cfg)
    head = _cold(rng, blocks=2, tail=0)
    with obs.session() as sess:
        (r1,) = _run(router, [router.enqueue(
            np.concatenate([head, head[:1]]), 5)])
        assert _count(sess, "router.disagg_requests") == 1
        # Same full blocks, different tail: every stem is now
        # resident on the decode replica — the hop is pure waste.
        p2 = np.concatenate([head, head[1:2]])
        (r2,) = _run(router, [router.enqueue(p2, 5)])
        assert _count(sess, "router.disagg_requests") == 1
        assert _count(sess, "router.disagg_warm_skips") >= 1
    solo = np.asarray(generate(params, p2[None], cfg, 5))[0]
    np.testing.assert_array_equal(r2.tokens, solo)


def test_prefill_failure_falls_back_never_errors(engine_params, rng,
                                                 monkeypatch):
    from helpers import generate

    params, cfg = engine_params
    router, pre, _dec = _fleet(params, cfg)

    def boom(tokens):
        raise RuntimeError("prefill replica died mid-build")

    monkeypatch.setattr(pre, "export_blocks", boom)
    prompt = _cold(rng, blocks=2, tail=1)
    with obs.session() as sess:
        (res,) = _run(router, [router.enqueue(prompt, 5)])
        assert _count(sess, "router.disagg_fallbacks") == 1
        assert _count(sess, "router.disagg_requests") == 0
    assert res.ok
    solo = np.asarray(generate(params, prompt[None], cfg, 5))[0]
    np.testing.assert_array_equal(res.tokens, solo)


# ------------------------------------------------------------ streaming


def test_stream_first_token_before_terminal(engine_params, rng):
    from helpers import generate

    params, cfg = engine_params
    eng = _paged(params, cfg)
    router = Router([InProcessReplica("r0", eng)])
    prompt = rng.integers(0, 64, (6,)).astype(np.int32)
    rid = router.enqueue(prompt, 8)
    gen = router.stream(rid)
    first = next(gen)
    # The whole point: a token in hand while the request decodes.
    assert router.poll(rid) is None
    tokens = [first] + list(gen)
    res = router.take(rid)
    assert res.ok and tokens == list(res.generated)
    solo = np.asarray(generate(params, prompt[None], cfg, 8))[0]
    np.testing.assert_array_equal(res.tokens, solo)


def test_stream_across_the_disagg_hop(engine_params, rng):
    from helpers import generate

    params, cfg = engine_params
    router, _pre, _dec = _fleet(params, cfg)
    prompt = _cold(rng, blocks=2, tail=1)
    with obs.session() as sess:
        rid = router.enqueue(prompt, 6)
        assert _count(sess, "router.disagg_requests") == 1
        tokens = list(router.stream(rid))
    solo = np.asarray(generate(params, prompt[None], cfg, 6))[0]
    assert tokens == list(solo[prompt.size:])
    assert router.take(rid).ok


def test_waterfall_renders_the_block_transfer_hop(engine_params, rng,
                                                  tmp_path):
    params, cfg = engine_params
    trace = str(tmp_path / "disagg.jsonl")
    router, _pre, _dec = _fleet(params, cfg)
    prompt = _cold(rng, blocks=2, tail=1)
    with obs.session(trace_path=trace):
        rid = router.enqueue(prompt, 5)
        res = router.drain(rid)
        assert res.ok
    wf = request_waterfall(read_trace(trace), rid)
    assert wf["found"] and wf["status"] == "ok"
    names = [s["name"] for s in wf["stages"]]
    assert "router.prefill" in names
    assert "router.block_transfer" in names
    assert "serving.finish" in names
    hop = next(s for s in wf["stages"]
               if s["name"] == "router.block_transfer")
    assert hop["src"] == "pre" and hop["dst"] == "dec"
    assert hop["blocks"] == 2 and hop["bytes"] > 0


# ------------------------------------------------------- the endpoints


def test_endpoint_disagg_routes_and_discovery(engine_params, rng,
                                              tmp_path):
    params, cfg = engine_params
    pre_eng, dec_eng = _paged(params, cfg), _paged(params, cfg)
    pre_ep = EngineEndpoint(pre_eng, host_id=0, role="prefill",
                            coord_dir=str(tmp_path))
    dec_ep = EngineEndpoint(dec_eng, host_id=1, role="decode",
                            coord_dir=str(tmp_path))
    pre_ep.start(step=True)
    dec_ep.start(step=True)
    try:
        from distkeras_tpu.serving import discover_replicas

        found = {r.name: r for r in discover_replicas(str(tmp_path))}
        assert found["host0"].role == "prefill"
        assert found["host1"].role == "decode"

        pre = HttpReplica("pre", pre_ep.addr, role="prefill")
        dec = HttpReplica("dec", dec_ep.addr, role="decode")
        prompt = _cold(rng, blocks=2, tail=1)
        # The raw transfer surface: POST /prefill -> shipment,
        # POST /blocks -> adoption dict, POST /unpin releases.
        ship = pre.prefill_blocks(prompt)
        assert len(ship) == 2 and ship.block == BLOCK
        imported = dec.import_blocks(ship)
        assert imported["blocks"] == 2 and imported["hits"] == 0
        dec.unpin(int(imported["prefix_id"]))
        # GET /stream: 404 for unknown ids maps to None.
        assert dec.partial(123456789) is None

        router = Router([pre, dec], health_interval=0.0)
        router.refresh_residency()
        rid = router.enqueue(prompt, 5)
        deadline = time.monotonic() + 60.0
        while router.poll(rid) is None:
            router.pump()
            assert time.monotonic() < deadline
            time.sleep(0.01)
        res = router.take(rid)
        assert res.ok and len(res.generated) == 5
        # The hop landed the decode on the decode endpoint, warm.
        assert dec_eng.stem_hit_blocks >= 2
        assert pre_eng.stem_hit_blocks == 0 or not pre_eng.running()
    finally:
        pre_ep.stop()
        dec_ep.stop()
