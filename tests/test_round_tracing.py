"""The names the program gives its own work (docs/observability.md,
"A serving round" and "Names on the device timeline"): the span tree
and the counts of one ``ContinuousBatcher.step()``, the three Pallas
kernels' names, the sublayer scopes in the operation metadata of the
lowered programs, and the program names the benchmark's readers match.
"""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import distkeras_tpu as dk
from distkeras_tpu import obs
from distkeras_tpu.models import transformer as tfm
from distkeras_tpu.obs import read_trace
from distkeras_tpu.parallel.mesh import MeshSpec, make_mesh
from helpers import toy_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_len=64)
ROUND_CHILDREN = ("serving.pump", "serving.admit_chunk", "serving.step",
                  "serving.collect", "serving.emit_loop", "serving.reap")


@pytest.fixture(scope="module")
def rounds(tmp_path_factory):
    """A scripted run: B (5 tokens) decodes while A (21 tokens, three
    chunks of 8) is admitted and prefilled between B's decode steps —
    its middle chunk INSIDE B's decode program (round 2, ``fused``), its
    last as a program of its own (round 3).  A call dispatches its
    decode round and reads the one before, so a round's ``tokens`` are
    the previous dispatch's.  Returns the ``serving.round`` spans in
    order and every span."""
    path = str(tmp_path_factory.mktemp("rounds") / "t.jsonl")
    params = toy_params(CFG)
    with obs.session(trace_path=path):
        eng = dk.ContinuousBatcher(params, CFG, lanes=2, max_queue=4,
                                   prefill_chunk=8, prompt_buckets=(8,))
        eng.step()                          # 0: nothing to do
        eng.enqueue(np.arange(5), 8)        # B: one admission program
        eng.step()                          # 1: B's first step goes out
        eng.enqueue(np.arange(21), 4)       # A: first chunk [0, 8)
        eng.step()                          # 2: A's chunk [8, 16) in B's step;
        #                                        B's first token comes back
        eng.step()                          # 3: A's chunk [12, 20), both decode
        eng.step()                          # 4: both tokens of round 3
    spans = [r for r in read_trace(path) if r["kind"] == "span"]
    return [s for s in spans if s["name"] == "serving.round"], spans


@pytest.mark.parametrize("child", ROUND_CHILDREN)
def test_round_is_parent_of(rounds, child):
    """Round 3 runs every boundary: pump, a continuation chunk as a
    program of its own, the decode dispatch, the read of the round
    before, the emit loop, the reap — each a child of the round, inside
    its interval."""
    rnds, spans = rounds
    rnd = rnds[3]
    (sp,) = [s for s in spans
             if s["name"] == child and s["parent"] == rnd["id"]]
    assert sp["depth"] == rnd["depth"] + 1
    assert rnd["t0"] <= sp["t0"]
    assert sp["t0"] + sp["dur"] <= rnd["t0"] + rnd["dur"] + 1e-9


def test_idle_round_is_marked_and_dispatches_nothing(rounds):
    rnds, spans = rounds
    assert [bool(r["fields"].get("idle")) for r in rnds] == [
        True, False, False, False, False]
    idle = rnds[0]
    names = {s["name"] for s in spans if s["parent"] == idle["id"]}
    assert names == {"serving.pump", "serving.reap"}
    assert idle["fields"]["tokens"] == idle["fields"]["kv_live"] == 0
    # The admission spans keep their fields (the prefill reader sums
    # ``bucket``) and carry the request's id.
    admits = [s for s in spans if s["name"] in ("serving.admit",
                                                "serving.admit_chunk")]
    assert [s["fields"]["bucket"] for s in admits] == [8, 8, 8]
    # ``positions``: what each program writes that is neither padding
    # (B: 4 warm tokens in a bucket of 8) nor written before (A's
    # backed-up tail [12, 20) adds four); a request's sum to its
    # prompt length less one — with the chunk that rode a decode step
    # (``test_a_fused_chunk_is_said_by_its_step``: A's [8, 16)).
    assert [s["fields"]["positions"] for s in admits] == [4, 8, 4]
    assert all("request_id" in s["fields"] for s in admits)


@pytest.mark.parametrize("i,want", [
    # B holds its 5 prompt tokens once the step dispatched here has
    # run (the token that step decodes is the next step's input, not
    # yet in the cache); the step is unread, so nothing is emitted.
    (1, {"lanes_busy": 1, "lanes_admitting": 0, "kv_live": 5,
         "chunks": 1, "tokens": 0}),
    # The round PERF.md describes: A's first chunk ran at admission and
    # a continuation chunk in this step(), so TWO chunks stand between
    # two decode dispatches — the second of them inside this round's
    # decode program (``fused``).  A is mid-prefill: positions [0, 12)
    # lie before its next chunk.  The token is round 1's.
    (2, {"lanes_busy": 2, "lanes_admitting": 1, "kv_live": 6 + 12,
         "chunks": 2, "fused": 1, "tokens": 1}),
    # A's last chunk landed (a program of its own: it un-parks A) and A
    # joined this decode: its 21 prompt tokens are in, its first token
    # is the next input — and comes back a call later: the token
    # emitted here is B's, of round 2.
    (3, {"lanes_busy": 2, "lanes_admitting": 0, "kv_live": 7 + 21,
         "chunks": 1, "fused": 0, "tokens": 1}),
    (4, {"lanes_busy": 2, "lanes_admitting": 0, "kv_live": 8 + 22,
         "chunks": 0, "fused": 0, "tokens": 2}),
])
def test_round_counts_equal_a_hand_count(rounds, i, want):
    rnds, _ = rounds
    got = {k: rnds[i]["fields"][k] for k in want}
    assert got == want


def _admits(spans):
    return [s for s in spans if s["name"] in ("serving.admit",
                                              "serving.admit_chunk")]


def test_attended_is_max_len_on_the_dense_path(rounds):
    """No kernel on this backend: every admission program's attention
    reads all of the lane's slots, and says so."""
    _, spans = rounds
    assert [s["fields"]["attended"] for s in _admits(spans)] == [
        CFG.max_len] * 3


def _interpreted_kernels(monkeypatch):
    """A TPU backend's choice of attention (the admission and the
    decode kernel wherever their shape rules allow), run through the
    Pallas interpreter."""
    from distkeras_tpu.models import generate as gen
    from distkeras_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    for name in ("flash_prefix_attention", "flash_decode_attention"):
        kernel = getattr(attention, name)
        monkeypatch.setattr(gen, name, lambda *a, kernel=kernel: kernel(
            *a, interpret=True))
    return gen


def test_attended_is_the_chunks_end_on_the_bounded_path(tmp_path,
                                                        monkeypatch):
    """Where the admission programs hold the bounded kernel (a TPU
    backend, kernel-legal widths; here the interpreter stands in),
    ``attended`` is ``start + bucket``: A's 20 warm tokens are chunks
    [0, 8), [8, 16) and the backed-up tail [12, 20).  Never above
    ``max_len``; ``bucket`` and the other fields stay."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=256, n_heads=2,
                                n_kv_heads=1, n_layers=1, d_ff=64,
                                max_len=128)
    _interpreted_kernels(monkeypatch)
    path = str(tmp_path / "t.jsonl")
    with obs.session(trace_path=path):
        eng = dk.ContinuousBatcher(
            toy_params(cfg), cfg, lanes=2,
            max_queue=4, prefill_chunk=8, prompt_buckets=(8,))
        eng.enqueue(np.arange(21), 2)
        for _ in range(3):
            eng.step()
    admits = _admits([r for r in read_trace(path) if r["kind"] == "span"])
    assert [s["name"] for s in admits] == [
        "serving.admit", "serving.admit_chunk", "serving.admit_chunk"]
    assert [s["fields"]["attended"] for s in admits] == [8, 16, 20]
    assert [s["fields"]["bucket"] for s in admits] == [8, 8, 8]
    assert all(s["fields"]["attended"] <= cfg.max_len for s in admits)
    assert set(admits[0]["fields"]) == {"bucket", "positions", "chunks",
                                        "lane", "request_id", "attended",
                                        "program"}
    assert set(admits[1]["fields"]) == {"bucket", "positions", "remaining",
                                        "request_id", "attended", "program"}


def _steps(spans):
    return [s for s in spans if s["name"] == "serving.step"]


def _n_attended(step):
    return {k: step["fields"][k] for k in ("n", "attended")}


def test_step_attended_is_every_slot_on_the_dense_path(rounds):
    """No kernel on this backend: every decode dispatch reads all
    ``max_len`` slots of every lane, busy or not, and says so beside
    ``n``."""
    _, spans = rounds
    steps = _steps(spans)
    assert len(steps) == 4                       # the idle round has none
    assert [_n_attended(s) for s in steps] == [
        {"n": 1, "attended": 2 * CFG.max_len}] * 4
    assert all(set(s["fields"]) == {"n", "attended", "seq", "program"}
               for s in steps if "bucket" not in s["fields"])


def test_a_fused_chunk_is_said_by_its_step(rounds):
    """Round 2's decode program also admits A's chunk [8, 16): the
    round's ONE ``serving.step`` says what a ``serving.admit_chunk``
    span would have (``bucket``, ``positions``, ``remaining``,
    ``request_id``; the chunk's reads as ``chunk_attended``, the decode
    rows' stay ``attended``), names the third program, and NO
    ``serving.admit_chunk`` span is opened for the chunk — the
    admission reader divides the ``_admit`` programs' device time by
    those spans' ``bucket``.  ``serving.round.fused`` counts it."""
    rnds, spans = rounds
    assert [r["fields"]["fused"] for r in rnds] == [0, 0, 1, 0, 0]
    kids = [s for s in spans if s["parent"] == rnds[2]["id"]]
    assert "serving.admit_chunk" not in {s["name"] for s in kids}
    (step,) = [s for s in kids if s["name"] == "serving.step"]
    (a_admit,) = [s for s in spans if s["name"] == "serving.admit"
                  and s["fields"]["chunks"] == 3]
    assert step["fields"] == {
        "n": 1, "attended": 2 * CFG.max_len, "seq": 2,
        "program": "jit_round_chunk", "bucket": 8, "positions": 8,
        "remaining": 1, "request_id": a_admit["fields"]["request_id"],
        "chunk_attended": CFG.max_len}
    # The one admission chunk that is a span is A's last, in round 3.
    (chunk,) = [s for s in spans if s["name"] == "serving.admit_chunk"]
    assert chunk["parent"] == rnds[3]["id"]
    assert chunk["fields"]["remaining"] == 0


def test_step_attended_follows_the_lanes_on_the_bounded_path(tmp_path,
                                                             monkeypatch):
    """Where the decode program holds the per-lane bounded kernel (a
    TPU backend, kernel-legal shapes; here the interpreter stands in),
    ``attended`` is each decoding lane's position rounded up to the
    kernel's smallest copy — 256 of the 1024 slots here — plus the whole row of
    every lane that is free or still admitting; a window of ``n`` steps
    reads ``n`` times.  Its tokens are the dense path's."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=256, n_heads=2,
                                n_kv_heads=1, n_layers=1, d_ff=64,
                                max_len=1024)
    params = toy_params(cfg)

    def run(path):
        with obs.session(trace_path=path):
            eng = dk.ContinuousBatcher(params, cfg, lanes=3, max_queue=4,
                                       prefill_chunk=8, prompt_buckets=(8,))
            eng.enqueue(np.arange(5), 8)     # B: decoding from position 4
            out = [eng.step()]
            eng.enqueue(np.arange(21), 4)    # A: admitting for two rounds
            out += [eng.step(), eng.step(2)]
        return out, _steps([r for r in read_trace(path)
                            if r["kind"] == "span"])

    dense_out, dense = run(str(tmp_path / "dense.jsonl"))
    assert [s["fields"]["attended"] for s in dense] == [3072, 3072, 6144]
    gen = _interpreted_kernels(monkeypatch)
    assert gen.decode_read_unit(cfg, 1, {"k": jnp.zeros(())}) == 256
    out, steps = run(str(tmp_path / "bounded.jsonl"))
    # B alone; B and the admitting A; B and A over a window of two.
    assert [_n_attended(s) for s in steps] == [
        {"n": 1, "attended": 256 + 2 * 1024},
        {"n": 1, "attended": 256 + 2 * 1024},
        {"n": 2, "attended": 2 * (256 + 256 + 1024)}]
    assert out == dense_out


# --------------------------- which program a span launched, which launch it read


def _lowered(eng, **how):
    """``{"decode_step" | "admit" | "round_chunk": text}``: an engine's
    three programs, lowered (the third where the engine fuses)."""
    out = {}
    for spec in eng.traced_for_analysis():
        key = "decode_step" if spec.name.endswith("decode_step") else "admit"
        out[key] = spec.fn.lower(*spec.args).as_text(**how)
    if eng._round_chunk is not None:
        out["round_chunk"] = eng._round_chunk.lower(
            *eng._pargs(), eng.cache, eng.cur, eng.pos, eng.keys, eng.temps,
            eng.tps, eng.mps, jnp.zeros((1, eng.prefill_chunk), jnp.int32),
            jnp.int32(0), jnp.int32(0)).as_text(**how)
    return out


def _lowered_names(eng):
    """The lowered modules' names of an engine's programs: what an "XLA
    Modules" event of the device trace is called (before its ``(id)``)."""
    out = {}
    for key, text in _lowered(eng).items():
        (out[key],) = re.findall(r"module @(\S+)", text)
    return out


@pytest.fixture(scope="module", params=[False, True],
                ids=["closure", "hot_swap"])
def declared(request, tmp_path_factory):
    """A chunked admission beside a decoding lane, by an engine that
    closes over its weights and by one that takes them as an argument
    (other functions, other names): the spans, and the three programs'
    lowered names."""
    path = str(tmp_path_factory.mktemp("declared") / "t.jsonl")
    with obs.session(trace_path=path):
        eng = dk.ContinuousBatcher(toy_params(CFG), CFG, lanes=2,
                                   hot_swap=request.param, max_queue=4,
                                   prefill_chunk=8, prompt_buckets=(8,))
        eng.enqueue(np.arange(5), 4)
        eng.step()
        eng.enqueue(np.arange(21), 2)
        while eng.running():
            eng.step()
    spans = [r for r in read_trace(path) if r["kind"] == "span"]
    return spans, _lowered_names(eng)


@pytest.mark.parametrize("span,programs", [
    ("serving.step", ("decode_step", "round_chunk")),
    ("serving.admit", ("admit",)), ("serving.admit_chunk", ("admit",))])
def test_program_is_the_lowered_modules_name(declared, span, programs):
    """A dispatching span's ``program`` is what the device trace will
    call the launch — taken from the jitted callable, so a renamed or
    fused program changes it with no reader edited.  A decode round
    launches one of two: the plain step, or the step that also admits
    the round's continuation chunk."""
    spans, lowered = declared
    said = {s["fields"]["program"] for s in spans if s["name"] == span}
    assert said == {lowered[p] for p in programs}
    assert all(lowered[p].startswith("jit_") for p in programs)
    if span == "serving.step":
        fused = {s["fields"]["program"] for s in spans
                 if s["name"] == span and "bucket" in s["fields"]}
        assert fused == {lowered["round_chunk"]}


def test_the_fused_program_is_neither_readers(declared):
    """``decode_step_ms`` finds its program by ``step_n``,
    ``prefill_ms_per_ktok`` by ``_admit``: the third program's name
    matches neither, so each metric keeps reading the program its
    ``what`` says — the plain step, the standalone admission."""
    name = declared[1]["round_chunk"]
    assert name.startswith("jit_round_chunk")
    for metric in ("decode_step_ms", "prefill_ms_per_ktok"):
        assert not re.search(_pattern(metric), name), (metric, name)


def _scripted(kind, path):
    """Two requests through an engine of ``kind`` under a session until
    nothing is left on the device; returns the spans."""
    from helpers import serve_cfg, spec_draft_cfg

    cfg = serve_cfg()
    params = toy_params(cfg)
    prompts = [np.arange(5, dtype=np.int32), np.arange(3, 10, dtype=np.int32)]
    with obs.session(trace_path=path):
        if kind == "speculative":
            draft = spec_draft_cfg()
            eng = dk.SpeculativeBatcher(params, toy_params(draft, 1), cfg,
                                        draft, lanes=2, n_draft=2,
                                        max_queue=2)
        elif kind == "paged":
            eng = dk.PagedBatcher(params, cfg, lanes=2, block=8, max_queue=2)
        elif kind == "resize":
            eng = dk.ContinuousBatcher(
                params, cfg, lane_tiers=(1, 2), max_queue=1,
                scale_up_after=1, scale_down_after=2, prompt_buckets=(8,))
        else:
            eng = dk.ContinuousBatcher(params, cfg, lanes=2, max_queue=2)
        eng.enqueue(prompts[0], 6)
        eng.step()
        eng.step()
        if kind in ("resize", "shutdown"):
            assert eng._inflight is not None
        eng.enqueue(prompts[1], 4)      # resize: scales up, flushing first
        if kind == "shutdown":
            eng.step()
            eng.shutdown()              # flushes the round in flight
        else:
            for _ in range(40):
                if not (eng.running() or eng.queued
                        or eng._inflight is not None):
                    break
                eng.step()
        assert eng._inflight is None
    return [r for r in read_trace(path) if r["kind"] == "span"]


@pytest.mark.parametrize("kind,lag", [
    ("inflight", 1), ("shutdown", None), ("resize", None), ("paged", 0),
    ("speculative", 0)])
def test_collect_names_the_dispatch_it_reads(kind, lag, tmp_path):
    """``serving.step``'s ``seq`` counts the engine's decode dispatches
    from 1 with no hole; every ``serving.collect`` names a ``seq`` that
    was dispatched before it began and that no earlier collect read,
    and once the engine has drained every dispatch has been read — with
    a round in flight (the read lags the dispatch by one call), after a
    ``_flush_round`` (shutdown; a resize), and where each round is read
    by the call that dispatched it (the paged engine; the speculative
    one, whose read is inside its ``serving.step``)."""
    spans = sorted(_scripted(kind, str(tmp_path / "t.jsonl")),
                   key=lambda s: s["t0"])
    steps = _steps(spans)
    assert [s["fields"]["seq"] for s in steps] == list(
        range(1, len(steps) + 1)) and len(steps) >= 4
    read = []
    for c in (s for s in spans if s["name"] == "serving.collect"):
        assert set(c["fields"]) == {"seq", "wait_ms"}
        dispatched = {s["fields"]["seq"] for s in steps
                      if s["t0"] <= c["t0"]}
        assert c["fields"]["seq"] in dispatched
        assert c["fields"]["seq"] not in read
        read.append(c["fields"]["seq"])
    assert read == sorted(read) and len(read) == len(steps)
    if lag is not None:
        # Inside one call: the read is of the dispatch ``lag`` before
        # (the collect's parent is the step's round; the step itself in
        # the speculative engine).
        key = "id" if kind == "speculative" else "parent"
        owner = {s[key]: s["fields"]["seq"] for s in steps}
        pairs = [(owner[c["parent"]], c["fields"]["seq"]) for c in spans
                 if c["name"] == "serving.collect" and c["parent"] in owner]
        assert pairs and all(k - r == lag for k, r in pairs)


def test_host_ms_is_the_rounds_time_less_its_waits(rounds):
    """``serving.round``'s ``host_ms``: written as the round closes, so
    never more than its duration, and short of it by what the round's
    ``serving.collect`` children waited for the device."""
    rnds, spans = rounds
    for rnd in rnds:
        waited = sum(s["fields"]["wait_ms"] for s in spans
                     if s["name"] == "serving.collect"
                     and s["parent"] == rnd["id"])
        own = rnd["dur"] * 1e3 - waited
        assert 0 <= rnd["fields"]["host_ms"] <= own + 1e-6
        # (what the span still does after the field is written: its own
        # exit; a loaded box may be slow there, not by this much)
        assert rnd["fields"]["host_ms"] == pytest.approx(own, abs=50)
    assert any(s["name"] == "serving.collect" for s in spans)


@pytest.mark.parametrize("kind", ["lanes", "paged"])
def test_no_session_walks_no_lane_table_for_a_span(kind, tmp_path,
                                                   monkeypatch):
    """With no session ``step()`` evaluates no span argument that walks
    the lane table or asks the model's shape rules: ``_step_attended``
    and ``_attended`` are not called (they are once a span is live) —
    and the transcripts are the traced run's."""
    from distkeras_tpu.serving.engine import _LaneEngine
    from helpers import serve_cfg

    calls = {"_step_attended": 0, "_attended": 0}
    for name in calls:
        real = getattr(_LaneEngine, name)

        def counted(self, *a, _real=real, _name=name):
            calls[_name] += 1
            return _real(self, *a)

        monkeypatch.setattr(_LaneEngine, name, counted)
    cfg = serve_cfg()
    params = toy_params(cfg)

    def serve():
        make = (lambda: dk.PagedBatcher(params, cfg, lanes=2, block=8,
                                        max_queue=2, prefill_chunk=8)
                ) if kind == "paged" else (
            lambda: dk.ContinuousBatcher(params, cfg, lanes=2, max_queue=2,
                                         prefill_chunk=8,
                                         prompt_buckets=(8,)))
        eng = make()
        rids = [eng.enqueue(np.arange(5, dtype=np.int32), 5)]
        eng.step()
        rids.append(eng.enqueue(np.arange(20, dtype=np.int32), 3))
        while eng.running() or eng._inflight is not None:
            eng.step()
        return [eng.take(r).tokens.tolist() for r in rids]

    plain = serve()
    assert calls == {"_step_attended": 0, "_attended": 0}
    with obs.session(trace_path=str(tmp_path / "t.jsonl")):
        traced = serve()
    assert calls["_step_attended"] > 0 and calls["_attended"] > 0
    assert traced == plain


# ------------------------------------------ names on the device timeline


def _pattern(metric):
    with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                           metric + ".json")) as f:
        return json.load(f)["args"]["pattern"]


@pytest.fixture(scope="module")
def engine_programs():
    """The decode-step, the admission and the fused program of a
    hot-swap engine (the benchmark's), lowered: ``{"decode_step" |
    "admit" | "round_chunk": text}``."""
    params = toy_params(CFG)
    eng = dk.ContinuousBatcher(params, CFG, lanes=2, hot_swap=True,
                               prefill_chunk=8, prompt_buckets=(8,))
    return _lowered(eng, debug_info=True)


@pytest.mark.parametrize("program,metric", [
    ("decode_step", "decode_step_ms"), ("admit", "prefill_ms_per_ktok")])
def test_program_names_match_the_benchmark_readers(engine_programs,
                                                   program, metric):
    """An "XLA Modules" event is named after the jitted function
    (``jit_step_n_p(...)``, ``jit__admit(...)``); the accepted readers
    find the programs by these names, so they are pinned here and not
    renamed."""
    (module,) = re.findall(r"module @(\S+)", engine_programs[program])
    assert module.startswith("jit_")
    assert re.search(_pattern(metric), module), (module, _pattern(metric))


def _scopes_in(text):
    """The vocabulary words that stand as a scope in some operation's
    ``loc("jit(f)/jvp(embed)/gather")`` of a lowered program."""
    locs = " ".join(set(re.findall(r'loc\("([^"]+)"', text)))
    return {sc for sc in tfm.SCOPES
            if re.search(rf"(?<![\w.]){sc}(?![\w.])", locs)}


@pytest.mark.parametrize("program,absent", [
    # ``loop_exit`` is what sits between two passes: an unlooped stack
    # has no such operation.
    ("decode_step", {"loop_exit"}),
    # An admission discards the chunk's logits: the head is traced and
    # then pruned as dead code, so the program never computes it.
    ("admit", {"head", "loop_exit"}),
    # The fused round's head runs over the lanes' rows.
    ("round_chunk", {"loop_exit"})])
def test_serving_programs_hold_every_scope(engine_programs, program,
                                           absent):
    assert _scopes_in(engine_programs[program]) \
        == set(tfm.SCOPES) - absent


LOOPED = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=64,
    rope=True, ffn_gated=True, tie_head=False, post_norms=True,
    fused_qkv=True, n_passes=3)


@pytest.fixture(scope="module")
def looped_programs():
    """The same two programs of a LOOPED hot-swap engine."""
    params = toy_params(LOOPED)
    eng = dk.ContinuousBatcher(params, LOOPED, lanes=2, hot_swap=True,
                               prefill_chunk=8, prompt_buckets=(8,))
    out = {}
    for spec in eng.traced_for_analysis():
        key = "decode_step" if spec.name.endswith("decode_step") else "admit"
        out[key] = spec.fn.lower(*spec.args).as_text(debug_info=True)
    return out


@pytest.mark.parametrize("program,metric,absent", [
    ("decode_step", "decode_step_ms", set()),
    ("admit", "prefill_ms_per_ktok", {"head"})])
def test_looped_programs_keep_their_names_and_hold_every_scope(
        looped_programs, program, metric, absent):
    """The looped step is read by the readers that read the unlooped
    one (program names unchanged), holds the whole vocabulary —
    ``loop_exit`` for the per-pass final norm included — and no model
    operation of it stands outside a scope: the output norms are
    ``norm``, the gated product ``mlp``, the untied head ``head``."""
    text = looped_programs[program]
    (module,) = re.findall(r"module @(\S+)", text)
    assert re.search(_pattern(metric), module), module
    assert _scopes_in(text) == set(tfm.SCOPES) - absent
    heavy = [loc for loc in set(re.findall(r'loc\("([^"]+)"', text))
             if re.search(r"/(dot_general|exp|logistic|rsqrt)$", loc)]
    assert heavy
    for loc in heavy:
        assert any(re.search(rf"(?<![\w.]){sc}(?![\w.])", loc)
                   for sc in tfm.SCOPES), loc


RETENTION = tfm.TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=64,
    rope=True, ffn_gated=True, tie_head=False, fused_qkv=True, qk_norm=True,
    layer_types=("retention", "retention"))


@pytest.fixture(scope="module")
def retention_run(tmp_path_factory):
    """A retention stack (a lane's plane is a state, not keys and
    values) through a two-lane hot-swap engine: the trace's records,
    and its two programs lowered."""
    path = str(tmp_path_factory.mktemp("ret") / "t.jsonl")
    with obs.session(trace_path=path):
        eng = dk.ContinuousBatcher(toy_params(RETENTION), RETENTION, lanes=2,
                                   hot_swap=True, prefill_chunk=8,
                                   prompt_buckets=(8,), max_queue=4)
        eng.enqueue(np.arange(5, dtype=np.int32), 3)
        eng.step()
        eng.enqueue(np.arange(20, dtype=np.int32), 2)   # admitting: 3 chunks
        while eng.running():
            eng.step()
    texts = {("decode_step" if spec.name.endswith("decode_step") else "admit"):
             spec.fn.lower(*spec.args).as_text(debug_info=True)
             for spec in eng.traced_for_analysis()}
    return read_trace(path), texts


def test_state_planes_are_named_in_the_layout_and_the_rounds(retention_run,
                                                            rounds):
    """``serving.kv_layout`` says what a lane's state costs (at any
    position); ``serving.round`` how many lanes' state the step it
    dispatched read and wrote — not the free, finished or admitting
    ones'."""
    records, _ = retention_run
    (layout,) = [r["fields"] for r in records
                 if r.get("name") == "serving.kv_layout"]
    per_lane = 2 * 2 * (9 * 16 * 16 + 9 * 16) * 4     # layers, heads, s + z
    assert (layout["planes_state"], layout["state_bytes_per_lane"],
            layout["state_dtype"]) == (2, per_lane, "float32")
    # ... and of latent planes that it has none (tests/test_latent.py
    # pins a latent stack's fields, kernels and scopes)
    assert (layout["planes_latent"], layout["latent_width"],
            layout["bytes_per_slot_latent"]) == (0, 0, 0)
    # ... nor ssm planes (tests/test_ssm.py pins an ssm stack's fields)
    assert (layout["planes_ssm"], layout["ssm_bytes_per_lane"]) == (0, 0)
    mine = [r["fields"] for r in records
            if r.get("name") == "serving.round"]
    assert [r["state_lanes"] for r in mine][:3] == [1, 1, 2]
    assert mine[1]["lanes_admitting"] == 1 and mine[1]["lanes_busy"] == 2
    assert max(r["state_lanes"] for r in mine) == 2
    assert all(r["state_lanes"] == 0 for r in mine if r.get("idle"))
    # an engine without state planes says nothing of them
    assert all("state_lanes" not in r["fields"] for r in rounds[0])


@pytest.mark.parametrize("program,metric,scopes", [
    ("decode_step", "decode_step_ms", ("ret_gate", "ret_state")),
    ("admit", "prefill_ms_per_ktok", ("ret_gate", "ret_state", "ret_chunk"))])
def test_retention_programs_keep_their_names_and_hold_the_ret_scopes(
        retention_run, program, metric, scopes):
    """The programs are read by the readers that read every other
    engine's (names unchanged); the gate and the state's update stand
    inside ``attn`` under their own scopes, a chunk's pairs under
    ``ret_chunk`` (``models/transformer.py::RET_SCOPES``)."""
    text = retention_run[1][program]
    (module,) = re.findall(r"module @(\S+)", text)
    assert re.search(_pattern(metric), module), module
    locs = " ".join(set(re.findall(r'loc\("([^"]+)"', text)))
    for scope in scopes:
        assert scope in tfm.RET_SCOPES
        assert re.search(rf"(?<![\w.]){scope}(?![\w.])", locs), scope
    assert re.search(r"attn/ret_gate(?![\w.])", locs)
    assert re.search(r"attn/ret_state(?![\w.])", locs)


def test_the_state_kernel_is_named_ret_state_step():
    """The decode update's Pallas call is ``ret_state_step``: the
    compiled instruction, and with it the profile's event (row
    ``mosaic:ret_state_step`` of ``breakdown.device_ops``), carries the
    name ``step_ret_state_roofline``'s pattern looks for."""
    from distkeras_tpu.ops import retention as ret

    sd = jax.ShapeDtypeStruct
    text = jax.jit(ret.ret_state_step.__wrapped__).trace(
        sd((2, 8, 8, 128), jnp.float32),
        sd((2, 2, 8, 65, 128, 128), jnp.float32),
        sd((2, 2, 8, 65, 128), jnp.float32), sd((), jnp.int32),
        sd((2,), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
    assert 'kernel_name = "ret_state_step"' in text
    assert re.search(r'ret_state_step/pallas_call"', text)
    assert re.search(_pattern("step_ret_state_roofline"), "ret_state_step.3")


def test_the_chunk_kernel_is_named_ret_chunk_fwd(monkeypatch):
    """A retention layer's admission chunk at heads of 128 is ONE
    Pallas call, ``ret_chunk_fwd`` (row ``mosaic:ret_chunk_fwd`` of
    ``breakdown.device_ops``, 8 an admission of the cell), under
    ``attn`` / ``ret_chunk`` of an admission program, and the decode
    kernel's roofline does not take it for its own."""
    from distkeras_tpu.ops import retention as ret

    from distkeras_tpu.models import generate as gen
    from distkeras_tpu.serving.engine import _make_lane_admit

    monkeypatch.setattr(ret, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(RETENTION, d_model=128, n_heads=4,
                              n_kv_heads=2, d_head=128, n_layers=1,
                              max_len=128, layer_types=("retention",),
                              ffn_types=("dense",))
    sd = jax.ShapeDtypeStruct
    shapes = lambda make: jax.tree.map(lambda a: sd(a.shape, a.dtype),
                                       jax.eval_shape(make))
    # The engine's in-place admission program, traced as the TPU would.
    admit = _make_lane_admit(None, cfg, take_params=True, in_place=True)
    text = jax.jit(admit).trace(
        shapes(lambda: toy_params(cfg)),
        shapes(lambda: gen.init_cache(cfg, 2)), sd((1, 64), jnp.int32),
        *(sd((), jnp.int32),) * 3).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    (module,) = re.findall(r"module @(\S+)", text)
    assert re.search(_pattern("prefill_ms_per_ktok"), module), module
    assert text.count('kernel_name = "ret_chunk_fwd"') == 1
    # (the jitted launcher is a function of its own in the module: the
    # scope stands on its call, the kernel's name on the call inside)
    assert re.search(r'"attn/ret_chunk/jit\(ret_chunk_fwd\)"', text)
    assert re.search(r'"ret_chunk_fwd/pallas_call"', text)
    for name in ("ret_chunk_fwd", "ret_chunk_fwd.7"):
        assert not re.search(_pattern("step_ret_state_roofline"), name)


def test_the_ssm_kernels_are_named_and_no_other_reader_takes_them():
    """The ssm layers' Pallas calls are ``ssm_state_step`` (a decode
    step) and ``ssm_chunk_fwd`` (an admission chunk): the compiled
    instructions, and with them the profile's events, carry the names
    ``step_ssm_state_roofline`` and ``step_ssm_chunk_roofline`` look
    for — and each name is matched by its own metric's pattern alone:
    not the other's, nor ``step_n``, ``_admit``, ``^gmm``,
    ``^ret_state_step`` or the latent kernels'."""
    from distkeras_tpu.ops import ssm

    sd = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    slab = sd((2, 3, 16, 256), f32)
    texts = {
        "ssm_state_step": jax.jit(ssm.ssm_state_step.__wrapped__).trace(
            sd((3, 2, 256), f32), sd((3, 16, 3), f32), sd((16, 256), f32),
            sd((256,), f32), slab, sd((), i32), sd((3,), i32)),
        "ssm_chunk_fwd": jax.jit(ssm.ssm_chunk_fwd.__wrapped__).trace(
            sd((64, 256), f32), sd((64, 256), f32), sd((64, 16), f32),
            sd((64, 16), f32), sd((16, 256), f32), sd((256,), f32), slab,
            sd((), i32), sd((), i32), sd((), i32), sd((), jnp.bool_))}
    own = {"ssm_state_step": "step_ssm_state_roofline",
           "ssm_chunk_fwd": "step_ssm_chunk_roofline"}
    names = ("decode_step_ms", "prefill_ms_per_ktok", "step_moe_gmm_roofline",
             "step_ret_state_roofline", "step_mla_decode_roofline",
             "step_mla_prefix_roofline", "step_mla_hbm_roofline") + tuple(
                 own.values())
    for kernel, traced in texts.items():
        text = traced.lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)
        assert f'kernel_name = "{kernel}"' in text
        for metric in names:
            hit = re.search(_pattern(metric), f"{kernel}.3") is not None
            assert hit == (metric == own[kernel]), (kernel, metric)


TRAIN_CFG = tfm.TransformerConfig(
    vocab_size=256, d_model=256, n_heads=2, n_layers=2, d_ff=512,
    max_len=256, rope=True, remat=True, ce_chunks=2, attention_window=128)


@pytest.fixture(scope="module", params=["one_device", "shard_map"])
def train_step_text(request, devices):
    """``make_train_step`` at kernel-legal widths, lowered for the TPU
    from here (tests/test_attention.py, "TPU lowering, no chip"): on one
    device, and with the rows sharded over ``data=4``, where the kernels
    sit in ``_per_shard``'s shard_map."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distkeras_tpu.ops import attention

    sharding = None
    if request.param == "shard_map":
        mesh = make_mesh(MeshSpec(data=4), devices=devices[:4])
        sharding = NamedSharding(mesh, P("data"))
    opt = optax.sgd(1e-2)
    params = jax.eval_shape(
        lambda: toy_params(TRAIN_CFG))
    state = (params, jax.eval_shape(opt.init, params))
    rows = jax.ShapeDtypeStruct((4, 257), jnp.int32, sharding=sharding)
    mp = pytest.MonkeyPatch()
    mp.setattr(attention, "_on_tpu", lambda: True)
    try:
        traced = jax.jit(tfm.make_train_step(TRAIN_CFG, opt)).trace(
            state, rows, None, rows)
        lowered = traced.lower(lowering_platforms=("tpu",))
    finally:
        mp.undo()
    text = lowered.as_text(debug_info=True)
    assert ("shard_map" in text) == (request.param == "shard_map")
    return _Lowered(text, _kernel_calls(traced.jaxpr.jaxpr))


class _Lowered(str):
    """The lowered text, and the Pallas calls the program makes by
    kernel name (``kernel_calls``): every call SITE counts — the
    segmented launchers are jitted (ops/attention.py), so the text
    holds a kernel once and calls it once a layer and pass; the
    compiler inlines the calls, and the compiled program and the
    profile hold every one."""

    def __new__(cls, text, kernel_calls):
        self = super().__new__(cls, text)
        self.kernel_calls = kernel_calls
        return self


def _kernel_calls(jaxpr):
    import collections

    calls = collections.Counter()

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr)
    return calls


@pytest.mark.parametrize("kernel,calls", [
    ("flash_fwd", 2), ("flash_bwd_dq", 1), ("flash_bwd_dkv", 1)])
def test_kernel_names_stand_in_the_lowered_train_step(train_step_text,
                                                      kernel, calls):
    """Each Pallas call's location ends ``<kernel>/pallas_call``: the
    compiled instruction, and with it the profile's event, is named
    after the component before ``pallas_call`` (``flash_fwd.3``), not
    after the transform that wrapped the call.  Per layer: the forward
    twice (once rematerialised), dq once, dkv once — three kernels a
    layer application, no fourth for the tile bounds."""
    assert train_step_text.kernel_calls[kernel] \
        == TRAIN_CFG.n_layers * calls
    assert set(train_step_text.kernel_calls) \
        == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert f'kernel_name = "{kernel}"' in train_step_text
    assert re.search(rf'{kernel}/pallas_call"', train_step_text)


def test_train_step_holds_every_scope_but_the_kv_slab(train_step_text):
    # (nor ``loop_exit``: no looped stack trains)
    assert _scopes_in(train_step_text) \
        == set(tfm.SCOPES) - {"kv_slab", "loop_exit"}


# ----------------------------------------------------- the run report


def _span(name, t0, dur, id, parent=None, **fields):
    return {"kind": "span", "name": name, "t0": t0, "dur": dur, "id": id,
            "parent": parent, "depth": 0 if parent is None else 1,
            "fields": fields}


def test_report_splits_the_step_gap_by_span():
    """Two rounds by hand.  The gap runs from the end of round 1's
    ``serving.step`` (t=5) to the start of round 2's first dispatching
    span, an ``admit_chunk`` at t=14: 9 = emit_loop 2 + reap 1 + caller
    3 (of which the caller's own pump 1) + round 2's pump 1 + 2 inside
    the rounds but in no child."""
    from distkeras_tpu.obs.report import (build_report, render_report,
                                          serving_rounds)

    counts = dict(lanes_busy=2, lanes_admitting=1, kv_live=10, tokens=1)
    recs = [
        _span("serving.round", 0, 9, 1, chunks=1, **counts),
        _span("serving.pump", 0, 1, 2, 1),
        _span("serving.step", 1, 4, 3, 1, n=1),
        _span("serving.emit_loop", 5.5, 2, 4, 1),
        _span("serving.reap", 7.5, 1, 5, 1),
        _span("serving.pump", 10, 1, 6),              # the caller's own
        _span("serving.round", 12, 8, 7, chunks=2, **counts),
        _span("serving.pump", 12.5, 1, 8, 7),
        _span("serving.admit_chunk", 14, 1, 9, 7, bucket=8, attended=24),
        _span("serving.step", 15, 4, 10, 7, n=1),
        _span("serving.round", 21, 1, 11, chunks=0, idle=True,
              **dict(counts, tokens=0)),
    ]
    out = serving_rounds(recs)
    assert (out["rounds"], out["idle"], out["chunks_max"]) == (3, 1, 2)
    assert out["mean"]["chunks"] == 1.5 and out["mean"]["kv_live"] == 10
    gap = out["gap"]
    assert gap["n"] == 1 and gap["p50_s"] == pytest.approx(9)
    assert gap["split_mean_s"] == pytest.approx(
        {"emit_loop": 2, "reap": 1, "pump": 2, "round": 2, "caller": 2})
    assert sum(gap["split_mean_s"].values()) == pytest.approx(9)
    assert serving_rounds([r for r in recs
                           if r["name"] != "serving.round"]) is None
    assert "step gap" in render_report(build_report(recs))
    # The attended share needs the engine's slots a lane.
    assert out["attended"] == {"programs": 1, "mean": 24}
    assert serving_rounds(recs, max_len=64)["attended"]["share"] == 0.375
    assert "= 37.5% of the slab" in render_report(build_report(recs, 64))
    assert "pass --max-len" in render_report(build_report(recs))


@pytest.mark.parametrize("attended,share", [((128, 256), "15.6%"),
                                            ((20, 40), "100.0%")],
                         ids=["dense", "bounded"])
def test_report_sets_the_decode_steps_attended_against_kv_live(attended,
                                                               share):
    """``serving.step``'s ``attended`` beside the admissions': slots a
    decode step read (a window of ``n`` steps reads ``n`` times) and
    the share of them the rounds' ``kv_live`` says were live."""
    from distkeras_tpu.obs.report import (build_report, render_report,
                                          serving_rounds)

    counts = dict(lanes_busy=2, lanes_admitting=0, chunks=0, tokens=2)
    recs = [
        _span("serving.round", 0, 5, 1, kv_live=20, **counts),
        _span("serving.step", 1, 3, 2, 1, n=1, attended=attended[0]),
        _span("serving.round", 6, 5, 3, kv_live=20, **counts),
        _span("serving.step", 7, 3, 4, 3, n=2, attended=attended[1]),
    ]
    att = serving_rounds(recs)["attended_step"]
    assert att["steps"] == 3 and att["mean"] == sum(attended) / 3
    assert att["live_share"] == pytest.approx(60 / sum(attended))
    assert (f"3 decode steps read {sum(attended) / 3:.6g} cache slots "
            f"each, {share} of them live") in render_report(
                build_report(recs))
    # A trace from before the field existed reports none.
    for r in recs:
        r["fields"].pop("attended", None)
    assert "attended_step" not in serving_rounds(recs)


def test_report_prints_the_fused_share_of_chunks():
    """Four decoding rounds by hand, five chunks between them, two of
    which rode their round's decode program: the report says how often
    the mechanism engaged, counts a fused chunk's reads among the
    admissions', and a request's waterfall counts the chunk."""
    from distkeras_tpu.obs.report import (build_report, render_report,
                                          request_waterfall, serving_rounds)

    counts = dict(lanes_busy=2, lanes_admitting=1, kv_live=10, tokens=1)
    fused = dict(bucket=8, positions=8, remaining=1, request_id=7,
                 chunk_attended=16)
    recs = [
        _span("serving.round", 0, 4, 1, chunks=2, fused=1, **counts),
        _span("serving.admit", 0.5, 1, 2, 1, bucket=8, attended=8,
              request_id=7),
        _span("serving.step", 2, 1, 3, 1, n=1, **fused),
        _span("serving.round", 5, 4, 4, chunks=1, fused=1, **counts),
        _span("serving.step", 6, 1, 5, 4, n=1,
              **dict(fused, remaining=0, chunk_attended=24)),
        _span("serving.round", 10, 4, 6, chunks=2, fused=0, **counts),
        _span("serving.admit_chunk", 10.5, 1, 7, 6, bucket=8, attended=32,
              request_id=7),
        _span("serving.step", 12, 1, 8, 6, n=1),
        _span("serving.round", 15, 4, 9, chunks=0, fused=0, **counts),
        _span("serving.step", 16, 1, 10, 9, n=1),
        _span("serving.round", 20, 1, 11, chunks=0, fused=0, idle=True,
              **dict(counts, tokens=0)),
    ]
    out = serving_rounds(recs)
    assert out["fused"] == {"chunks": 5, "fused": 2}
    assert out["attended"] == {"programs": 4, "mean": 20}
    assert ("fused: 2 of 5 admission chunks (40.0%) went through the "
            "layers inside a decode step's program"
            ) in render_report(build_report(recs))
    assert request_waterfall(recs, 7)["prefill_chunks"] == 3
    # A trace from before the field reports no share.
    for r in recs:
        r["fields"].pop("fused", None)
    assert "fused" not in serving_rounds(recs)


def test_report_prints_the_overlapped_share_and_the_median_wait():
    """Three decoding rounds by hand: the first dispatches with nothing
    unread, the next two with the round before in flight, a fourth only
    reads.  Two of three dispatches overlapped; the waits' median."""
    from distkeras_tpu.obs.report import (build_report, render_report,
                                          serving_rounds)

    counts = dict(lanes_busy=1, lanes_admitting=0, kv_live=5, chunks=0)
    recs = [
        _span("serving.round", 0, 2, 1, tokens=0, **counts),
        _span("serving.step", 0.5, 1, 2, 1, n=1),
        _span("serving.round", 3, 8, 3, tokens=1, overlapped=True, **counts),
        _span("serving.step", 3.5, 1, 4, 3, n=1),
        _span("serving.collect", 5, 5, 5, 3, n=1, wait_ms=5.0),
        _span("serving.round", 12, 8, 6, tokens=1, overlapped=True,
              **counts),
        _span("serving.step", 12.5, 1, 7, 6, n=1),
        _span("serving.collect", 14, 5, 8, 6, n=1, wait_ms=7.0),
        _span("serving.round", 21, 2, 9, tokens=1, **counts),
        _span("serving.collect", 21.5, 1, 10, 9, n=1, wait_ms=0.25),
    ]
    ov = serving_rounds(recs)["overlap"]
    assert ov == {"dispatched": 3, "share": pytest.approx(2 / 3),
                  "wait_p50_ms": 5.0}
    assert ("overlap: 66.7% of 3 decode dispatches went out with the "
            "round before unread; wait for a round's tokens p50=5ms"
            ) in render_report(build_report(recs))
    # ``host_ms`` (the round's time less its waits) is printed beside
    # the wait where the rounds carry it.
    for r, ms in zip([r for r in recs if r["name"] == "serving.round"],
                     (2.0, 3.0, 1.0, 1.75)):
        r["fields"]["host_ms"] = ms
    assert serving_rounds(recs)["overlap"]["host_p50_ms"] == 1.875
    assert ("p50=5ms, the host's own work in a round p50=1.88ms"
            ) in render_report(build_report(recs))
    # A trace from before ``serving.collect`` reports none.
    old = [r for r in recs if r["name"] != "serving.collect"]
    assert "overlap" not in serving_rounds(old)
