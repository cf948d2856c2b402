"""The yardstick's arithmetic: traffic, flops, trace reduction, the
open-loop clock."""

import gzip
import json
import os

import numpy as np
import pytest

from conftest import BENCH
import flops
import trace_reduce
from traffic import requests as req_gen


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


OPEN = {"name": "open", "generator": "requests", "rate_per_s": 1.6,
        "prompt_len": {"median": 1536, "sigma": 0.8, "min": 256, "max": 6144},
        "output_len": {"median": 32, "sigma": 0.7, "min": 8, "max": 128}}


@pytest.mark.parametrize("m", [mix("repo_batch"), OPEN],
                         ids=["repo_batch", "open"])
def test_requests_reproduce_and_every_seed_gets_the_same_stream(m):
    a, b = req_gen.make(m, 2147499999, 49152), req_gen.make(m, 2147499999, 49152)
    c = req_gen.make(m, 5, 49152)
    for i in (0, 1, 63, 64, 200):
        da, pa, na = a.get(i)
        db, pb, nb = b.get(i)
        assert da == db and na == nb and (pa == pb).all()
    lens = lambda g, k: sorted(len(g.get(i)[1]) for i in range(
        64 * k, 64 * k + 64))
    assert lens(a, 0) == lens(c, 0) == lens(a, 3)
    outs = lambda g: sorted(g.get(i)[2] for i in range(64))
    assert outs(a) == outs(c)
    # the same order too (an order from the seed changes the work:
    # traffic/requests.py); the seed draws the token values, and one
    # block's order is not the next one's
    stream = lambda g, lo: [(len(g.get(i)[1]), g.get(i)[2])
                            for i in range(lo, lo + 64)]
    assert stream(a, 0) == stream(c, 0) != stream(a, 64)
    assert not (a.get(0)[1] == c.get(0)[1]).all()
    lo, hi = m["prompt_len"]["min"], m["prompt_len"]["max"]
    assert all(lo <= len(a.get(i)[1]) <= hi for i in range(64))
    # a prompt and its answer fit a lane of the published 8192 positions
    assert hi + m["output_len"]["max"] <= 8192


def test_open_loop_rate_is_exact_over_a_block():
    m = OPEN
    g = req_gen.make(m, 11, 49152)
    due = [g.get(i)[0] for i in range(128)]
    assert due == sorted(due)
    assert due[63] == pytest.approx(64 / m["rate_per_s"])
    assert due[127] == pytest.approx(128 / m["rate_per_s"])
    assert req_gen.make(mix("repo_batch"), 11, 49152).get(0)[0] is None
    # stratified, not Poisson: the same gaps in every block
    gaps = lambda lo: sorted(np.diff([0.0 if lo == 0 else due[lo - 1]]
                                     + due[lo:lo + 64]))
    assert gaps(0) == pytest.approx(gaps(64))


def test_first_token_is_timed_from_the_due_instant():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_engine", os.path.join(BENCH, "drivers", "serve_engine.py"))
    drv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(drv)
    late = drv.Req(0, due=10.0, prompt=np.zeros(4, np.int32), max_new=2)
    # (the loop got to it half a second late: that is not where time starts)
    late.stamps, late.done, late.status = [10.7, 10.8], True, "ok"
    lost = drv.Req(1, due=11.0, prompt=np.zeros(4, np.int32), max_new=2)
    ttft, failed = drv.first_token_times([late, lost], worst=20.0)
    assert ttft == pytest.approx([0.7, 9.0]) and failed == 1
    n, gaps = drv.window_tokens([late, lost], 10.75, 11.0)
    assert n == 1 and gaps == pytest.approx([0.1])


def test_packed_docs_reproduce_and_count_targets():
    from traffic import packed_docs

    m = mix("pack4k")
    r1, s1 = packed_docs.make(m, 2147499999, 49152, 12)
    r2, s2 = packed_docs.make(m, 2147499999, 49152, 12)
    assert (r1 == r2).all() and (s1 == s2).all()
    assert r1.shape == (12, 4097) and r1.dtype == np.int32
    t = packed_docs.target_tokens(s1)
    brute = [sum(1 for i in range(4096) if row[i] and row[i] == row[i + 1])
             for row in s1]
    assert t.tolist() == brute


def test_attended_pairs_against_a_mask():
    rng = np.random.default_rng(0)
    segs = np.zeros((3, 40), np.int32)
    for r in range(3):
        cuts = np.sort(rng.choice(np.arange(1, 36), 3, replace=False))
        segs[r, :36] = 1 + (np.arange(36)[:, None] >= cuts[None]).sum(1)
    for window in (None, 5):
        want = 0
        for row in segs:
            for i in range(40):
                for j in range(i + 1):
                    if row[i] and row[i] == row[j] and (
                            window is None or i - j < window):
                        want += 1
        assert flops.attended_pairs(segs, window) == want


def test_train_flops_at_published_widths():
    with open(os.path.join(
            BENCH, "configs", "starcoder2-3b-l4_repo-block.json")) as f:
        tc = json.load(f)["transformer_config"]
    assert flops.matmul_params_per_token(tc) == 4 * (
        2 * 3072 * 3072 + 2 * 3072 * 256 + 2 * 3072 * 12288) + 49152 * 3072
    assert flops.train_flops(tc, 1, 0) == 6 * flops.matmul_params_per_token(tc)


def test_union_busy_and_exposed():
    busy, gaps = trace_reduce.union_length([(0, 10), (5, 20), (30, 40)])
    assert busy == 30 and gaps == [(20, 30)]
    ev = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        ["fusion.1", 0, 10], ["all-gather.2", 10, 5], ["fusion.3", 12, 8],
        ["all-reduce.4", 30, 10]]}}, "host": [
        ["$lanes.py:1 step", 0, 100], ["$x.py:2 inner", 21, 8]], "lines": {}}
    out = trace_reduce.reduce(ev)
    assert out["busy_s"] == pytest.approx(30e-9)
    assert out["window_s"] == pytest.approx(40e-9)
    assert out["idle_gaps"][0][0] == "lanes.py:step"
    # all-gather alone for 2 ns (10-12), all-reduce alone for 10 ns.
    assert trace_reduce.exposed(ev, "all-") == pytest.approx(12e-9)
    assert trace_reduce.matching(ev, "fusion") == pytest.approx([10e-9, 8e-9])


def test_a_pattern_sees_an_operation_s_own_name_not_its_operands():
    """On the chip an operation's label is its whole HLO instruction.
    A product that READS a gathered weight is compute; a ``while`` only
    contains the operations of its body."""
    product = ("%fusion.9 = f32[2,8]{1,0} fusion(f32[2,4]{1,0} %p.1, "
               "bf16[4,8]{1,0} %all-gather.7), kind=kOutput, "
               "calls=%fused_computation.3")
    gather = ("%all-gather.7 = bf16[4,8]{1,0} all-gather(bf16[1,8]{1,0} "
              "%copy-done.2), channel_id=1, dimensions={0}")
    kernel = ("%checkpoint.4 = f32[8]{0} custom-call(f32[8]{0} %fusion.9), "
              'custom_call_target="tpu_custom_call"')
    ev = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        ["%while.2 = (s32[]) while((s32[]) %tuple.1), body=%b", 0, 100],
        [gather, 0, 10], [product, 10, 50], [kernel, 60, 40]]}},
        "host": [], "lines": {}}
    assert trace_reduce.op_name(product) == "fusion.9"
    assert trace_reduce.matching(ev, "all-gather") == pytest.approx([10e-9])
    assert trace_reduce.exposed(ev, "^all-gather") == pytest.approx(10e-9)
    assert trace_reduce.matching(ev, "tpu_custom_call") == []
    assert trace_reduce.matching(ev, "tpu_custom_call", text=True) == \
        pytest.approx([40e-9])
    assert trace_reduce.op_group(kernel) == "mosaic:checkpoint"


def test_collective_share_of_the_recorded_fsdp_trace():
    """The first 100 ms of a traced step of sc2-3b.train.fsdp4 on four
    TPU v5e chips (PR 23).  Products on device 0 name an all-gather
    among their operands and take tens of times longer; only the 38
    all-gathers themselves and the asynchronous collectives' start and
    done operations count."""
    ev = trace_reduce.load_sample(os.path.join(
        BENCH, "tests", "data", "trace_sample_fsdp4.json.gz"))
    with open(os.path.join(BENCH, "layer_metrics",
                           "collective_exposed_share.json")) as f:
        args = json.load(f)["args"]
    durs = trace_reduce.matching(ev, args["pattern"])
    assert len(durs) == 81 and sum(durs) == pytest.approx(2.139771e-3)
    sec = trace_reduce.exposed(ev, args["pattern"], 4)
    window = trace_reduce.reduce(ev, 4)["window_s"]
    assert sec == pytest.approx(1.831677e-3, rel=1e-6)
    assert 100 * sec / window == pytest.approx(1.8018, rel=1e-4)
    whole_text = trace_reduce.matching(ev, "all-gather", text=True)
    own = trace_reduce.matching(ev, "^all-gather")
    assert len(own) == 38 < len(whole_text) and sum(whole_text) > 5 * sum(own)


def test_kernel_roofline_scales_to_the_steps_the_trace_holds():
    """A trace that caught one step more than the trainer was asked to
    profile (4 for 3) holds a third more kernel time and a third more
    least time: the share stays, it does not rise by a third."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("kernel_roofline", os.path.join(
        BENCH, "readers", "kernel_roofline.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    tc = {"d_model": 256, "n_heads": 2, "n_kv_heads": 1, "n_layers": 2,
          "d_ff": 512, "vocab_size": 64, "attention_window": None}
    with open(os.path.join(BENCH, "layer_metrics",
                           "flash_attn_roofline.json")) as f:
        args = json.load(f)["args"]
    per_step = tc["n_layers"] * sum(args["calls"].values())
    call = '%jvp__.1 = f32[8]{0} custom-call(), custom_call_target="tpu_custom_call"'

    def share(steps_in_trace):
        ops = [[call, 1000 * i, 500] for i in range(per_step * steps_in_trace)]
        rec = {"trace": {"events": {"devices": {"/device:TPU:0": {
            "ops": ops, "modules": []}}}}, "chips": 1, "traced_steps": 3,
            "traced_segments": np.ones((3, 129), np.int32),
            "conf": {"transformer_config": tc},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
        return reader.read(rec, args)

    assert share(4) == pytest.approx(share(3)) and share(3) > 0


def test_reduction_of_the_recorded_trace():
    """A cut of a real TPU v5e trace (PR 23, sc1b.serve.batch): the
    numbers the reduction gave when it was recorded."""
    path = os.path.join(BENCH, "tests", "data", "trace_sample.json.gz")
    with open(os.path.join(BENCH, "tests", "data",
                           "trace_sample.expected.json")) as f:
        want = json.load(f)
    out = trace_reduce.reduce(trace_reduce.load_sample(path))
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert out["device_ops"][0][0] == want["device_ops"][0][0]
    assert out["idle_gaps"][0][0] == want["idle_gaps"][0][0]
    assert 0 < out["busy_s"] <= out["window_s"]
