"""Reader for the absorbed decode kernel's share of its roofline: the
least time the chip could take for the ``mla_decode_fwd`` calls the
trace holds (a call is one latent layer of one decode step) over their
device time.

A call's least time is the larger of two bounds over the positions its
lanes hold (``flops_mla.decode_kernel_least_s``): their bytes at the
least layout (``kv_lora_rank + qk_rope_head_dim`` values a position)
over peak HBM bytes/s, and the absorbed form's products (every head
against the row, then the row's latent part) over peak FLOP/s.
Positions a call: the mean, over the rounds that dispatched a step
while the profiler ran, of ``kv_live`` — or of the step's ``attended``
where that is less (``kv_live`` also counts what an ADMITTING lane has
filled, which no decode step reads; ``attended`` is the decoding
lanes' positions rounded up to the kernel's smallest copy).

args: ``pattern`` (default ``^mla_decode_fwd``: the kernel's calls on
the "XLA Ops" line).  None where the trace holds no such call (a
program without the kernel).
"""

import json

import flops_mla
import trace_reduce as reduce


def read(record, args):
    trace = record.get("trace")
    if not trace or not record.get("peaks"):
        return None
    durs = reduce.matching(trace["events"],
                           args.get("pattern", "^mla_decode_fwd"), "ops")
    lo, hi = record["profile_window"]
    live = [r["kv_live"] for r in flops_mla.rounds_between(record, lo, hi)]
    read_ = [r["fields"]["attended"] for r in record.get("obs_events", ())
             if r.get("kind") == "span" and r["name"] == "serving.step"
             and lo <= r["t0"] < hi and "attended" in r["fields"]]
    if not durs or not live:
        return None
    slots = sum(live) / len(live)
    if read_:
        slots = min(slots, sum(read_) / len(read_))
    itemsize = {"bfloat16": 2, "float32": 4}[record["conf"]["param_dtype"]]
    t_bytes, t_ops = flops_mla.decode_kernel_least_s(
        record["conf"]["transformer_config"], slots, record["peaks"],
        itemsize)
    print(json.dumps({"note": "mla_decode_roofline",
                      "bound_by": "bytes" if t_bytes >= t_ops
                      else "operations", "kernel_calls": len(durs),
                      "positions_a_call": slots}), flush=True)
    return 100.0 * len(durs) * max(t_bytes, t_ops) / sum(durs)
