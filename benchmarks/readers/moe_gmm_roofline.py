"""Reader for the grouped-product kernel's share of its roofline: the
least time the chip could take for the held experts' products the
traced programs made (the larger of operations / peak FLOP/s and bytes
/ peak bytes/s, from shapes: ``flops_moe``) over the device time of
the matching Mosaic calls in the trace.

A sparse layer's application makes two calls (gate and up side by
side, then down).  Bytes: every held expert's three matrices once an
application (the program reads each whether or not a token reached it:
an empty group keeps a row tile) and the rows in and out; operations:
2 x rows x an expert's weights.  Rows: the ``moe_held`` of the ``serving.round``
spans that began while the profiler ran (decode) and, for the
admission spans there, ``positions`` x ``moe_top_k`` x sparse layers x
the rounds' held share.

args: ``pattern`` (default ``^gmm``: the kernel's calls on the "XLA
Ops" line).  None where the trace holds no such call (a program whose
grouped product is no kernel) or the rounds say no ``moe_held``.
"""

import json

import flops_moe
import trace_reduce as reduce


def read(record, args):
    trace, events = record.get("trace"), record.get("obs_events", ())
    if not trace or not record.get("peaks"):
        return None
    durs = reduce.matching(trace["events"], args.get("pattern", "^gmm"),
                           "ops")
    lo, hi = record["profile_window"]
    inside = [r for r in events if r.get("kind") == "span"
              and lo <= r["t0"] < hi]
    rounds = [r["fields"] for r in inside if r["name"] == "serving.round"
              and "moe_held" in r["fields"]]
    assigned = sum(r["moe_assigned"] for r in rounds)
    if not durs or not assigned:
        return None
    tc, peaks = record["conf"]["transformer_config"], record["peaks"]
    itemsize = {"bfloat16": 2, "float32": 4}[record["conf"]["param_dtype"]]
    held = sum(r["moe_held"] for r in rounds)
    admitted = sum(r["fields"].get("positions", r["fields"]["bucket"])
                   for r in inside
                   if r["name"] in ("serving.admit", "serving.admit_chunk"))
    rows = held + (admitted * tc["moe_top_k"] * flops_moe.sparse_layers(tc)
                   * held / assigned)
    d, f = tc["d_model"], tc.get("moe_d_ff") or tc["d_ff"]
    applications = len(durs) / 2
    t_bytes = itemsize * (
        applications * flops_moe.held_experts(tc)
        * flops_moe.expert_params(tc) + rows * (2 * d + 3 * f)
    ) / peaks["hbm_bytes_per_s"]
    t_ops = (2 * rows * flops_moe.expert_params(tc)
             / peaks["bf16_flops_per_s"])
    print(json.dumps({"note": "moe_gmm_roofline", "rows": rows,
                      "bound_by": ("bytes" if t_bytes >= t_ops
                                   else "operations"),
                      "kernel_calls": len(durs)}), flush=True)
    return 100.0 * max(t_bytes, t_ops) / sum(durs)
