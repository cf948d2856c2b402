"""Reader for a kernel's share of its roofline: the least time the
chip could take for the calls the traced steps made (the larger of
operations / peak FLOP/s and bytes / peak bytes/s, from shapes) over
the device time of the matching events in the trace.

The traced steps call, per layer and step: the forward kernel twice
(once more in the rematerialised backward), dq once, dkv once.  Pairs
are the attended area of the traced rows, so a kernel that computes
whole blocks of a mostly masked tile is charged for them.

args: ``pattern`` (searched in the whole instruction text of an
operation: the Mosaic calls' ``custom_call_target``), ``calls`` (kernel
kind -> calls per layer per step).  The trace may hold more steps than
the trainer was asked to profile (it also catches the step that was
still running when the profiler started), so the least time is scaled
to the steps whose kernel calls the trace really holds: matching
events / (layers x calls per layer per step).  Which of the two bounds
each kernel is printed on an earlier line.
"""


import json

import flops
import trace_reduce as reduce


def read(record, args):
    trace, segs = record.get("trace"), record.get("traced_segments")
    if not trace or segs is None or not record.get("peaks"):
        return None
    durs = reduce.matching(trace["events"], args["pattern"], "ops", text=True)
    if not durs:
        return None
    tc, peaks = record["conf"]["transformer_config"], record["peaks"]
    inputs = segs[:, :-1]
    # One device's share of the traced rows (rows shard over chips).
    pairs = flops.attended_pairs(
        inputs, tc.get("attention_window")) / record["chips"]
    tokens = inputs.size / record["chips"]
    least, bound = 0.0, {}
    for kind, n in args["calls"].items():
        t_ops = flops.attention_kernel_flops(tc, pairs, kind) \
            / peaks["bf16_flops_per_s"]
        t_mem = flops.attention_kernel_bytes(tc, tokens, kind) \
            / peaks["hbm_bytes_per_s"]
        bound[kind] = "operations" if t_ops >= t_mem else "bytes"
        least += n * tc["n_layers"] * max(t_ops, t_mem)
    per_step = tc["n_layers"] * sum(args["calls"].values())
    steps_in_trace = len(durs) / per_step
    least *= steps_in_trace / record["traced_steps"]
    print(json.dumps({"note": "kernel_roofline", "bound_by": bound,
                      "kernel_calls": len(durs),
                      "steps_in_trace": steps_in_trace}), flush=True)
    return 100.0 * least / sum(durs)
