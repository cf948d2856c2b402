"""Reader for the state-update kernel's share of its roofline: the
least time the chip could take for the ``ret_state_step`` calls the
trace holds — bound by bytes: the decoding lanes' ``S`` and ``z`` at
the least layout, read and written once a call (a call is one layer of
one decode step) — over their device time.

Decoding lanes a call: the mean ``state_lanes`` of the rounds that
dispatched a step while the profiler ran.

args: ``pattern`` (default ``^ret_state_step``: the kernel's calls on
the "XLA Ops" line).  None where the trace holds no such call (a
program without the kernel) or the rounds say no ``state_lanes``.
"""

import json

import flops_retention
import trace_reduce as reduce


def read(record, args):
    trace = record.get("trace")
    if not trace or not record.get("peaks"):
        return None
    durs = reduce.matching(trace["events"],
                           args.get("pattern", "^ret_state_step"), "ops")
    lanes = flops_retention.decoding_lanes(record)
    size = flops_retention.state_itemsize(record)
    if not durs or not lanes or size is None:
        return None
    tc = record["conf"]["transformer_config"]
    per_call = 2 * sum(lanes) / len(lanes) * flops_retention.state_bytes(
        tc, layers=1, itemsize=size)
    print(json.dumps({"note": "ret_state_roofline", "bound_by": "bytes",
                      "kernel_calls": len(durs),
                      "bytes_a_call": per_call}), flush=True)
    return (100.0 * len(durs) * per_call / record["peaks"]["hbm_bytes_per_s"]
            / sum(durs))
