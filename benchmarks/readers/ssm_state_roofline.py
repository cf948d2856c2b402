"""Reader for the decode kernel's share of its roofline: the least
time the chip could take for the ``ssm_state_step`` calls the trace
holds — bound by bytes: the decoding lanes' states ``s [N, E]``
float32, read and written once a call (a call is one ssm layer of one
decode step) — over their device time.  (The conv's tail, 3 rows a
lane, is cut and put back by XLA beside the kernel: it is counted in
``step_ssm_hbm_roofline``, not here.)

Decoding lanes a call: the mean ``state_lanes`` of the rounds that
dispatched a step while the profiler ran.

args: ``pattern`` (default ``^ssm_state_step``: the kernel's calls on
the "XLA Ops" line).  None where the trace holds no such call (a
program without the kernel) or the program names no ssm planes.
"""

import json

import flops_ssm
import trace_reduce as reduce


def read(record, args):
    trace = record.get("trace")
    if (not trace or not record.get("peaks")
            or flops_ssm.ssm_layout(record) is None):
        return None
    durs = reduce.matching(trace["events"],
                           args.get("pattern", "^ssm_state_step"), "ops")
    lanes = [r["state_lanes"] for r in flops_ssm.rounds_between(
        record, *record["profile_window"])]
    if not durs or not lanes:
        return None
    tc = record["conf"]["transformer_config"]
    per_call = (2 * sum(lanes) / len(lanes) * 4 * tc["ssm_state"]
                * flops_ssm.inner(tc))
    print(json.dumps({"note": "ssm_state_roofline", "bound_by": "bytes",
                      "kernel_calls": len(durs),
                      "bytes_a_call": per_call}), flush=True)
    return (100.0 * len(durs) * per_call / record["peaks"]["hbm_bytes_per_s"]
            / sum(durs))
