"""Reader for the chunk kernel's share of its roofline: the least time
the chip could take for the ``ssm_chunk_fwd`` calls the trace holds (a
call is one ssm layer of one admission chunk) over their device time.

The least time of a call is the larger of its bytes over the peak
bytes/s — the chunk's real rows' ``x`` and ``Delta`` in and ``y`` out,
``B`` and ``C`` in, the lane's state in and out once — and its
operations (the update's and ``C . s``'s multiply-adds) over the peak
bf16 FLOP/s (``flops_ssm.chunk_kernel_least``).  The scan is bound by
the vector and transcendental units, for which the table of peaks has
no entry: the note beside the number says how many elementwise
operations and ``exp`` the calls required.  Real rows: the
``positions`` of the admission spans that began while the profiler
ran; the trace may hold one admission more or less than the spans (one
in flight at either edge), so the least time is the spans' mean call
times the calls the trace holds.

args: ``pattern`` (default ``^ssm_chunk_fwd``: the kernel's calls on
the "XLA Ops" line).  None where the trace holds no such call (a
program without the kernel) or no admission began in the window.
"""

import json

import flops_ssm
import trace_reduce as reduce


def read(record, args):
    trace = record.get("trace")
    if not trace or not record.get("peaks"):
        return None
    durs = reduce.matching(trace["events"],
                           args.get("pattern", "^ssm_chunk_fwd"), "ops")
    lo, hi = record["profile_window"]
    rows = [r["fields"].get("positions", r["fields"]["bucket"])
            for r in record.get("obs_events", ())
            if r.get("kind") == "span"
            and r["name"] in ("serving.admit", "serving.admit_chunk")
            and lo <= r["t0"] < hi]
    if not durs or not rows:
        return None
    tc = record["conf"]["transformer_config"]
    least = [flops_ssm.chunk_kernel_least(tc, n, record["peaks"])
             for n in rows]
    mean = sum(max(b, o) for b, o in least) / len(rows)
    ops, exps = flops_ssm.scan_elementwise(tc, sum(rows) / len(rows))
    print(json.dumps({"note": "ssm_chunk_roofline",
                      "bound_by": ("bytes" if sum(b for b, _ in least)
                                   >= sum(o for _, o in least)
                                   else "operations"),
                      "kernel_calls": len(durs), "admissions": len(rows),
                      "elementwise_ops_a_call": ops,
                      "exp_a_call": exps,
                      "mean_call_us": 1e6 * sum(durs) / len(durs)}),
          flush=True)
    return 100.0 * mean * len(durs) / sum(durs)
