"""Reader for the expanded chunk kernel's share of its roofline: the
least time the chip could take for the ``mla_prefix_fwd`` calls the
trace holds (a call is one latent layer of one admission) over their
device time.

Bound by operations (``flops_mla.prefix_kernel_flops``), the LEAST
the model's equations ask of an admission of ``bucket`` rows after
``start`` positions: ``c · wkv_b`` for the chunk's own rows and the
chunk's causal pairs; the rows' bytes (1,152 a position) are under a
hundredth of that time.  The expanded kernel keeps no keys or values,
so it also rebuilds those of the ``start`` earlier positions on every
call: that is its own choice, credited with nothing, and the note says
what share of required + rebuilt it is (``rebuilt_share``).  The
admissions: the ``serving.admit`` / ``serving.admit_chunk`` spans that
began while the profiler ran, ``start = attended - bucket`` (the
bounded path says so); the bucket's padding rows are charged as the
kernel computes them.  The trace may hold one admission more or less
than the spans (one in flight at either edge), so the least time is
the spans' mean call times the calls the trace holds.

args: ``pattern`` (default ``^mla_prefix_fwd``: the kernel's calls on
the "XLA Ops" line).  None where the trace holds no such call (a
program without the kernel) or no admission began in the window.
"""

import json

import flops_mla
import trace_reduce as reduce


def read(record, args):
    trace = record.get("trace")
    if not trace or not record.get("peaks"):
        return None
    durs = reduce.matching(trace["events"],
                           args.get("pattern", "^mla_prefix_fwd"), "ops")
    lo, hi = record["profile_window"]
    admits = [r["fields"] for r in record.get("obs_events", ())
              if r.get("kind") == "span"
              and r["name"] in ("serving.admit", "serving.admit_chunk")
              and lo <= r["t0"] < hi
              and r["fields"].get("attended", 0) >= r["fields"]["bucket"]]
    if not durs or not admits:
        return None
    tc = record["conf"]["transformer_config"]
    starts = [min(f["attended"], record["max_len"]) - f["bucket"]
              for f in admits]
    need = sum(flops_mla.prefix_kernel_flops(tc, f["bucket"], s)
               for f, s in zip(admits, starts))
    rebuilt = sum(flops_mla.prefix_rebuilt_flops(tc, s) for s in starts)
    print(json.dumps({"note": "mla_prefix_roofline", "bound_by": "operations",
                      "kernel_calls": len(durs), "admissions": len(admits),
                      "rebuilt_share": rebuilt / (need + rebuilt)}),
          flush=True)
    return (100.0 * need / len(admits) * len(durs)
            / record["peaks"]["bf16_flops_per_s"] / sum(durs))
