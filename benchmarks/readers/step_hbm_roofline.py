"""Reader for the decode step's share of its HBM roofline: the bytes
the traced decode steps REQUIRE (``flops_looped.decode_step_bytes``:
the layers' weights once a pass, the head, the live cache slots) /
peak HBM bytes/s, over the device time of the step programs the trace
holds.

Live slots: the mean ``kv_live`` of the decoding ``serving.round``
spans that began while the profiler ran; a slot's bytes: the
``bytes_per_slot`` of the engine's ``serving.kv_layout`` event, so the
reader knows nothing of the cache's layout.  The step reads every slot
of the slab, live or not: bytes it does not require count against it.

args: ``pattern`` (default ``step_n``: the step programs on the
"XLA Modules" line).  None where the program has no
``serving.kv_layout`` event (a program older than it) or the trace no
step program.
"""

import flops_looped
import trace_reduce as reduce


def read(record, args):
    trace, events = record.get("trace"), record.get("obs_events", ())
    layout = [r for r in events if r.get("name") == "serving.kv_layout"]
    if not trace or not layout or not record.get("peaks"):
        return None
    durs = reduce.matching(trace["events"], args.get("pattern", "step_n"),
                           "modules")
    lo, hi = record["profile_window"]
    live = [r["fields"]["kv_live"] for r in events
            if r.get("kind") == "span" and r["name"] == "serving.round"
            and lo <= r["t0"] < hi and not r["fields"].get("idle")
            and "kv_live" in r["fields"]]
    if not durs or not live:
        return None
    itemsize = {"bfloat16": 2, "float32": 4}[record["conf"]["param_dtype"]]
    need = len(durs) * flops_looped.decode_step_bytes(
        record["conf"]["transformer_config"], sum(live) / len(live),
        layout[-1]["fields"]["bytes_per_slot"], itemsize)
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / sum(durs)
