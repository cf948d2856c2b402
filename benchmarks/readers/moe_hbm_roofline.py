"""Reader for the decode step's share of its HBM roofline in a typed
stack with routed experts: the bytes the traced decode steps REQUIRE
(``flops_moe.decode_step_bytes``: the weights once, held experts only;
the live cache slots by kind) / peak HBM bytes/s, over the device time
of the step programs the trace holds.

Live slots: the mean ``kv_live`` (full planes) and ``kv_live_window``
(rings) of the decoding ``serving.round`` spans that began while the
profiler ran; a slot's bytes by kind: ``bytes_per_slot_full`` and
``bytes_per_slot_window`` of the engine's ``serving.kv_layout`` event.

args: ``pattern`` (default ``step_n``: the step programs on the
"XLA Modules" line).  None where the program's ``serving.kv_layout``
names no two kinds of plane (a program older than them) or the trace
holds no step program.
"""

import flops_moe
import trace_reduce as reduce


def read(record, args):
    trace, events = record.get("trace"), record.get("obs_events", ())
    layout = [r["fields"] for r in events
              if r.get("name") == "serving.kv_layout"
              and "bytes_per_slot_window" in r.get("fields", {})]
    if not trace or not layout or not record.get("peaks"):
        return None
    durs = reduce.matching(trace["events"], args.get("pattern", "step_n"),
                           "modules")
    lo, hi = record["profile_window"]
    live = [(r["fields"]["kv_live"], r["fields"]["kv_live_window"])
            for r in events
            if r.get("kind") == "span" and r["name"] == "serving.round"
            and lo <= r["t0"] < hi and not r["fields"].get("idle")
            and "kv_live_window" in r["fields"]]
    if not durs or not live:
        return None
    itemsize = {"bfloat16": 2, "float32": 4}[record["conf"]["param_dtype"]]
    need = len(durs) * flops_moe.decode_step_bytes(
        record["conf"]["transformer_config"],
        sum(f for f, _ in live) / len(live),
        sum(w for _, w in live) / len(live),
        layout[-1]["bytes_per_slot_full"],
        layout[-1]["bytes_per_slot_window"], itemsize)
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / sum(durs)
