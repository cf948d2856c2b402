"""Reader for the decode step's share of its HBM roofline in a stack of
retention layers: the bytes the traced decode steps REQUIRE
(``flops_retention.decode_step_bytes``: the layers' weights and the
head once, and the state of every decoding lane read and written once,
at the least layout) / peak HBM bytes/s, over the device time of the
step programs the trace holds.

Decoding lanes: the mean ``state_lanes`` of the ``serving.round`` spans
that dispatched a step while the profiler ran; the state's item size:
``state_dtype`` of the engine's ``serving.kv_layout`` event.

args: ``pattern`` (default ``step_n``: the step programs on the "XLA
Modules" line).  None where the program's ``serving.kv_layout`` names
no state planes (a program older than them) or the trace holds no step
program.
"""

import flops_retention
import trace_reduce as reduce

def read(record, args):
    trace = record.get("trace")
    size = flops_retention.state_itemsize(record)
    if not trace or size is None or not record.get("peaks"):
        return None
    durs = reduce.matching(trace["events"], args.get("pattern", "step_n"),
                           "modules")
    lanes = flops_retention.decoding_lanes(record)
    if not durs or not lanes:
        return None
    need = len(durs) * flops_retention.decode_step_bytes(
        record["conf"]["transformer_config"], sum(lanes) / len(lanes),
        flops_retention.ITEMSIZE[record["conf"]["param_dtype"]], size)
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / sum(durs)
