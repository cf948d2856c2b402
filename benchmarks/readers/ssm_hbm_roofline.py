"""Reader for the decode step's share of its HBM roofline in a stack of
ssm layers among full-attention layers: the bytes the traced decode
steps REQUIRE (``flops_ssm.decode_step_bytes``: every weight once, the
state and conv tail of every decoding lane read and written once, the
attention layers' live keys and values read once) / peak HBM bytes/s,
over the device time of the step programs the trace holds.

Decoding lanes and live positions: the mean ``state_lanes`` and
``kv_live`` of the decoding ``serving.round`` spans that began while
the profiler ran.

args: ``pattern`` (default ``step_n``: the step programs on the
"XLA Modules" line).  None where the program's ``serving.kv_layout``
names no ssm planes (a program older than them) or the trace holds no
step program.
"""

import flops_ssm
import trace_reduce as reduce


def read(record, args):
    trace = record.get("trace")
    if (not trace or not record.get("peaks")
            or flops_ssm.ssm_layout(record) is None):
        return None
    durs = reduce.matching(trace["events"], args.get("pattern", "step_n"),
                           "modules")
    rounds = flops_ssm.rounds_between(record, *record["profile_window"])
    if not durs or not rounds:
        return None
    mean = lambda key: sum(r.get(key, 0) for r in rounds) / len(rounds)
    need = len(durs) * flops_ssm.decode_step_bytes(
        record["conf"]["transformer_config"], mean("state_lanes"),
        mean("kv_live"), flops_ssm.ITEMSIZE[record["conf"]["param_dtype"]])
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / sum(durs)
