"""Reader for the decode step's share of its HBM roofline in a stack
of latent layers: the bytes the traced decode steps REQUIRE
(``flops_mla.decode_step_bytes``: the weights once, held experts only;
the live cached positions at the least layout, ``kv_lora_rank +
qk_rope_head_dim`` values a layer) / peak HBM bytes/s, over the device
time of the step programs the trace holds.

Live positions: the mean ``kv_live`` of the decoding ``serving.round``
spans that began while the profiler ran.

args: ``pattern`` (default ``step_n``: the step programs on the
"XLA Modules" line).  None where the program's ``serving.kv_layout``
names no latent planes (a program older than them) or the trace holds
no step program.
"""

import flops_mla
import trace_reduce as reduce


def read(record, args):
    trace = record.get("trace")
    if (not trace or not record.get("peaks")
            or flops_mla.latent_layout(record) is None):
        return None
    durs = reduce.matching(trace["events"], args.get("pattern", "step_n"),
                           "modules")
    live = [r["kv_live"] for r in flops_mla.rounds_between(
        record, *record["profile_window"])]
    if not durs or not live:
        return None
    itemsize = {"bfloat16": 2, "float32": 4}[record["conf"]["param_dtype"]]
    need = len(durs) * flops_mla.decode_step_bytes(
        record["conf"]["transformer_config"], sum(live) / len(live), itemsize)
    return 100.0 * need / record["peaks"]["hbm_bytes_per_s"] / sum(durs)
