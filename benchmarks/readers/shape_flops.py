"""Reader for utilisation from shapes: the operations the window's
steps REQUIRED (``flops.train_flops``: recomputation not counted,
attention over the causal, windowed, within-document area, padding
not at all) per second per chip, over the chip's peak.

args: none.
"""


import flops


def read(record, args):
    segs = record.get("window_segments")
    if segs is None or not record.get("peaks"):
        return None
    tc = record["conf"]["transformer_config"]
    inputs = segs[:, :-1]
    need = flops.train_flops(
        tc, int((inputs != 0).sum()),
        flops.attended_pairs(inputs, tc.get("attention_window")))
    per_chip = need / record["window_s"] / record["chips"]
    return 100.0 * per_chip / record["peaks"]["bf16_flops_per_s"]
