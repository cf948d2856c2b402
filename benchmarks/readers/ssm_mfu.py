"""Reader for the share of the chip's peak that the operations a stack
of ssm layers among full-attention layers REQUIRES take, end to end:
``serve_mfu``'s stretches, rounds and positions, counted by
``flops_ssm``.

Over the decoding rounds (``serving.round`` spans, not ``idle``) of the
window on either side of the profiler.  Positions: the rounds'
``tokens`` (decoded) and the ``positions`` of the admission spans that
began in the stretch, by their true number.  A position costs the
products by parameters (the head once a decoded token) and the scan's
updates; attention its attended pairs: a decoding round's queries
attend its ``kv_live`` positions, an admission's ``n`` new positions
after ``start`` attend ``n * (start + n / 2)``.  (A fused round's
``serving.step`` ``positions`` would count as an admission's: this
stack's rounds do not fuse.)

args: none.  None where the program names no ssm planes (a program
older than them) or records no round spans.
"""

import flops_ssm


def read(record, args):
    if flops_ssm.ssm_layout(record) is None:
        return None
    spans = [r for r in record.get("obs_events", ())
             if r.get("kind") == "span"]
    lo, hi = record["window"]
    cut = record.get("profile_window", (hi, hi))
    tc = record["conf"]["transformer_config"]
    max_len = record["max_len"]
    need = wall = 0.0
    for a, b in ((lo, cut[0]), (cut[1], hi)):
        rounds = [r["fields"] | {"t0": r["t0"], "dur": r["dur"]}
                  for r in spans if r["name"] == "serving.round"
                  and a <= r["t0"] < b and not r["fields"].get("idle")
                  and "tokens" in r["fields"]]
        if len(rounds) < 2:
            continue
        t0, t1 = rounds[0]["t0"], rounds[-1]["t0"] + rounds[-1]["dur"]
        admits = [r["fields"] for r in spans
                  if (r["name"] in ("serving.admit", "serving.admit_chunk")
                      or (r["name"] == "serving.step"
                          and "positions" in r["fields"]))
                  and t0 <= r["t0"] < t1]
        new = [f.get("positions", f.get("bucket", 0)) for f in admits]
        start = [f.get("attended", max_len) - f.get("bucket", 0)
                 if f.get("attended", max_len) < max_len else 0
                 for f in admits]
        pairs = sum(r.get("kv_live", 0) for r in rounds) + sum(
            n * (s + n / 2) for n, s in zip(new, start))
        decoded = sum(r["tokens"] for r in rounds)
        need += (decoded * flops_ssm.position_flops(tc, True)
                 + sum(new) * flops_ssm.position_flops(tc, False)
                 + flops_ssm.pair_flops(tc, pairs))
        wall += t1 - t0
    if not wall or not record.get("peaks"):
        return None
    return 100.0 * need / wall / record["peaks"]["bf16_flops_per_s"]
