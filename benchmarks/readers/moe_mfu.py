"""Reader for the share of the chip's peak that the operations the CUT
requires take, end to end, for a typed stack with routed experts:
``serve_mfu``'s stretches, rounds and positions, counted by
``flops_moe`` (held assignments only, by the measured share; window
layers' pairs capped at the window).

Over the decoding rounds (``serving.round`` spans, not ``idle``) of the
window on either side of the profiler.  Positions: the rounds'
``tokens`` (decoded) and the ``positions`` of the admission spans that
began in the stretch.  The held share of an assignment: the stretch's
``moe_held / moe_assigned`` (decode rounds route on the device and say
so; an admission's assignments are given the same share).  Attention:
a decoding round's queries attend its ``kv_live`` slots in a full
layer and its ``kv_live_window`` in a window layer; an admission's
``n`` new positions after ``start`` attend ``n * (start + n / 2)``
pairs in a full layer and at most ``window`` each in a window layer.

args: none.  None where the program records no ``moe_assigned`` (a
program older than the routed feed-forward) or no round spans.
"""

import flops_moe


def read(record, args):
    spans = [r for r in record.get("obs_events", ())
             if r.get("kind") == "span"]
    lo, hi = record["window"]
    cut = record.get("profile_window", (hi, hi))
    tc = record["conf"]["transformer_config"]
    max_len, window = record["max_len"], tc.get("sliding_window") or 0
    need = wall = 0.0
    for a, b in ((lo, cut[0]), (cut[1], hi)):
        rounds = [r["fields"] | {"t0": r["t0"], "dur": r["dur"]}
                  for r in spans if r["name"] == "serving.round"
                  and a <= r["t0"] < b and not r["fields"].get("idle")
                  and "tokens" in r["fields"]]
        assigned = sum(r.get("moe_assigned", 0) for r in rounds)
        if len(rounds) < 2 or not assigned:
            continue
        share = sum(r.get("moe_held", 0) for r in rounds) / assigned
        t0, t1 = rounds[0]["t0"], rounds[-1]["t0"] + rounds[-1]["dur"]
        admits = [r["fields"] for r in spans
                  if r["name"] in ("serving.admit", "serving.admit_chunk")
                  and t0 <= r["t0"] < t1]
        decoded = sum(r["tokens"] for r in rounds)
        new = [f.get("positions", f["bucket"]) for f in admits]
        start = [f["attended"] - f["bucket"]
                 if f.get("attended", max_len) < max_len else 0
                 for f in admits]
        pairs_full = sum(r.get("kv_live", 0) for r in rounds) + sum(
            n * (s + n / 2) for n, s in zip(new, start))
        pairs_window = sum(r.get("kv_live_window", 0) for r in rounds) + sum(
            n * min(window, s + n / 2) for n, s in zip(new, start))
        need += (decoded * flops_moe.position_flops(tc, True, share)
                 + sum(new) * flops_moe.position_flops(tc, False, share)
                 + flops_moe.attention_flops(tc, pairs_full, pairs_window))
        wall += t1 - t0
    if not wall or not record.get("peaks"):
        return None
    return 100.0 * need / wall / record["peaks"]["bf16_flops_per_s"]
