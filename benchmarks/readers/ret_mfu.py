"""Reader for the share of the chip's peak that the operations a stack
of retention layers REQUIRES take, end to end: ``serve_mfu``'s
stretches, rounds and positions, counted by ``flops_retention``.

Over the decoding rounds (``serving.round`` spans, not ``idle``) of the
window on either side of the profiler.  Positions: the rounds'
``tokens`` (decoded) and the ``positions`` of the admission spans that
began in the stretch.  A position costs the products by parameters (the
head once a decoded token) and the state's update and query by the
recurrent count; an admission's ``n`` new positions also attend their
own chunk's ``n (n + 1) / 2`` pairs (what lies before the chunk reaches
them through the state, whatever its length).

args: none.  None where the program's rounds say no ``state_lanes`` (a
program older than the state planes) or there are no round spans.
"""

import flops_retention


def read(record, args):
    spans = [r for r in record.get("obs_events", ())
             if r.get("kind") == "span"]
    lo, hi = record["window"]
    cut = record.get("profile_window", (hi, hi))
    tc = record["conf"]["transformer_config"]
    need = wall = 0.0
    for a, b in ((lo, cut[0]), (cut[1], hi)):
        rounds = [r for r in spans if r["name"] == "serving.round"
                  and a <= r["t0"] < b and not r["fields"].get("idle")
                  and "tokens" in r["fields"]
                  and "state_lanes" in r["fields"]]
        if len(rounds) < 2:
            continue
        t0, t1 = rounds[0]["t0"], rounds[-1]["t0"] + rounds[-1]["dur"]
        new = [r["fields"].get("positions", r["fields"]["bucket"])
               for r in spans
               if r["name"] in ("serving.admit", "serving.admit_chunk")
               and t0 <= r["t0"] < t1]
        decoded = sum(r["fields"]["tokens"] for r in rounds)
        need += (decoded * flops_retention.position_flops(tc, True)
                 + sum(new) * flops_retention.position_flops(tc, False)
                 + flops_retention.pair_flops(
                     tc, sum(n * (n + 1) / 2 for n in new)))
        wall += t1 - t0
    if not wall or not record.get("peaks"):
        return None
    return 100.0 * need / wall / record["peaks"]["bf16_flops_per_s"]
