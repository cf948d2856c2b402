"""Reader for the share of the chip's peak the served model's required
operations take, end to end: positions processed x operations a
position REQUIRES (``flops_looped``) / wall time / peak bf16 FLOP/s.

Over the decoding rounds (``serving.round`` spans, not ``idle``) of the
window on either side of the profiler — before it started, and after
``stop_trace`` returned (which takes many seconds: no round begins
meanwhile); the Python tracer slows the host while it runs.  In each
stretch, positions: the rounds' ``tokens`` (decoded) and the
``positions`` of every ``serving.admit`` / ``serving.admit_chunk`` span
that began in it — the prompt positions the program writes that are
neither bucket padding nor written before, so a request's prompt
counts once and as long as it is (a program older than that field is
read by its ``bucket``).  Attention: a decoding round's queries attend
its ``kv_live`` slots; an admission's ``n`` new positions attend the
``start`` slots before the chunk and their own causal triangle, ``n *
(start + n / 2)`` pairs, with ``start = attended - bucket`` where the
span says so (the bounded path) and 0 where ``attended`` is all of the
lane (the dense path: the triangle alone, an undercount).  Wall time:
the stretch's first round's start to its last round's end.

args: none.  None where the program records no round spans.
"""

import flops_looped


def read(record, args):
    spans = [r for r in record.get("obs_events", ())
             if r.get("kind") == "span"]
    lo, hi = record["window"]
    cut = record.get("profile_window", (hi, hi))
    tc = record["conf"]["transformer_config"]
    max_len = record["max_len"]
    need = wall = 0.0
    for a, b in ((lo, cut[0]), (cut[1], hi)):
        rounds = [r for r in spans if r["name"] == "serving.round"
                  and a <= r["t0"] < b and not r["fields"].get("idle")
                  and "tokens" in r["fields"]]
        if len(rounds) < 2:
            continue
        t0 = rounds[0]["t0"]
        t1 = rounds[-1]["t0"] + rounds[-1]["dur"]
        admits = [r["fields"] for r in spans
                  if r["name"] in ("serving.admit", "serving.admit_chunk")
                  and t0 <= r["t0"] < t1]
        decoded = sum(r["fields"]["tokens"] for r in rounds)
        new = [f.get("positions", f["bucket"]) for f in admits]
        start = [f["attended"] - f["bucket"]
                 if f.get("attended", max_len) < max_len else 0
                 for f in admits]
        admitted = sum(new)
        pairs = sum(r["fields"].get("kv_live", 0) for r in rounds) + sum(
            n * (s + n / 2) for n, s in zip(new, start))
        need += (decoded * flops_looped.position_flops(tc, decoded=True)
                 + admitted * flops_looped.position_flops(tc, decoded=False)
                 + flops_looped.attention_flops(tc, pairs))
        wall += t1 - t0
    if not wall or not record.get("peaks"):
        return None
    return 100.0 * need / wall / record["peaks"]["bf16_flops_per_s"]
