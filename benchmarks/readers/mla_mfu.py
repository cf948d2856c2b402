"""Reader for the share of the chip's peak that the operations the CUT
requires take, end to end, for a stack of latent layers over routed
experts: ``moe_mfu``'s stretches, rounds and positions, counted by
``flops_mla`` — the model's own equations (``c · wkv_b`` once a
position, attention ``2 * heads * (nope + rope + v)`` a pair), not the
absorbed form's products.

Over the decoding rounds of the window on either side of the profiler.
Positions: the rounds' ``tokens`` (decoded) and the ``positions`` of
the admission spans that began in the stretch; the held share of an
assignment: the stretch's ``moe_held / moe_assigned`` (1 where the
stack routes nothing).  Attention: a decoding round's queries attend
its ``kv_live`` positions; an admission's ``n`` new positions after
``start`` attend ``n * (start + n / 2)`` pairs.

args: none.  None where the program names no latent planes (a program
older than them) or records no round spans.
"""

import flops_mla


def read(record, args):
    if flops_mla.latent_layout(record) is None:
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
        assigned = sum(r.get("moe_assigned", 0) for r in rounds)
        share = (sum(r.get("moe_held", 0) for r in rounds) / assigned
                 if assigned else 1.0)
        t0, t1 = rounds[0]["t0"], rounds[-1]["t0"] + rounds[-1]["dur"]
        admits = [r["fields"] for r in spans
                  if r["name"] in ("serving.admit", "serving.admit_chunk")
                  and t0 <= r["t0"] < t1]
        decoded = sum(r["tokens"] for r in rounds)
        new = [f.get("positions", f["bucket"]) for f in admits]
        start = [f["attended"] - f["bucket"]
                 if f.get("attended", max_len) < max_len else 0
                 for f in admits]
        pairs = sum(r.get("kv_live", 0) for r in rounds) + sum(
            n * (s + n / 2) for n, s in zip(new, start))
        need += (decoded * flops_mla.position_flops(tc, True, share)
                 + sum(new) * flops_mla.position_flops(tc, False, share)
                 + flops_mla.attention_flops(tc, pairs))
        wall += t1 - t0
    if not wall or not record.get("peaks"):
        return None
    return 100.0 * need / wall / record["peaks"]["bf16_flops_per_s"]
