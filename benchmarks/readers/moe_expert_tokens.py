"""Reader for the tokens a held expert gets in a decode round: the mean
over the window's decoding rounds of ``moe_held`` (assignments that
fell on held experts: ``serving.round``) / held experts / sparse
layers.  The number the cut's faithfulness rests on: a deployment's
chips would send an expert the tokens of all of them.

args: none.  None where the program's rounds say no ``moe_held``.
"""

import flops_moe


def read(record, args):
    lo, hi = record["window"]
    tc = record["conf"]["transformer_config"]
    held = [r["fields"]["moe_held"] for r in record.get("obs_events", ())
            if r.get("kind") == "span" and r["name"] == "serving.round"
            and lo <= r["t0"] < hi and "moe_held" in r["fields"]]
    per_round = flops_moe.held_experts(tc) * flops_moe.sparse_layers(tc)
    if not held or not per_round:
        return None
    return sum(held) / len(held) / per_round
