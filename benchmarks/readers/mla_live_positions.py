"""Reader for the length the cell really attends: the positions a
decoding lane holds in its latent planes, mean over the window's
decoding rounds — ``kv_live`` (``serving.round``) over the lanes that
decode (``lanes_busy - lanes_admitting``).  What the decode kernel's
and the step's bytes follow; the seed's order of the requests must not
move it.

args: none.  None where the program names no latent planes or its
rounds say no ``kv_live``.
"""

import flops_mla


def read(record, args):
    if flops_mla.latent_layout(record) is None:
        return None
    rounds = [r for r in flops_mla.rounds_between(record, *record["window"])
              if r.get("lanes_busy", 0) > r.get("lanes_admitting", 0)]
    if not rounds:
        return None
    return (sum(r["kv_live"] for r in rounds)
            / sum(r["lanes_busy"] - r["lanes_admitting"] for r in rounds))
