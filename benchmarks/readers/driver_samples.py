"""Reader over what the serving driver sampled after every step of the
window: ``100 * mean(sample) / capacity``.

args: ``sum`` (``busy_sum`` = lanes running, ``kv_sum`` = live
positions of running requests), ``capacity`` (``lanes`` or
``lanes*max_len``).
"""


def read(record, args):
    s = record.get("samples")
    if not s or not s["n"]:
        return None
    cap = record["lanes"]
    if args["capacity"] == "lanes*max_len":
        cap *= record["max_len"]
    return 100.0 * s[args["sum"]] / s["n"] / cap
