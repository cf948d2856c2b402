"""Reader over the counts a program span carries: the mean of one
field of the ``span``s that began in the measured window.

args: ``span`` (the span's name), ``field``, and optionally ``per``:
``lanes*max_len`` turns the mean into a percentage of the KV slots the
engine holds.  Rounds the program marks ``idle`` dispatched no decode
step and are left out: the mean is per decoding round.

Returns None where the program records no such span or field (a
program older than the span), so the metric is left out of the line.
"""


def read(record, args):
    lo, hi = record["window"]
    values = [rec["fields"][args["field"]]
              for rec in record.get("obs_events", ())
              if rec.get("kind") == "span" and rec["name"] == args["span"]
              and lo <= rec["t0"] < hi
              and not rec["fields"].get("idle")
              and args["field"] in rec["fields"]]
    if not values:
        return None
    mean = sum(values) / len(values)
    if args.get("per") == "lanes*max_len":
        return 100.0 * mean / (record["lanes"] * record["max_len"])
    if args.get("per"):
        raise ValueError(f"unknown per {args['per']!r}")
    return mean
