"""Reader over ``LMTrainer.step_timer.phases`` (host wall time per
named phase, summed over the call's steps): the phase's share of the
window, scaled to the steps that lay inside it.

args: ``phase``.
"""


def read(record, args):
    phases = record.get("step_timer") or {}
    if args["phase"] not in phases:
        return None
    seconds, calls = phases[args["phase"]]
    if not calls:
        return None
    steps = record["notes"]["steps"]
    return 100.0 * seconds / calls * steps / record["window_s"]
