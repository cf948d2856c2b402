"""A reader added as a new file: the steps the window made."""


def read(record, args):
    return record["notes"].get("steps")
