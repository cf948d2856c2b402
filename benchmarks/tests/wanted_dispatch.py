"""The four per-layer metrics that read what PR 35 put into the
program's spans (``program``, ``seq``, ``host_ms``), run in the four
accepted serving cells WITHOUT an edit to a file that is there.

As ``wanted.py`` (whose tree this starts from, so the frame-based
anchor of ``step_gap_ms`` is printed in the same run, to set beside the
``seq`` join's): a copy of the benchmark plus

    data/wanted_dispatch/layer_metrics/*.json   the four metric files
    data/tiny/readers/span_dispatch.py          their reader
    data/wanted_dispatch/per_layer.json         cell -> names appended
                                                LAST to its ``per_layer``

and one cell run in it with the contract's four switches:

    python3 benchmarks/tests/wanted_dispatch.py \
        --workload kexaone.serve.chat --seed 7 --seconds 51 --trace 1

The tree goes to ``<checkout>/.bench_scratch/wanted_dispatch``.  What
this prints is a builder's measurement ("(chip, PR 35)"), not the
driver's.  On a program older than the fields the four metrics are
left out of the line and nothing raises.
"""

import glob
import json
import os
import shutil
import sys

import wanted

HERE = wanted.HERE
DATA = os.path.join(HERE, "data", "wanted_dispatch")


def build(root):
    """``wanted.build``'s tree with the four metric files added and
    their names appended to the four serving cells.  Nothing may
    overwrite a file of the benchmark."""
    wanted.build(root)
    for src in glob.glob(os.path.join(DATA, "layer_metrics", "*.json")):
        dst = os.path.join(root, "layer_metrics", os.path.basename(src))
        assert not os.path.exists(dst), f"{dst} is a benchmark file"
        shutil.copy(src, dst)
    with open(os.path.join(DATA, "per_layer.json")) as f:
        appended = json.load(f)
    for cell, names in appended.items():
        path = os.path.join(root, "workloads", cell + ".json")
        with open(path) as f:
            spec = json.load(f)
        spec["per_layer"] += names
        with open(path, "w") as f:
            json.dump(spec, f, indent=1)
    return root


if __name__ == "__main__":
    sys.path.insert(0, wanted.BENCH)
    import run

    run.main(sys.argv[1:], root=build(os.path.join(
        os.path.dirname(wanted.BENCH), ".bench_scratch", "wanted_dispatch")))
