"""The per-layer metrics that read what PR 25 put into the program, run
in the accepted cells WITHOUT an edit to a file that is there.

A cell's file names its per-layer metrics, so reporting a new metric in
an accepted cell is an edit to ``workloads/<cell>.json`` — a
``benchmark`` PR's business.  Until one makes it, this builds the tree
that PR would leave: a copy of the benchmark plus

    data/wanted/layer_metrics/*.json   the six metric files, as wanted
    data/tiny/readers/span_*.py        the two new readers
    data/wanted/per_layer.json         cell -> names appended to its
                                       ``per_layer`` list (nothing else
                                       of a cell's file changes)

and runs one cell in it, with the contract's four switches:

    python3 benchmarks/tests/wanted.py --workload sc1b.serve.batch \
        --seed 7 --seconds 51 --trace 1

The tree goes to ``<checkout>/.bench_scratch/wanted`` (git ignores
it).  The accepted benchmark is ``benchmarks/run.py``; what this prints
is a builder's measurement (PERF.md says "(chip, PR 25)"), not the
driver's.
"""

import glob
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
PARTS = ("configs", "workloads", "traffic", "layer_metrics", "drivers",
         "readers", "end_to_end.json", "reference.py", "trace_reduce.py",
         "flops.py", "peaks.json")


def build(root):
    """The benchmark with the wanted files added and the wanted names
    appended, at ``root`` (made anew).  Nothing may overwrite a file
    of the benchmark."""
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    for part in PARTS:
        src = os.path.join(BENCH, part)
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(
            src, os.path.join(root, part))
    wanted = os.path.join(HERE, "data", "wanted")
    new = glob.glob(os.path.join(wanted, "layer_metrics", "*.json")) \
        + glob.glob(os.path.join(HERE, "data", "tiny", "readers",
                                 "span_*.py"))
    for src in new:
        dst = os.path.join(root, os.path.basename(os.path.dirname(src)),
                           os.path.basename(src))
        assert not os.path.exists(dst), f"{dst} is a benchmark file"
        shutil.copy(src, dst)
    with open(os.path.join(wanted, "per_layer.json")) as f:
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
    sys.path.insert(0, BENCH)
    import run

    run.main(sys.argv[1:], root=build(os.path.join(
        os.path.dirname(BENCH), ".bench_scratch", "wanted")))
