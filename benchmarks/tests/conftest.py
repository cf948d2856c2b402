"""benchmarks/tests: run by hand, ``JAX_PLATFORMS=cpu python3 -m pytest
benchmarks/tests -q``.  The driver's tier-1 command does not collect
them (it runs ``tests/``)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, REPO)
sys.path.insert(0, BENCH)

PARTS = ("configs", "workloads", "traffic", "layer_metrics", "drivers",
         "readers", "reference.py", "trace_reduce.py", "flops.py",
         "peaks.json")


@pytest.fixture(scope="session")
def tree(tmp_path_factory):
    """A copy of the benchmark with the rehearsal cells, their
    configurations, mixes, layer metrics and one reader ADDED AS NEW
    FILES — no file of the benchmark is edited, which is the point."""
    root = tmp_path_factory.mktemp("bench_tree")
    for part in PARTS:
        src = os.path.join(BENCH, part)
        dst = os.path.join(root, part)
        (shutil.copytree if os.path.isdir(src) else shutil.copy)(src, dst)
    before = {os.path.relpath(os.path.join(d, f), root)
              for d, _, fs in os.walk(root) for f in fs}
    tiny = os.path.join(HERE, "data", "tiny")
    for d, _, fs in os.walk(tiny):
        for f in fs:
            rel = os.path.relpath(os.path.join(d, f), tiny)
            assert rel not in before, f"{rel} would overwrite a benchmark file"
            os.makedirs(os.path.dirname(os.path.join(root, rel)),
                        exist_ok=True)
            shutil.copy(os.path.join(d, f), os.path.join(root, rel))
    return str(root)


def run_cell(root, cell, trace, seconds=1.5, seed=2147491619, rehearse=True,
             env=None):
    """One run of ``run.main`` in a process of its own.  The command
    line has only the contract's four switches; another tree and a run
    off the TPU are arguments of ``main`` that only these tests pass."""
    call = (f"import sys; sys.path.insert(0, {BENCH!r}); import run; "
            f"run.main(sys.argv[1:], root={root!r}, rehearse={rehearse!r})")
    cmd = [sys.executable, "-c", call, "--workload", cell, "--seed",
           str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    e = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    e.update(env or {})
    p = subprocess.run(cmd, capture_output=True, text=True, env=e,
                       cwd=REPO, timeout=900)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, (json.loads(lines[-1]) if lines and p.returncode == 0 else None)
