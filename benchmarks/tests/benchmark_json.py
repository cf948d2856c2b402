"""``BENCHMARK.json`` as the benchmark's own files say it, so that the
two cannot disagree: ``end_to_end.json`` (command, paths, run_seconds,
the end-to-end metrics and their bounds, the order of the cells),
``workloads/*.json`` (each names its configuration, its mix and its
end-to-end and per-layer metrics), ``configs/*.json`` and
``layer_metrics/*.json``.  ``test_files.py`` compares; a PR that has
added its files rewrites the file with

    python3 benchmarks/tests/benchmark_json.py
"""

from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(ROOT)


def load(*parts):
    with open(os.path.join(ROOT, *parts), encoding="utf-8") as f:
        return json.load(f)


def names(kind):
    return sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, kind))
                  if f.endswith(".json"))


def where(cells, key, name):
    """``{"workloads": [...]}`` for a metric that only some cells list
    under ``key``, nothing for one that all do."""
    listed = [c["name"] for c in cells if name in c[key]]
    return {} if len(listed) == len(cells) else {"workloads": listed}


def build():
    head = load("end_to_end.json")
    order = head["cells_in_order"]
    cells = [load("workloads", n + ".json") for n in sorted(
        names("workloads"), key=lambda n: (
            order.index(n) if n in order else len(order), n))]
    configs = []
    for n in dict.fromkeys(c["config"] for c in cells):
        c = load("configs", n + ".json")
        configs.append({"name": c["name"], "source": c["source"],
                        "file": f"benchmarks/configs/{n}.json",
                        "reduced": c["reduced"],
                        "why": c["what_this_is"][:200]})
    per_layer = []
    for n in names("layer_metrics"):
        m = load("layer_metrics", n + ".json")
        if any(n in c["per_layer"] for c in cells):
            per_layer.append({**{k: m[k] for k in (
                "name", "unit", "better", "source", "layer", "moves")},
                **where(cells, "per_layer", n)})
    end_to_end = [{**{k: m[k] for k in ("name", "unit", "better", "bound",
                                        "source")},
                   **where(cells, "end_to_end", m["name"])}
                  for m in head["end_to_end"]]
    return {"command": head["command"], "paths": head["paths"],
            "run_seconds": head["run_seconds"], "configs": configs,
            "workloads": [{k: c[k] for k in ("name", "config", "traffic",
                                             "chips", "why")} for c in cells],
            "end_to_end": end_to_end, "per_layer": per_layer}


if __name__ == "__main__":
    with open(os.path.join(REPO, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(build(), f, indent=1)
        f.write("\n")
