"""The benchmark's one entry point.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, one configuration, one traffic
mix or one per-layer metric is a file of its own, found by name
(benchmarks/README.md):

    workloads/<cell>.json      -> config, traffic, chips, driver, and the
                                  names of its end_to_end and per_layer
                                  metrics
    configs/<config>.json      -> the sizes as run, engine/trainer arguments
    traffic/<traffic>.json     -> parameters of the mix, and its generator
    layer_metrics/<name>.json  -> reader, arguments, layer, moves
    drivers/<driver>.py        -> run(ctx) -> Record
    readers/<reader>.py        -> read(record, args) -> number or None

The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
with ``--trace 1``, ``breakdown``).  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics.  No TPU, or
fewer chips than the cell asks for: exit code 3 and no result line.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # set-up is counted from here

import argparse
import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(root, kind, name):
    """``<root>/<kind>/<name>.py`` as a module, by file: a later PR adds
    a driver, a generator or a reader as a new file, no list to edit."""
    path = os.path.join(root, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind[:-1]} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def layer_metrics_for(root, cell):
    """The ``layer_metrics/<name>.json`` of every name under the cell's
    ``per_layer``: a cell names its metrics, a metric names no cell."""
    out = []
    for name in cell.get("per_layer", ()):
        m = load_json(os.path.join(root, "layer_metrics", name + ".json"))
        if m["name"] != name:
            raise SystemExit(f"layer metric file {name}.json names "
                             f"{m['name']!r}")
        out.append(m)
    return out


@dataclasses.dataclass
class Context:
    """What a driver is given."""
    root: str            # the benchmark's directory
    repo: str            # the checkout
    scratch: str         # a directory inside the checkout for traces
    cell: dict
    conf: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    meter: object        # CompileMeter
    t_process: float
    devices: list        # the chips the cell asks for
    peaks: dict          # this device_kind's row of peaks.json

    def module(self, kind, name):
        return load_module(self.root, kind, name)


class CompileMeter:
    """Counts every program JAX asks the backend for (compiled, or
    fetched from the persistent cache) — the idea of chip_smoke.py's
    ``Meter``.  A driver snapshots it at the start and the end of its
    window: the difference has to be zero."""

    def __init__(self):
        import jax.monitoring

        self.programs = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += duration

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def device_block(root, chips, rehearse):
    """The ``device`` object, or exit 3 when this is not the machine
    the cell asks for.  An unknown ``device_kind`` is an error too: the
    table of peaks has no default."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not rehearse:
        print(f"benchmark: the cell needs a TPU; JAX found {platform!r}",
              file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < chips:
        print(f"benchmark: the cell needs {chips} chip(s); JAX found "
              f"{len(devs)}", file=sys.stderr)
        raise SystemExit(3)
    kind = devs[0].device_kind
    peaks = load_json(os.path.join(root, "peaks.json"))["devices"]
    if kind not in peaks and not rehearse:
        print(f"benchmark: device_kind {kind!r} is not in peaks.json",
              file=sys.stderr)
        raise SystemExit(3)
    return ({"platform": platform, "kind": kind, "count": chips},
            peaks.get(kind), list(devs[:chips]))


def memory_peak(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def main(argv=None, root=HERE, rehearse=False):
    """``root`` and ``rehearse`` are for ``benchmarks/tests`` alone
    (another benchmark tree; a run off the TPU, whose result names the
    platform it ran on): the command line has no switch for them."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    root = os.path.abspath(root)
    repo = os.path.dirname(HERE)
    # The checkout for the program, the benchmark's tree for its own
    # modules (reference, trace_reduce, flops, traffic.*).
    sys.path[:0] = [root, repo]

    cell_file = os.path.join(root, "workloads", args.workload + ".json")
    if not os.path.isfile(cell_file):
        raise SystemExit(f"no cell {args.workload!r}: {cell_file}")
    cell = load_json(cell_file)
    conf = load_json(os.path.join(root, "configs", cell["config"] + ".json"))
    mix = load_json(os.path.join(root, "traffic", cell["traffic"] + ".json"))

    # The program's own cache placement (JAX_COMPILATION_CACHE_DIR if
    # set, else <checkout>/.jax_cache), before anything compiles; the
    # benchmark also keeps the programs JAX would skip as too quick to
    # be worth caching, so a second run builds nothing.
    from distkeras_tpu.utils.misc import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    device, peaks, devices = device_block(root, int(cell["chips"]),
                                          rehearse)
    meter = CompileMeter()
    scratch = os.path.join(repo, ".bench_scratch", args.workload)
    os.makedirs(scratch, exist_ok=True)
    ctx = Context(root=root, repo=repo, scratch=scratch, cell=cell,
                  conf=conf, mix=mix, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace),
                  meter=meter, t_process=T_PROCESS, devices=devices,
                  peaks=peaks)
    driver = load_module(root, "drivers", cell["driver"])
    record = driver.run(ctx)

    print(json.dumps({"note": "run", "workload": args.workload,
                      "seed": args.seed, "cache_dir": cache_dir,
                      "programs_requested": meter.programs,
                      "cache_hits": meter.hits,
                      "compile_s": meter.seconds,
                      "notes": record.get("notes", {})}), flush=True)

    metrics = {}
    if not args.trace:
        for name in cell["end_to_end"]:
            value, unit = record["end_to_end"][name]
            metrics[name] = {"value": value, "unit": unit}
    else:
        for m in layer_metrics_for(root, cell):
            reader = load_module(root, "readers", m["reader"])
            value = reader.read(record, m.get("args", {}))
            if value is not None:   # nothing to read: leave it out
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    device["memory_peak_bytes"] = memory_peak(devices)
    out = {"correct": bool(record["correct"]),
           "attempted": int(record["attempted"]),
           "failed": int(record["failed"]),
           "metrics": metrics, "device": device}
    if args.trace:
        summary = record.get("trace")
        if (summary is None or summary.get("busy_s", 0) <= 0) \
                and not rehearse:
            print("benchmark: the traced run saw no operation on the "
                  "device", file=sys.stderr)
            raise SystemExit(4)
        summary = summary or {"busy_s": 0.0, "window_s": 0.0,
                              "device_ops": [], "idle_gaps": []}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["device_ops"][:10],
                            "idle_gaps": summary["idle_gaps"][:10]}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
