"""Headline benchmark: CIFAR-CNN training throughput on TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count"}.  On any backend but TPU it
prints nothing and exits non-zero: a CPU wall clock is not a device
metric.

The north-star target (BASELINE.json) is >=10x samples/sec vs an
8-executor Spark CPU baseline on the CIFAR-10 small CNN.  The reference
publishes no numbers, so the baseline is the measured proxy from
scripts/measure_cpu_baseline.py: a single-process Keras
``train_on_batch`` CPU loop (the reference worker's exact hot path,
reference: distkeras/workers.py) x 8 executors, charging the reference
nothing for its parameter-server overhead.  Measured 2026-07-29:
267.1 samples/sec single-process -> 2137 samples/sec 8-executor proxy
(see BASELINE.md).

Measurement methodology lives in ONE place — scripts/bench_suite.py
(bf16 policy, jitted donated-state step, device-resident data,
float(loss) barrier); this driver just wraps its cifar_cnn config with
the vs_baseline ratio.
"""

import json
import os
import sys

os.environ.setdefault("KERAS_BACKEND", "jax")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "scripts"))

SPARK8_CPU_PROXY_SPS = 2137.0  # samples/sec; provenance in module docstring


def main():
    from distkeras_tpu.utils.misc import configure_compile_cache

    configure_compile_cache()
    from bench_suite import bench_cifar_cnn, peak_flops, tpu_device_fields

    device = tpu_device_fields()
    sps, step_s, step_flops = bench_cifar_cnn()[:3]
    line = {
        "metric": "cifar_cnn_train_throughput",
        "value": round(sps, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sps / SPARK8_CPU_PROXY_SPS, 2),
        **device,
    }
    peak = peak_flops()
    if peak and step_flops:
        line["mfu"] = round(step_flops / step_s / peak, 4)
    print(json.dumps(line))


if __name__ == "__main__":
    main()
