#!/usr/bin/env python3
"""The benchmark's one entry::

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It needs a TPU with as many chips as the cell asks for:
without one it exits non-zero and prints no result line.  It loads the
cell (``benchmark/workloads/<cell>.json``), its configuration
(``benchmark/configs/<config>.json``) and its runner
(``benchmark/runners/<runner>.py``), all found by name, lets the runner
set up, warm up, measure and compare, and prints one JSON object as the
last line of standard output.

A later PR adds a cell, a configuration, a builder, a runner or a
per-layer metric by adding files and ``BENCHMARK.json`` entries; nothing
here names one.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts) -> dict:
    path = os.path.join(HERE, *parts)
    with open(path) as f:
        return json.load(f)


def load_metric(name: str):
    """The reader of one per-layer metric, ``metrics/<name>.py``.  A
    quantity split by the end-to-end metric it moves (``x.train``,
    ``x.serve``) that is read the same way on both sides has the one
    reader ``metrics/x.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(HERE, "metrics", name.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_of(device_kind: str) -> dict:
    table = load_json("peaks.json")
    for prefix, row in table.items():
        if not prefix.startswith("_") and device_kind.startswith(prefix):
            return row
    raise SystemExit(f"benchmark: no peaks for device kind {device_kind!r} "
                     f"in benchmark/peaks.json - nothing was run")


def configure_cache() -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (or where JAX_COMPILATION_CACHE_DIR says), every program kept."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def cell_metrics(bench: dict, cell: str, group: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell is to
    report: those without a ``workloads`` key or with the cell in it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def device_report(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def emit(result: dict) -> None:
    """Each number compared beside its limit: as the last lines of
    standard error, and last in the result's line."""
    checks = result.pop("checks", {})
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}"
              + (f" ({c['at']})" if c.get("at") else ""), file=sys.stderr)
    sys.stderr.flush()
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = load_json("workloads", args.workload + ".json")
    config = load_json("configs", cell["config"] + ".json")
    sys.path.insert(0, ROOT)
    if importlib.util.find_spec("paddle_tpu") is None:
        raise SystemExit("benchmark: the program (paddle_tpu) is not in this "
                         "checkout - nothing was run")

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX found "
                         f"{devices[0].platform!r} - nothing was run")
    chips = int(cell["chips"])
    if len(devices) < chips:
        raise SystemExit(f"benchmark: {args.workload} needs {chips} chips, "
                         f"JAX found {len(devices)} - nothing was run")
    peaks = peaks_of(devices[0].device_kind)
    configure_cache()

    runner = importlib.import_module("benchmark.runners." + cell["runner"])
    ctx = {
        "name": args.workload, "cell": cell, "config": config,
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "t_start": T_START, "peaks": peaks,
        "end_to_end": {m["name"]: m["unit"] for m in
                       cell_metrics(bench, args.workload, "end_to_end")},
        "per_layer": {m["name"]: m["unit"] for m in
                      cell_metrics(bench, args.workload, "per_layer")},
        "load_metric": load_metric,
        "device_report": lambda: device_report(devices, chips),
    }
    result = runner.run(ctx)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
