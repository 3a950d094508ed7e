"""One run of one cell: ``python3 benchmarks/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``; the result is the last line.

Driven by data: the cell is an entry of ``BENCHMARK.json``; its configuration
is the file that entry's config names, its traffic mix is
``benchmarks/traffic/<traffic>.json``, the mix's ``kind`` names
``benchmarks/kinds/<kind>.py``, and each per-layer metric is read by
``benchmarks/metrics/<metric>.py``. Nothing here lists a cell or a metric.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse
import importlib.util
import json
import os
import sys

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device", "breakdown")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def context(root: str, workload: str) -> dict:
    """Everything data says about one cell (no JAX, no program)."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    mix = load_json(os.path.join(
        root, bench["paths"][0], "traffic", cell["traffic"] + ".json"))
    readers, units = {}, {}
    for metric in bench["per_layer"]:
        if "workloads" in metric and workload not in metric["workloads"]:
            continue
        path = os.path.join(root, bench["paths"][0], "metrics",
                            metric["name"] + ".py")
        readers[metric["name"]] = load_module(
            path, "metric_" + metric["name"].replace(".", "_")).read
        units[metric["name"]] = metric["unit"]
    return {
        "root": root, "cell": cell, "mix": mix,
        "config": load_json(os.path.join(root, config_entry["file"])),
        "config_file": os.path.join(root, config_entry["file"]),
        "readers": readers, "units": units,
        "kind_file": os.path.join(root, bench["paths"][0], "kinds",
                                  mix["kind"] + ".py"),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    ctx = context(ROOT, args.workload)
    ctx.update(seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
               started=STARTED)
    kind = load_module(ctx["kind_file"], "kind_" + ctx["mix"]["kind"])
    try:
        result = kind.run(ctx)
    except kind.ChipError as error:
        print(f"benchmark: {error}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps({k: result[k] for k in RESULT_KEYS if k in result}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
