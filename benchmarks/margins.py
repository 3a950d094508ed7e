"""The readings the limits of ``correct`` are set from, in one process.

For each seed: the program's first updates against the float32 reference (a
sound run), and for the first ``--controls`` seeds the reference itself in a
lower precision put in the program's place (``fp8``: the control, the step
below what the configuration states).
No measured window (``--seconds`` of 0.2 closes it after one update). Prints
a table and writes it to ``chiprun_out/margins_<workload>.json``.

    python3 benchmarks/margins.py --workload train-large-phase1 --seeds 12 --controls 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONTROL = "fp8"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--controls", type=int, default=3)
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import run as bench_run

    rows = []
    for i in range(args.seeds):
        seed = 2 ** 31 + 1000 + 7919 * i
        ctx = bench_run.context(ROOT, args.workload)
        ctx.update(seed=seed, seconds=0.2, trace=False,
                   started=time.perf_counter(),
                   controls=[CONTROL] if i < args.controls else [])
        kind = bench_run.load_module(ctx["kind_file"], "kind_margins")
        result = kind.run(ctx)
        row = {"seed": seed, "sound": result["readings"],
               "controls": result.get("controls", {}), "raw": result["raw"],
               "correct": result["correct"],
               "comparison_s": result["comparison_s"],
               "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
        rows.append(row)
        print("MARGIN", json.dumps({k: v for k, v in row.items() if k != "raw"}),
              flush=True)
    names = sorted(rows[0]["sound"])
    print("\nnumber: largest sound | the control's smallest")
    table = {}
    for name in names:
        sound = max(r["sound"][name] for r in rows)
        entry = {"sound_max": sound}
        got = [r["controls"][CONTROL][name] for r in rows
               if name in r["controls"].get(CONTROL, {})]
        if got:
            entry[CONTROL + "_min"] = min(got)
        table[name] = entry
        print(name, entry)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"margins_{args.workload}.json"), "w") as f:
        json.dump({"rows": rows, "table": table}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
