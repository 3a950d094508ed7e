"""Look at a trace by hand, and cut the small recorded trace the tests use.

    python3 benchmarks/trace/describe.py <trace_dir> [--cut out.json]

Prints every plane and line with its event count and first names. ``--cut``
writes the events within 15 ms of the longest idle gap of the first device
(at most 2000, ``bench:*`` annotations kept) in ``read_trace_dir``'s layout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.trace import reduce  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("trace_dir")
    parser.add_argument("--cut", default=None)
    args = parser.parse_args(argv)
    planes = reduce.read_trace_dir(args.trace_dir)
    for plane in planes:
        print("plane", plane["name"])
        for line in plane["lines"]:
            names = [e[0] for e in line["events"][:6]]
            print(f"  line {line['name']!r}: {len(line['events'])} events {names}")
    summary = reduce.summarize(planes)
    print(json.dumps({k: v if not isinstance(v, list) else v[:12]
                      for k, v in summary.items()}, indent=1))
    if args.cut:
        device = next(p for p in planes if p["name"].startswith("/device:TPU:"))
        events = sorted((e for line in device["lines"]
                         if line["name"] == reduce.OPS_LINE
                         for e in line["events"]), key=lambda e: e[1])
        spans = reduce.union([[e[1], e[1] + e[2]] for e in events])
        gap = max(zip(spans, spans[1:]), key=lambda ab: ab[1][0] - ab[0][1])
        lo, hi = gap[0][1] - 15e6, gap[1][0] + 15e6
        cut = []
        for plane in planes:
            lines = []
            for line in plane["lines"]:
                keep = [e for e in line["events"] if lo <= e[1] <= hi and (
                    plane["name"] == device["name"] and line["name"] == reduce.OPS_LINE
                    or e[0].startswith(reduce.ANNOTATION))][:2000]
                if keep:
                    lines.append({"name": line["name"], "events": keep})
            if lines:
                cut.append({"name": plane["name"], "lines": lines})
        with open(args.cut, "w") as f:
            json.dump(cut, f)
        print("cut", sum(len(l["events"]) for p in cut for l in p["lines"]),
              "events to", args.cut)
    return 0


if __name__ == "__main__":
    sys.exit(main())
