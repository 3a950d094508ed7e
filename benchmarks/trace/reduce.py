"""From a profiler trace to numbers: the reduction every PR is measured by.

Input is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` into plain lists (``read_trace_dir``), so the
reduction itself (``summarize``) runs on the small recorded trace under
``tests/`` without a chip.

A device plane is one named ``/device:TPU:<n>``. Its ``XLA Ops`` line holds
one event per executed HLO op, named by the whole instruction; the name XLA
gave the op (``%fusion.707``: the text before `` = ``) is kept, no renaming.
Ops nest (a ``%while`` spans its body's ops), so an op's time is its self
time: its duration less its children's. Busy time is the union of the events'
intervals; the window runs from the first to the last of them; idle is the
rest. A collective is an op whose name starts with one of ``COLLECTIVES``;
its exposed time is the part of it during which no other leaf op runs on that
device; a collective in flight on the ``Async XLA Ops`` line counts as a
collective and as busy. The host's ``bench:*`` annotations (written by the kind's probes with
``jax.profiler.TraceAnnotation``) are on the same clock; an idle gap is
shared among the annotations that overlap it, and what none covers goes to
``loop`` (the trainer's own code between the probes: logging, the fetch of
the loss, telemetry).
"""

from __future__ import annotations

import glob
import os

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"  # copies and collectives in flight beside the ops
ANNOTATION = "bench:"


def read_trace_dir(trace_dir: str) -> list:
    """[{name, lines: [{name, events: [[name, start_ns, duration_ns]]}]}]"""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in ProfileData.from_file(max(paths, key=os.path.getmtime)).planes:
        lines = []
        for line in plane.lines:
            events = [[e.name.split(" = ")[0], float(e.start_ns),
                       float(e.duration_ns)] for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: list) -> list:
    """Merged, sorted [start, end] intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _length(intervals: list) -> float:
    return sum(end - start for start, end in intervals)


def _minus(intervals: list, holes: list) -> list:
    """The parts of merged ``intervals`` that merged ``holes`` do not cover."""
    out, j = [], 0
    for start, end in intervals:
        cursor = start
        while j < len(holes) and holes[j][1] <= cursor:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < end:
            if holes[k][0] > cursor:
                out.append([cursor, holes[k][0]])
            cursor = max(cursor, holes[k][1])
            k += 1
        if cursor < end:
            out.append([cursor, end])
    return out


def self_times(events: list) -> list:
    """[name, start, end, self_ns, is_leaf] per event of one line: nested
    events (a child lies inside its parent) give their time to the child."""
    out, stack = [], []
    for name, start, duration in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + duration
        while stack and stack[-1][2] <= start:
            stack.pop()
        row = [name, start, end, duration, True]
        if stack:
            stack[-1][3] -= duration
            stack[-1][4] = False
        stack.append(row)
        out.append(row)
    return out


def is_collective(name: str) -> bool:
    return name.lstrip("%").startswith(COLLECTIVES)


def summarize(planes: list) -> dict:
    """busy_s, window_s (averaged over the device planes), device_ops and
    idle_gaps as [[name, seconds]] longest first, collective_s and
    collective_exposed_s per device on average, and n_devices."""
    devices = [p for p in planes if p["name"].startswith("/device:TPU:")]
    annotations = []
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            annotations += [(e[0][len(ANNOTATION):], e[1], e[1] + e[2])
                            for e in line["events"] if e[0].startswith(ANNOTATION)]
    busy = window = coll = exposed = 0.0
    ops, gaps = {}, {}
    used = 0
    for plane in devices:
        events = [e for line in plane["lines"] if line["name"] == OPS_LINE
                  for e in line["events"]]
        if not events:
            continue
        used += 1
        in_flight = [e for line in plane["lines"] if line["name"] == ASYNC_LINE
                     for e in line["events"] if is_collective(e[0])]
        spans = union([[e[1], e[1] + e[2]] for e in events + in_flight])
        busy += _length(spans)
        window += spans[-1][1] - spans[0][0]
        timed = self_times(events)
        for name, _start, _end, own, _leaf in timed:
            ops[name] = ops.get(name, 0.0) + max(own, 0.0)
        collectives = union([[e[1], e[2]] for e in timed if is_collective(e[0])]
                            + [[e[1], e[1] + e[2]] for e in in_flight])
        others = union([[e[1], e[2]] for e in timed
                        if e[4] and not is_collective(e[0])])
        coll += _length(collectives)
        exposed += _length(_minus(collectives, others))
        for (_, gap_start), (gap_end, _) in zip(spans, spans[1:]):
            left = gap_end - gap_start
            for name, a_start, a_end in annotations:
                overlap = min(gap_end, a_end) - max(gap_start, a_start)
                if overlap > 0:
                    gaps[name] = gaps.get(name, 0.0) + overlap
                    left -= overlap
            gaps["loop"] = gaps.get("loop", 0.0) + max(left, 0.0)
    if not used:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line: {[p['name'] for p in planes]}")
    ranked = lambda d: [[k, v / used * 1e-9] for k, v in
                        sorted(d.items(), key=lambda kv: -kv[1])]
    return {
        "n_devices": used,
        "busy_s": busy / used * 1e-9,
        "window_s": window / used * 1e-9,
        "collective_s": coll / used * 1e-9,
        "collective_exposed_s": exposed / used * 1e-9,
        "device_ops": ranked(ops),
        "idle_gaps": ranked(gaps),
    }
