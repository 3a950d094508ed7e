"""From a profiler trace to the step's parts, passes and the loop's spans.

What ``reduce.py`` cannot see, because ``read_trace_dir`` keeps an event's
name and drops everything else, this module reads from the same
``.xplane.pb``:

* **Device ops by name.** On a TPU the op's ``op_name`` (module path, named
  scopes, the pass markers JAX writes: ``jvp(``, ``transpose(``,
  ``rematted_computation``) is the ``tf_op`` stat of the event's METADATA,
  which ``jax.profiler.ProfileData`` does not expose (looked at on the chip,
  PR 25: an event's own stats are its offsets). So the file is read with a
  small decoder of the protobuf wire format (``read_xspace``; the schema is
  tsl's ``xplane.proto``). Every device leaf op's self time
  (``reduce.self_times``) is classed twice by the rules in ``scopes.json``,
  which are fragments of the path and of the instruction's kind, never an op's
  number: by **pass** (recompute, backward, optimizer, forward, other) and by
  **part** (attention core, dropout, FFN, ...). What no rule places is
  ``unattributed``.
* **The program's host spans** (``train``, ``train:*`` on the loop's thread,
  ``prefetch:*`` / ``data:*`` on the feeding threads; the names are
  ``bert_pytorch_tpu.telemetry.profiler.SPANS``), per thread. Each device idle
  gap is shared among the INNERMOST ``train:*`` spans open on the loop's
  thread while it lasts (``none`` where none is); spans of other threads that
  overlap a gap are listed beside it, not subtracted.

A reader gets no trace directory in its context (``kinds/train.py`` passes
none, and may not be edited by the PR that added this file), so ``for_run``
finds the run's trace by the kind's own prefix: the newest ``*.xplane.pb``
under ``tempfile.gettempdir()/bench_train_*/trace``. The ``benchmark`` issue
queued in ROADMAP.md replaces that with ``planes`` in the reader's context.
The trace is parsed once per process. A trace without the program's spans or
scopes (the parent of PR 25) gives ``None``: the readers then report nothing.

By hand:

    python3 benchmarks/trace/scopes.py <trace_dir or .xplane.pb> [--cut out.json]

prints the reduction; ``--cut`` writes the events within 12 ms of the longest
idle gap of the first device (at most 1500 device ops, with their op names,
and the program's and the benchmark's host spans) in ``read_xspace``'s
layout: ``benchmarks/tests/recorded_scopes.json`` is such a cut.
"""

from __future__ import annotations

import glob
import json
import os
import struct
import sys
import tempfile

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks.trace import reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = "train"                      # the step annotation
LOOP = "train:"                     # spans of the loop's thread
FEEDERS = ("prefetch:", "data:")    # spans of the feeding threads
PASSES = ("recompute", "backward", "optimizer", "forward", "other")

_reductions = {}  # path of a trace -> its reduction (one parse per process)


def rules() -> dict:
    with open(os.path.join(HERE, "scopes.json"), encoding="utf-8") as f:
        return json.load(f)


# -- the file ----------------------------------------------------------------

def _varint(buf, i):
    result = shift = 0
    while True:
        byte = buf[i]
        i += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    the bytes of a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")
        yield key >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _stats(bufs, stat_names) -> dict:
    """XStat messages -> {name: value} (ints, floats, strings; bytes left out)."""
    out = {}
    for buf in bufs:
        name = value = None
        for field, v in _fields(buf):
            if field == 1:
                name = stat_names.get(v)
            elif field in (3, 4):
                value = v
            elif field == 2:
                value = struct.unpack("<d", v)[0]
            elif field == 5:
                value = _text(v)
            elif field == 7:
                value = stat_names.get(v)
        if name is not None and value is not None:
            out[name] = value
    return out


def read_xspace(path: str, planes=("/device:TPU:", "/host:CPU")) -> list:
    """[{name, lines: [{name, events: [[name, start_ns, duration_ns, more]]}]}]
    of the planes whose name starts with one of ``planes``. ``name`` is the
    text before `` = `` (as ``reduce.read_trace_dir`` keeps it); ``more`` is
    the op's ``op_name`` on a device plane (None where the op has none) and
    the event's stats on the host (``{"step_num": 7}``; None where none)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out = []
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, lines, metadata, stat_names = "", [], {}, {}
        for f, v in _fields(plane):
            if f == 2:
                name = _text(v)
            elif f == 3:
                lines.append(v)
            elif f in (4, 5):  # maps: id -> XEventMetadata / XStatMetadata
                entry = dict(_fields(v))
                if f == 4:
                    metadata[entry[1]] = entry[2]
                else:
                    stat_names[entry[1]] = _text(
                        dict(_fields(entry[2])).get(2, b""))
        if not name.startswith(tuple(planes)):
            continue
        on_device = name.startswith("/device:")
        named = {}
        for key, buf in metadata.items():
            text, stats = "", []
            for f, v in _fields(buf):
                if f == 2:
                    text = _text(v)
                elif f == 5:
                    stats.append(v)
            op_name = _stats(stats, stat_names).get("tf_op") if on_device else None
            named[key] = (text.split(" = ")[0],
                          op_name.rstrip(":") if op_name else None)
        out_lines = []
        for line in lines:
            line_name, t0, events = "", 0, []
            for f, v in _fields(line):
                if f == 2:
                    line_name = _text(v)
                elif f == 3:
                    t0 = v
                elif f == 4:
                    key = offset_ps = duration_ps = 0
                    stats = []
                    for f2, v2 in _fields(v):
                        if f2 == 1:
                            key = v2
                        elif f2 == 2:
                            offset_ps = v2
                        elif f2 == 3:
                            duration_ps = v2
                        elif f2 == 4:
                            stats.append(v2)
                    text, more = named.get(key, ("", None))
                    if stats and not on_device:
                        more = _stats(stats, stat_names) or None
                    events.append([text, offset_ps / 1e3,
                                   duration_ps / 1e3, more])
            for event in events:  # offsets count from the line's timestamp
                event[1] += t0
            out_lines.append({"name": line_name, "events": events})
        out.append({"name": name, "lines": out_lines})
    return out


def newest_trace(under: str | None = None) -> str | None:
    """The newest ``.xplane.pb`` under ``under`` (a directory, searched to
    any depth), or under the ``train`` kind's working directories."""
    pattern = (os.path.join(under, "**", "*.xplane.pb") if under else
               os.path.join(tempfile.gettempdir(), "bench_train_*", "trace",
                            "**", "*.xplane.pb"))
    paths = glob.glob(pattern, recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


# -- the rules -----------------------------------------------------------------

def kind_of(instruction: str) -> str:
    """``%rng-bit-generator.38`` -> ``%rng-bit-generator``: the number XLA
    gave the instruction is dropped before any rule sees it."""
    head, dot, tail = instruction.rpartition(".")
    return head if dot and tail.isdigit() else instruction


def classify(op_name: str | None, instruction: str, table: dict) -> tuple:
    """(pass, part) of one device op. The rules see the op's ``op_name`` path
    and the instruction's kind; in each list the first rule with a fragment
    in that text decides. ``part`` is None where no rule places the op."""
    text = f"{op_name or ''} {kind_of(instruction)}"
    found = []
    for group in ("pass", "part"):
        found.append(next(
            (rule["name"] for rule in table[group]
             if any(fragment in text for fragment in rule["fragments"])), None))
    return found[0] or "other", found[1]


# -- the reduction -------------------------------------------------------------

def _own_intervals(spans: list) -> list:
    """[(name, [[start, end]])]: each span's interval less the spans that lie
    inside it (so at any instant only the innermost open span holds it)."""
    out = []
    for name, start, end in spans:
        inside = reduce.union([[s, e] for n, s, e in spans
                               if start <= s and e <= end
                               and (s, e, n) != (start, end, name)])
        out.append((name, reduce._minus([[start, end]], inside)))
    return out


def _overlap(intervals: list, start: float, end: float) -> float:
    return sum(max(0.0, min(end, e) - max(start, s)) for s, e in intervals)


def reduce_scopes(planes: list, table: dict | None = None) -> dict:
    """Seconds, mean over the device planes: ``busy_s``; ``by_pass`` and
    ``by_part`` (self time of every device op; they each sum to ``busy_s``
    where ops of one device do not overlap); ``unattributed_s`` (no part rule
    placed it); ``kernels`` (self time by kind of the custom calls);
    ``idle_s`` and its shares ``idle_by_span`` (innermost ``train:*`` span of
    the loop's thread, ``none`` where none was open) and, beside them,
    ``idle_beside`` (``prefetch:*`` / ``data:*`` spans of other threads that
    overlap the gaps). ``spans``: {thread: [[name, start_ns, end_ns, step]]}
    of the program's spans. ``has_spans`` / ``has_scopes``: whether the trace
    holds any span / any scope the program of PR 25 writes."""
    table = table or rules()
    threads = {}
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for index, line in enumerate(plane["lines"]):
            mine = [e for e in line["events"]
                    if e[0] == STEP or e[0].startswith((LOOP,) + FEEDERS)]
            if mine:
                threads[f"{line['name']}#{index}"] = sorted(
                    ([e[0], e[1], e[1] + e[2],
                      (e[3] or {}).get("step_num")] for e in mine),
                    key=lambda s: (s[1], -s[2]))
    loop = [s[:3] for spans in threads.values() for s in spans
            if s[0].startswith(LOOP)]
    beside = [s[:3] for spans in threads.values() for s in spans
              if s[0].startswith(FEEDERS)]
    loop_own = _own_intervals(loop)

    devices = [p for p in planes if p["name"].startswith("/device:TPU:")]
    busy = idle = unattributed = 0.0
    by_pass, by_part, kernels, by_span, by_beside = {}, {}, {}, {}, {}
    has_scopes, used, classed = False, 0, {}

    def add(table_, key, ns):
        table_[key] = table_.get(key, 0.0) + ns

    for plane in devices:
        events = sorted((e for line in plane["lines"]
                         if line["name"] == reduce.OPS_LINE
                         for e in line["events"]), key=lambda e: (e[1], -e[2]))
        if not events:
            continue
        used += 1
        in_flight = [e for line in plane["lines"]
                     if line["name"] == reduce.ASYNC_LINE
                     for e in line["events"] if reduce.is_collective(e[0])]
        busy_spans = reduce.union([[e[1], e[1] + e[2]]
                                   for e in events + in_flight])
        busy += sum(e - s for s, e in busy_spans)
        # self_times sorts as the events are sorted here: row i is event i
        for event, row in zip(events, reduce.self_times(
                [e[:3] for e in events])):
            own = max(row[3], 0.0)
            key = (event[3], kind_of(event[0]))
            if key not in classed:  # a few thousand distinct ops, 10^5 events
                classed[key] = classify(event[3], event[0], table) + (
                    any(m in (event[3] or "") for m in table["scope_markers"]),)
            op_pass, part, scoped = classed[key]
            add(by_pass, op_pass, own)
            add(by_part, part or "unattributed", own)
            if part is None:
                unattributed += own
            if key[1].lstrip("%") in table["kernels"]:
                add(kernels, key[1].lstrip("%"), own)
            has_scopes = has_scopes or scoped
        for (_, gap_start), (gap_end, _) in zip(busy_spans, busy_spans[1:]):
            idle += gap_end - gap_start
            left = gap_end - gap_start
            for name, own in loop_own:
                share = _overlap(own, gap_start, gap_end)
                if share:
                    add(by_span, name, share)
                    left -= share
            add(by_span, "none", max(left, 0.0))
            for name, start, end in beside:
                share = _overlap([[start, end]], gap_start, gap_end)
                if share:
                    add(by_beside, name, share)
    if not used:
        raise ValueError("the trace holds no device plane with an "
                         f"{reduce.OPS_LINE!r} line")

    def seconds(table_):
        return {k: v / used * 1e-9 for k, v in sorted(
            table_.items(), key=lambda kv: -kv[1])}

    return {
        "n_devices": used, "busy_s": busy / used * 1e-9,
        "idle_s": idle / used * 1e-9,
        "by_pass": seconds(by_pass), "by_part": seconds(by_part),
        "unattributed_s": unattributed / used * 1e-9,
        "kernels": seconds(kernels),
        "idle_by_span": seconds(by_span), "idle_beside": seconds(by_beside),
        "spans": threads, "has_spans": bool(loop), "has_scopes": has_scopes,
    }


# -- what the readers call -------------------------------------------------------

def for_run(ctx: dict) -> dict | None:
    """The reduction of the run's traced window, or None where there is
    nothing to read: an empty context, no trace, or a trace that holds
    neither a span nor a scope of the program (the parent of PR 25)."""
    if not ctx.get("summary") or not ctx.get("updates"):
        return None
    path = newest_trace()
    if path is None:
        return None
    if path not in _reductions:
        found = reduce_scopes(read_xspace(path))
        _reductions[path] = found
        print("scopes: " + json.dumps({
            k: found[k] for k in ("busy_s", "idle_s", "by_pass", "by_part",
                                  "unattributed_s", "kernels", "idle_by_span",
                                  "idle_beside", "has_spans", "has_scopes")}))
    found = _reductions[path]
    return found if found["has_spans"] or found["has_scopes"] else None


def device_ms(ctx: dict, group: str, *names: str) -> float | None:
    """Per update, the device time of the passes (``group`` "by_pass") or
    parts ("by_part") named; None without the program's scopes."""
    found = for_run(ctx)
    if not found or not found["has_scopes"]:
        return None
    return 1e3 * sum(found[group].get(n, 0.0) for n in names) / ctx["updates"]


def idle_ms(ctx: dict, *spans: str) -> float | None:
    """Per update, the device idle time under the loop's spans named
    (``none``: under no span); None without the program's spans."""
    found = for_run(ctx)
    if not found or not found["has_spans"]:
        return None
    return 1e3 * sum(found["idle_by_span"].get(s, 0.0)
                     for s in spans) / ctx["updates"]


# -- by hand -------------------------------------------------------------------

def cut(planes: list, reach_ns: float = 12e6, most: int = 1500) -> list:
    """The events round the longest idle gap of the first device."""
    device = next(p for p in planes if p["name"].startswith("/device:TPU:"))
    ops = sorted((e for line in device["lines"]
                  if line["name"] == reduce.OPS_LINE for e in line["events"]),
                 key=lambda e: e[1])
    spans = reduce.union([[e[1], e[1] + e[2]] for e in ops])
    before, after = max(zip(spans, spans[1:]),
                        key=lambda pair: pair[1][0] - pair[0][1])
    lo, hi = before[1] - reach_ns, after[0] + reach_ns
    out = []
    for plane in planes:
        lines = []
        for line in plane["lines"]:
            if plane["name"] == device["name"]:
                keep = [e for e in line["events"]
                        if line["name"] == reduce.OPS_LINE
                        and lo <= e[1] and e[1] + e[2] <= hi][:most]
            else:
                keep = [e for e in line["events"] if lo <= e[1] <= hi and (
                    e[0] == STEP or e[0].startswith(
                        (LOOP, reduce.ANNOTATION) + FEEDERS))]
            if keep:
                lines.append({"name": line["name"], "events": keep})
        if lines:
            out.append({"name": plane["name"], "lines": lines})
    return out


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("trace", help="a trace directory or an .xplane.pb")
    parser.add_argument("--cut", default=None)
    args = parser.parse_args(argv)
    path = args.trace if os.path.isfile(args.trace) else newest_trace(args.trace)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {args.trace}")
    planes = read_xspace(path)
    found = reduce_scopes(planes)
    for thread, spans in found.pop("spans").items():
        print(f"thread {thread}: {len(spans)} spans, first {spans[:8]}")
    print(json.dumps(found, indent=1))
    if args.cut:
        kept = cut(planes)
        with open(args.cut, "w", encoding="utf-8") as f:
            json.dump(kept, f, separators=(",", ":"))
        print("cut", sum(len(line["events"]) for p in kept
                         for line in p["lines"]), "events to", args.cut)
    return 0


if __name__ == "__main__":
    sys.exit(main())
