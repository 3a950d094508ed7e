"""From the trainer's telemetry file to where set-up went.

``setup_s`` is the host's time from the start of the process to the opening
of the window. The program accounts for the first part of it, up to the end
of its barrier on the first update, in three kinds of record of its telemetry
JSONL (``docs/telemetry.md`` "Start-up"):

* one ``startup``: seconds since the process was created at which ``main``
  was entered (``main_entered_s``), each ``startup:*`` span began and ended
  (``phases``), how long the first update waited for its batch, spent in its
  step call and waited for the device (``first_batch_wait_s``,
  ``first_call_s``, ``first_sync_s``), when that wait ended
  (``time_to_first_update_s``), and what none of these covers
  (``unattributed_s``);
* a ``compile`` for every call of ``train_step`` that traced, lowered,
  compiled or loaded: ``trace_s``, ``lower_s``, ``backend_compile_s`` (JAX's
  compile-or-load call; on a cache hit it is ``cache_load_s`` and little
  more, so the two are NOT added);
* a ``compile_cost`` for the step's cost record: ``analysis_s`` is the whole
  of ``memory.analyze_executable`` (the step lowered again, its executable
  asked for, its text and costs read), with the same four fields for what
  happened inside it.

Seven numbers come out (``reduce_startup``). ``step_lower_s`` and
``step_executable_s`` count every record of the step in the run, so the first
six add up to ``time_to_first_update_s`` only where every such record lies
before the ``startup`` record. The decoder kinds come close (their probe's
second check update loads the step once more after it); the BERT kind lowers
and loads the timed step again at its warm-up call, after the record (0.5 s
and 0.1 s warm, a third compile when cold), so in the three BERT cells the two
hold time that lies outside ``time_to_first_update_s``, in ``setup_s``'s
remainder. The ``startup:`` line prints the records in the file's order, which
tells the two apart:

=================  ==========================================================
before_main_s      ``main_entered_s``: interpreter, imports, and whatever the
                   caller did before ``main`` (here: the benchmark's shards,
                   its look for a chip, its probes)
prepare_s          the top-level phases but ``startup:state_init``
state_init_s       ``startup:state_init`` (``startup:restore`` inside it)
step_lower_s       ``train_step``: trace + lower of every ``compile``, and of
                   every ``compile_cost`` its ``analysis_s`` less the
                   compile-or-load inside it
step_executable_s  ``train_step``: ``backend_compile_s`` of both kinds
first_update_s     the first update (batch wait + step call + sync) less the
                   two rows above as far as they lie before the ``startup``
                   record, i.e. inside that call
unattributed_pct   ``unattributed_s`` as a share of ``time_to_first_update_s``
=================  ==========================================================

A reader gets no path in its context in the ``train`` kind, so the file is
found as ``scopes.py`` finds the trace: it is the ``out/*.jsonl`` beside the
``trace`` directory of ``scopes.newest_trace``. A run whose file holds no
``startup`` record (a program from before these records) gives ``None``,
and the readers then report nothing.
"""

from __future__ import annotations

import glob
import json
import os

from benchmarks.trace import scopes

STEP = "train_step"
STATE_INIT = "startup:state_init"

_reductions = {}  # path of a telemetry file -> its reduction


def telemetry_file(trace_path: str) -> str | None:
    """The trainer's JSONL of the run that wrote ``trace_path``
    (``<work>/trace/**/*.xplane.pb`` -> ``<work>/out/*.jsonl``)."""
    here = os.path.dirname(trace_path)
    while os.path.basename(here) != "trace":
        parent = os.path.dirname(here)
        if parent == here:
            return None
        here = parent
    found = glob.glob(os.path.join(os.path.dirname(here), "out", "*.jsonl"))
    return max(found, key=os.path.getmtime) if found else None


def read_records(path: str) -> list:
    """The ``startup`` records and the step's ``compile`` / ``compile_cost``
    records of one telemetry file, in the file's order."""
    kept = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            if '"startup"' not in line and STEP not in line:
                continue
            record = json.loads(line)
            kind = record.get("kind")
            if kind == "startup" or (kind in ("compile", "compile_cost")
                                     and record.get("fn") == STEP):
                kept.append(record)
    return kept


def reduce_startup(records: list) -> dict | None:
    """The seven numbers of the module docstring, with ``lowerings`` (how
    many records of the step lowered something) and
    ``time_to_first_update_s``; None without a ``startup`` record."""
    at = next((i for i, r in enumerate(records)
               if r.get("kind") == "startup"), None)
    if at is None:
        return None
    start = records[at]

    def lower(r):
        if r["kind"] == "compile_cost":  # its trace and lower lie inside
            return r["analysis_s"] - r["backend_compile_s"]
        return r["trace_s"] + r["lower_s"]

    step = [r for r in records if r.get("kind") != "startup"]
    before = records[:at]
    lower_s = sum(lower(r) for r in step)
    executable_s = sum(r["backend_compile_s"] for r in step)
    inside_call = sum(lower(r) + r["backend_compile_s"] for r in before)
    top = [p for p in start["phases"] if p["parent"] is None]
    spent = lambda phases: sum(p["end_s"] - p["start_s"] for p in phases)
    total = start["time_to_first_update_s"]
    first = (start["first_batch_wait_s"] + start["first_call_s"]
             + start["first_sync_s"])
    return {
        "before_main_s": start["main_entered_s"],
        "prepare_s": spent([p for p in top if p["name"] != STATE_INIT]),
        "state_init_s": spent([p for p in top if p["name"] == STATE_INIT]),
        "step_lower_s": lower_s,
        "step_executable_s": executable_s,
        "first_update_s": first - inside_call,
        "unattributed_pct": 100.0 * start["unattributed_s"] / total,
        "lowerings": sum(1 for r in step if r["lower_s"] > 0),
        "time_to_first_update_s": total,
    }


def for_run(ctx: dict) -> dict | None:
    """The reduction of the run's telemetry file, or None where there is
    nothing to read: an empty context, no trace to find the file by, or a
    file without a ``startup`` record."""
    if not ctx.get("summary") or not ctx.get("updates"):
        return None
    trace = scopes.newest_trace(ctx.get("trace_dir"))
    path = telemetry_file(trace) if trace else None
    if path is None:
        return None
    if path not in _reductions:
        records = read_records(path)
        found = reduce_startup(records)
        _reductions[path] = found
        if found is not None:
            print("startup: " + json.dumps({"reduced": found,
                                            "records": records}))
    return _reductions[path]


def value(ctx: dict, name: str) -> float | None:
    found = for_run(ctx)
    return None if found is None else float(found[name])
