"""From the trainer's telemetry file to what start-up TRACED.

``setup_step_lower_s.train`` (``startup.py``) is one number, and most of it
is ``trace_s``: host Python that runs before JAX can ask its cache. Since PR
51 every ``compile`` / ``compile_cost`` record says what that Python was, in
``trace_parts`` (``docs/telemetry.md`` "Start-up"; booked by the hooks of
``bert_pytorch_tpu/utils/trace_parts.py``): the flax modules' self seconds by
class, the seconds and the count of kernel builds (a ``pallas_call`` site or
megablox call reached while tracing) by kernel name, the optimizer's update,
and as the remainder JAX's own passes. The init program has a ``compile``
record of its own, ``fn`` ``init_state``. Five numbers come out
(``reduce_parts``), over every record of ``train_step`` in the run, as
``startup.py`` counts them:

=================  ==========================================================
trace_model_s      the modules' self seconds
trace_kernels_s    the kernel builds' seconds
kernel_builds      how many builds: against the kernel calls an update makes
                   on the device, which kernels share one trace (a jitted
                   entry point: one build a distinct shape) and which are
                   traced anew at every call, forward and in the backward rule
trace_other_s      ``optimizer_s`` + ``other_s``: the three ``trace_*`` add up
                   to the records' ``trace_s``
init_program_s     ``trace_s`` + ``lower_s`` + ``backend_compile_s`` of the
                   ``init_state`` record
=================  ==========================================================

The file is found as ``startup.py`` finds it. A file whose records of the
step carry no ``trace_parts`` (a program from before them) gives ``None`` and
the readers report nothing. The ``startup_parts:`` line prints the tables
whole.
"""

from __future__ import annotations

import json

from benchmarks.trace import scopes, startup

INIT = "init_state"

_reductions = {}  # path of a telemetry file -> its reduction


def read_records(path: str) -> list:
    """The ``compile`` / ``compile_cost`` records of the step and of the
    init program, in the file's order."""
    kept = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            if startup.STEP not in line and INIT not in line:
                continue
            record = json.loads(line)
            if record.get("kind") in ("compile", "compile_cost") and \
                    record.get("fn") in (startup.STEP, INIT):
                kept.append(record)
    return kept


def reduce_parts(records: list) -> dict | None:
    """The five numbers of the module docstring, with the tables they were
    summed from; None where no record of the step has ``trace_parts``."""
    step = [r for r in records
            if r.get("fn") == startup.STEP and "trace_parts" in r]
    if not step:
        return None
    modules, kernels = {}, {}
    optimizer_s = other_s = outside_s = 0.0
    for parts in (r["trace_parts"] for r in step):
        for table, rows, count, seconds in (
                (modules, parts["modules"], "calls", "self_s"),
                (kernels, parts["kernels"], "builds", "build_s")):
            for name, row in rows.items():
                held = table.setdefault(name, [0, 0.0])
                held[0] += row[count]
                held[1] += row[seconds]
        optimizer_s += parts["optimizer_s"]
        other_s += parts["other_s"]
        outside_s += parts["outside_trace_s"]
    init = [r for r in records
            if r.get("fn") == INIT and r.get("kind") == "compile"]
    return {
        "trace_model_s": sum(s for _, s in modules.values()),
        "trace_kernels_s": sum(s for _, s in kernels.values()),
        "kernel_builds": sum(n for n, _ in kernels.values()),
        "trace_other_s": optimizer_s + other_s,
        "init_program_s": sum(r["trace_s"] + r["lower_s"]
                              + r["backend_compile_s"] for r in init),
        "trace_s": sum(r["trace_s"] for r in step),
        "optimizer_s": optimizer_s, "other_s": other_s,
        "outside_trace_s": outside_s,
        "modules": modules, "kernels": kernels,   # name -> [count, seconds]
        "init": [{k: r[k] for k in ("trace_s", "lower_s", "backend_compile_s",
                                    "cache_load_s", "cache")} for r in init],
    }


def for_run(ctx: dict) -> dict | None:
    """The reduction of the run's telemetry file, or None where there is
    nothing to read: an empty context, no trace to find the file by, or a
    file without ``trace_parts``."""
    if not ctx.get("summary") or not ctx.get("updates"):
        return None
    trace = scopes.newest_trace(ctx.get("trace_dir"))
    path = startup.telemetry_file(trace) if trace else None
    if path is None:
        return None
    if path not in _reductions:
        found = _reductions[path] = reduce_parts(read_records(path))
        if found is not None:
            print("startup_parts: " + json.dumps(found))
    return _reductions[path]


def value(ctx: dict, name: str) -> float | None:
    found = for_run(ctx)
    return None if found is None else float(found[name])
