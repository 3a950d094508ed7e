#!/usr/bin/env python
"""Offline telemetry summary + baseline-diff regression verdict.

Thin CLI shim over :mod:`bert_pytorch_tpu.telemetry.report` (also
installed as the ``telemetry-report`` console script) so the tool runs
straight from a checkout. Imports only stdlib + the report/schema
modules — no jax — so it works anywhere, including CI boxes without the
accelerator stack.

Usage::

    python tools/telemetry_report.py RUN.jsonl              # summary
    python tools/telemetry_report.py RUN.jsonl BASE.jsonl   # diff + verdict

Exit 0 = no regression, 1 = regression (named in the output),
2 = missing file. ``--format json`` prints one stable versioned object
(``{"version": 1, ..., "rc": N}`` — the tools/check_all.py contract);
``--json`` is the legacy machine shape. Tolerance knobs: ``--step-tol
--p95-tol --mfu-tol --mem-tol --grad-tol`` (docs/telemetry.md has a
worked example).
"""

from __future__ import annotations

import sys

from _bootstrap import load_by_path

_report = load_by_path(
    "_telemetry_report_engine", "bert_pytorch_tpu", "telemetry", "report.py")

if __name__ == "__main__":
    sys.exit(_report.main())
