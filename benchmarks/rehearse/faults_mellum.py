"""One planted fault read through the cell's own kind at THE CELL'S sizes and
limits, on the four chips: the flow of ``benchmarks/margins.py`` (``--seconds``
0.2 closes the window after one update, no control), the fault planted round
the trainer alone, so the reference runs with the program's modules as they
are. ``plant`` is what ``benchmarks/tests/test_train_mellum.py`` plants at a
small size. One process a fault (the chips belong to one process at a time):

    python3 benchmarks/rehearse/faults_mellum.py <fault> <seed>

prints ``FAULT {...}``: ``correct``, the limits that fail, every reading.

The two faults are the two ways an expert axis goes wrong in silence (the
loss at the seeded weights is ln V under both, and nothing crashes):

* ``whole_tensors_not_summed``: the gradients of what every chip holds whole
  (attention, norms, routers) are NOT summed over the axis: each chip updates
  its copy from its own rows' part.
* ``terms_to_wrong_tokens``: what comes back through the exchange on one chip
  is given to the wrong tokens: chip 1's returned terms turned by one place.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
CELL = "train-mellum2-ep4-seq8192"
FAULTS = ("whole_tensors_not_summed", "terms_to_wrong_tokens")


def plant(mp, fault):
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu import pretrain

    if fault == "whole_tensors_not_summed":
        def each_chip_its_own(sums, param_specs, rows_over):
            """In the place of the update's one sum over the chips: every
            tensor keeps the terms it has (the cell's rows are divided over
            the expert axis alone), over the number of chips as before."""
            chips = jax.lax.axis_size(rows_over)
            return jax.tree_util.tree_map(lambda g: g / chips, sums)

        mp.setattr(pretrain, "_sums_over_chips", each_chip_its_own)
    elif fault == "terms_to_wrong_tokens":
        real = jax.lax.all_to_all

        def all_to_all(t, axis, *a, **k):
            """The real exchange (the expert layer's rounds are the step's
            only all-to-alls of three axes over the FIRST of them; the deal
            of the tokens round the chips goes over the second and is left
            alone); the [chips, rows, H] buffers that reach chip 1 (rows on
            their way out, terms on their way back) turned by one place along
            their rows."""
            out = real(t, axis, *a, **k)
            if out.ndim != 3 or tuple(a[:1]) != (0,):
                return out
            turned = jnp.roll(out, 1, axis=1)
            return jnp.where(jax.lax.axis_index(axis) == 1, turned, out)

        mp.setattr(jax.lax, "all_to_all", all_to_all)
    else:
        raise ValueError(f"fault must be one of {FAULTS}, got {fault!r}")


def read(ctx: dict, kind, fault: str) -> dict:
    """``kind.measure(ctx)`` with ``fault`` planted round the trainer."""
    from pytest import MonkeyPatch

    real_drive = kind.base.drive

    def drive_with_the_fault(*a, **k):
        with MonkeyPatch.context() as planted:
            plant(planted, fault)
            return real_drive(*a, **k)

    kind.base.drive = drive_with_the_fault
    try:
        return kind.measure(ctx)
    finally:
        kind.base.drive = real_drive


def main() -> int:
    from benchmarks import run as bench_run
    from benchmarks.kinds import train as base

    fault, seed = sys.argv[1], int(sys.argv[2])
    ctx = bench_run.context(ROOT, CELL)
    ctx.update(seed=seed, seconds=0.2, trace=False,
               started=time.perf_counter(), controls=[])
    base.require_chips(int(ctx["cell"]["chips"]))
    kind = bench_run.load_module(ctx["kind_file"], "kind_faults")
    result = read(ctx, kind, fault)
    limits = ctx["mix"]["check"]["limits"]
    failing = sorted(k for k, v in result["readings"].items()
                     if k in limits and not v <= limits[k])
    print("FAULT", json.dumps({
        "fault": fault, "seed": seed, "correct": result["correct"],
        "fails": failing, "readings": result["readings"],
        "comparison_s": result["comparison_s"],
        "memory_peak_bytes": result["device"]["memory_peak_bytes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
