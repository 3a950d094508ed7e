"""One planted fault read through the cell's own kind at THE CELL'S sizes and
limits, on the chip: the flow of ``benchmarks/margins.py`` (``--seconds`` 0.2
closes the window after one update, no control), the fault planted round the
trainer alone, so the reference runs with the program's modules as they are.
``plant`` is what ``benchmarks/tests/test_train_keye.py`` plants at a small
size. One process a fault (a chip belongs to one process at a time):

    python3 benchmarks/rehearse/faults_keye.py <fault> <seed>

prints ``FAULT {...}``: ``correct``, the limits that fail, every reading.
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
CELL = "train-keye-vl2-30b-seq16384"
FAULTS = ("index_loss_left_out", "indexer_input_not_detached",
          "top_k_one_short", "micro_batch_dropped")


def plant(mp, fault):
    from bert_pytorch_tpu import pretrain
    from bert_pytorch_tpu.models import keye_vl

    if fault == "index_loss_left_out":
        mp.setattr(keye_vl.KeyeVLForCausalLM, "objective_terms", lambda self: {})
    elif fault == "indexer_input_not_detached":
        mp.setattr(keye_vl, "detach", lambda h: h)
    elif fault == "top_k_one_short":
        real = keye_vl.sparse_attention
        mp.setattr(keye_vl, "sparse_attention",
                   lambda q, k, v, qi, ki, w, topk, backend:
                   real(q, k, v, qi, ki, w, topk - 1, backend))
    elif fault == "micro_batch_dropped":
        import jax

        real_step = pretrain.make_train_step

        def make_broken(*args, **kwargs):
            step = real_step(*args, **kwargs)

            def dropped(state, batch):
                return step(state, jax.tree_util.tree_map(
                    lambda a: a.at[-1].set(a[0]), batch))

            dropped.lower = step.lower
            return dropped

        mp.setattr(pretrain, "make_train_step", make_broken)
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
