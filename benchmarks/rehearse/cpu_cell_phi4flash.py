"""Rehearsal 1 for the ``train_phi4flash`` kind: the cell's whole control flow
on the CPU at a tiny size (the cut's six layers at width 64, rows of 64
tokens, a window of 8, scan chunks of 16; the Pallas kernels in the
interpreter). Counts and control flow only: nothing this prints is a device
number, and its result line says platform cpu. (``cpu_cell_lm.py`` and
``cpu_cell_laguna.py`` do the same for their kinds.)

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse/cpu_cell_phi4flash.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=96,
            num_attention_heads=8, num_key_value_heads=4, sliding_window=8,
            mamba_dt_rank=4, scan_chunk=16,
            # 40 times narrower than the cell: weights six times larger keep
            # the activations' sizes, so that the scan's term is heard beside
            # its skip term as it is at the real widths
            initializer_range=0.12)
# Wide enough that no float32 rounding of a tiny model fails them; the cell's
# own limits are readings of the real size on the chip (PERF.md, section 2).
LOOSE = {"loss_gap_first": 0.01, "loss_gap_later": 0.01,
         "grad_global_norm_gap": 0.05, "grad_norm_gap_worst_leaf": 0.2,
         "delta_norm_gap_worst_leaf": 0.2, "head_grad_rel_diff": 0.05,
         "all_grad_rel_diff": 0.1, "feed_faults": 0}


def tiny_context(workload: str, seed: int, seconds: float, tmp: str) -> dict:
    sys.path.insert(0, ROOT)
    from benchmarks import run as bench_run

    ctx = bench_run.context(ROOT, workload)
    config = dict(ctx["config"], **TINY)
    config_file = os.path.join(tmp, "tiny_config.json")
    with open(config_file, "w") as f:
        json.dump(config, f)
    mix = dict(ctx["mix"], seq_len=64, sequences=64, trace_updates=2)
    mix["documents"] = dict(mix["documents"], median_tokens=20, min_tokens=4,
                            max_tokens=64)
    mix["check"] = dict(mix["check"], limits=LOOSE)
    ctx.update(config=config, config_file=config_file, mix=mix, seed=seed,
               seconds=seconds, trace=False, started=time.perf_counter())
    return ctx


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", default="train-phi4-mini-flash-seq8192")
    parser.add_argument("--seed", type=int, default=2 ** 31 + 11)
    parser.add_argument("--seconds", type=float, default=1.0)
    args = parser.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with tempfile.TemporaryDirectory() as tmp:
        ctx = tiny_context(args.workload, args.seed, args.seconds, tmp)
        from benchmarks import run as bench_run
        kind = bench_run.load_module(ctx["kind_file"], "kind_rehearsal")
        result = kind.measure(ctx)
    print(json.dumps(result))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
