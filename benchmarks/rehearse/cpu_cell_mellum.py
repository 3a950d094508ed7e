"""Rehearsal 1 and 2 for the ``train_mellum`` kind: the cell's whole control
flow on the CPU's four virtual devices at a tiny size (a period of four
layers at width 64: 4 heads of 16 on 2 key-value heads, a window of 16, 8
experts top-2 divided over the expert axis with the slots' exchange, 512 ids
of vocabulary divided four ways, rows of 64 tokens, 1 row a device a
micro-batch), under the cell's own ``--mesh ep=4``. Counts and control flow
only: nothing this prints is a device number, and its result line says
platform cpu. (``cpu_cell_laguna.py``, whose loose limits this uses, does the
same for the ``train_laguna`` kind on one device.)

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse/cpu_cell_mellum.py \
        [--float32] [--controls fp8] [--fault <faults_mellum.FAULTS>]

``--float32`` runs the trainer in float32 under ``FLOAT32_LIMITS`` (what
``benchmarks/tests/test_train_mellum.py`` judges a sound run, the control and
the planted faults by, each in a process of its own: this one asks for four
virtual devices before JAX starts, which a test session of one-device kinds
cannot).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.rehearse.cpu_cell_laguna import LOOSE  # noqa: E402

WORKLOAD = "train-mellum2-ep4-seq8192"
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=96,
            num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, sliding_window=16, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            moe_piece_multiple=8)
LOOSE = dict(LOOSE, exchange_slots_gap=0)
FLOAT32_LIMITS = {"loss_gap_first": 1e-4, "loss_gap_later": 3e-4,
                  "grad_global_norm_gap": 1e-3, "grad_norm_gap_worst_leaf": 5e-3,
                  "head_grad_rel_diff": 1e-3, "all_grad_rel_diff": 1e-3,
                  "delta_norm_gap_worst_leaf": 2e-2, "feed_faults": 0,
                  "exchange_slots_gap": 0}


def tiny_context(workload: str, seed: int, seconds: float, tmp: str,
                 float32: bool = False) -> dict:
    from benchmarks import run as bench_run

    ctx = bench_run.context(ROOT, workload)
    config = dict(ctx["config"], **TINY)
    config_file = os.path.join(tmp, "tiny_config.json")
    with open(config_file, "w") as f:
        json.dump(config, f)
    mix = dict(ctx["mix"], seq_len=64, sequences=128, trace_updates=2)
    mix["documents"] = dict(mix["documents"], median_tokens=20, min_tokens=4,
                            max_tokens=64)
    mix["check"] = dict(mix["check"], limits=LOOSE)
    if float32:
        mix["trainer_args"] = ["--dtype", "float32", "--remat", "full",
                               "--mesh", "ep=4"]
        mix["check"] = dict(mix["check"], limits=FLOAT32_LIMITS)
    ctx.update(config=config, config_file=config_file, mix=mix, seed=seed,
               seconds=seconds, trace=False, started=time.perf_counter())
    return ctx


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", default=WORKLOAD)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 11)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--float32", action="store_true")
    parser.add_argument("--controls", nargs="*", default=None)
    parser.add_argument("--fault", default=None)
    args = parser.parse_args(argv)
    with tempfile.TemporaryDirectory() as tmp:
        ctx = tiny_context(args.workload, args.seed, args.seconds, tmp,
                           args.float32)
        if args.controls is not None:
            ctx["controls"] = list(args.controls)
        from benchmarks import run as bench_run
        kind = bench_run.load_module(ctx["kind_file"], "kind_rehearsal")
        if args.fault:
            # the fault lives in the PROGRAM alone: the reference runs after
            # the trainer has returned, with the program's modules as they were
            from benchmarks.rehearse import faults_mellum
            result = faults_mellum.read(ctx, kind, args.fault)
        else:
            result = kind.measure(ctx)
    result.pop("raw", None)
    print(json.dumps(result))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
