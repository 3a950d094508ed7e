"""Rehearsal 1 and 2 of the on-chip-measurement guide: a cell's whole control
flow on the CPU at a tiny size (``configs/bert_small_config.json`` widths, a
few rows), a four-chip cell on four virtual devices. Counts and control flow
only: nothing this prints is a device number, and its result line says
platform cpu.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse/cpu_cell.py --workload train-large-phase1
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


def tiny_context(workload: str, seed: int, seconds: float, tmp: str,
                 layers: int = 2) -> dict:
    sys.path.insert(0, ROOT)
    from benchmarks import run as bench_run

    ctx = bench_run.context(ROOT, workload)
    with open(os.path.join(ROOT, "configs", "bert_small_config.json")) as f:
        small = json.load(f)
    config = dict(ctx["config"])
    for key in ("hidden_size", "num_attention_heads", "intermediate_size"):
        config[key] = small[key]
    config["num_hidden_layers"] = layers
    config["vocab_size"] = 2048
    config_file = os.path.join(tmp, "tiny_config.json")
    with open(config_file, "w") as f:
        json.dump(config, f)
    mix = dict(ctx["mix"], seq_len=32, max_predictions_per_seq=5,
               local_batch_size=2, global_batch_size_per_chip=4,
               sequences=512, trace_updates=2)
    mix["lengths"] = dict(mix["lengths"], min_tokens=6)
    mix["check"] = dict(mix["check"], block_rows=2)
    ctx.update(config=config, config_file=config_file, mix=mix, seed=seed,
               seconds=seconds, trace=False, started=time.perf_counter())
    return ctx


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=2 ** 31 + 11)
    parser.add_argument("--seconds", type=float, default=1.0)
    parser.add_argument("--chips", type=int, default=None,
                        help="rehearse the cell under --mesh dp=N on N virtual "
                             "devices (a four-chip cell that is not a cell yet)")
    args = parser.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    with tempfile.TemporaryDirectory() as tmp:
        ctx = tiny_context(args.workload, args.seed, args.seconds, tmp)
        if args.chips:
            ctx["cell"] = dict(ctx["cell"], chips=args.chips)
        if ctx["cell"]["chips"] > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={ctx['cell']['chips']}")
        from benchmarks import run as bench_run
        kind = bench_run.load_module(ctx["kind_file"], "kind_rehearsal")
        result = kind.measure(ctx)
    print(json.dumps(result))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
