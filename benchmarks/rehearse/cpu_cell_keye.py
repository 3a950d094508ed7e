"""Rehearsal 1 for the ``train_keye`` kind: the cell's whole control flow on
the CPU at a tiny size (two layers at width 64: 4 / 2 heads of 16 over the 16
keys a 4 x 8 indexer chooses, 4 of 8 experts held top-3, rows of 64 tokens,
micro-batches of 1 row). Counts and control flow only: nothing this prints is
a device number, and its result line says platform cpu.
(``cpu_cell_laguna.py``, whose loose limits this uses, does the same for the
``train_laguna`` kind.)

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse/cpu_cell_keye.py
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
sys.path.insert(0, ROOT)

from benchmarks.rehearse.cpu_cell_laguna import LOOSE  # noqa: E402

TINY = dict(vocab_size=512, hidden_size=64, head_dim=16, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, num_experts=4,
            ep_size=2, ep_rank=0, num_experts_per_tok=3,
            moe_intermediate_size=32, moe_piece_multiple=8,
            sa_config=dict(indexer_num_heads=4, indexer_head_dim=8,
                           indexer_num_kv_heads=1, topk=16, q_chunk_size=512,
                           kv_chunk_size=512))
# (the two exact numbers read 0 at any precision: the cell's own limits)
LOOSE = dict(LOOSE, index_grad_rel_diff=0.5, objective_leak_rel=1e-6,
             chosen_pairs_gap=1e-6)


def tiny_context(workload: str, seed: int, seconds: float, tmp: str) -> dict:
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
    parser.add_argument("--workload", default="train-keye-vl2-30b-seq16384")
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
