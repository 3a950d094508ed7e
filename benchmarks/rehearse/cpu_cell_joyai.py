"""Rehearsal 1 for the ``train_joyai`` kind: the cell's whole control flow on
the CPU at a tiny size (a dense and an expert layer at width 64 and the
module: 4 heads of 16 + 8 turned over values of 16, latents of 48 and 32, 4 of
8 experts held top-3 with the shared expert, rows of 64 tokens, micro-batches
of 1 row). Counts and control flow only: nothing this prints is a device
number, and its result line says platform cpu. (``cpu_cell_laguna.py``, whose
loose limits this uses, does the same for the ``train_laguna`` kind.)

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse/cpu_cell_joyai.py
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

WORKLOAD = "train-joyai-flash-seq8192"
TINY = dict(vocab_size=512, hidden_size=64, intermediate_size=96,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16, head_dim=8,
            n_routed_experts=4, ep_size=2, ep_rank=0, num_experts_per_tok=3,
            moe_intermediate_size=32, moe_piece_multiple=8)
LOOSE = dict(LOOSE, mtp_loss_gap=0.02, mtp_grad_rel_diff=0.5,
             latent_grad_rel_diff=0.5)


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
    parser.add_argument("--workload", default=WORKLOAD)
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
