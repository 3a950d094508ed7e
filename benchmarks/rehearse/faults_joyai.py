"""One planted fault read through the cell's own kind at THE CELL'S sizes and
limits, on the chip: the flow of ``benchmarks/margins.py`` (``--seconds`` 0.2
closes the window after one update, no control), the fault planted round the
trainer alone, so the reference runs with the program's modules as they are.
``plant`` is what ``benchmarks/tests/test_train_joyai.py`` plants at a small
size. One process a fault (a chip belongs to one process at a time):

    python3 benchmarks/rehearse/faults_joyai.py <fault> <seed>

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
CELL = "train-joyai-flash-seq8192"
FAULTS = ("mtp_left_out", "mtp_input_shifted_back", "latent_norms_left_out",
          "shared_key_first_head_only")


def plant(mp, fault):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import joyai

    if fault == "mtp_left_out":  # (the module runs; its term is not added)
        mp.setattr(joyai.JoyAIForCausalLM, "prediction_streams",
                   lambda self: {joyai.MTP: (2, 0.0)})
    elif fault == "mtp_input_shifted_back":
        def further_streams(self, x, embedded, shared):
            hidden, counters = self.mtp(
                x, jnp.roll(embedded, 1, axis=1), *shared)
            return {joyai.MTP: hidden}, counters

        mp.setattr(joyai.JoyAIForCausalLM, "further_streams", further_streams)
    elif fault == "latent_norms_left_out":
        real = joyai.RMSNorm

        class Skipped(nn.Module):
            """The two latent norms' parameter, and no norm."""
            epsilon: float = 1e-5
            dtype: object = jnp.float32

            @nn.compact
            def __call__(self, x):
                self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
                return x.astype(self.dtype)

        mp.setattr(joyai, "RMSNorm", lambda epsilon, dtype, name=None: (
            Skipped if name in ("q_a_norm", "kv_a_norm") else real)(
                epsilon, dtype, name=name))
    elif fault == "shared_key_first_head_only":
        real_share = joyai.share_key
        mp.setattr(joyai, "share_key", lambda k_r, heads: jnp.concatenate(
            [k_r, jax.lax.stop_gradient(real_share(k_r, heads - 1))], axis=2))
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
