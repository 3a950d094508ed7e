"""Rehearsal 3 for the ``train_joyai`` kind: the cell's whole train step and
the reference's gradient program, compiled at the real size by the TPU's own
compiler for a described v5e chip. Nothing runs: this shows what the chip's
compiler refuses (the flash kernels at a head of 128 + 64, the step's memory)
and what a program needs of the chip's memory, never a time. Not a chip run,
and a compile that passes here is not a fit (PERF.md 4: the chip's own
compiler has refused a step this one passed). The step is
``compile_real_laguna.compile_step`` (it builds whatever family the cell's
configuration names); the reference's program is this family's.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse/compile_real_joyai.py
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.rehearse.compile_real_laguna import compile_step  # noqa: E402
from benchmarks.rehearse.compile_real_lm import _report  # noqa: E402

WORKLOAD = "train-joyai-flash-seq8192"


def compile_reference(ctx, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.reference import joyai_f32 as ref

    mix = ctx["mix"]
    c = ref.sizes(ctx["config"])
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda k: ref.seeded_params(k, c),
                            jax.random.key(0, impl="threefry2x32"))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), params)
    # (this family's ``follow`` passes a micro-batch's rows one at a time)
    ids = jax.ShapeDtypeStruct((1, mix["seq_len"]), jnp.int32, sharding=one)
    out = {}
    for precision in ref.PRECISIONS:
        fn = jax.jit(jax.value_and_grad(
            lambda p, i: ref.objective(p, c, i, precision), has_aux=True))
        out[precision] = _report(fn.lower(params, ids).compile())
    return out


def main() -> int:
    from jax.experimental import topologies

    from benchmarks import run as bench_run

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    ctx = bench_run.context(ROOT, WORKLOAD)
    step = compile_step(ctx, topo)
    step.pop("window_kernels")  # (the laguna cell's count; none here)
    print(WORKLOAD, "step", json.dumps(step), flush=True)
    print(WORKLOAD, "reference", json.dumps(compile_reference(ctx, topo)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
