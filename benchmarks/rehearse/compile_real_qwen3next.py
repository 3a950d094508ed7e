"""Rehearsal 3 for the ``train_qwen3next`` kind: the cell's whole train step, and
the reference's gradient program, compiled at the real size by the TPU's own
compiler for a described v5e chip. Nothing runs: this shows what the chip's
compiler refuses and what a program needs of the chip's memory, never a
time. Not a chip run, and a compile that passes here is not a fit (PERF.md 4:
the chip's own compiler has refused a step this one passed). The step is
``compile_real_laguna.compile_step`` (it builds whatever family the cell's
configuration names); the reference's program is this family's.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse/compile_real_qwen3next.py \
        [--remat full] [--no-reference]
"""

from __future__ import annotations

import argparse
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


def compile_reference(ctx, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.reference import qwen3next_f32 as ref

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
            lambda p, i: ref.next_token_loss(p, c, i, precision), has_aux=True))
        out[precision] = _report(fn.lower(params, ids).compile())
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", default="train-qwen3-next-80b-seq8192")
    parser.add_argument("--remat", default=None)
    parser.add_argument("--no-reference", action="store_true")
    args = parser.parse_args(argv)
    from jax.experimental import topologies

    from benchmarks import run as bench_run

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    ctx = bench_run.context(ROOT, args.workload)
    step = compile_step(ctx, topo, args.remat)
    step.pop("window_kernels")  # (the laguna cell's count; none here)
    print(args.workload, "step", json.dumps(step), flush=True)
    if not args.no_reference:
        print(args.workload, "reference",
              json.dumps(compile_reference(ctx, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
