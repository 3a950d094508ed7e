"""Rehearsal 3 for the ``train_phi4flash`` kind: the cell's whole train step, and
the reference's gradient program, compiled at the real size by the TPU's own
compiler for a described v5e chip (the selective scan's and the differential
flash kernels through Mosaic). Nothing runs: this shows what the chip's
compiler refuses and what a program needs of the chip's memory, never a
time. Not a chip run. (``compile_real_lm.py`` and ``compile_real_laguna.py``
do the same for their kinds.)

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse/compile_real_laguna.py \
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

from benchmarks.rehearse.compile_real_lm import _report  # noqa: E402


def compile_step(ctx, topo, remat=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bert_pytorch_tpu import optim, pretrain
    from bert_pytorch_tpu.config import load_model_config
    from bert_pytorch_tpu.models import build_pretraining_model
    from bert_pytorch_tpu.ops.pallas import attention, common, selective_scan
    from bert_pytorch_tpu.parallel import (MeshConfig, create_mesh,
                                           logical_axis_rules)

    for module in (common, attention, selective_scan):  # compiled, as on the chip
        module.interpret_mode = lambda: False
    mix, chips = ctx["mix"], int(ctx["cell"]["chips"])
    args = list(mix.get("trainer_args", []))
    flag = lambda name, default: (args[args.index(name) + 1]
                                  if name in args else default)
    config = load_model_config(ctx["config_file"])
    model = build_pretraining_model(
        config, jnp.bfloat16, remat=remat or flag("--remat", "none"),
        # 'auto' on a TPU at this length is the kernel
        attention_backend="pallas")
    recipe = mix["recipe"]
    schedule = optim.make_schedule(
        "constant", recipe["learning_rate"], recipe["warmup_proportion"],
        recipe["max_steps"])
    tx = optim.adamw(schedule, b1=recipe["b1"], b2=recipe["b2"],
                     eps=recipe["eps"], weight_decay=recipe["weight_decay"],
                     weight_decay_mask=optim.no_decay_mask,
                     max_grad_norm=recipe["max_grad_norm"])
    mesh = create_mesh(MeshConfig(data=-1), devices=list(topo.devices[:chips]))
    sample = (jnp.zeros((1, config.init_sample_length), jnp.int32),)
    micro = mix["global_batch_size_per_chip"] // mix["local_batch_size"]
    with mesh, jax.default_prng_impl("rbg"):
        shardings = pretrain.state_shardings(
            mesh, model, logical_axis_rules("dp"), sample)
        b_shardings = pretrain.batch_shardings(mesh, {"input_ids": 3})
        state = jax.eval_shape(
            pretrain.make_init_fn(model, tx, sample, shardings),
            jax.random.PRNGKey(0))
        step = pretrain.make_train_step(
            model, tx, schedule=schedule, next_sentence=False,
            shardings=shardings, batch_shardings_=b_shardings, mesh=mesh)
        batch = {"input_ids": jax.ShapeDtypeStruct(
            (micro, mix["local_batch_size"] * chips, mix["seq_len"]), np.int32)}
        compiled = step.lower(state, batch).compile()
    params = sum(int(np.prod(leaf.shape))
                 for leaf in jax.tree_util.tree_leaves(state.params))
    text = compiled.as_text()
    return dict(_report(compiled), parameters=params,
                diff_kernels=text.count("flash_diff_"),
                scan_kernels=text.count("selective_scan_"),
                remat_fusions=text.count(".remat"))


def compile_reference(ctx, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.reference import phi4flash_f32 as ref

    mix = ctx["mix"]
    c = ref.sizes(ctx["config"])
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda k: ref.seeded_params(k, c),
                            jax.random.key(0, impl="threefry2x32"))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), params)
    ids = jax.ShapeDtypeStruct((mix["local_batch_size"], mix["seq_len"]),
                               jnp.int32, sharding=one)
    out = {}
    for precision in ref.PRECISIONS:
        fn = jax.jit(jax.value_and_grad(
            lambda p, i: ref.next_token_loss(p, c, i, precision)))
        out[precision] = _report(fn.lower(params, ids).compile())
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", default="train-phi4-mini-flash-seq8192")
    parser.add_argument("--remat", default=None)
    parser.add_argument("--no-reference", action="store_true")
    args = parser.parse_args(argv)
    from jax.experimental import topologies

    from benchmarks import run as bench_run

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    ctx = bench_run.context(ROOT, args.workload)
    print(args.workload, "step", json.dumps(compile_step(ctx, topo, args.remat)),
          flush=True)
    if not args.no_reference:
        print(args.workload, "reference",
              json.dumps(compile_reference(ctx, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
