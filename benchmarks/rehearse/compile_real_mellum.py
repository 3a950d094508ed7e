"""Rehearsal 3 for the ``train_mellum`` kind: the cell's whole train step under
its own mesh (``--mesh ep=4``: the experts and the vocabulary's rows divided
over four chips, the slots' exchange inside) and the reference's gradient
program with its arrays placed over the four chips, compiled at the real size
by the TPU's own compiler for a described v5e 2x2. Nothing runs: this shows
what the chip's compiler refuses (a kernel, a collective inside a loop, the
memory of one chip) and which collectives it put in, never a time. Not a chip
run, and a compile that passes here is not a fit (PERF.md 4).

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse/compile_real_mellum.py \
        [--no-reference]
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

WORKLOAD = "train-mellum2-ep4-seq8192"
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter")


def compile_step(ctx, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bert_pytorch_tpu import optim, pretrain
    from bert_pytorch_tpu.config import load_model_config
    from bert_pytorch_tpu.models import build_pretraining_model
    from bert_pytorch_tpu.ops import moe
    from bert_pytorch_tpu.ops.pallas import attention, common
    from bert_pytorch_tpu.parallel import (MeshSpec, create_mesh,
                                           logical_axis_rules)

    for module in (common, attention, moe):  # compiled, as on the chip
        module.interpret_mode = lambda: False
    mix, chips = ctx["mix"], int(ctx["cell"]["chips"])
    args = list(mix["trainer_args"])
    # (the last one counts, as argparse reads them)
    flag = lambda name: args[max(i for i, a in enumerate(args) if a == name) + 1]
    config = load_model_config(ctx["config_file"])
    model = build_pretraining_model(
        config, jnp.bfloat16, remat=flag("--remat"),
        attention_backend="pallas")  # 'auto' on a TPU at this length
    recipe = mix["recipe"]
    schedule = optim.make_schedule(
        "constant", recipe["learning_rate"], recipe["warmup_proportion"],
        recipe["max_steps"])
    tx = optim.adamw(schedule, b1=recipe["b1"], b2=recipe["b2"],
                     eps=recipe["eps"], weight_decay=recipe["weight_decay"],
                     weight_decay_mask=optim.no_decay_mask,
                     max_grad_norm=recipe["max_grad_norm"])
    spec = MeshSpec.parse(flag("--mesh"))
    mesh = create_mesh(spec.mesh_config(), devices=list(topo.devices[:chips]))
    sample = (jnp.zeros((1, config.init_sample_length), jnp.int32),)
    micro = mix["global_batch_size_per_chip"] // mix["local_batch_size"]
    with mesh, jax.default_prng_impl("rbg"):
        shardings = pretrain.state_shardings(
            mesh, model, logical_axis_rules(spec), sample)
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
                window_kernels=text.count("flash_window_"),
                remat_fusions=text.count(".remat"),
                collectives={name: text.count(" " + name + "(")
                             + text.count(" " + name + "-start(")
                             for name in COLLECTIVES})


def compile_reference(ctx, topo) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import mellum_f32 as ref

    mix, chips = ctx["mix"], int(ctx["cell"]["chips"])
    c = ref.sizes(ctx["config"])
    devices = list(topo.devices[:chips])
    params = jax.eval_shape(lambda k: ref.seeded_params(k, c),
                            jax.random.key(0, impl="threefry2x32"))
    params = {name: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=ref.placement(name, a.shape, devices))
        for name, a in params.items()}
    ids = jax.ShapeDtypeStruct(
        (mix["local_batch_size"] * chips, mix["seq_len"]), jnp.int32,
        sharding=ref.rows_placement(devices))
    where = {name: a.sharding for name, a in params.items()}
    out = {}
    for precision in ref.PRECISIONS:
        fn = jax.jit(jax.value_and_grad(
            lambda p, i: ref.next_token_loss(p, c, i, precision), has_aux=True),
            out_shardings=((None, None), where))
        out[precision] = _report(fn.lower(params, ids).compile())
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--no-reference", action="store_true")
    args = parser.parse_args(argv)
    from jax.experimental import topologies

    from benchmarks import run as bench_run

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    ctx = bench_run.context(ROOT, WORKLOAD)
    print(WORKLOAD, "step", json.dumps(compile_step(ctx, topo)), flush=True)
    if not args.no_reference:
        print(WORKLOAD, "reference",
              json.dumps(compile_reference(ctx, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
