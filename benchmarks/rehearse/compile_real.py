"""Rehearsal 3 of the on-chip-measurement guide: every cell's step, and the
reference's gradient program, compiled at the real size by the TPU's own
compiler for a described v5e (one chip, or the 2x2 mesh for a four-chip
cell). Nothing runs: this shows what the chip's compiler refuses and what a
program needs of the chip's memory, never a time. Not a chip run.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse/compile_real.py [--workload NAME]
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


def compile_cell(ctx, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bert_pytorch_tpu import optim, pretrain
    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.ops.pallas import attention, common, layernorm
    from bert_pytorch_tpu.parallel import (MeshConfig, create_mesh,
                                           logical_axis_rules)

    for module in (common, attention, layernorm):  # compiled, as on the chip
        module.interpret_mode = lambda: False
    mix, chips = ctx["mix"], int(ctx["cell"]["chips"])
    with open(os.path.join(ROOT, mix["recipe_file"])) as f:
        recipe_file = json.load(f)
    config = BertConfig.from_json_file(ctx["config_file"])
    config.vocab_size += -config.vocab_size % 8
    seq, max_pred = mix["seq_len"], mix["max_predictions_per_seq"]
    model = BertForPreTraining(
        config, dtype=jnp.bfloat16, remat=recipe_file.get("remat", "none"),
        attention_backend=recipe_file.get("attention_backend", "auto"))
    recipe = mix["recipe"]
    schedule = optim.warmup_poly_schedule(
        recipe["learning_rate"], recipe["warmup_proportion"], recipe["max_steps"])
    tx = optim.lamb(schedule, weight_decay_mask=optim.no_decay_mask)
    mesh = create_mesh(MeshConfig(data=-1), devices=list(topo.devices[:chips]))
    sample = (jnp.zeros((1, seq), jnp.int32),) * 3
    batch_spec = {"input_ids": 3, "segment_ids": 3, "input_mask": 3,
                  "masked_lm_labels": 3, "next_sentence_labels": 2}
    micro = mix["global_batch_size_per_chip"] // mix["local_batch_size"]
    with mesh, jax.default_prng_impl("rbg"):
        shardings = pretrain.state_shardings(
            mesh, model, logical_axis_rules("dp"), sample)
        b_shardings = pretrain.batch_shardings(mesh, batch_spec)
        state = jax.eval_shape(
            pretrain.make_init_fn(model, tx, sample, shardings),
            jax.random.PRNGKey(0))
        step = pretrain.make_train_step(
            model, tx, schedule=schedule, next_sentence=True,
            shardings=shardings, batch_shardings_=b_shardings,
            max_pred_per_seq=max_pred, mesh=mesh)
        batch = {key: jax.ShapeDtypeStruct(
            (micro, mix["local_batch_size"] * chips) + (seq,) * (ndim - 2),
            np.int32) for key, ndim in batch_spec.items()}
        compiled = step.lower(state, batch).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    return {
        "argument_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduce_ops": text.count(" all-reduce(") + text.count(" all-reduce-start("),
    }


def compile_reference(ctx, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.reference import bert_f32

    mix = ctx["mix"]
    c = bert_f32.sizes(ctx["config"])
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.eval_shape(lambda k: bert_f32.seeded_params(k, c),
                            jax.random.key(0, impl="threefry2x32"))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), params)
    rows, seq = mix["check"]["block_rows"], mix["seq_len"]
    ids = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=one)
    block = {k: ids for k in ("input_ids", "segment_ids", "input_mask",
                              "masked_lm_labels")}
    block["next_sentence_labels"] = jax.ShapeDtypeStruct(
        (rows,), jnp.int32, sharding=one)
    out = {}
    for precision in bert_f32.PRECISIONS:
        fn = bert_f32.make_block_grad(c, precision, mix["max_predictions_per_seq"])
        mem = fn.lower(params, block, 1.0, 1.0).compile().memory_analysis()
        out[precision] = {"argument_bytes": mem.argument_size_in_bytes,
                          "temp_bytes": mem.temp_size_in_bytes}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", default=None)
    args = parser.parse_args(argv)
    from jax.experimental import topologies

    from benchmarks import run as bench_run

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    for name in names:
        if args.workload and name != args.workload:
            continue
        ctx = bench_run.context(ROOT, name)
        print(name, "step", json.dumps(compile_cell(ctx, topo)), flush=True)
        print(name, "reference", json.dumps(compile_reference(ctx, topo)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
