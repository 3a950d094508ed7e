"""Whole train steps compiled for the chip without the chip: the phase-2
step against a 16 GB chip, the qwen3_next and the KeyeVL2 cells' steps at
their published widths (150 s of compiling each, the longest tests of the
suite), and the dp4 step's
dropout masks after the SPMD partitioner. Apart from
``tests/test_chip_compile.py`` (the kernels) so that xdist's ``--dist
loadfile`` runs the two on two workers; ``described_chip.py`` says what lets
two processes compile at once. Nothing runs."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from described_chip import (HBM_BYTES, TRAIN_SHAPES,  # noqa: F401
                            _assert_kernel, _compile_for_the_chip, chip, topo)


def test_qwen3_next_step_compiles_at_the_published_widths(topo, monkeypatch):
    """The qwen3_next cell's whole train step at its real size (626 M
    parameters, 4 micro-batches of 2 rows of 8192 tokens, ``--remat full``,
    AdamW) through the rehearsal's own ``compile_step``: the TPU's compiler
    takes it within a 16 GB chip WITHOUT rematerializing on its own account
    (a ``.remat`` fusion is the compiler making room: the rule's rows one at
    a time, the weights read in column blocks and the rematerialized gated
    norm are what keep it from having to), and the step holds the three
    ``flash_gated_*`` kernels and the grouped products. A compile that passes
    here is not a fit (PERF.md 4): the chip's own compiler has the last word."""
    import benchmarks.run as bench_run
    from benchmarks.rehearse.compile_real_laguna import compile_step
    from bert_pytorch_tpu.ops import moe
    from bert_pytorch_tpu.ops.pallas import attention, common

    # (the rehearsal sets these for good, for its own process: here they are
    # put back when the test ends, or every later test of this worker would
    # compile its kernels for a CPU)
    for module in (common, attention, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    ctx = bench_run.context(bench_run.ROOT, "train-qwen3-next-80b-seq8192")
    step = compile_step(ctx, topo)
    assert step["parameters"] == 625_994_816
    assert step["remat_fusions"] == 0
    assert step["argument_bytes"] == pytest.approx(12 * 625_994_816, rel=1e-3)
    assert step["tpu_custom_calls"] >= 3 + 3 * 4  # the flash kernels, gmm x 4


def test_keye_vl_step_compiles_at_the_published_widths(topo, monkeypatch):
    """The KeyeVL2 cell's whole train step at its real size (610 M
    parameters, 2 micro-batches of 1 row of 16,384 tokens, ``--remat full``,
    AdamW) through the rehearsal's own ``compile_step``: the TPU's compiler
    takes it within a 16 GB chip without rematerializing on its own account
    (with the core's output kept in all nine layers it makes room by sixteen
    ``.remat`` fusions, here as on the chip: ``keye_vl.CORE_KEPT_LAYERS``),
    and the step holds the sparse attention's kernels (the choice, the
    objective's kernel and the core's forward once a layer and micro-batch:
    what they make is kept across remat; the core's forward twice in the two
    layers that do not keep its output) and the grouped products."""
    import benchmarks.run as bench_run
    from benchmarks.rehearse.compile_real_laguna import compile_step
    from bert_pytorch_tpu.ops import moe
    from bert_pytorch_tpu.ops.pallas import attention, common

    for module in (common, attention, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    ctx = bench_run.context(bench_run.ROOT, "train-keye-vl2-30b-seq16384")
    step = compile_step(ctx, topo)
    assert step["parameters"] == 610_476_288
    assert step["remat_fusions"] == 0
    assert step["argument_bytes"] == pytest.approx(12 * 610_476_288, rel=1e-3)
    # a layer: the choice, the core's forward and its two backward kernels,
    # the objective's once (5), the rotary turns (6), the grouped products
    # (14); the first two layers run the core's forward again in their
    # recompute
    assert step["tpu_custom_calls"] == 9 * (5 + 6 + 14) + 2


# -- the whole phase-2 train step ---------------------------------------------

def _compile_train_step(model, tx, devices, accum, rows, seq, max_pred,
                        schedule=None):
    """``pretrain.make_train_step`` as run_pretraining.py builds it, under a
    ``dp`` mesh of the described ``devices``, lowered for ``accum``
    micro-batches of ``rows`` sequences and compiled."""
    from bert_pytorch_tpu import pretrain
    from bert_pytorch_tpu.parallel import (MeshConfig, create_mesh,
                                           logical_axis_rules)

    mesh = create_mesh(MeshConfig(data=-1), devices=list(devices))
    sample = (jnp.zeros((1, seq), jnp.int32),) * 3
    batch_spec = {"input_ids": 3, "segment_ids": 3, "input_mask": 3,
                  "masked_lm_labels": 3, "next_sentence_labels": 2}
    with mesh:
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
            (accum, rows) + (seq,) * (ndim - 2), np.int32)
            for key, ndim in batch_spec.items()}
        return step.lower(state, batch).compile()


def test_phase2_train_step_fits_one_chip(chip):
    """``pretrain.make_train_step`` as run_pretraining.py builds it for the
    phase-2 recipe — BERT-large, seq 512, 80 predictions, local batch 28,
    ``remat='dots'``, the fused kernel — compiled for one described chip:
    the kernel is in the program, and arguments plus temporaries stay under
    the chip's 16 GB. ``memory_analysis`` counts this one program, not what
    else the process keeps on the device."""
    from bert_pytorch_tpu import optim
    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config = BertConfig.from_json_file(
        os.path.join(repo, "configs", "bert_large_uncased_config.json"))
    config.vocab_size += -config.vocab_size % 8
    model = BertForPreTraining(config, dtype=jnp.bfloat16, remat="dots",
                               attention_backend="pallas")
    schedule = optim.warmup_poly_schedule(4e-3, 0.128, 1563)
    tx = optim.lamb(schedule, weight_decay_mask=optim.no_decay_mask)
    compiled = _compile_train_step(
        model, tx, [chip], accum=1, rows=TRAIN_SHAPES[512], seq=512,
        max_pred=80, schedule=schedule)
    _assert_kernel(compiled, "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < HBM_BYTES, (
        f"phase-2 step needs {total / 2**30:.2f} GiB "
        f"({mem.argument_size_in_bytes / 2**30:.2f} arguments + "
        f"{mem.temp_size_in_bytes / 2**30:.2f} temporaries) of a "
        f"{HBM_BYTES / 2**30:.0f} GiB chip")


# -- the data-parallel step draws each chip's dropout masks on that chip ------

def test_dp4_step_draws_local_masks(topo):
    """A small ``make_train_step`` with ``rbg`` dropout under ``dp=4`` on the
    described 2x2, read after the TPU compiler's SPMD partitioner: every
    ``rng-bit-generator`` makes ONE chip's share of a mask (ops/dropout.py).
    The partitioner does not split that instruction, so a mask asked for at
    the global batch size would show here at four times the size."""
    from bert_pytorch_tpu import optim
    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining

    seq, rows, heads, hidden = 128, 8, 2, 128  # rows a chip
    config = BertConfig(
        vocab_size=512, hidden_size=hidden, num_hidden_layers=2,
        num_attention_heads=heads, intermediate_size=256,
        max_position_embeddings=seq)
    model = BertForPreTraining(config, dtype=jnp.bfloat16, remat="dots",
                               attention_backend="xla")
    text = _compile_train_step(
        model, optim.lamb(1e-3), topo.devices, accum=2, rows=rows * 4,
        seq=seq, max_pred=20).as_text()
    drawn = {tuple(int(d) for d in dims.split(","))
             for dims in re.findall(
                 r"u32\[([0-9,]+)\]\S* rng-bit-generator\(", text)}
    assert drawn == {(rows, heads, seq, seq), (rows, seq, hidden)}, drawn
