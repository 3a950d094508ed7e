"""SWAG multiple-choice finetuning runner.

Beyond-reference capability: the reference defines ``BertForMultipleChoice``
(modeling.py:1131-1197) but nothing in that repo can train it. This runner
finetunes the 4-way choice head on SWAG-format CSVs in the original SWAG
BERT recipe (lr 2e-5, 3 epochs, warmup 0.1; the original recipe's max seq 80
is raised to a TPU-friendly default of 128) and reports choice accuracy.

Same conventions as run_glue.py: model config JSON supplies vocab/tokenizer,
``--init_checkpoint`` accepts native or foreign (torch/TF) archives, one
JSON summary line at the end.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from bert_pytorch_tpu import optim, telemetry
from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.data import swag
from bert_pytorch_tpu.data.tokenization import (
    get_bpe_tokenizer,
    get_wordpiece_tokenizer,
)
from bert_pytorch_tpu.models import BertForMultipleChoice
from bert_pytorch_tpu.ops.grad_utils import clip_by_global_norm
from bert_pytorch_tpu.utils import checkpoint as ckpt
from bert_pytorch_tpu.utils import logging as logger
from bert_pytorch_tpu.utils import preemption
from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache
from bert_pytorch_tpu.data import DevicePrefetcher
from run_glue import batches  # padded fixed-shape batches + valid mask


def parse_arguments(argv=None):
    parser = argparse.ArgumentParser(description="TPU BERT SWAG finetuning")
    parser.add_argument("--train_file", type=str, required=True)
    parser.add_argument("--val_file", type=str, default=None)
    parser.add_argument("--model_config_file", type=str, required=True)
    parser.add_argument("--init_checkpoint", type=str, default=None)
    parser.add_argument("--output_dir", type=str, default=None)
    parser.add_argument("--vocab_file", type=str, default=None)
    parser.add_argument("--uppercase", action="store_true")
    parser.add_argument("--tokenizer", type=str, default=None,
                        choices=["wordpiece", "bpe"])
    parser.add_argument("--epochs", type=int, default=3)
    parser.add_argument("--lr", type=float, default=2e-5)
    parser.add_argument("--warmup_proportion", type=float, default=0.1)
    parser.add_argument("--clip_grad", type=float, default=1.0)
    parser.add_argument("--batch_size", type=int, default=16)
    parser.add_argument("--max_seq_len", type=int, default=128)
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--compile_cache_dir", type=str, default="",
                        help="persistent XLA compilation cache directory; "
                             "default <checkout>/.jax_cache, and "
                             "JAX_COMPILATION_CACHE_DIR wins when set")
    parser.add_argument("--dtype", type=str, default="bfloat16",
                        choices=["bfloat16", "float32"])
    parser.add_argument("--save_steps", type=int, default=0,
                        help="periodic checkpoint cadence (optimizer "
                             "steps): async writes (device snapshot + "
                             "background write); final/emergency stays "
                             "synchronous. 0 disables")
    # device prefetch (data/device_prefetch.py; shared runner flag)
    from bert_pytorch_tpu.data import device_prefetch as dp_cli
    dp_cli.add_cli_args(parser)
    # telemetry (docs/telemetry.md)
    # telemetry: canonical flag set shared by every runner. Default
    # sync cadence stays 1: these are small models where a per-step
    # sync is cheap and step-exact sentinels are worth it — but since
    # PR 7 the loop itself no longer fetches the loss per step (it
    # accumulates on device; jaxlint HS101), so a user-set
    # --telemetry_sync_every N genuinely syncs only every Nth step
    # (telemetry/cli.py; docs/telemetry.md)
    telemetry.add_cli_args(parser, sync_every_default=1)
    args = parser.parse_args(argv)

    with open(args.model_config_file) as f:
        configs = json.load(f)
    if args.vocab_file is None:
        args.vocab_file = configs.get("vocab_file")
        if args.vocab_file is None:
            raise ValueError("vocab_file must be in model config or CLI")
    if args.tokenizer is None:
        args.tokenizer = configs.get("tokenizer", "wordpiece")
    return args


def main(args):
    enable_compile_cache(args.compile_cache_dir)
    telemetry_jsonl = telemetry.default_jsonl_path(
        args, args.output_dir, "swag")
    telemetry_sink = (logger.JSONLHandler(telemetry_jsonl, overwrite=False)
                      if telemetry_jsonl else None)
    logger.init(handlers=[logger.StreamHandler()]
                + ([telemetry_sink] if telemetry_sink else []))
    if args.tokenizer == "wordpiece":
        tokenizer = get_wordpiece_tokenizer(args.vocab_file,
                                            uppercase=args.uppercase)
    else:
        tokenizer = get_bpe_tokenizer(args.vocab_file, uppercase=args.uppercase)

    arrays = {"train": swag.convert_examples_to_arrays(
        swag.read_swag_examples(args.train_file), tokenizer, args.max_seq_len)}
    if args.val_file:
        arrays["val"] = swag.convert_examples_to_arrays(
            swag.read_swag_examples(args.val_file), tokenizer,
            args.max_seq_len)
    logger.info("examples: " + " ".join(
        f"{k}={len(v['labels'])}" for k, v in arrays.items()))

    config = BertConfig.from_json_file(args.model_config_file)
    if config.vocab_size % 8 != 0:
        config.vocab_size += 8 - (config.vocab_size % 8)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    model = BertForMultipleChoice(config, num_choices=swag.NUM_CHOICES,
                                  dtype=dtype)

    sample = (jnp.zeros((1, swag.NUM_CHOICES, args.max_seq_len), jnp.int32),) * 3
    import flax.linen as nn

    params = nn.unbox(
        model.init(jax.random.PRNGKey(args.seed), *sample))["params"]
    if args.init_checkpoint:
        from bert_pytorch_tpu.models import load_pretrained_encoder

        params = load_pretrained_encoder(args.init_checkpoint, config, params)
        logger.info(f"loaded pretrained encoder from {args.init_checkpoint}")

    steps_per_epoch = max(
        1, -(-len(arrays["train"]["labels"]) // args.batch_size))
    total_steps = steps_per_epoch * args.epochs
    schedule = optim.warmup_linear_schedule(
        args.lr, args.warmup_proportion, total_steps)
    tx = optim.adamw(schedule, weight_decay=0.01, bias_correction=False,
                     weight_decay_mask=optim.no_decay_mask)
    opt_state = tx.init(params)

    def scores_fn(p, batch, dropout_rng=None):
        deterministic = dropout_rng is None
        rngs = None if deterministic else {"dropout": dropout_rng}
        return model.apply(
            {"params": p}, batch["input_ids"], batch["segment_ids"],
            batch["input_mask"], deterministic, rngs=rngs)

    stats_every = telemetry.stats_every(args)

    def train_step(params, opt_state, batch, valid, dropout_rng):
        def loss_fn(p):
            scores = scores_fn(p, batch, dropout_rng)  # [B, C]
            per_ex = optax.softmax_cross_entropy_with_integer_labels(
                scores.astype(jnp.float32), batch["labels"])
            weights = valid.astype(jnp.float32)
            return jnp.sum(per_ex * weights) / jnp.maximum(weights.sum(), 1.0)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads, _ = clip_by_global_norm(grads, args.clip_grad)
        updates, opt_state2 = tx.update(grads, opt_state, params)
        metrics = {"loss": loss}
        health = telemetry.finetune_grad_health(
            params, grads, updates, opt_state, stats_every)
        if health is not None:
            metrics["grad_health"] = health
        return optax.apply_updates(params, updates), opt_state2, metrics

    # Telemetry facade (docs/telemetry.md). One SWAG example is
    # NUM_CHOICES encoder passes, so flops_per_seq scales by the choices.
    from bert_pytorch_tpu.utils import flops as flops_util
    tele = telemetry.from_args(
        args,
        sink=telemetry_sink,
        seq_per_step=args.batch_size,
        flops_per_seq=swag.NUM_CHOICES
        * flops_util.bert_finetune_flops_per_seq(
            config, args.max_seq_len, head_outputs=1,
            per_token_head=False, pooled=True),
        output_dir=args.output_dir or None,
        process="swag")

    train_step = tele.instrument(
        jax.jit(train_step, donate_argnums=(0, 1)), "train_step")
    eval_step = tele.instrument(jax.jit(scores_fn), "eval_step")

    def evaluate():
        correct = total = 0
        for batch, valid in batches(arrays["val"], args.batch_size, False,
                                    np.random.default_rng(0)):
            scores = np.asarray(eval_step(params, batch), np.float32)
            preds = scores.argmax(axis=-1)
            correct += int(((preds == batch["labels"]) & valid).sum())
            total += int(valid.sum())
        return correct / max(total, 1)

    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    t0 = time.perf_counter()
    seen = 0
    global_step = 0
    # Graceful preemption (docs/fault_tolerance.md): stop at the next
    # step boundary, checkpoint through the normal end-of-run path,
    # exit EXIT_PREEMPTED. Handlers stay installed THROUGH the
    # checkpoint write below (a grace-period re-delivery must not kill
    # it); restored in the finally even on exceptions.
    stop = preemption.GracefulStop().install()
    prefetcher = None
    try:
        for epoch in range(args.epochs):
            # Device-side epoch loss accumulation (run_glue pattern): a
            # per-step float(loss) would block on the device every step
            # (jaxlint HS101); the epoch-end mean is the only fetch.
            loss_sum = None
            n_steps = 0
            # Device prefetch + h2d_wait attribution (run_glue pattern).
            prefetcher = DevicePrefetcher(
                batches(arrays["train"], args.batch_size, True, rng),
                stage=lambda bv: (jax.device_put(bv[0]), bv[1]),
                depth=args.device_prefetch)
            tele.attach_prefetcher(prefetcher)
            for batch, valid in tele.timed(
                    iter(prefetcher), first_step=global_step + 1):
                key, sub = jax.random.split(key)
                with telemetry.span("train:dispatch"):
                    params, opt_state, metrics = train_step(
                        params, opt_state, batch, valid, sub)
                tele.dispatch_done()
                global_step += 1
                tele.step_done(global_step, metrics)
                loss = metrics["loss"]
                loss_sum = loss if loss_sum is None else loss_sum + loss
                n_steps += 1
                # valid is the host-side numpy padding mask from
                # batches() — the stage fn device_puts only the batch.
                seen += int(valid.sum())  # jaxlint: disable=HS101
                if args.save_steps and args.output_dir \
                        and global_step % args.save_steps == 0:
                    # Periodic async save (joined before exit below).
                    with tele.checkpoint_stall():
                        ckpt.save_checkpoint(
                            args.output_dir, global_step,
                            {"model": params}, async_write=True)
                if stop.requested:
                    break
            prefetcher.close()
            if n_steps:
                logger.info(
                    f"epoch {epoch}: "
                    f"train_loss={float(loss_sum) / n_steps:.4f}")
            if stop.requested:
                logger.info(
                    f"termination signal ({stop.signal_name}) received; "
                    "checkpointing and exiting cleanly "
                    f"(exit code {preemption.EXIT_PREEMPTED})")
                tele.emit(preemption.preemption_record(global_step, stop))
                break
        train_time = time.perf_counter() - t0
        tele.finish(global_step, summary={
            "training_seq_per_sec":
                round(seen / train_time, 2) if train_time else 0.0})

        results = {
            "e2e_train_time": train_time,
            "training_sequences_per_second":
                seen / train_time if train_time else 0,
            "terminated_by_signal": stop.requested,
        }
        if args.val_file and not stop.requested:
            results["accuracy"] = evaluate()
        logger.info(json.dumps({"swag_summary": results}))

        if args.output_dir:
            os.makedirs(args.output_dir, exist_ok=True)
            # Stamped with the step actually REACHED (see run_glue.py).
            # Synchronous on purpose: the durability write before exit;
            # joins any in-flight periodic async write first. (No
            # checkpoint_stall wrapper: telemetry is already flushed.)
            ckpt.save_checkpoint(
                args.output_dir, global_step, {"model": params})
            with open(os.path.join(args.output_dir,
                                   "eval_results_swag.json"), "w") as f:
                json.dump(results, f, indent=2)
        # No exit until any in-flight async periodic write has landed.
        ckpt.wait_for_pending_save()
    finally:
        if prefetcher is not None:
            prefetcher.close()
        stop.restore()
    logger.close()
    return results


if __name__ == "__main__":
    outcome = main(parse_arguments())
    if outcome.get("terminated_by_signal"):
        sys.exit(preemption.EXIT_PREEMPTED)
