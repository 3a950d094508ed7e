"""GLUE finetuning runner — sequence classification / regression on TPU.

Beyond-reference capability: the reference downloads GLUE
(utils/download.py:81-101) but has no runner that consumes it; this closes
the loop with a `BertForSequenceClassification` finetune in the classic BERT
GLUE recipe (lr 2e-5, 3 epochs, warmup 0.1, AdamW, max_seq 128). All nine
tasks from the downloader's TSV layout are supported
(:mod:`bert_pytorch_tpu.data.glue`), including the STS-B regression path
(num_labels=1, MSE) and MNLI's matched/mismatched dev sets.

Follows the same conventions as run_ner.py / run_squad.py: model config
JSON supplies vocab/tokenizer, ``--init_checkpoint`` accepts this
framework's checkpoints or foreign (torch/TF) archives, results land in a
dllogger-style one-line JSON summary.
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
from bert_pytorch_tpu.data import DevicePrefetcher, glue
from bert_pytorch_tpu.data.tokenization import (
    get_bpe_tokenizer,
    get_wordpiece_tokenizer,
)
from bert_pytorch_tpu.models import BertForSequenceClassification
from bert_pytorch_tpu.models.losses import _xent_ignore
from bert_pytorch_tpu.ops.grad_utils import clip_by_global_norm
from bert_pytorch_tpu.utils import checkpoint as ckpt
from bert_pytorch_tpu.utils import logging as logger
from bert_pytorch_tpu.utils import preemption
from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache


def parse_arguments(argv=None):
    parser = argparse.ArgumentParser(description="TPU BERT GLUE finetuning")
    parser.add_argument("--task", type=str, required=True,
                        choices=sorted(glue.PROCESSORS))
    parser.add_argument("--data_dir", type=str, required=True,
                        help="Directory holding the task's train/dev TSVs")
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
    parser.add_argument("--batch_size", type=int, default=32)
    parser.add_argument("--max_seq_len", type=int, default=128)
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--compile_cache_dir", type=str, default="",
                        help="persistent XLA compilation cache directory; "
                             "default <checkout>/.jax_cache, and "
                             "JAX_COMPILATION_CACHE_DIR wins when set")
    parser.add_argument("--dtype", type=str, default="bfloat16",
                        choices=["bfloat16", "float32"])
    parser.add_argument("--skip_eval", action="store_true")
    parser.add_argument("--save_steps", type=int, default=0,
                        help="periodic checkpoint cadence (optimizer "
                             "steps): saves ride the ASYNC write path "
                             "(device snapshot + background write, "
                             "utils/checkpoint.py) so the loop never "
                             "blocks on disk; the final/emergency "
                             "checkpoint stays synchronous. 0 disables")
    # device prefetch: stage batches onto device ahead of the loop
    # (data/device_prefetch.py; one flag shared by every runner)
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


def batches(arrays: dict, batch_size: int, shuffle: bool, rng):
    """Yield dict minibatches; the last partial batch is padded to a full
    batch with repeated rows plus a ``valid`` mask so every jitted call sees
    one static shape (one compile, XLA-friendly)."""
    n = len(arrays["labels"])
    order = rng.permutation(n) if shuffle else np.arange(n)
    for i in range(0, n, batch_size):
        idx = order[i:i + batch_size]
        valid = np.ones(batch_size, bool)
        if len(idx) < batch_size:
            valid[len(idx):] = False
            idx = np.concatenate([idx, np.zeros(batch_size - len(idx), idx.dtype)])
        yield {k: v[idx] for k, v in arrays.items()}, valid


def main(args):
    enable_compile_cache(args.compile_cache_dir)
    processor = glue.PROCESSORS[args.task]()
    regression = processor.regression
    num_labels = 1 if regression else len(processor.labels)
    telemetry_jsonl = telemetry.default_jsonl_path(
        args, args.output_dir, "glue")
    telemetry_sink = (logger.JSONLHandler(telemetry_jsonl, overwrite=False)
                      if telemetry_jsonl else None)
    logger.init(handlers=[logger.StreamHandler()]
                + ([telemetry_sink] if telemetry_sink else []))

    if args.tokenizer == "wordpiece":
        tokenizer = get_wordpiece_tokenizer(args.vocab_file,
                                            uppercase=args.uppercase)
    else:
        tokenizer = get_bpe_tokenizer(args.vocab_file, uppercase=args.uppercase)

    splits = {"train": processor.get_train_examples(args.data_dir)}
    if not args.skip_eval:
        splits["dev"] = processor.get_dev_examples(args.data_dir)
    arrays = {
        name: glue.features_to_arrays(
            glue.convert_examples_to_features(
                examples, tokenizer, args.max_seq_len,
                processor.labels, regression),
            regression)
        for name, examples in splits.items()
    }
    logger.info(
        f"task={args.task} train={len(arrays['train']['labels'])} "
        + (f"dev={len(arrays['dev']['labels'])}" if "dev" in arrays else "")
    )

    config = BertConfig.from_json_file(args.model_config_file)
    if config.vocab_size % 8 != 0:
        config.vocab_size += 8 - (config.vocab_size % 8)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    model = BertForSequenceClassification(config, num_labels=num_labels,
                                          dtype=dtype)

    sample = (jnp.zeros((1, args.max_seq_len), jnp.int32),) * 3
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
    # bias_correction=False for parity with the sibling finetune runners'
    # FusedAdam recipe (run_squad.py, run_ner.py; optim/transforms.py).
    tx = optim.adamw(schedule, weight_decay=0.01, bias_correction=False,
                     weight_decay_mask=optim.no_decay_mask)
    opt_state = tx.init(params)

    def loss_from_logits(logits, labels, valid):
        weights = valid.astype(jnp.float32)
        if regression:
            err = (logits.squeeze(-1).astype(jnp.float32) - labels) ** 2
            return jnp.sum(err * weights) / jnp.maximum(weights.sum(), 1.0)
        return _xent_ignore(
            logits.astype(jnp.float32), jnp.where(valid, labels, -1), -1)

    stats_every = telemetry.stats_every(args)

    def train_step(params, opt_state, batch, valid, dropout_rng):
        def loss_fn(p):
            logits = model.apply(
                {"params": p}, batch["input_ids"], batch["segment_ids"],
                batch["input_mask"], False, rngs={"dropout": dropout_rng})
            return loss_from_logits(logits, batch["labels"], valid)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads, _ = clip_by_global_norm(grads, args.clip_grad)
        updates, opt_state2 = tx.update(grads, opt_state, params)
        metrics = {"loss": loss}
        health = telemetry.finetune_grad_health(
            params, grads, updates, opt_state, stats_every)
        if health is not None:
            metrics["grad_health"] = health
        return optax.apply_updates(params, updates), opt_state2, metrics

    # Telemetry facade (docs/telemetry.md): step-time windows + MFU, trace
    # window, compile attribution, loss sentinel, optional heartbeat.
    from bert_pytorch_tpu.utils import flops as flops_util
    tele = telemetry.from_args(
        args,
        sink=telemetry_sink,
        seq_per_step=args.batch_size,
        flops_per_seq=flops_util.bert_finetune_flops_per_seq(
            config, args.max_seq_len, head_outputs=num_labels,
            per_token_head=False, pooled=True),
        output_dir=args.output_dir or None,
        process="glue")

    train_step = tele.instrument(
        jax.jit(train_step, donate_argnums=(0, 1)), "train_step")

    @jax.jit
    def eval_step(params, batch):
        return model.apply(
            {"params": params}, batch["input_ids"], batch["segment_ids"],
            batch["input_mask"])

    eval_step = tele.instrument(eval_step, "eval_step")

    def evaluate():
        preds, labels = [], []
        for batch, valid in batches(arrays["dev"], args.batch_size, False,
                                    np.random.default_rng(0)):
            logits = np.asarray(eval_step(params, batch), np.float32)
            out = (logits.squeeze(-1) if regression
                   else logits.argmax(axis=-1))
            preds.append(out[valid])
            labels.append(batch["labels"][valid])
        return glue.compute_metrics(
            args.task, np.concatenate(preds), np.concatenate(labels))

    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    t0 = time.perf_counter()
    seen = 0
    global_step = 0
    # Graceful preemption (docs/fault_tolerance.md): stop at the next
    # step boundary, write the emergency checkpoint through the normal
    # end-of-run path, exit EXIT_PREEMPTED. Handlers stay installed
    # THROUGH the checkpoint write below (the grace period may re-deliver
    # the signal; the default disposition would kill the write mid-file)
    # and are restored in the finally even on exceptions.
    stop = preemption.GracefulStop().install()
    prefetcher = None
    try:
        for epoch in range(args.epochs):
            # Epoch loss accumulates ON DEVICE: one scalar add rides each
            # step's dispatch, and the only host fetch is the epoch-end
            # mean. A per-step float(loss) here would be a blocking host
            # sync every step — jaxlint HS101 (docs/static_analysis.md)
            # now enforces what used to be a review-memory rule, and
            # --telemetry_sync_every > 1 actually buys something.
            loss_sum = None
            n_steps = 0
            # Device prefetch: the batch is staged onto device by a
            # background thread while the previous step runs; data_wait
            # then measures only featurization stalls, with the staging
            # share attributed to the h2d_wait sub-phase.
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
                    # Periodic save, async: the loop pays the device-side
                    # snapshot only; the write overlaps training
                    # (wait_for_pending_save below joins it before exit).
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
        if not args.skip_eval and not stop.requested:
            results.update(evaluate())
        logger.info(
            json.dumps({"glue_summary": {"task": args.task, **results}}))

        if args.output_dir:
            os.makedirs(args.output_dir, exist_ok=True)
            # Stamped with the step actually REACHED — a preempted run's
            # emergency checkpoint must not masquerade as a fully-trained
            # ckpt_<total_steps> artifact. SYNCHRONOUS on purpose: this is
            # the durability write before exit, and it joins any in-flight
            # periodic async write to the same directory first. (No
            # checkpoint_stall wrapper: telemetry is already flushed —
            # only in-loop saves feed the ckpt_step windows.)
            ckpt.save_checkpoint(
                args.output_dir, global_step, {"model": params})
            with open(os.path.join(args.output_dir,
                                   f"eval_results_{args.task}.json"),
                      "w") as f:
                json.dump(results, f, indent=2)
        # No exit until any in-flight async periodic write has landed — a
        # fast exit must never truncate one (docs/fault_tolerance.md).
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
