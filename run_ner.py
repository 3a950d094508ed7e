"""NER finetuning runner — TPU-native counterpart of reference run_ner.py.

Capability parity (SURVEY.md §3.4): CoNLL-style data via
bert_pytorch_tpu.data.ner_dataset, BertForTokenClassification with
``len(labels)+1`` classes (reference run_ner.py:224; id 0 reserved),
pretrained-checkpoint warm start, AdamW(bias_correction=False) with the
``1/(1+0.05*epoch)`` LambdaLR decay (:243-245), per-step global-norm grad
clipping (:145-170), per-epoch validation and final test with macro-F1 over
non-special tokens (:127-142 — computed here in numpy, no sklearn
dependency).
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
from bert_pytorch_tpu.data import DevicePrefetcher
from bert_pytorch_tpu.data.ner_dataset import NERDataset
from bert_pytorch_tpu.data.tokenization import (
    get_bpe_tokenizer,
    get_wordpiece_tokenizer,
)
from bert_pytorch_tpu.models import BertForTokenClassification
from bert_pytorch_tpu.models.losses import token_classification_loss
from bert_pytorch_tpu.ops.grad_utils import clip_by_global_norm
from bert_pytorch_tpu.utils import checkpoint as ckpt
from bert_pytorch_tpu.utils import logging as logger
from bert_pytorch_tpu.utils import preemption
from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache


def parse_arguments(argv=None):
    parser = argparse.ArgumentParser(description="TPU BERT NER finetuning")
    parser.add_argument("--train_file", type=str, required=True)
    parser.add_argument("--val_file", type=str, default=None)
    parser.add_argument("--test_file", type=str, default=None)
    parser.add_argument("--labels", type=str, nargs="+", required=True)
    parser.add_argument("--model_config_file", type=str, required=True)
    parser.add_argument("--model_checkpoint", type=str, default=None)
    parser.add_argument("--vocab_file", type=str, default=None)
    parser.add_argument("--uppercase", action="store_true")
    parser.add_argument("--tokenizer", type=str, default=None,
                        choices=["wordpiece", "bpe"])
    parser.add_argument("--epochs", type=int, default=5)
    parser.add_argument("--lr", type=float, default=5e-6)
    parser.add_argument("--clip_grad", type=float, default=5.0)
    parser.add_argument("--batch_size", type=int, default=32)
    parser.add_argument("--max_seq_len", type=int, default=128)
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--output_dir", type=str, default=None,
                        help="where the finetuned model checkpoint lands "
                             "(end of run, and on graceful preemption); "
                             "omitted = no checkpoint (pre-PR-5 behavior)")
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
    # telemetry (docs/telemetry.md) — this runner has no output dir, so the
    # file sinks are opt-in
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
        args.tokenizer = configs.get("tokenizer")
        if args.tokenizer is None:
            raise ValueError("tokenizer must be in model config or CLI")
    return args


def macro_f1(predictions: np.ndarray, labels: np.ndarray) -> float:
    """Macro-F1 over non-special positions (labels > 0), numpy reimplementation
    of the sklearn call at reference run_ner.py:127-142."""
    preds = predictions.argmax(axis=-1)
    keep = labels > 0
    y_true = labels[keep]
    y_pred = preds[keep]
    classes = np.unique(y_true)
    f1s = []
    for c in classes:
        tp = np.sum((y_pred == c) & (y_true == c))
        fp = np.sum((y_pred == c) & (y_true != c))
        fn = np.sum((y_pred != c) & (y_true == c))
        precision = tp / (tp + fp) if tp + fp else 0.0
        recall = tp / (tp + fn) if tp + fn else 0.0
        f1s.append(2 * precision * recall / (precision + recall)
                   if precision + recall else 0.0)
    return float(np.mean(f1s)) if f1s else 0.0


def batches(dataset, batch_size, shuffle, rng):
    order = rng.permutation(len(dataset)) if shuffle else np.arange(len(dataset))
    for i in range(0, len(order) - batch_size + 1, batch_size):
        idx = order[i:i + batch_size]
        seqs, labels, masks = zip(*(dataset[j] for j in idx))
        yield (np.stack(seqs), np.stack(labels), np.stack(masks))


def main(args):
    enable_compile_cache(args.compile_cache_dir)
    rng = np.random.default_rng(args.seed)
    telemetry_sink = (logger.JSONLHandler(args.telemetry_jsonl,
                                          overwrite=False)
                      if args.telemetry_jsonl else None)
    logger.init(handlers=[logger.StreamHandler()]
                + ([telemetry_sink] if telemetry_sink else []))

    if args.tokenizer == "wordpiece":
        tokenizer = get_wordpiece_tokenizer(args.vocab_file,
                                            uppercase=args.uppercase)
    else:
        tokenizer = get_bpe_tokenizer(args.vocab_file, uppercase=args.uppercase)

    datasets = {"train": NERDataset(args.train_file, tokenizer, args.labels,
                                    args.max_seq_len)}
    for split, path in (("val", args.val_file), ("test", args.test_file)):
        if path:
            datasets[split] = NERDataset(path, tokenizer, args.labels,
                                         args.max_seq_len)
    id_to_label = {i: l for i, l in enumerate(args.labels, start=1)}

    config = BertConfig.from_json_file(args.model_config_file)
    if config.vocab_size % 8 != 0:
        config.vocab_size += 8 - (config.vocab_size % 8)
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    model = BertForTokenClassification(
        config, num_labels=len(args.labels) + 1, dtype=dtype)

    sample = (jnp.zeros((1, args.max_seq_len), jnp.int32),) * 3
    import flax.linen as nn

    params = nn.unbox(model.init(jax.random.PRNGKey(args.seed), *sample))["params"]
    if args.model_checkpoint:
        from bert_pytorch_tpu.models import load_pretrained_encoder

        params = load_pretrained_encoder(args.model_checkpoint, config, params)
        logger.info(f"loaded pretrained encoder from {args.model_checkpoint}")

    # AdamW(bias_correction=False) + per-epoch 1/(1+0.05*epoch) decay
    # (reference run_ner.py:243-245). The epoch index is passed per step.
    base_tx = optim.adamw(1.0, bias_correction=False, weight_decay=0.0)
    opt_state = base_tx.init(params)

    stats_every = telemetry.stats_every(args)

    def train_step(params, opt_state, batch, dropout_rng, epoch):
        seqs, labels, masks = batch

        def loss_fn(p):
            logits = model.apply({"params": p}, seqs, None, masks, False,
                                 rngs={"dropout": dropout_rng})
            return token_classification_loss(logits, labels)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads, _ = clip_by_global_norm(grads, args.clip_grad)
        updates, opt_state2 = base_tx.update(grads, opt_state, params)
        lr = args.lr / (1.0 + 0.05 * epoch)
        updates = jax.tree_util.tree_map(lambda u: u * lr, updates)
        metrics = {"loss": loss}
        health = telemetry.finetune_grad_health(
            params, grads, updates, opt_state, stats_every)
        if health is not None:
            metrics["grad_health"] = health
        return optax.apply_updates(params, updates), opt_state2, metrics

    # Telemetry facade (docs/telemetry.md).
    from bert_pytorch_tpu.utils import flops as flops_util
    tele = telemetry.from_args(
        args,
        sink=telemetry_sink,
        seq_per_step=args.batch_size,
        flops_per_seq=flops_util.bert_finetune_flops_per_seq(
            config, args.max_seq_len, head_outputs=len(args.labels) + 1),
        # output_dir anchors the heartbeat/postmortem fallbacks the other
        # runners already get (run_ner gained --output_dir in PR 5 but
        # never passed it through).
        output_dir=args.output_dir or None,
        process="ner")

    train_step = tele.instrument(
        jax.jit(train_step, donate_argnums=(0, 1)), "train_step")

    @jax.jit
    def eval_step(params, seqs, masks):
        return model.apply({"params": params}, seqs, None, masks)

    eval_step = tele.instrument(eval_step, "eval_step")

    def evaluate(split):
        dataset = datasets[split]
        all_logits, all_labels, losses = [], [], []
        for seqs, labels, masks in batches(dataset, args.batch_size, False, rng):
            logits = np.asarray(eval_step(params, seqs, masks), np.float32)
            losses.append(float(token_classification_loss(
                jnp.asarray(logits), jnp.asarray(labels))))
            all_logits.append(logits)
            all_labels.append(labels)
        if not all_logits:
            return 0.0, 0.0
        f1 = macro_f1(np.concatenate(all_logits), np.concatenate(all_labels))
        return float(np.mean(losses)), f1

    key = jax.random.PRNGKey(args.seed)
    results = {}
    global_step = 0
    # Graceful preemption (docs/fault_tolerance.md): stop at the next
    # step boundary, checkpoint (with --output_dir), exit EXIT_PREEMPTED.
    # Handlers stay installed THROUGH the checkpoint write below (a
    # grace-period re-delivery must not kill it); restored in the finally.
    stop = preemption.GracefulStop().install()
    prefetcher = None
    try:
        for epoch in range(args.epochs):
            t0 = time.perf_counter()
            # Device-side epoch loss accumulation (run_glue pattern): a
            # per-step float(loss) would block on the device every step
            # (jaxlint HS101); the epoch-end mean is the only fetch.
            loss_sum = None
            n_steps = 0
            # Device prefetch + h2d_wait attribution (run_glue pattern).
            prefetcher = DevicePrefetcher(
                batches(datasets["train"], args.batch_size, True, rng),
                stage=jax.device_put, depth=args.device_prefetch)
            tele.attach_prefetcher(prefetcher)
            for batch in tele.timed(
                    iter(prefetcher), first_step=global_step + 1):
                key, sub = jax.random.split(key)
                with telemetry.span("train:dispatch"):
                    params, opt_state, metrics = train_step(
                        params, opt_state, batch, sub, epoch)
                tele.dispatch_done()
                global_step += 1
                tele.step_done(global_step, metrics)
                loss = metrics["loss"]
                loss_sum = loss if loss_sum is None else loss_sum + loss
                n_steps += 1
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
            if stop.requested:
                logger.info(
                    f"termination signal ({stop.signal_name}) received; "
                    "checkpointing and exiting cleanly "
                    f"(exit code {preemption.EXIT_PREEMPTED})")
                tele.emit(preemption.preemption_record(global_step, stop))
                break
            mean_loss = float(loss_sum) / n_steps if n_steps else float("nan")
            msg = (f"epoch {epoch}: train_loss={mean_loss:.4f} "
                   f"({time.perf_counter() - t0:.1f}s)")
            if "val" in datasets:
                val_loss, val_f1 = evaluate("val")
                results["val_f1"] = val_f1
                msg += f" val_loss={val_loss:.4f} val_f1={val_f1:.4f}"
            logger.info(msg)

        results["terminated_by_signal"] = stop.requested
        if "test" in datasets and not stop.requested:
            test_loss, test_f1 = evaluate("test")
            results["test_f1"] = test_f1
            logger.info(f"test_loss={test_loss:.4f} test_f1={test_f1:.4f}")
        tele.finish(global_step)
        if args.output_dir:
            os.makedirs(args.output_dir, exist_ok=True)
            # Synchronous on purpose: the durability write before exit;
            # joins any in-flight periodic async write first.
            ckpt.save_checkpoint(
                args.output_dir, global_step, {"model": params})
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
