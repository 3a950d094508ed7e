"""SQuAD v1.1/v2.0 finetuning + prediction runner — TPU-native counterpart of
reference run_squad.py.

Capability parity (SURVEY.md §3.3): example reading / sliding-window
featurization with pickle cache, span-loss finetuning of
BertForQuestionAnswering (AdamW bias_correction=False + linear warmup — the
FusedAdam path of run_squad.py:980-996 — or BertAdam with its internal
schedule for the fp32 path, :999-1002), batched prediction into RawResults,
n-best span decoding with text realignment (bert_pytorch_tpu/squad.py), the
official-eval-script subprocess oracle (:1197-1204), and the dllogger-style
summary metrics (e2e_train_time, training_sequences_per_second,
e2e_inference_time, exact_match, F1; :1206-1224). bf16 on TPU replaces the
Apex AMP O2 path; DDP is replaced by batch sharding over the device mesh.
"""

from __future__ import annotations

import argparse
import json
import os
import pickle
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bert_pytorch_tpu import optim, pretrain, squad, telemetry
from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.data.tokenization import (
    get_bpe_tokenizer,
    get_wordpiece_tokenizer,
)
from bert_pytorch_tpu.models import BertForQuestionAnswering
from bert_pytorch_tpu.models.losses import span_loss
from bert_pytorch_tpu.ops.grad_utils import global_norm
from bert_pytorch_tpu.parallel import MeshConfig, create_mesh, logical_axis_rules
from bert_pytorch_tpu.utils import checkpoint as ckpt
from bert_pytorch_tpu.utils import logging as logger
from bert_pytorch_tpu.utils import preemption
from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache
from bert_pytorch_tpu.utils.dist import is_main_process


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description="TPU BERT SQuAD finetuning")
    parser.add_argument("--output_dir", type=str, required=True)
    parser.add_argument("--init_checkpoint", type=str, default=None,
                        help="pretraining checkpoint (.msgpack), torch .bin/.pt, TF ckpt prefix, or pretrained archive dir")
    parser.add_argument("--config_file", type=str, required=True,
                        help="BERT model config json")
    parser.add_argument("--train_file", type=str, default=None)
    parser.add_argument("--predict_file", type=str, default=None)
    parser.add_argument("--max_seq_length", type=int, default=384)
    parser.add_argument("--doc_stride", type=int, default=128)
    parser.add_argument("--max_query_length", type=int, default=64)
    parser.add_argument("--do_train", action="store_true")
    parser.add_argument("--do_predict", action="store_true")
    parser.add_argument("--do_eval", action="store_true")
    parser.add_argument("--train_batch_size", type=int, default=32)
    parser.add_argument("--predict_batch_size", type=int, default=8)
    parser.add_argument("--learning_rate", type=float, default=3e-5)
    parser.add_argument("--num_train_epochs", type=float, default=2.0)
    parser.add_argument("--max_steps", type=int, default=-1)
    parser.add_argument("--warmup_proportion", type=float, default=0.1)
    parser.add_argument("--n_best_size", type=int, default=20)
    parser.add_argument("--max_answer_length", type=int, default=30)
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--compile_cache_dir", type=str, default="",
                        help="persistent XLA compilation cache directory; "
                             "default <checkout>/.jax_cache, and "
                             "JAX_COMPILATION_CACHE_DIR wins when set")
    parser.add_argument("--gradient_accumulation_steps", type=int, default=1)
    parser.add_argument("--do_lower_case", action="store_true")
    parser.add_argument("--version_2_with_negative", action="store_true")
    parser.add_argument("--null_score_diff_threshold", type=float, default=0.0)
    parser.add_argument("--vocab_file", type=str, default=None)
    parser.add_argument("--tokenizer", type=str, default=None,
                        choices=["wordpiece", "bpe"])
    parser.add_argument("--optimizer", type=str, default="adamw",
                        choices=["adamw", "bert_adam"],
                        help="adamw+linear-warmup = the reference fp16 path; "
                             "bert_adam = its fp32 path")
    parser.add_argument("--max_grad_norm", type=float, default=1.0)
    parser.add_argument("--dtype", type=str, default="bfloat16",
                        choices=["bfloat16", "float32", "float16"],
                        help="bfloat16 is the TPU default (no loss scaling "
                             "needed); float16 is the reference-parity AMP "
                             "mode (apex O2 + GradScaler, reference "
                             "run_squad.py:980-996) with a dynamic loss "
                             "scaler")
    parser.add_argument("--init_loss_scale", type=float, default=2.0 ** 16,
                        help="fp16 only: initial dynamic loss scale "
                             "(default matches torch GradScaler's 2**16)")
    parser.add_argument("--log_freq", type=int, default=50)
    # telemetry: canonical flag set shared by every runner
    # (telemetry/cli.py; docs/telemetry.md)
    telemetry.add_cli_args(parser)
    # device prefetch (data/device_prefetch.py; shared runner flag)
    from bert_pytorch_tpu.data import device_prefetch as dp_cli
    dp_cli.add_cli_args(parser)
    parser.add_argument("--save_steps", type=int, default=0,
                        help="periodic checkpoint cadence (optimizer "
                             "steps): async writes (device snapshot + "
                             "background write); the end-of-train/"
                             "emergency checkpoint stays synchronous. "
                             "0 disables")
    parser.add_argument("--json_summary", type=str, default="squad_log.json")
    parser.add_argument("--eval_script", type=str, default=None)
    parser.add_argument("--skip_checkpoint", action="store_true")
    parser.add_argument("--skip_cache", action="store_true")
    parser.add_argument("--cache_dir", type=str, default=None)
    parser.add_argument("--mesh_data", type=int, default=-1,
                        help="data-parallel mesh size; -1 = all local devices "
                             "(batch sizes must divide it)")
    args = parser.parse_args(argv)

    # vocab/tokenizer ride in the model config (reference run_squad.py:862-876)
    with open(args.config_file) as f:
        configs = json.load(f)
    if args.vocab_file is None:
        args.vocab_file = configs.get("vocab_file")
        if args.vocab_file is None:
            raise ValueError("vocab_file must be in the model config or CLI")
    if args.tokenizer is None:
        args.tokenizer = configs.get("tokenizer")
        if args.tokenizer is None:
            raise ValueError("tokenizer must be in the model config or CLI")
    if not args.do_train and not args.do_predict:
        raise ValueError("At least one of do_train or do_predict required")
    if args.do_train and not args.train_file:
        raise ValueError("do_train requires train_file")
    if args.do_predict and not args.predict_file:
        raise ValueError("do_predict requires predict_file")
    return args


def build_tokenizer(args):
    if args.tokenizer == "wordpiece":
        return get_wordpiece_tokenizer(args.vocab_file,
                                       uppercase=not args.do_lower_case)
    return get_bpe_tokenizer(args.vocab_file, uppercase=not args.do_lower_case)


def cached_features(args, examples, tokenizer, is_training, tag):
    """Pickle-cached featurization (reference run_squad.py:1027-1043)."""
    src = args.train_file if is_training else args.predict_file
    cache_dir = args.cache_dir or os.path.dirname(os.path.abspath(src))
    cache_file = os.path.join(
        cache_dir,
        f"{os.path.basename(src)}_{args.tokenizer}_{args.max_seq_length}_"
        f"{args.doc_stride}_{args.max_query_length}_{tag}.feat")
    if os.path.exists(cache_file) and not args.skip_cache:
        with open(cache_file, "rb") as f:
            return pickle.load(f)
    features = squad.convert_examples_to_features(
        examples, tokenizer, args.max_seq_length, args.doc_stride,
        args.max_query_length, is_training)
    if not args.skip_cache and is_main_process():
        try:
            with open(cache_file, "wb") as f:
                pickle.dump(features, f)
        except OSError:
            pass
    return features


def load_init_params(args, abstract_params, config):
    """Start from a pretraining checkpoint: copy the shared 'bert' encoder
    subtree; the QA head keeps its fresh init (the strict=False analog of
    reference run_squad.py:957-961).

    Accepts our msgpack checkpoints AND foreign pretrained archives — a
    directory with config.json + pytorch_model.bin / bert_model.ckpt.*, a
    torch .bin/.pt file, or a TF checkpoint prefix (the reference
    from_pretrained surface, modeling.py:659-799)."""
    from bert_pytorch_tpu.models import load_pretrained_encoder

    target = jax.device_get(abstract_params)
    return load_pretrained_encoder(
        args.init_checkpoint, config, target, fallback_full_tree=True)


def features_to_arrays(features, is_training):
    arrays = {
        "input_ids": np.asarray([f.input_ids for f in features], np.int32),
        "segment_ids": np.asarray([f.segment_ids for f in features], np.int32),
        "input_mask": np.asarray([f.input_mask for f in features], np.int32),
    }
    if is_training:
        arrays["start_positions"] = np.asarray(
            [f.start_position for f in features], np.int32)
        arrays["end_positions"] = np.asarray(
            [f.end_position for f in features], np.int32)
    return arrays


def main(args):
    enable_compile_cache(args.compile_cache_dir)
    np.random.seed(args.seed)
    devices = None
    if args.mesh_data > 0:
        devices = jax.devices()[: args.mesh_data]
    mesh = create_mesh(MeshConfig(data=-1), devices=devices)
    os.makedirs(args.output_dir, exist_ok=True)
    args.telemetry_jsonl = telemetry.default_jsonl_path(
        args, args.output_dir, "squad")
    args.heartbeat_file = args.heartbeat_file or os.path.join(
        args.output_dir, "heartbeat.json")
    args.profile_dir = args.profile_dir or os.path.join(
        args.output_dir, "profile")
    # Sink shared between the logger (train records) and TrainTelemetry
    # (docs/telemetry.md); telemetry records go ONLY to the JSONL.
    telemetry_sink = logger.JSONLHandler(
        args.telemetry_jsonl, overwrite=False, is_primary=is_main_process())
    logger.init(handlers=[
        logger.StreamHandler(verbose=is_main_process(),
                             is_primary=is_main_process()),
        logger.FileHandler(os.path.join(args.output_dir, args.json_summary),
                           is_primary=is_main_process()),
        telemetry_sink,
    ])

    config = BertConfig.from_json_file(args.config_file)
    if config.vocab_size % 8 != 0:
        config.vocab_size += 8 - (config.vocab_size % 8)
    dtype = {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
             "float32": jnp.float32}[args.dtype]
    model = BertForQuestionAnswering(config, dtype=dtype)
    tokenizer = build_tokenizer(args)
    rules = logical_axis_rules("dp")

    seq = args.max_seq_length
    sample = (jnp.zeros((1, seq), jnp.int32),) * 3
    summary = {}

    with mesh:
        shardings_abstract = jax.eval_shape(
            lambda r: model.init(r, *sample), jax.random.PRNGKey(0))
        import flax.linen as nn
        from bert_pytorch_tpu.parallel.sharding import params_shardings

        p_shardings = params_shardings(mesh, shardings_abstract, rules)["params"]
        init_params = nn.unbox(
            jax.jit(lambda r: model.init(r, *sample),
                    out_shardings={"params": p_shardings})(
                jax.random.PRNGKey(args.seed)))["params"]
        if args.init_checkpoint:
            host_params = load_init_params(args, init_params, config)
            init_params = jax.device_put(host_params, p_shardings)
        params = init_params

        batch_sh = pretrain.batch_shardings(
            mesh, {"input_ids": 2, "segment_ids": 2, "input_mask": 2,
                   "start_positions": 1, "end_positions": 1})
        # [B,...] (no accumulation axis): batch axis 0 over data mesh axes
        from jax.sharding import NamedSharding, PartitionSpec as P

        from bert_pytorch_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP
        batch_sh = {k: NamedSharding(mesh, P((AXIS_DATA, AXIS_FSDP)))
                    for k in batch_sh}

        # Telemetry facade (docs/telemetry.md): step-time windows + MFU,
        # profiler trace window, compile attribution, non-finite sentinel
        # (host-side isfinite on the fetched loss), rank-0 heartbeat.
        from bert_pytorch_tpu.utils import flops as flops_util
        tele = telemetry.from_args(
            args,
            sink=telemetry_sink,
            is_primary=is_main_process(),
            seq_per_step=args.train_batch_size if args.do_train else None,
            flops_per_seq=flops_util.bert_finetune_flops_per_seq(
                config, args.max_seq_length, head_outputs=2),
            output_dir=args.output_dir,
            process="squad")

        if args.do_train:
            train_examples = squad.read_squad_examples(
                args.train_file, True, args.version_2_with_negative)
            train_features = cached_features(
                args, train_examples, tokenizer, True, "train")
            n = len(train_features)
            micro_bs = args.train_batch_size // args.gradient_accumulation_steps
            steps_per_epoch = n // args.train_batch_size
            total_steps = (args.max_steps if args.max_steps > 0 else
                           int(steps_per_epoch * args.num_train_epochs))
            logger.info(f"training features: {n}, optimizer steps: {total_steps}")

            mask = optim.no_decay_mask
            if args.optimizer == "adamw":
                schedule = optim.warmup_linear_schedule(
                    args.learning_rate, args.warmup_proportion, total_steps,
                    offset=0)
                tx = optim.adamw(schedule, bias_correction=False,
                                 weight_decay_mask=mask)
            else:
                tx = optim.bert_adam(
                    args.learning_rate, schedule="warmup_linear",
                    warmup=args.warmup_proportion, t_total=total_steps,
                    weight_decay_mask=mask)
            fp16 = args.dtype == "float16"
            if fp16:
                # Reference-parity AMP (apex O2 + loss scaling,
                # run_squad.py:980-996): the scaler state rides in
                # opt_state like the reference's amp state.
                tx = optim.dynamic_loss_scale(
                    tx, init_scale=args.init_loss_scale)
            opt_state = tx.init(params)

            stats_every = telemetry.stats_every(args)

            def train_step(params, opt_state, batch, rng):
                loss_scale = opt_state.scale if fp16 else 1.0

                def loss_fn(p):
                    start_logits, end_logits = model.apply(
                        {"params": p}, batch["input_ids"],
                        batch["segment_ids"], batch["input_mask"],
                        False, rngs={"dropout": rng})
                    loss = span_loss(start_logits, end_logits,
                                     batch["start_positions"],
                                     batch["end_positions"])
                    return loss * loss_scale, loss
                (_, loss), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                if args.optimizer == "adamw" and args.max_grad_norm > 0:
                    # grads carry loss_scale in fp16; clip on the TRUE norm
                    # (the multiplicative clip commutes with the wrapper's
                    # unscale)
                    gnorm = global_norm(grads) / loss_scale
                    scale = jnp.minimum(1.0, args.max_grad_norm / (gnorm + 1e-6))
                    grads = jax.tree_util.tree_map(lambda g: g * scale, grads)
                updates, opt_state2 = tx.update(grads, opt_state, params)
                import optax
                metrics = {"loss": loss}
                health = telemetry.finetune_grad_health(
                    params, grads, updates, opt_state, stats_every,
                    fp16_scale=loss_scale if fp16 else None)
                if health is not None:
                    metrics["grad_health"] = health
                return optax.apply_updates(params, updates), opt_state2, metrics

            train_step = tele.instrument(
                jax.jit(train_step, donate_argnums=(0, 1)), "train_step")

            rng = jax.random.PRNGKey(args.seed)
            order = np.random.permutation(n)
            global_step = 0
            t_start = time.perf_counter()
            seqs = 0
            epoch = 0
            losses = []

            def epoch_batches():
                """Featurize one epoch's HOST batches; the device
                prefetcher below stages them onto device ahead of the
                loop, so data_wait measures featurization stalls only
                (staging reports as the h2d_wait sub-phase)."""
                for i in range(0, n - args.train_batch_size + 1,
                               args.train_batch_size):
                    idx = order[i:i + args.train_batch_size]
                    feats = [train_features[j] for j in idx]
                    yield features_to_arrays(feats, True)

            from bert_pytorch_tpu.data import DevicePrefetcher

            def epoch_prefetcher():
                p = DevicePrefetcher(
                    epoch_batches(),
                    stage=lambda arrays: {
                        k: jax.device_put(v, batch_sh[k])
                        for k, v in arrays.items()},
                    depth=args.device_prefetch)
                tele.attach_prefetcher(p)
                return p

            # Graceful preemption (docs/fault_tolerance.md): stop at the
            # next step boundary, checkpoint via the normal end-of-train
            # write below, exit EXIT_PREEMPTED from __main__.
            # Handlers stay installed THROUGH the end-of-train checkpoint
            # write below (a grace-period re-delivery must not kill it);
            # restored in the finally even on exceptions.
            stop = preemption.GracefulStop().install()
            prefetcher = None
            try:
                while global_step < total_steps and not stop.requested:
                    prefetcher = epoch_prefetcher()
                    for batch in tele.timed(
                            iter(prefetcher), first_step=global_step + 1):
                        rng, sub = jax.random.split(rng)
                        with telemetry.span("train:dispatch"):
                            params, opt_state, metrics = train_step(
                                params, opt_state, batch, sub)
                        tele.dispatch_done()
                        global_step += 1
                        seqs += args.train_batch_size
                        loss = metrics["loss"]
                        tele.step_done(global_step, metrics)
                        if global_step % args.log_freq == 0:
                            losses.append(float(loss))
                            logger.log(tag="train", step=global_step,
                                       step_loss=float(loss),
                                       samples_per_second=seqs / (
                                           time.perf_counter() - t_start))
                        if args.save_steps and not args.skip_checkpoint \
                                and is_main_process() \
                                and global_step % args.save_steps == 0:
                            # Periodic async save (device snapshot +
                            # background write; joined before the final
                            # write / predict reads below).
                            with tele.checkpoint_stall():
                                ckpt.save_checkpoint(
                                    args.output_dir, global_step,
                                    {"model": params,
                                     "config": config.to_dict()},
                                    keep=1, async_write=True)
                        if global_step >= total_steps or stop.requested:
                            break
                    prefetcher.close()
                    epoch += 1
                    order = np.random.permutation(n)
                if stop.requested:
                    logger.info(
                        f"termination signal ({stop.signal_name}) received; "
                        "checkpointing and exiting cleanly "
                        f"(exit code {preemption.EXIT_PREEMPTED})")
                    tele.emit(
                        preemption.preemption_record(global_step, stop))
                    summary["terminated_by_signal"] = True
                train_time = time.perf_counter() - t_start
                summary["e2e_train_time"] = train_time
                summary["training_sequences_per_second"] = seqs / train_time
                summary["final_loss"] = float(loss)
                tele.finish(global_step, summary={
                    "training_seq_per_sec": round(seqs / train_time, 2)})

                if not args.skip_checkpoint and is_main_process():
                    # A preemption stop must still land this write — it IS
                    # the emergency checkpoint for this runner. Synchronous
                    # on purpose; it joins any in-flight periodic async
                    # write to the same directory first, so checkpoints
                    # land in order. (No checkpoint_stall wrapper:
                    # telemetry is already flushed.)
                    ckpt.save_checkpoint(args.output_dir, global_step,
                                         {"model": params,
                                          "config": config.to_dict()},
                                         keep=1)
                # Join any in-flight async write BEFORE the predict path
                # below reads checkpoints back / the process exits.
                ckpt.wait_for_pending_save()
            finally:
                if prefetcher is not None:
                    prefetcher.close()
                stop.restore()

        if args.do_predict and not summary.get("terminated_by_signal"):
            # A preempted run exits after its emergency checkpoint; the
            # grace period is for durability, not for inference.
            eval_examples = squad.read_squad_examples(
                args.predict_file, False, args.version_2_with_negative)
            eval_features = cached_features(
                args, eval_examples, tokenizer, False, "predict")
            logger.info(f"predict features: {len(eval_features)}")

            @jax.jit
            def predict_step(params, batch):
                return model.apply({"params": params}, batch["input_ids"],
                                   batch["segment_ids"], batch["input_mask"])

            predict_step = tele.instrument(predict_step, "predict_step")

            t_infer = time.perf_counter()
            results = []
            bs = args.predict_batch_size
            # pad to full batches for static shapes
            padded = list(eval_features)
            while len(padded) % bs != 0:
                padded.append(eval_features[-1])
            for i in range(0, len(padded), bs):
                feats = padded[i:i + bs]
                arrays = features_to_arrays(feats, False)
                batch = {k: jax.device_put(v, batch_sh[k])
                         for k, v in arrays.items()}
                start_logits, end_logits = predict_step(params, batch)
                start_logits = np.asarray(start_logits, np.float32)
                end_logits = np.asarray(end_logits, np.float32)
                for j, f in enumerate(feats):
                    if i + j < len(eval_features):
                        results.append(squad.RawResult(
                            unique_id=f.unique_id,
                            start_logits=start_logits[j].tolist(),
                            end_logits=end_logits[j].tolist()))
            summary["e2e_inference_time"] = time.perf_counter() - t_infer

            answers, nbest, null_odds = squad.get_answers(
                eval_examples, eval_features, results, args)
            output_prediction_file = os.path.join(
                args.output_dir, "predictions.json")
            with open(output_prediction_file, "w") as f:
                f.write(json.dumps(answers, indent=4) + "\n")
            with open(os.path.join(args.output_dir,
                                   "nbest_predictions.json"), "w") as f:
                f.write(json.dumps(nbest, indent=4) + "\n")
            output_null_odds_file = None
            if args.version_2_with_negative:
                # The v2.0 official metric's best-threshold search
                # consumes these (reference writes the same file,
                # run_squad.py:1190-1194).
                output_null_odds_file = os.path.join(
                    args.output_dir, "null_odds.json")
                with open(output_null_odds_file, "w") as f:
                    f.write(json.dumps(null_odds, indent=4) + "\n")

            if args.do_eval and args.eval_script:
                # Official-oracle evaluation (reference run_squad.py:1197-1204)
                eval_cmd = [sys.executable, args.eval_script,
                            args.predict_file, output_prediction_file]
                if output_null_odds_file:
                    eval_cmd += ["--na-prob-file", output_null_odds_file,
                                 "--na-prob-thresh",
                                 str(args.null_score_diff_threshold)]
                proc = subprocess.run(
                    eval_cmd, capture_output=True, text=True, check=True)
                scores = json.loads(proc.stdout)
                summary["exact_match"] = scores.get("exact_match")
                summary["F1"] = scores.get("f1")

    logger.log(tag="summary", step=0, **{
        k: v for k, v in summary.items() if isinstance(v, (int, float))})
    logger.info(f"summary: {summary}")
    logger.close()
    return summary


if __name__ == "__main__":
    outcome = main(parse_args())
    if outcome.get("terminated_by_signal"):
        sys.exit(preemption.EXIT_PREEMPTED)
