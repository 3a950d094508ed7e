"""BERT/RoBERTa pretraining runner — TPU-native counterpart of reference
run_pretraining.py.

Capability parity (SURVEY.md §2.1 "Pretraining runner"): CLI > JSON config >
defaults argument handling, device-mesh setup (replacing NCCL DDP), bf16
policy (replacing AMP), gradient accumulation inside one jitted step
(replacing no_sync microbatching), LAMB + warmup-decay schedule, auto-resume
with phase-switch optimizer surgery, contiguous-chunk sharded data streaming,
multi-sink logging, checkpoint cadence with last-3 retention, and the
``training_seq_per_sec`` summary metric (run_pretraining.py:597-599).

Single-host example (smoke config, CPU-runnable):
  python run_pretraining.py --input_dir data/ --output_dir out/ \
      --model_config_file configs/bert_base_config.json \
      --global_batch_size 8 --local_batch_size 8 --steps 3 --max_steps 10
"""

from __future__ import annotations

import argparse
import dataclasses
import math
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bert_pytorch_tpu import optim, pretrain, telemetry
from bert_pytorch_tpu.config import (load_model_config,
                                     parse_args_with_config_file, require_args)
from bert_pytorch_tpu.data import DataLoader, DistributedSampler, ShardedPretrainingDataset
from bert_pytorch_tpu.models import BertForPreTraining, build_pretraining_model
from bert_pytorch_tpu.ops import dropout
from bert_pytorch_tpu.ops.attention import resolve_backend
from bert_pytorch_tpu.ops.pallas.common import device_report
from bert_pytorch_tpu.ops.remat import kept_residual_bytes
from bert_pytorch_tpu.parallel import (MeshSpec, MeshSpecError, create_mesh,
                                       logical_axis_rules)
from bert_pytorch_tpu.parallel import launcher
from bert_pytorch_tpu.parallel.mesh import (AXIS_DATA, AXIS_EXPERT, AXIS_FSDP,
                                            AXIS_PIPE)
from bert_pytorch_tpu.testing import faults
from bert_pytorch_tpu.utils import checkpoint as ckpt
from bert_pytorch_tpu.utils import logging as logger
from bert_pytorch_tpu.utils import preemption
from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache
from bert_pytorch_tpu.utils.dist import (
    agree_on_resume_step,
    get_rank,
    get_world_size,
    is_main_process,
)


def parse_arguments(argv=None) -> argparse.Namespace:
    """Reference parse_arguments (run_pretraining.py:75-177) with TPU-mesh
    flags replacing the CUDA/apex ones."""
    parser = argparse.ArgumentParser(description="TPU BERT pretraining")
    # data / io
    parser.add_argument("--input_dir", type=str, default=None)
    parser.add_argument("--output_dir", type=str, default=None)
    parser.add_argument("--model_config_file", type=str, default=None)
    parser.add_argument("--config_file", type=str, default=None,
                        help="JSON overriding defaults; CLI overrides JSON")
    parser.add_argument("--log_prefix", type=str, default="pretraining")
    # schedule / steps
    parser.add_argument("--max_steps", type=int, default=None,
                        help="total optimizer steps of the phase (t_total)")
    parser.add_argument("--steps", type=int, default=None,
                        help="optimizer steps to run in this invocation")
    parser.add_argument("--previous_phase_end_step", type=int, default=0)
    parser.add_argument("--learning_rate", type=float, default=6e-3)
    parser.add_argument("--lr_decay", type=str, default="poly",
                        choices=["poly", "linear", "cosine", "constant"])
    parser.add_argument("--warmup_proportion", type=float, default=0.2843)
    # batch
    parser.add_argument("--global_batch_size", type=int, default=None)
    parser.add_argument("--local_batch_size", type=int, default=None)
    # masking
    parser.add_argument("--max_predictions_per_seq", type=int, default=20)
    parser.add_argument("--masked_token_fraction", type=float, default=0.15)
    # sequence packing (docs/packing.md; Krell et al. 2021,
    # arXiv:2107.02027): concatenate short samples into one row with
    # block-diagonal attention, per-sequence position restart, and
    # per-sequence NSP heads — ~2x effective phase-1 throughput on real
    # length distributions, with telemetry's padding_efficiency measuring
    # exactly what was gained
    parser.add_argument("--pack_sequences", action="store_true",
                        help="pack short samples into full rows on the fly "
                             "(data/packing.py greedy first-fit-decreasing, "
                             "packed within each shard). Shards that were "
                             "packed OFFLINE (tools/encode_data.py "
                             "--pack_sequences) are detected automatically "
                             "and need no flag")
    parser.add_argument("--max_sequences_per_pack", type=int, default=8,
                        help="cap on sequences per packed row (on-the-fly "
                             "mode; offline-packed shards carry their own). "
                             "Also scales the per-row MLM prediction "
                             "budget: max_predictions_per_seq applies per "
                             "SEQUENCE, as unpacked")
    parser.add_argument(
        "--num_workers", type=int, default=0,
        help="DataLoader producer processes (reference run_pretraining.py:"
             "394-395 num_workers=4). 0 = single background thread — KEEP "
             "THE DEFAULT at BERT shapes: the thread path reads each "
             "shard once, where strided process workers each re-read "
             "every shard (about half the thread path's rate in a CPU "
             "run). >0 pays off only if per-sample featurization grows "
             "to dominate IO (data/loader.py docstring).")
    # held-out evaluation (beyond the reference, which never evaluates
    # during pretraining; uses pretrain.make_eval_step)
    parser.add_argument("--val_input_dir", type=str, default=None,
                        help="directory of held-out HDF5 shards; enables a "
                             "validation MLM-loss pass")
    parser.add_argument("--num_steps_per_eval", type=int, default=200,
                        help="optimizer steps between validation passes")
    parser.add_argument("--eval_batches", type=int, default=16,
                        help="validation batches per pass")
    # device prefetch (data/device_prefetch.py): keep N batches resident
    # on device so data_wait measures only true producer stalls — the one
    # flag shared by every runner
    from bert_pytorch_tpu.data import device_prefetch as dp_cli
    dp_cli.add_cli_args(parser)
    # checkpoint / logging cadence
    parser.add_argument("--num_steps_per_checkpoint", type=int, default=200)
    parser.add_argument("--keep_checkpoints", type=int, default=3)
    parser.add_argument("--checkpoint_write", type=str, default="async",
                        choices=["async", "sync"],
                        help="periodic checkpoint write mode: 'async' "
                             "snapshots the state on device and writes "
                             "from a background thread (the step pays only "
                             "the device-side copy; utils/checkpoint.py), "
                             "'sync' blocks the step for the full "
                             "fetch+serialize+write — the before/after "
                             "that the checkpoint-step p95 in the "
                             "telemetry compares. Final/emergency "
                             "checkpoints are always synchronous")
    parser.add_argument("--checkpoint_layout", type=str, default="gathered",
                        choices=["gathered", "sharded"],
                        help="'gathered' (default) writes one full msgpack "
                             "per checkpoint (state gathered to host); "
                             "'sharded' writes per-process shard files of "
                             "slice records plus an index, records the "
                             "mesh spec in the integrity manifest, and "
                             "loads back under ANY topology (elastic "
                             "resume: save on 8 ways, resume on 4; "
                             "utils/checkpoint.py)")
    parser.add_argument("--skip_final_checkpoint", action="store_true",
                        help="skip the end-of-run checkpoint write. For "
                             "benchmark/capture runs whose artifact is the "
                             "metrics log: at BERT-large the final state is "
                             "multi-GB and the device->host pull can dominate "
                             "a short run's wallclock. A checkpoint requested "
                             "by a termination signal is still written")
    parser.add_argument("--log_steps", type=int, default=1)
    parser.add_argument("--disable_tensorboard", action="store_true",
                        help="skip the TensorBoard sink. Its writer "
                             "backend import (torch) costs ~25s of "
                             "startup on a throttled CPU box — child "
                             "processes that never read TB events (the "
                             "chaos harness, CI smoke runs) skip it; the "
                             "JSONL/CSV/text sinks carry every record "
                             "anyway")
    parser.add_argument("--term_check_steps", type=int, default=10,
                        help="how often (in optimizer steps) to act on a "
                             "received SIGTERM/SIGUSR1: checkpoint and exit "
                             "cleanly. TPU VMs / SLURM preemption send "
                             "SIGTERM with a short grace period; the check "
                             "runs at a fixed step cadence so multi-host "
                             "jobs agree collectively on when to stop. "
                             "0 disables graceful termination")
    # data-path resilience (docs/fault_tolerance.md): HDF5 shard reads
    # retry with backoff (utils/retry.py); startup verification either
    # warn-skips unreadable shards (the reference's stance) or fails fast
    parser.add_argument("--data_read_retries", type=int, default=2,
                        help="retries per HDF5 shard open/read (exponential "
                             "backoff + jitter) before the read is a hard "
                             "failure; transient storage errors cost a "
                             "delay, not the run")
    parser.add_argument("--data_retry_base_s", type=float, default=0.2,
                        help="pre-jitter base backoff for shard-read "
                             "retries (doubles per retry, capped at 30s)")
    parser.add_argument("--shard_error_policy", type=str, default="skip",
                        choices=["skip", "abort"],
                        help="a shard unreadable past the retries at "
                             "STARTUP: 'skip' warns and trains on the "
                             "rest (reference behavior); 'abort' fails "
                             "fast. Mid-stream failures always abort — "
                             "the index space is fixed at startup")
    parser.add_argument("--fault_spec", type=str, default="",
                        help="TEST-ONLY deterministic fault injection "
                             "(testing/faults.py; docs/fault_tolerance.md), "
                             "e.g. 'die@7' or 'shard_errorx2,nonfinite@5'; "
                             "also armable via BERT_FAULTS. Empty disables")
    # telemetry (docs/telemetry.md): step-time decomposition + MFU windows,
    # profiler trace windows, compile events, failure sentinels, heartbeat,
    # hung-step watchdog — canonical flag set shared by every runner
    # (telemetry/cli.py)
    telemetry.add_cli_args(parser, window_default=20, sync_every_default=4)
    # numerics / memory
    parser.add_argument("--dtype", type=str, default="bfloat16",
                        choices=["bfloat16", "float32", "float16"],
                        help="activation dtype; bfloat16 is the TPU "
                             "default (no loss scaling needed). float16 is "
                             "the reference-parity AMP mode and enables a "
                             "dynamic loss scaler (GradScaler analog, "
                             "reference run_pretraining.py:314-318)")
    parser.add_argument("--init_loss_scale", type=float, default=2.0 ** 16,
                        help="fp16 only: initial dynamic loss scale "
                             "(default matches torch GradScaler's 2**16)")
    parser.add_argument("--loss_scale_growth_interval", type=int,
                        default=2000,
                        help="fp16 only: consecutive finite steps before "
                             "the loss scale doubles")
    parser.add_argument("--checkpoint_activations", action="store_true",
                        help="shorthand for --remat full (reference "
                             "checkpointed_forward, modeling.py:503-520)")
    parser.add_argument("--remat", type=str, default=None,
                        choices=["none", "dots", "full"],
                        help="activation rematerialization policy; 'dots' "
                             "(keep matmul outputs and attention's named "
                             "residuals, ops/remat.py: the dropout mask of "
                             "the XLA path at B*H*S*S bytes a layer, the "
                             "flash kernel's output and log-sum-exp; "
                             "recompute all else) unlocks ~2x larger "
                             "microbatches and is the fastest configuration "
                             "on 16GB v5e chips; 'full' keeps nothing")
    parser.add_argument("--attention_backend", type=str, default="auto",
                        choices=["auto", "xla", "pallas", "ring"],
                        help="'auto' picks the measured winner by sequence "
                             "length: XLA <256, fused Pallas kernel >=256")
    parser.add_argument("--compile_cache_dir", type=str, default="",
                        help="persistent XLA compilation cache directory; "
                             "restarted/resumed jobs reuse compiled "
                             "executables instead of recompiling (~minutes "
                             "for BERT-large). Default <checkout>/.jax_cache; "
                             "JAX_COMPILATION_CACHE_DIR, when set, wins over "
                             "both (utils/compile_cache.py)")
    parser.add_argument("--rng_impl", type=str, default="rbg",
                        choices=["rbg", "threefry2x32"],
                        help="dropout PRNG: 'rbg' uses the TPU hardware "
                             "random generator (~16%% faster end-to-end than "
                             "threefry, which synthesizes every mask bit in "
                             "ALU ops); threefry2x32 gives JAX's default "
                             "cross-platform reproducible streams. Under a "
                             "mesh each shard of the batch draws its own "
                             "stream with either (ops/dropout.py)")
    # optimizer
    parser.add_argument("--optimizer", type=str, default="lamb",
                        choices=["lamb", "adamw"])
    parser.add_argument("--weight_decay", type=float, default=0.01)
    parser.add_argument("--max_grad_norm", type=float, default=1.0,
                        help="global-norm gradient clipping of lamb; of adamw "
                             "only with --adamw_clip")
    parser.add_argument("--adamw_clip", action="store_true",
                        help="adamw clips at --max_grad_norm too (the causal "
                             "recipe; off: adamw as it always ran here)")
    parser.add_argument("--adam_beta2", type=float, default=0.999,
                        help="second-moment decay of adamw")
    parser.add_argument("--adam_eps", type=float, default=1e-6,
                        help="epsilon of adamw")
    # K-FAC (SURVEY §2.2)
    parser.add_argument("--kfac", action="store_true")
    parser.add_argument("--kfac_stat_decay", type=float, default=0.95)
    parser.add_argument("--kfac_damping", type=float, default=0.001)
    parser.add_argument("--kfac_kl_clip", type=float, default=0.001)
    parser.add_argument("--kfac_factor_interval", type=int, default=10)
    parser.add_argument("--kfac_inv_interval", type=int, default=100)
    parser.add_argument("--kfac_inv_method", type=str, default="cholesky",
                        choices=["cholesky", "eigen"],
                        help="'cholesky' = damped factor inverses (40x "
                             "faster than TPU eigh at BERT-large factor "
                             "sizes); 'eigen' = eigenbasis preconditioning "
                             "(kfac_pytorch's eigen method)")
    parser.add_argument("--kfac_capture", type=str, default="train",
                        choices=["train", "stats"],
                        help="'train' (default): harvest Kronecker factors "
                             "from microbatch 0 of the training step's own "
                             "backward (the reference's free hook capture, "
                             "run_pretraining.py:320-355 — no extra "
                             "forward/backward at factor_interval=1). "
                             "'stats': decoupled stats pass on "
                             "--kfac_stats_batch rows every "
                             "factor_interval steps (pp strategies use "
                             "this; it is also the knob for stats batches "
                             "smaller than a microbatch)")
    parser.add_argument("--kfac_capture_microbatches", type=str,
                        default="first", choices=["first", "all"],
                        help="fused capture source on factor-due steps: "
                             "'first' taps microbatch 0 only (capture "
                             "cost amortizes over the accumulation); "
                             "'all' accumulates statistics over every "
                             "microbatch's backward — kfac_pytorch's "
                             "exact accumulation semantics, capture cost "
                             "proportional to accumulation_steps")
    parser.add_argument("--kfac_stats_batch", type=int, default=16,
                        help="total sequences (strided across the global "
                             "batch, so every data shard contributes) used "
                             "for the factor-statistics pass; the tapped "
                             "model's activation/cotangent captures are the "
                             "K-FAC memory peak, and factor EMAs over "
                             "factor_interval steps don't need the full "
                             "batch (0 = use the whole microbatch)")
    parser.add_argument("--kfac_skip_layers", type=str, nargs="+",
                        default=["embeddings", "predictions"])
    # mesh
    parser.add_argument("--mesh", type=str, default="dp=-1",
                        help="declarative mesh spec, e.g. "
                             "'dp=4,fsdp=2,pipe=2,seq=1' (keys accept "
                             "pp/sp/tp aliases; parallel/mesh.py MeshSpec). "
                             "Any axis product is expressible — rules, "
                             "device mesh, and collective wiring derive "
                             "from the spec (docs/parallelism.md). The "
                             "default puts every device on the data axis")
    parser.add_argument("--seed", type=int, default=42)

    args = parse_args_with_config_file(parser, argv)
    require_args(args, ["input_dir", "output_dir", "model_config_file",
                        "max_steps", "global_batch_size", "local_batch_size"])
    return args


def _devices_holding(tree) -> int:
    """How many distinct devices hold an addressable shard of ``tree``."""
    return len({shard.device for leaf in jax.tree_util.tree_leaves(tree)
                if isinstance(leaf, jax.Array)
                for shard in leaf.addressable_shards})


def _share_on_first_device(tree) -> float:
    """Fraction of ``tree``'s bytes that the first local device holds: 1.0
    when replicated, ~1/n when sharded n ways."""
    first = jax.local_devices()[0]
    leaves = [leaf for leaf in jax.tree_util.tree_leaves(tree)
              if isinstance(leaf, jax.Array)]
    held = sum(shard.data.nbytes for leaf in leaves
               for shard in leaf.addressable_shards if shard.device == first)
    return held / max(1, sum(leaf.nbytes for leaf in leaves))


def setup_training(args):
    """Mesh + logging + accumulation math (reference setup_training,
    run_pretraining.py:180-230)."""
    jax.config.update("jax_default_prng_impl", args.rng_impl)
    cache_dir = enable_compile_cache(args.compile_cache_dir)
    spec = MeshSpec.parse(args.mesh)
    spec.validate(packed=bool(args.pack_sequences))
    # The first touch of the devices: the rendezvous on a pod, the chip
    # runtime's start.
    with telemetry.span("startup:backend"):
        launcher.initialize()
        mesh = create_mesh(spec.mesh_config())
    # Record the RESOLVED spec (data=-1 replaced by the realized size):
    # checkpoint manifests and telemetry label topologies with it.
    args.mesh_spec = dataclasses.replace(
        spec, data=mesh.shape[AXIS_DATA] // spec.dcn_data)
    # Fail fast if any batch shard's pipe/seq/model replicas span hosts:
    # the per-process loaders would feed the same global rows different data.
    pretrain.check_batch_process_locality(mesh)
    args.model_output_dir = os.path.join(args.output_dir, "pretrain_ckpts")
    if is_main_process():
        os.makedirs(args.model_output_dir, exist_ok=True)

    # Telemetry sink shared between the logger (ordinary train records) and
    # the TrainTelemetry facade (its records go ONLY there); built in main().
    args.telemetry_jsonl = telemetry.default_jsonl_path(
        args, args.output_dir, args.log_prefix)
    args.heartbeat_file = args.heartbeat_file or os.path.join(
        args.output_dir, "heartbeat.json")
    args.profile_dir = args.profile_dir or os.path.join(
        args.output_dir, "profile")
    args.telemetry_sink = logger.JSONLHandler(
        args.telemetry_jsonl, overwrite=False, is_primary=is_main_process())
    handlers = [
        logger.StreamHandler(verbose=is_main_process(),
                             is_primary=is_main_process()),
        logger.FileHandler(
            os.path.join(args.output_dir, args.log_prefix + ".txt"),
            overwrite=False, is_primary=is_main_process()),
        logger.CSVHandler(
            os.path.join(args.output_dir, args.log_prefix + "_metrics.csv"),
            overwrite=False, is_primary=is_main_process()),
        args.telemetry_sink,
    ]
    if not args.disable_tensorboard:
        handlers.insert(2, logger.TensorBoardHandler(
            os.path.join(args.output_dir, "tensorboard"),
            is_primary=is_main_process()))
    logger.init(handlers=handlers)
    logger.info(
        f"mesh initialized: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
        f"({jax.process_count()} processes, {len(jax.devices())} devices, "
        f"spec {args.mesh_spec.canonical()})"
    )
    args.device_report = device_report()
    logger.info(
        "running on {platform} ({device_kind} x {device_count}), Pallas "
        "kernels {kernels}".format(**args.device_report)
        + f"; compile cache {cache_dir}")
    if args.rng_impl != "threefry2x32":
        # rbg streams are not stable across platforms/XLA versions the way
        # threefry is — say so once, loudly, since it changes dropout draws.
        logger.info(
            f"dropout PRNG: {args.rng_impl} (hardware RNG; streams are not "
            "reproducible across platforms/XLA versions — pass --rng_impl "
            "threefry2x32 for JAX's portable default)")

    if args.dtype == "float16":
        if args.kfac:
            raise ValueError(
                "--dtype float16 is the first-order parity mode; K-FAC "
                "runs in bf16/f32 (no loss scaler needed on TPU)")
        if args.mesh_spec.pipe > 1:
            raise ValueError(
                "--dtype float16 is not supported with pipeline "
                "parallelism; use bfloat16 (the TPU default)")

    # Accumulation math (reference :213-228), in global terms: one optimizer
    # step consumes global_batch_size sequences as accumulation_steps
    # microbatches of local_batch_size per data shard.
    n_data = (mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
              * mesh.shape[AXIS_EXPERT])
    global_microbatch = args.local_batch_size * n_data
    if args.global_batch_size % global_microbatch != 0:
        raise ValueError(
            f"global_batch_size={args.global_batch_size} must be divisible by "
            f"local_batch_size*data_shards={global_microbatch}"
        )
    args.accumulation_steps = args.global_batch_size // global_microbatch
    if (args.mesh_spec.seq > 1 and args.mesh_spec.pipe == 1
            and args.attention_backend != "ring"):
        # A seq axis exists to avoid O(S^2) dense attention; never
        # silently densify (same stance as ops/attention.py's
        # non-divisible check). seq x pipe instead runs the manual ring
        # body inside the pipeline's shard_map (pretrain.py).
        logger.info("mesh seq>1: switching attention_backend to "
                    "'ring' (was '%s')" % args.attention_backend)
        args.attention_backend = "ring"
    if args.global_batch_size % jax.process_count() != 0:
        raise ValueError("global_batch_size must divide by process count")
    args.host_batch_per_step = args.global_batch_size // jax.process_count()
    return args, mesh


def _kept_across_remat(model, config, micro_batch, seq) -> str:
    """The start-up line that says what the rematerialized regions keep
    across remat by name (ops/remat.py) and what it costs, from shapes: per
    region and micro-batch on one data shard. The encoder's regions are its
    layers, which keep attention's names under 'dots'; a decoder family says
    what it asked 'full' for and in what shapes
    (``CausalDecoder.kept_across_remat``)."""
    asked = (model.kept_across_remat() if hasattr(model, "kept_across_remat")
             else dict(keeping=(), regions=config.num_hidden_layers,
                       heads=config.num_attention_heads,
                       head_dim=config.head_dim))
    dropout = getattr(config, "attention_probs_dropout_prob", 0.0) > 0.0
    path = resolve_backend(model.attention_backend, seq, dropout)
    kept = kept_residual_bytes(
        model.remat, path, dropout, batch=micro_batch, seq=seq,
        heads=asked["heads"], head_dim=asked["head_dim"], dtype=model.dtype,
        keeping=asked["keeping"])
    line = f"remat {model.remat}, attention path {path} at seq {seq}: "
    if not kept:
        return line + "no named residual kept across remat"
    total = sum(kept.values()) * asked["regions"]
    return line + "kept across remat per region and micro-batch: " + ", ".join(
        f"{name} {size} B" for name, size in kept.items()
    ) + f" ({total / 1e9:.2f} GB over {asked['regions']} regions)"


def prepare_model(args, mesh):
    """Model config + auto-resume discovery (reference prepare_model,
    run_pretraining.py:233-274)."""
    # The family (and with it the model and the objective) comes from the
    # file's ``model_type``: none is BERT, ``nemotron_h`` the hybrid decoder,
    # ``laguna`` the decoder of mixed window and full attention, ``phi4flash``
    # the decoder of selective scans, differential attention and one kept
    # memory and K/V, ``zaya`` the decoder of attention inside a latent and a
    # router that hands its state from layer to layer, ``qwen3_next`` the
    # decoder of gated delta-rule layers and gated softmax attention.
    config = load_model_config(args.model_config_file)
    if config.vocab_size % 8 != 0:  # MXU-friendly padding (reference :237)
        config.vocab_size += 8 - (config.vocab_size % 8)

    model = build_pretraining_model(
        config,
        dtype={"bfloat16": jnp.bfloat16, "float16": jnp.float16,
               "float32": jnp.float32}[args.dtype],
        remat=args.remat or ("full" if args.checkpoint_activations else "none"),
        attention_backend=args.attention_backend,
    )
    args.objective = getattr(model, "objective", "mlm")
    if args.objective == "causal_lm" and (
            args.kfac or args.pack_sequences or args.val_input_dir
            or args.mesh_spec.pipe > 1):
        raise ValueError(
            "the causal_lm objective trains through the plain step: no "
            "--kfac, --pack_sequences, --val_input_dir or pipe axis")

    # Newest VERIFIED checkpoint: the walk-back verifies each retained
    # checkpoint's integrity manifest and skips corrupt/unreadable files
    # (utils/checkpoint.py; docs/fault_tolerance.md) instead of crashing
    # the job — collecting what it skipped for the resume record below.
    skipped: list = []
    found = ckpt.load_latest_checkpoint(
        args.model_output_dir, on_skip=skipped.append)
    # Multi-host: all processes must resume from the SAME step even when
    # they observe the shared checkpoint dir differently (utils/dist.py).
    agreed = agree_on_resume_step(None if found is None else found[0])
    if agreed is None:
        found = None
    elif found is None or found[0] != agreed:
        # This process must re-load the agreed step; failure here is fatal
        # (no silent divergence).
        found = (agreed, ckpt.load_checkpoint(
            ckpt.checkpoint_path(args.model_output_dir, agreed)))
    checkpoint = None
    global_step = 0
    args.resume_step = 0
    if found is None and skipped:
        # The worst recovery case — retained checkpoints exist but NONE
        # verified/loaded — must be a loud, auditable artifact, not just
        # transient warnings before a silent restart from step 0.
        logger.info(
            f"NO loadable checkpoint: all {len(skipped)} retained "
            "checkpoint(s) failed verification/decode; training restarts "
            "from scratch (tools/verify_checkpoint.py audits the damage)")
        args.telemetry_sink.write_record({
            "kind": "fault", "tag": "telemetry",
            "fault": "resume_walk_back_exhausted", "injected": False,
            "step": 0, "skipped": skipped,
        })
    if found is not None:
        resume_step, checkpoint = found
        args.resume_step = resume_step
        if args.previous_phase_end_step > resume_step:
            raise ValueError(
                f"previous_phase_end_step={args.previous_phase_end_step} cannot "
                f"be larger than resume_step={resume_step}")
        global_step = resume_step - args.previous_phase_end_step
        logger.info(f"Resume from step {resume_step} checkpoint"
                    + (f" ({len(skipped)} newer checkpoint(s) skipped as "
                       "corrupt/unreadable)" if skipped else ""))
        # Telemetry resume record (schema v1): which step resumed and
        # exactly what the walk-back passed over — recovery decisions
        # become auditable artifacts, not log prose.
        args.telemetry_sink.write_record({
            "kind": "resume", "tag": "telemetry", "step": int(resume_step),
            "skipped": skipped,
        })
    return model, config, checkpoint, global_step


def prepare_optimizer(args, params_example=None):
    """LAMB/AdamW + schedule (reference prepare_optimizers,
    run_pretraining.py:277-357)."""
    schedule = optim.make_schedule(
        args.lr_decay, args.learning_rate, args.warmup_proportion, args.max_steps)
    mask = optim.no_decay_mask
    if args.optimizer == "lamb":
        tx = optim.lamb(
            schedule, weight_decay=args.weight_decay,
            weight_decay_mask=mask, max_grad_norm=args.max_grad_norm)
    else:
        tx = optim.adamw(
            schedule, b2=args.adam_beta2, eps=args.adam_eps,
            weight_decay=args.weight_decay, weight_decay_mask=mask,
            max_grad_norm=args.max_grad_norm if args.adamw_clip else None)
    if args.dtype == "float16":
        # Reference-parity AMP: fp16 activations + dynamic loss scaling
        # (GradScaler, run_pretraining.py:314-318); scaler state rides in
        # the checkpoint's optimizer tree like the reference's 'scaler'.
        tx = optim.dynamic_loss_scale(
            tx, init_scale=args.init_loss_scale,
            growth_interval=args.loss_scale_growth_interval)
    return tx, schedule


def prepare_dataset(args, config, checkpoint):
    """HDF5 discovery + tokenizer-derived mask id + sharded streaming
    (reference prepare_dataset, run_pretraining.py:360-402)."""
    input_files = []
    if os.path.isfile(args.input_dir):
        input_files.append(args.input_dir)
    elif os.path.isdir(args.input_dir):
        # sorted: rglob order is filesystem-dependent, and multi-host runs
        # must agree on the index space the sampler chunks over.
        input_files = sorted(
            str(p) for p in Path(args.input_dir).rglob("*.hdf5")
            if p.is_file())

    # Data-path resilience (docs/fault_tolerance.md): retried shard IO,
    # startup skip-vs-abort policy, fault records into the telemetry JSONL.
    resilience = dict(
        read_retries=args.data_read_retries,
        retry_base_delay_s=args.data_retry_base_s,
        shard_error_policy=args.shard_error_policy,
        on_fault=args.telemetry_sink.write_record)
    if args.objective == "causal_lm":
        # Rows of token ids, every row full: nothing to mask, nothing to pack.
        from bert_pytorch_tpu.data import TokenRowsDataset
        dataset = TokenRowsDataset(input_files, **resilience)
        args.packed, args.pack_k = False, 1
        sampler = DistributedSampler(
            dataset, num_replicas=jax.process_count(),
            rank=jax.process_index())
        if checkpoint is not None and "sampler" in checkpoint:
            sampler.load_state_dict(checkpoint["sampler"])
        loader = DataLoader(dataset, sampler,
                            batch_size=args.host_batch_per_step,
                            drop_last=True, num_workers=args.num_workers)
        logger.info(f"Rows of token ids in dataset: {len(dataset)}")
        return loader, sampler, None

    mask_token_id = getattr(config, "mask_token_id", None)
    vocab_file = getattr(config, "vocab_file", None)
    if mask_token_id is None and vocab_file and os.path.exists(vocab_file):
        from bert_pytorch_tpu.data.tokenization import (
            get_bpe_tokenizer, get_wordpiece_tokenizer)
        kind = getattr(config, "tokenizer", "wordpiece")
        lowercase = getattr(config, "lowercase", True)
        tok = (get_wordpiece_tokenizer(vocab_file, uppercase=not lowercase)
               if kind == "wordpiece"
               else get_bpe_tokenizer(vocab_file, uppercase=not lowercase))
        # WordPiece convention first, then the BPE/RoBERTa one.
        mask_token_id = tok.token_to_id("[MASK]")
        if mask_token_id is None:
            mask_token_id = tok.token_to_id("<mask>")
    if mask_token_id is None:
        mask_token_id = 4  # synthetic-data default
        logger.info("No vocab_file/mask_token_id in model config; "
                    f"using mask_token_id={mask_token_id}")

    dataset = ShardedPretrainingDataset(
        input_files, int(mask_token_id), args.max_predictions_per_seq,
        args.masked_token_fraction, vocab_size=int(config.vocab_size),
        seed=args.seed + get_rank(), **resilience)
    # Sequence packing (docs/packing.md): offline-packed shards are
    # detected from the file layout; --pack_sequences packs on the fly.
    # Either way downstream sees packed rows with sequence_ids and
    # per-sequence NSP labels/cls positions.
    args.packed = bool(dataset.packed)
    args.pack_k = dataset.max_sequences_per_pack if dataset.packed else 1
    if dataset.packed:
        if args.pack_sequences:
            logger.info("shards are offline-packed; --pack_sequences "
                        "is a no-op")
        logger.info(f"offline-packed shards: up to {args.pack_k} "
                    "sequences per row")
    elif args.pack_sequences:
        from bert_pytorch_tpu.data import PackedPretrainingDataset
        dataset = PackedPretrainingDataset(
            dataset, max_sequences_per_pack=args.max_sequences_per_pack)
        args.packed = True
        args.pack_k = args.max_sequences_per_pack
        logger.info(
            f"on-the-fly sequence packing: {dataset.n_samples} samples -> "
            f"{len(dataset)} packed rows "
            f"(occupancy {dataset.occupancy:.3f}, up to "
            f"{args.pack_k} sequences per row)")
    sampler = DistributedSampler(
        dataset, num_replicas=jax.process_count(), rank=jax.process_index())
    if checkpoint is not None and "sampler" in checkpoint:
        sampler.load_state_dict(checkpoint["sampler"])
    loader = DataLoader(dataset, sampler,
                        batch_size=args.host_batch_per_step, drop_last=True,
                        num_workers=args.num_workers)
    logger.info(f"Samples in dataset: {len(dataset)}")
    logger.info(f"Samples per process: {len(sampler)}")
    logger.info(f"Sampler starting index: {sampler.index}")

    val_loader = None
    if args.val_input_dir:
        val_files = sorted(
            str(p) for p in Path(args.val_input_dir).rglob("*.hdf5")
            if p.is_file())
        val_dataset = ShardedPretrainingDataset(
            val_files, int(mask_token_id), args.max_predictions_per_seq,
            args.masked_token_fraction, vocab_size=int(config.vocab_size),
            seed=args.seed + 7919 + get_rank(), **resilience)
        val_sampler = DistributedSampler(
            val_dataset, num_replicas=jax.process_count(),
            rank=jax.process_index())
        val_loader = DataLoader(val_dataset, val_sampler,
                                batch_size=args.host_batch_per_step,
                                drop_last=True)
        logger.info(f"Validation samples: {len(val_dataset)}")
    return loader, sampler, val_loader


def main(args) -> dict:
    # Start-up (docs/telemetry.md "Start-up"): the startup:* spans from here
    # to the loop are kept until the first update, which emits them as the
    # run's one ``startup`` record.
    startup = telemetry.startup_open()
    with telemetry.span("startup:setup"):
        args, mesh = setup_training(args)
    with telemetry.span("startup:model"):
        model, config, checkpoint, global_step = prepare_model(args, mesh)
    with telemetry.span("startup:optimizer"):
        tx, schedule = prepare_optimizer(args)
    with telemetry.span("startup:data"):
        loader, sampler, val_loader = prepare_dataset(args, config, checkpoint)

        # What the data says of the step's shapes (the decoder families read
        # their first row for it).
        rules = logical_axis_rules(args.mesh_spec)
        causal_lm = args.objective == "causal_lm"
        if causal_lm:
            # No position table: the rows' own length is the sequence length,
            # and the parameters do not depend on it (a short sample
            # initializes).
            seq_len = int(loader.dataset[0]["input_ids"].shape[-1])
            sample = (jnp.zeros((1, config.init_sample_length), jnp.int32),)
        else:
            seq_len = config.max_position_embeddings
            sample = (jnp.zeros((1, seq_len), jnp.int32),) * 3
        # Packed rows: per-sequence NSP labels [B, K] + the packing arrays;
        # max_predictions_per_seq stays a per-SEQUENCE budget, so the per-ROW
        # MLM gather cap scales by the pack limit.
        packed = getattr(args, "packed", False)
        if packed:
            # Catches OFFLINE-packed shards too (auto-detected, no flag) —
            # setup_training's early check only sees --pack_sequences.
            try:
                args.mesh_spec.validate(packed=True)
            except MeshSpecError as e:
                raise ValueError(
                    f"packed pretraining data: {e}; re-encode the shards "
                    "unpacked or drop the seq axis") from None
        eff_max_pred = args.max_predictions_per_seq * (
            args.pack_k if packed else 1)
        batch_spec = {"input_ids": 3, "segment_ids": 3, "input_mask": 3,
                      "masked_lm_labels": 3,
                      "next_sentence_labels": 3 if packed else 2}
        if packed:
            batch_spec.update({"sequence_ids": 3, "cls_positions": 3})
        if causal_lm:
            batch_spec = {"input_ids": 3}
    with mesh:
        with telemetry.span("startup:state_init"):
            fp16 = args.dtype == "float16"
            shardings = pretrain.state_shardings(mesh, model, rules, sample,
                                                 loss_scaled=fp16)
            b_shardings = pretrain.batch_shardings(
                mesh, batch_spec, seq_sharded=args.mesh_spec.seq > 1)
            init_fn = pretrain.make_init_fn(model, tx, sample, shardings)
            # The init program's own ``compile`` record (fn "init_state":
            # traced, lowered, compiled or loaded, persisted or not), held
            # until the telemetry that emits it exists.
            init_compiles = []
            state = telemetry.CompileMonitor(
                emit=init_compiles.append).instrument(
                    init_fn, "init_state")(jax.random.PRNGKey(args.seed))

            if checkpoint is not None:
                with telemetry.span("startup:restore"):
                    # Restore onto an ABSTRACT template (shapes/dtypes
                    # only), not a device_get of the live state: on a
                    # multi-host fsdp/tp mesh the live state has
                    # non-addressable shards that device_get cannot fetch.
                    # Every process reads the full file and device_put
                    # slices out its addressable shards of the target
                    # sharding.
                    abstract = jax.eval_shape(
                        init_fn, jax.random.PRNGKey(args.seed))
                    params = ckpt.restore_tree(
                        abstract.params, checkpoint["model"])
                    opt_state = ckpt.restore_tree(
                        abstract.opt_state, checkpoint["optimizer"])
                    state = pretrain.TrainState(
                        params=jax.device_put(params, shardings.params),
                        opt_state=jax.device_put(
                            opt_state, shardings.opt_state),
                        rng=state.rng)
                    if args.resume_step >= args.previous_phase_end_step > 0:
                        # Phase-2 surgery (reference
                        # run_pretraining.py:298-309): schedule hyperparams
                        # come from the new config; only the optimizer step
                        # counter is rewritten.
                        state = state.replace(opt_state=optim.reset_count(
                            state.opt_state, global_step))
                        logger.info("Phase switch: optimizer count reset "
                                    f"to {global_step}")

            # Where the state really lives (a mesh built over four devices does
            # not by itself put anything on the last three): logged here,
            # stamped into the run summary with the first batch's placement.
            placement = {
                "params_devices": _devices_holding(state.params),
                "opt_state_devices": _devices_holding(state.opt_state),
                "params_share_on_first_device": round(
                    _share_on_first_device(state.params), 4),
            }
            logger.info(
                "state placed: params on {params_devices} device(s), optimizer "
                "state on {opt_state_devices}; the first device holds "
                "{params_share_on_first_device:.0%} of the parameter bytes"
                .format(**placement))

            # Grad-health due gate must count from THIS run's start: the host
            # reads it on a run-local 0-based sync cadence, while the restored
            # optimizer count is absolute — a resume step that is not a
            # multiple of the cadence would otherwise push every due step
            # onto an unsynced step (zero records for the whole resumed run).
            stats_phase = int(jax.device_get(
                optim.opt_step_count(state.opt_state)))

        with telemetry.span("startup:step_build"):
            kfac_obj = kfac_state = kfac_shardings = None
            kfac_fused = False
            if args.kfac:
                kfac_fused = args.kfac_capture == "train"
                if kfac_fused and args.mesh_spec.pipe > 1:
                    # The pipeline step has no fused-capture path (factors
                    # would need per-stage reassembly); fall back to the
                    # decoupled stats pass.
                    logger.info("kfac_capture=train is not supported with "
                                "pipeline parallelism; using 'stats'")
                    kfac_fused = False
                # Tapped twin of the model (same params, factor-capture taps on;
                # reference drives kfac_pytorch hooks at run_pretraining.py:320-355).
                # The fused-capture twin keeps the main model's remat so the
                # tapped microbatch-0 backward fits the same memory budget; the
                # stats-pass twin runs a small decoupled batch where remat only
                # costs recompute.
                model_tapped = BertForPreTraining(
                    config, dtype=model.dtype,
                    remat=model.remat if kfac_fused else "none",
                    attention_backend=args.attention_backend, kfac_tap=True)
                apply_loss, tap_shape_fn = pretrain.make_kfac_fns(
                    model_tapped, next_sentence=bool(config.next_sentence),
                    max_pred_per_seq=eff_max_pred)
                kfac_obj = optim.KFAC(
                    apply_loss, tap_shape_fn,
                    factor_decay=args.kfac_stat_decay,
                    damping=args.kfac_damping,
                    kl_clip=args.kfac_kl_clip,
                    inv_method=args.kfac_inv_method,
                    skip_layers=tuple(args.kfac_skip_layers))
                micro_b = args.global_batch_size // args.accumulation_steps
                sample_mb = {
                    "input_ids": np.zeros((micro_b, seq_len), np.int32),
                    "segment_ids": np.zeros((micro_b, seq_len), np.int32),
                    "input_mask": np.zeros((micro_b, seq_len), np.int32),
                    "masked_lm_labels": np.zeros((micro_b, seq_len), np.int32),
                    "next_sentence_labels": np.zeros((micro_b,), np.int32),
                }
                kfac_state = kfac_obj.init(state.params, sample_mb)
                kfac_shardings = optim.kfac_state_shardings(mesh, kfac_state)
                if checkpoint is not None and "preconditioner" in checkpoint:
                    kfac_state = ckpt.restore_tree(
                        kfac_state, checkpoint["preconditioner"])
                    kfac_state = jax.device_put(kfac_state, kfac_shardings)
                    # Recompute qa/qg from the restored factors: the checkpoint
                    # may hold the OTHER inv_method's operators (eigenvectors vs
                    # damped inverses share the same state slots/shapes), and a
                    # mid-interval resume would otherwise precondition with the
                    # wrong operator for up to inv_interval steps with no error.
                    kfac_state = kfac_obj.update_inverses(kfac_state)
                    logger.info("Restored K-FAC preconditioner state "
                                "(inverses recomputed from factors)")
                else:
                    kfac_state = jax.device_put(kfac_state, kfac_shardings)
                logger.info(
                    f"K-FAC enabled: {len(kfac_obj.specs)} layer groups, "
                    f"capture={'train (fused)' if kfac_fused else 'stats'}, "
                    f"damping={args.kfac_damping}, kl_clip={args.kfac_kl_clip}, "
                    f"factor_interval={args.kfac_factor_interval}, "
                    f"inv_interval={args.kfac_inv_interval}")

            dropout.forget_draws()  # draw_shards() below speaks of this step
            if args.mesh_spec.pipe > 1:
                if args.accumulation_steps < mesh.shape[AXIS_PIPE]:
                    raise ValueError(
                        f"pp needs accumulation_steps >= pipeline stages "
                        f"({args.accumulation_steps} < "
                        f"{mesh.shape[AXIS_PIPE]}); "
                        "raise global_batch_size or lower local_batch_size")
                train_step = pretrain.make_pp_train_step(
                    model, tx, mesh, schedule=schedule,
                    next_sentence=bool(config.next_sentence),
                    shardings=shardings, batch_shardings_=b_shardings,
                    max_pred_per_seq=eff_max_pred,
                    kfac=kfac_obj, kfac_shardings=kfac_shardings,
                    stats_every=telemetry.stats_every(args),
                    stats_phase=stats_phase)
            else:
                train_step = pretrain.make_train_step(
                    model, tx, schedule=schedule, mesh=mesh,
                    next_sentence=bool(getattr(config, "next_sentence", False)),
                    shardings=shardings, batch_shardings_=b_shardings,
                    max_pred_per_seq=eff_max_pred,
                    kfac=kfac_obj, kfac_shardings=kfac_shardings,
                    kfac_capture_model=model_tapped if kfac_fused else None,
                    kfac_factor_interval=args.kfac_factor_interval,
                    kfac_inv_interval=args.kfac_inv_interval if kfac_fused else 0,
                    kfac_capture_microbatches=args.kfac_capture_microbatches,
                    loss_scale=fp16,
                    stats_every=telemetry.stats_every(args),
                    stats_phase=stats_phase)

            # Telemetry (docs/telemetry.md): JSONL sink shared with the logger,
            # step-time decomposition windows, profiler trace window, compile
            # attribution, failure sentinels, rank-0 heartbeat. flops_per_seq is
            # refreshed once the DATA sequence length is known (phase-1 data is
            # 128 tokens while max_position_embeddings stays 512).
            from bert_pytorch_tpu.utils import flops as flops_util

            def flops_per_seq(seq):
                if causal_lm:
                    return flops_util.causal_lm_train_flops_per_seq(config, seq)
                return flops_util.bert_train_flops_per_seq(
                    config, seq, eff_max_pred,
                    next_sentence=bool(config.next_sentence))

            tele = telemetry.from_args(
                args,
                sink=args.telemetry_sink,
                is_primary=is_main_process(),
                seq_per_step=args.global_batch_size,
                flops_per_seq=flops_per_seq(seq_len),
                # Padding-aware accounting: the step's token budget; the train
                # step's real_tokens metric divides out the pads
                # (padding_efficiency in the window records).
                tokens_per_step=args.global_batch_size * seq_len,
                output_dir=args.output_dir,
                process="pretrain")
            tele.attach_loader(loader)
            for record in init_compiles:  # ahead of the step's own
                tele.compile_monitor.note(record)
            train_step = tele.instrument(train_step, "train_step")

            eval_step = None
            if val_loader is not None:
                from bert_pytorch_tpu.parallel import batch_sharding

                eval_step = tele.instrument(
                    pretrain.make_eval_step(
                        model, next_sentence=bool(config.next_sentence)),
                    "eval_step")
                # Keys follow the batch (offline-packed validation shards add
                # sequence_ids/cls_positions); every array shards the same way.
                eval_sharding = batch_sharding(mesh)

                # Every pass evaluates the SAME deterministic slice: the sampler
                # is reset to 0 first (the loader's prefetch over-advances it by
                # a race-dependent amount otherwise), and the batch count is a
                # pure function of the dataset size — so multi-host runs execute
                # the same number of collective eval steps on every host, and
                # logged val losses are comparable across passes and reruns.
                eval_n_batches = min(
                    args.eval_batches,
                    len(val_loader.sampler) // args.host_batch_per_step)

                def run_validation(params, step_no, epoch_no):
                    """Held-out MLM(+NSP) loss (the reference never evaluates
                    during pretraining)."""
                    if eval_n_batches == 0:
                        return
                    val_loader.sampler.index = 0
                    loss_sum = acc_sum = 0.0
                    n = 0
                    for vb in val_loader:
                        vloss, vacc = eval_step(
                            params, pretrain.put_batch(
                                vb, {k: eval_sharding for k in vb}))
                        loss_sum += float(vloss)
                        acc_sum += float(vacc)
                        n += 1
                        if n >= eval_n_batches:
                            break
                    logger.log(tag="val", step=step_no, epoch=epoch_no,
                               average_loss=loss_sum / n,
                               mlm_accuracy=acc_sum / n)

        steps_this_run = args.steps or (args.max_steps - global_step)
        steps_this_run = min(steps_this_run, args.max_steps - global_step)
        logger.info(f"Starting at global step {global_step}; running "
                    f"{steps_this_run} steps "
                    f"(accumulation_steps={args.accumulation_steps})")

        epoch = int(checkpoint["epoch"]) if checkpoint else 0
        step_in_run = 0
        train_start = time.perf_counter()
        samples_seen = 0
        last_metrics = {}
        # Graceful preemption (docs/fault_tolerance.md; beyond the
        # reference, whose only fault model is die-and-resubmit, SURVEY
        # §5.3): TPU-VM maintenance events and SLURM preemption deliver
        # SIGTERM/SIGUSR1 with a short grace period; an operator's Ctrl-C
        # delivers SIGINT. The shared GracefulStop handler only sets a
        # flag; the loop acts on it at a fixed step cadence so every host
        # of a multi-host job reaches the agreement collective at the same
        # step, then the normal end-of-run epilogue writes the final
        # checkpoint and __main__ exits with EXIT_PREEMPTED.
        terminated = False
        stop = preemption.GracefulStop()
        if args.term_check_steps:
            stop.install()
        # Deterministic fault injection (testing/faults.py): inert unless
        # --fault_spec / BERT_FAULTS armed it — the chaos harness's hooks
        # into this loop (die/term/hang after the checkpoint block,
        # metric poisoning before the sentinel sees the step).
        fault_plan = (faults.arm(args.fault_spec) if args.fault_spec
                      else faults.get_plan())
        # The DATA sequence length (what the FLOP/MFU accounting must use;
        # phase-1 data is 128 tokens while max_position_embeddings stays 512).
        data_seq_len = None
        draw_shards_logged = False
        # Position of the last TRAINED sample this epoch. The sampler's live
        # ``index`` runs ahead of training by the loader queue plus the
        # device_prefetch depth (the reference's checkpoints have the same
        # skew from its 4 DataLoader workers, src/dataset.py:401-425 — data
        # those pipelines had buffered is silently skipped on resume), and
        # near the end of an epoch its live ``epoch`` does too: the feed
        # crosses the boundary batches before the loop does. Checkpoints
        # therefore save THIS counter and the TRAINED epoch (``epoch``, taken
        # from the batch just trained), not the sampler's live pair.
        trained_index = sampler.index
        # Epoch boundaries the loop trained across, and what it waited for
        # the first batch of each new epoch, summed (run summary).
        feed_epoch_boundaries = 0
        feed_boundary_wait_s = 0.0

        def sampler_checkpoint_state():
            s = sampler.state_dict()
            s["index"], s["epoch"] = trained_index, epoch
            return s

        def dispatch_step(state, batch, kfac_state, global_step):
            """One optimizer step's dispatch (the only Python between
            batches; returns before the device finishes — telemetry's
            step timer owns the sync)."""
            if kfac_fused:
                # In-train capture: the step harvests factors from
                # microbatch 0's own backward, rebuilds inverses
                # in-jit on due steps from the factors it just
                # captured, and preconditions with them — the
                # exact kfac_pytorch optimizer.step() ordering
                # (hooks during backward, due inverses, update).
                # Both cadences are lax.cond-gated inside the one
                # compiled step; no host round trips.
                state, metrics, kfac_state = train_step(
                    state, batch, kfac_state)
            elif kfac_obj is not None:
                # kfac_pytorch cadence: factors (EMA) every
                # factor_interval steps from the current data, inverses
                # every inv_interval steps; both fire on the first step.
                if global_step % args.kfac_factor_interval == 0:
                    n_stats = args.kfac_stats_batch
                    if n_stats and n_stats < batch["input_ids"].shape[1]:
                        # Strided rows: every data shard of the global
                        # batch contributes to the statistics (a [:n]
                        # head-slice would sample only shard 0's data).
                        stride = batch["input_ids"].shape[1] // n_stats
                        mb0 = {k: v[0][::stride][:n_stats]
                               for k, v in batch.items()}
                    else:
                        mb0 = {k: v[0] for k, v in batch.items()}
                    kfac_state = kfac_obj.update_factors(
                        kfac_state, state.params, mb0,
                        jax.random.fold_in(
                            jax.random.PRNGKey(args.seed + 17), global_step))
                if global_step % args.kfac_inv_interval == 0:
                    kfac_state = kfac_obj.update_inverses(kfac_state)
                state, metrics = train_step(state, batch, kfac_state)
            else:
                state, metrics = train_step(state, batch)
            return state, metrics, kfac_state

        # Handlers stay installed through the final checkpoint write:
        # preemption re-delivers SIGTERM during the grace period, and
        # the default disposition would kill the write mid-file. The
        # finally also un-installs them on exceptions (in-process
        # callers must not inherit a handler over a dead flag).
        prefetcher = None
        try:
            # The feed (pretrain.device_prefetch; data/device_prefetch.py):
            # ONE for the whole run. A background thread keeps
            # --device_prefetch batches resident on device, so data_wait
            # below measures only true producer stalls and the staging
            # share reports as the h2d_wait sub-phase; at the end of an
            # epoch that thread, not this loop, sets the next epoch and
            # goes on, so the next epoch's first batches are staged while
            # this epoch's last are trained. Every item carries the epoch
            # its rows and masks belong to. Closed in the finally so an
            # abandoned run never leaks the thread.
            prefetcher = pretrain.device_prefetch(
                loader, args.accumulation_steps, b_shardings,
                depth=args.device_prefetch, start_epoch=epoch)
            tele.attach_prefetcher(prefetcher)
            # The profiler's step annotation and trace window count
            # step_in_run indices from 1. Every span below nests in it.
            for batch_epoch, batch in tele.timed(iter(prefetcher)):
                if batch_epoch != epoch:
                    # The loop crossed into a new epoch (the feed did so
                    # some batches ago): what it waited for this first
                    # batch is what the boundary cost.
                    epoch, trained_index = batch_epoch, 0
                    feed_epoch_boundaries += 1
                    feed_boundary_wait_s += tele.timer.data_wait_s()
                with telemetry.span("train:dispatch"):
                    state, metrics, kfac_state = dispatch_step(
                        state, batch, kfac_state, global_step)
                tele.dispatch_done()
                global_step += 1
                step_in_run += 1
                trained_index += args.host_batch_per_step
                if data_seq_len is None:
                    data_seq_len = int(batch["input_ids"].shape[-1])
                    placement["batch_devices"] = _devices_holding(batch)
                    if not causal_lm or model.kept_across_remat():
                        logger.info(_kept_across_remat(
                            model, config, args.local_batch_size,
                            data_seq_len))
                    if data_seq_len != seq_len:
                        # MFU must use the DATA shape, not the model cap.
                        tele.timer.flops_per_seq = flops_per_seq(
                            data_seq_len)
                        tele.timer.tokens_per_step = (
                            args.global_batch_size * data_seq_len)
                if not draw_shards_logged and dropout.draw_shards():
                    # Known once the dropout-on step has been traced.
                    draw_shards_logged = True
                    logger.info(
                        f"dropout masks drawn in {dropout.draw_shards()} "
                        "shard(s) of the batch (ops/dropout.py)")
                if step_in_run > 1:  # skip step-0 compile in throughput
                    samples_seen += args.global_batch_size
                if step_in_run == 1:
                    # Wait for the first step to EXECUTE before starting the
                    # clock (reference skips step 0 the same way, its
                    # run_pretraining.py:494-495). Dispatch of step 1 returns
                    # as soon as compilation ends; without this barrier the
                    # executable load and the first execution land inside
                    # the measured window.
                    jax.block_until_ready(metrics)
                    # Start-up ends here (the ``startup`` record).
                    tele.first_update_done(startup)
                    train_start = time.perf_counter()
                if fault_plan.active:
                    # Armed NaN injection replaces the fetched scalars
                    # BEFORE the sentinel observes this step.
                    metrics = fault_plan.poison_metrics(
                        global_step, metrics, emit=tele.emit)
                # Telemetry step close-out: device sync (per cadence) +
                # step-window emission + sentinel policy + heartbeat +
                # watchdog note. NonFiniteError propagates under
                # --sentinel_policy abort.
                tele.step_done(global_step, metrics)

                if global_step % args.log_steps == 0:
                    with telemetry.span("train:fetch_metrics"):
                        last_metrics = {
                            k: float(v) for k, v in metrics.items()}
                    if not tele.last_step_synced:
                        # The float() fetches above were this step's
                        # sync; feed the sentinel/heartbeat that missed
                        # the cadence. Both train steps emit the in-jit
                        # "finite" scalar; the isfinite(loss) fallback
                        # is defensive for any step that doesn't, so a
                        # missing key can't read as healthy.
                        finite = last_metrics.get("finite")
                        if finite is None:
                            finite = (1.0 if math.isfinite(
                                last_metrics["loss"]) else 0.0)
                        tele.sentinel.observe(
                            global_step, finite, last_metrics["loss"])
                        tele.heartbeat.beat(
                            global_step, last_metrics["loss"])
                    elapsed = time.perf_counter() - train_start
                    with telemetry.span("train:log"):
                        logger.log(
                            tag="train", step=global_step, epoch=epoch,
                            average_loss=last_metrics["loss"],
                            step_loss=last_metrics["loss"],
                            learning_rate=last_metrics.get(
                                "learning_rate", 0.0),
                            samples_per_second=samples_seen / max(
                                elapsed, 1e-9),
                            mlm_accuracy=last_metrics.get(
                                "mlm_accuracy", 0.0),
                            grad_norm=last_metrics.get("grad_norm", 0.0),
                            # the decoder's counters: routing, score
                            # tiles, scan chunks, readers of the carried
                            # tensors (causal_lm; pretrain._aux_metrics)
                            **{k: last_metrics[k]
                               for k in getattr(model, "COUNTERS", ())
                               if k in last_metrics})

                if (eval_step is not None
                        and global_step % args.num_steps_per_eval == 0):
                    with telemetry.span("train:eval"):
                        run_validation(state.params, global_step, epoch)

                if global_step % args.num_steps_per_checkpoint == 0:
                    save_step = global_step + args.previous_phase_end_step
                    contents = {"model": state.params,
                                "optimizer": state.opt_state,
                                "sampler": sampler_checkpoint_state(),
                                "epoch": epoch}
                    if kfac_state is not None:
                        contents["preconditioner"] = kfac_state
                    # Async (default): the loop pays only the
                    # device-side snapshot copy; the D2H fetch +
                    # msgpack + disk write overlap the next training
                    # steps. The stall context flags this step's
                    # duration (+ the save block) as a ckpt_step in
                    # the telemetry windows either way — what the
                    # checkpoint-step p95 comparison reads.
                    with tele.checkpoint_stall():
                        ckpt.save_checkpoint(
                            args.model_output_dir, save_step, contents,
                            keep=args.keep_checkpoints,
                            async_write=args.checkpoint_write == "async",
                            layout=args.checkpoint_layout,
                            mesh_spec=args.mesh_spec.as_dict())
                    logger.info(f"Saved checkpoint at step {save_step}")

                if fault_plan.active:
                    # die/term/hang fire AFTER the checkpoint block:
                    # die@N resumes from whatever N's cadence durably
                    # wrote — the hard-preemption model under test.
                    fault_plan.fire_process_faults(
                        global_step, emit=tele.emit)

                if (args.term_check_steps
                        and global_step % args.term_check_steps == 0):
                    flagged = stop.requested
                    if jax.process_count() > 1:
                        # Any-host semantics: the scheduler may signal hosts
                        # at different times; stop only when agreed, at the
                        # same step on every host (this allgather is the
                        # agreement point — all hosts reach it).
                        from jax.experimental import multihost_utils
                        flagged = bool(multihost_utils.process_allgather(
                            np.asarray([flagged])).any())
                    if flagged:
                        logger.info(
                            f"termination signal "
                            f"({stop.signal_name or 'peer host'}) "
                            "received; writing the final checkpoint "
                            "and exiting cleanly "
                            f"(exit code {preemption.EXIT_PREEMPTED})")
                        tele.emit(preemption.preemption_record(
                            global_step, stop))
                        terminated = True
                        break

                if step_in_run >= steps_this_run or global_step >= args.max_steps:
                    break

            if tele.profiler.active:  # run ended inside the profile window
                tele.profiler.stop(sync_target=metrics)
            if tele.profiler.done:
                logger.info(f"profiler trace written to {args.profile_dir}")

            train_time = time.perf_counter() - train_start
            seq_per_sec = samples_seen / max(train_time, 1e-9)
            logger.info(f"Total time: {train_time:.2f} s")
            logger.info(f"training_seq_per_sec = {seq_per_sec:.2f}")
            if feed_epoch_boundaries:
                logger.info(
                    f"the loop trained across {feed_epoch_boundaries} epoch "
                    f"boundaries and waited {feed_boundary_wait_s:.4f} s in "
                    "all for the first batches of the new epochs")
            # MFU: hardware-normalised counterpart of seq/s (the reference
            # reports raw seq/s only, run_pretraining.py:597-599); None — not
            # measured, and absent from the summary — off a TPU (the CPU
            # test mesh has no peak to divide by).
            from bert_pytorch_tpu.utils import flops as flops_util
            train_mfu = flops_util.mfu(
                seq_per_sec / max(jax.device_count(), 1),
                flops_per_seq(data_seq_len or seq_len),
                jax.devices()[0].device_kind)
            if train_mfu is not None:
                logger.info(f"training_mfu = {train_mfu:.4f}")
            # Final checkpoint so short runs resume exactly. A
            # termination-signal checkpoint overrides --skip_final_checkpoint:
            # preemption resume must survive capture-mode runs too.
            if not args.skip_final_checkpoint or terminated:
                save_step = global_step + args.previous_phase_end_step
                contents = {"model": state.params,
                            "optimizer": state.opt_state,
                            "sampler": sampler_checkpoint_state(),
                            "epoch": epoch}
                if kfac_state is not None:
                    contents["preconditioner"] = kfac_state
                # Final/emergency checkpoint stays SYNCHRONOUS: durability
                # before exit is the point (docs/fault_tolerance.md), and
                # save_checkpoint joins this directory's in-flight async
                # write first so checkpoints land in order.
                with tele.checkpoint_stall():
                    ckpt.save_checkpoint(
                        args.model_output_dir, save_step, contents,
                        keep=args.keep_checkpoints,
                        layout=args.checkpoint_layout,
                        mesh_spec=args.mesh_spec.as_dict())
            ckpt.wait_for_pending_save()
            # Flush the partial telemetry window + final heartbeat + run
            # summary (the JSONL sink itself is closed by logger.close()).
            run_summary = {
                "training_seq_per_sec": round(seq_per_sec, 2),
                "terminated_by_signal": terminated,
                # Topology label: telemetry-report groups/labels loss and
                # step-time trajectories per mesh product with this.
                "mesh_spec": args.mesh_spec.canonical(),
                # In how many shards of the batch the step draws a dropout
                # mask (ops/dropout.py): the data-parallel size when every
                # chip draws its own rows, 1 on one chip, 0 without dropout.
                "dropout_draw_shards": dropout.draw_shards(),
                # The one feed of the run goes on across epochs: boundaries
                # the loop trained across, and its waits (train:feed) for
                # the first batch of each new epoch, summed.
                "feed_epoch_boundaries": feed_epoch_boundaries,
                "feed_boundary_wait_s": round(feed_boundary_wait_s, 6),
                # What the run ran on (platform, device_kind, device_count,
                # kernels compiled|interpreted): a number in this artifact
                # is a device number only if this says "tpu".
                **args.device_report,
                **placement,
            }
            if train_mfu is not None:
                run_summary["training_mfu"] = round(train_mfu, 4)
            # Run-level padding accounting: what fraction of the token
            # budget was real work, and the throughput in real tokens —
            # the number packing moves even when seq/s (rows/s) doesn't.
            run_eff = tele.timer.run_padding_efficiency()
            if run_eff is not None:
                run_summary["padding_efficiency"] = round(run_eff, 4)
                run_summary["real_tokens_per_sec"] = round(
                    seq_per_sec * (data_seq_len or seq_len) * run_eff, 2)
            tele.finish(global_step, summary=run_summary)
            logger.close()
        finally:
            if prefetcher is not None:
                prefetcher.close()
            stop.restore()
        return {"global_step": global_step,
                "training_seq_per_sec": seq_per_sec,
                "training_mfu": train_mfu,
                "terminated_by_signal": terminated,
                **last_metrics}


if __name__ == "__main__":
    arguments = parse_arguments()
    np.random.seed(arguments.seed + get_rank())
    outcome = main(arguments)
    if outcome.get("terminated_by_signal"):
        # Distinct exit code (75 = EX_TEMPFAIL): "checkpointed cleanly
        # under preemption, resubmit me" — schedulers/drivers can key
        # auto-resubmission on it (docs/fault_tolerance.md).
        sys.exit(preemption.EXIT_PREEMPTED)
