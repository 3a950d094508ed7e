"""Benchmark: BERT-large phase-1 pretraining throughput on the local chip(s).

Runs the full jitted training step (microbatch scan, bf16 forward/backward,
LAMB with poly-warmup schedule) on synthetic phase-1-shaped data
(seq 128, max_pred 20) and reports sequences/second — the reference's
``training_seq_per_sec`` headline metric (run_pretraining.py:597-599).

Prints ONE JSON line:
  {"metric": "bert_large_phase1_seq_per_sec", "value": N,
   "unit": "seq/s/chip", "vs_baseline": N, "mfu": N}

The reference repo publishes no numbers (BASELINE.md); ``vs_baseline``
normalizes against the NVIDIA DeepLearningExamples BERT-large phase-1
per-A100 throughput (~360 seq/s, fp16 + LAMB) that the reference's configs
are tuned for — the closest external anchor the reference offers. ``mfu``
(model-FLOPs utilisation, utils/flops.py) is the hardware-normalised
number that does not depend on that anchor.

One process for each chip: the parent never imports JAX (a parent that
had touched JAX would hold the chip, and the child that needs it would
fail or hang). It starts the benchmark child ONCE (BENCH_CHILD=1 runs the
body directly), passes the child's output through, and exits with the
child's exit code: a run that fails, fails. The training child refuses to
run on anything but a TPU — a CPU number is not a device number. The
persistent XLA compile cache is placed by utils/compile_cache.py
(``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``).
BENCH_PACK=1 (or ``--pack_sequences``) benches the sequence-packed step on
synthetic mixed-length data and stamps padding_efficiency into the result
(docs/packing.md).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

A100_PHASE1_SEQ_PER_SEC = 360.0
# Phase-2 anchor: same NVIDIA recipe at seq 512 runs ~72 seq/s/A100 (the
# published phase-2:phase-1 per-GPU ratio is ~1:5).
A100_PHASE2_SEQ_PER_SEC = 72.0

# Per-chip microbatch. The phase-1 recipe uses 96/GPU on 40GB A100s
# (BASELINE.md); tuned for a 16GB v5e chip with fp32 master params.
# Measured on v5e (seq 128, max_pred 20, dropout on):
#   batch 32, remat none, threefry: 281 seq/s   (fits without remat)
#   batch 32, remat none, rbg:      327 seq/s   (hardware RNG for dropout)
#   batch 64, remat dots, rbg:      382 seq/s   (remat unlocks 2x batch)
#   batch 56, remat dots, rbg:      396 seq/s   (batch sweep peak: 48→388,
#                                                52→385, 56→396, 60→392, 64→382)
# NB: 56 is the single-chip BENCH shape. The shipped recipe configs keep
# local_batch_size 64: the recipes' global batch (65536 = 2^16) must divide
# by local_batch x data_shards for the accumulation split, and 56 doesn't;
# 64 is the fastest gbs-compatible per-chip batch (~3.5% below the peak).
# 'dots' remat keeps matmul outputs and recomputes elementwise ops in the
# backward; with the TPU hardware RNG ('rbg') that recompute is cheap, so the
# larger microbatch wins. With threefry the same config is SLOWER than
# batch 32 (recompute regenerates every dropout mask in ALU ops).
# BENCH_PHASE=2 switches to the phase-2 recipe shape (seq 512, max_pred 80)
# where the fused Pallas attention kernel is the winning backend
# (ops/attention.py: 84 vs ~52 seq/s); the driver's headline stays phase-1.
# Phase-2 batch sweep (pallas, remat dots, rbg): 24→81.7, 28→82.4, 32→82.2
# seq/s with 512-wide tiles; bh-batched tiles (G=8/program) lift 28 to
# 84.3. (The original 256x256 single-bh tiles measured 70.7.)
# BENCH_KFAC=1 preconditions with distributed K-FAC at the runner's default
# cadence (factors every 10 steps, inverses every 100): the measured window
# holds 2 factor passes + 1 Cholesky inverse update in 20 steps, so the
# reported number is steady-state throughput with the inverse amortization
# ~5x pessimistic. Measured (round 2, stats capture): 236 seq/s/chip vs
# 397 first-order (1.7x per-step cost: every-step preconditioning solves
# on the MXU + a 16-seq stats fwd/bwd every 10 steps + a Cholesky inverse
# update). BENCH_KFAC_CAPTURE selects the factor-capture mode: 'train'
# (default) harvests factors from microbatch 0 of the step's own backward
# (the fused hook-parity path, pretrain.make_train_step; CPU proxy at
# factor_interval=1: 0.83x the step cost of an equal-statistics stats
# pass, i.e. full-microbatch factor quality at the 16-row subsampled
# pass's price — KFAC_CAPTURE_BENCH_r04.jsonl); 'stats' keeps the
# round-3 decoupled stats pass for comparability with the round-2 number.
# BENCH_PACK=1 (or passing --pack_sequences on the command line) benches
# SEQUENCE PACKING (docs/packing.md): synthetic mixed-length samples are
# greedily packed into full rows (sequence_ids + per-sequence NSP heads +
# block-diagonal attention), and the result carries padding_efficiency —
# the fraction of the token budget that is real work. Compare against the
# default full-row run: rows/s stays ~flat while real tokens/s roughly
# doubles at Wikipedia-like length spreads (Krell 2021, arXiv:2107.02027).
# BENCH_SERVE=1 switches to the ONLINE-INFERENCE leg (docs/serving.md):
# instead of the training step, the child replays a synthetic request
# trace (tools/make_synthetic_data.py --requests shape) through the
# serve/ engine — AOT bucket warmup, dynamic batching, optional packing
# (BENCH_SERVE_PACK=1) — and stamps latency p50/p95/p99 (ms), requests/s,
# batch occupancy, and the trace-derived latency decomposition
# (queue_wait_share + per-phase p95s, serve/tracing.py — so the perf
# trajectory records WHERE serve time goes) into the result JSON. Knobs:
# BENCH_SERVE_REQUESTS (default 256), BENCH_SERVE_BATCH (default 8),
# BENCH_SERVE_BUCKETS (default "32,64,128"), BENCH_SERVE_RATE (req/s
# arrival rate; 0 = saturation replay, the default),
# BENCH_SERVE_TRACE_RATE (serve_trace head-sampling fraction, default
# 0.1), BENCH_SERVE_SLO_MS (p99 SLO target; 0 = disabled, the default
# — over-SLO requests are always traced), BENCH_SERVE_SLO_BUDGET
# (error-budget fraction for the report's burn verdict, default 0.01). BENCH_SERVE_QUANT=1 runs the
# INFERENCE-FAST-PATH comparison instead: fp32 vs quantized
# (BENCH_SERVE_QUANT_MODE, default int8) on the SAME trace, stamping
# per-leg p50/p95 + cold_start_s + weight bytes, the p50 speedup, and
# the warm-restart proof (a fresh engine against the persisted AOT
# compile cache must report zero cold compiles via the cache counter
# events — docs/serving.md "Inference fast path").
# BENCH_ASYNC=1 switches to the ASYNC-CHECKPOINT leg (docs/telemetry.md
# "checkpoint-step p95"): a deliberately large synthetic train state is
# saved on a fixed cadence during a paced step loop, once with blocking
# writes and once with async device-snapshot writes
# (utils/checkpoint.py save_checkpoint(async_write=True)), and the result
# stamps both checkpoint-step p95s against the steady-state step p95 —
# async should collapse the ratio toward 1x while blocking holds it at a
# multiple. Knobs: BENCH_ASYNC_STATE_MB (default 128), BENCH_ASYNC_STEPS
# (default 30), BENCH_ASYNC_STEP_MS (default 50), BENCH_ASYNC_CKPT_EVERY
# (default 5).
# Defaults keep two invariants on a throttled CPU box: the inter-save
# interval (step_ms * ckpt_every) exceeds the background write time (else
# saves legitimately join their predecessor — the designed backpressure),
# and the step time dwarfs the snapshot memcpy (on CPU the "device copy"
# is a real memcpy; on TPU it is a sub-ms D2D dispatch).
ASYNC = os.environ.get("BENCH_ASYNC", "0") == "1"
ASYNC_STATE_MB = int(os.environ.get("BENCH_ASYNC_STATE_MB", "96"))
ASYNC_STEPS = int(os.environ.get("BENCH_ASYNC_STEPS", "24"))
ASYNC_STEP_MS = float(os.environ.get("BENCH_ASYNC_STEP_MS", "400"))
ASYNC_CKPT_EVERY = int(os.environ.get("BENCH_ASYNC_CKPT_EVERY", "6"))
SERVE = os.environ.get("BENCH_SERVE", "0") == "1"
SERVE_PACK = os.environ.get("BENCH_SERVE_PACK", "0") == "1"
SERVE_REQUESTS = int(os.environ.get("BENCH_SERVE_REQUESTS", "256"))
SERVE_BATCH = int(os.environ.get("BENCH_SERVE_BATCH", "8"))
SERVE_BUCKETS = os.environ.get("BENCH_SERVE_BUCKETS", "32,64,128")
SERVE_RATE = float(os.environ.get("BENCH_SERVE_RATE", "0"))
# The serving dispatch plane the in-process serve legs drive
# (docs/serving.md "Continuous batching"): pipelined (default) or serial
# — the same A/B knob run_server.py exposes as --dispatch_mode.
SERVE_DISPATCH = os.environ.get("BENCH_SERVE_DISPATCH", "pipelined")
# BENCH_SERVE_SATURATION=1 runs the ROADMAP saturation curve instead
# (docs/serving.md "Continuous batching"): a closed-loop req/s vs p99
# sweep through the REAL fleet — supervisor-owned run_server.py replica
# subprocesses behind the router — at 1 and 2 replicas, pipelined vs
# serial dispatch legs replaying the same trace, every
# (replicas, mode, workers) point stamped into the result JSON. Knobs:
# BENCH_SERVE_SAT_REPLICAS ("1,2"), BENCH_SERVE_SAT_MODES
# ("pipelined,serial"), BENCH_SERVE_SAT_WORKERS ("2,6" — closed-loop
# client concurrency sweep), BENCH_SERVE_SAT_REQUESTS (per point,
# default 48), BENCH_SERVE_SAT_WARMUP_S (replica warmup budget, 240).
SERVE_SATURATION = os.environ.get("BENCH_SERVE_SATURATION", "0") == "1"
# BENCH_KERNELS=1 runs the RAW-SPEED KERNEL comparison (docs/serving.md
# "Raw-speed kernels"): the SAME synthetic trace replays through four
# engines — baseline (xla, unfused) -> fused epilogues -> int8 attention
# (pallas_infer_int8) -> measured-autotune int8 attention — stamping
# per-leg latency p50/p95, the fill_mask forward's output/accessed bytes
# from the joined compile_cost records (the epilogue-fusion win that is
# provable on CPU), weight bytes, and the warm-restart proof with the
# autotune winners file present: a fresh engine against the persisted
# AOT cache + winners JSON must report zero cold compiles. On this CPU
# box the Pallas legs run interpret-mode (their latency ranks kernel
# emulation, not the MXU) — bytes and zero-cold are the CPU-provable
# invariants; latency rides the on-chip capture harness. Knobs:
# BENCH_KERNELS_REQUESTS (default 32), BENCH_KERNELS_BATCH (default 4),
# BENCH_KERNELS_BUCKETS (default "32"), BENCH_KERNELS_VOCAB (model vocab,
# default 8192 — the tokenizer keeps the small covering trace vocab).
KERNELS = os.environ.get("BENCH_KERNELS", "0") == "1"
KERNELS_REQUESTS = int(os.environ.get("BENCH_KERNELS_REQUESTS", "32"))
KERNELS_BATCH = int(os.environ.get("BENCH_KERNELS_BATCH", "4"))
KERNELS_BUCKETS = os.environ.get("BENCH_KERNELS_BUCKETS", "32")
KERNELS_VOCAB = int(os.environ.get("BENCH_KERNELS_VOCAB", "8192"))
# BENCH_MESH=1 runs the STRATEGY-PRODUCT sweep (docs/parallelism.md): the
# SAME tiny model steps under several composed mesh specs on a forced-host
# 8-device CPU mesh (the one-mesh MeshSpec path end to end — spec parse,
# derived rules, composed collectives), stamping per-product step-time
# p50 and seq/s/chip (no MFU: a CPU has no peak). Products are only
# comparable WITHIN a spec, so each appends its own perf-ledger entry
# under a distinct config digest (CONFIG_DIGEST + the product's canonical
# spec). A product whose engine raises is recorded as skipped with the
# reason, not a failure.
# Knobs: BENCH_MESH_SPECS (';'-separated spec strings), BENCH_MESH_STEPS
# (default 8), BENCH_MESH_WARMUP (default 2).
MESH_SWEEP = os.environ.get("BENCH_MESH", "0") == "1"
MESH_SPECS = os.environ.get(
    "BENCH_MESH_SPECS", "dp=8;dp=4,fsdp=2;dp=2,fsdp=4;dp=4,pipe=2")
MESH_STEPS = int(os.environ.get("BENCH_MESH_STEPS", "8"))
MESH_WARMUP = int(os.environ.get("BENCH_MESH_WARMUP", "2"))
PACK = (os.environ.get("BENCH_PACK", "0") == "1"
        or "--pack_sequences" in sys.argv[1:])
PACK_K = int(os.environ.get("BENCH_PACK_K", "8"))
KFAC = os.environ.get("BENCH_KFAC", "0") == "1"
KFAC_CAPTURE = os.environ.get("BENCH_KFAC_CAPTURE", "train")
if KFAC_CAPTURE not in ("train", "stats"):
    raise ValueError(
        f"BENCH_KFAC_CAPTURE must be train|stats, got {KFAC_CAPTURE!r}")
PHASE = int(os.environ.get("BENCH_PHASE", "1"))
_P2 = PHASE == 2
REPO_ROOT = os.path.dirname(os.path.abspath(__file__))
# Optional telemetry sink (docs/telemetry.md): the child appends its
# compile events (fn/shapes digest/compile seconds/cache hit-miss), a
# run_summary (seq/s + MFU), and — on backends with allocator stats — a
# device-memory watermark record as schema-versioned JSONL, so capture
# passes record cold-vs-warm AND cost/memory evidence. When a baseline
# artifact exists (BENCH_TELEMETRY_BASELINE, default the committed
# repo-root BENCH_TELEMETRY.jsonl), the parent additionally runs
# tools/telemetry_report.py over the pair and attaches its regression
# verdict to the result JSON — the bench trajectory becomes
# machine-checkable instead of eyeballed.
TELEMETRY_JSONL = os.environ.get("BENCH_TELEMETRY_JSONL", "")
TELEMETRY_BASELINE = os.environ.get(
    "BENCH_TELEMETRY_BASELINE",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "BENCH_TELEMETRY.jsonl"))
# Longitudinal perf ledger (telemetry/ledger.py, docs/telemetry.md "Perf
# ledger"): with BENCH_LEDGER=<path> every successful capture appends one
# schema-linted ledger_entry (headline metrics + this config's digest), so
# a trajectory accumulates for the rolling-median drift gate
# (telemetry-report --ledger / tools/perf_ledger.py check). Unset = no
# ledger. The path is the caller's to choose; <repo>/PERF_LEDGER.jsonl is
# the driver's record of every PR and is not this program's to write.
LEDGER_PATH = os.environ.get("BENCH_LEDGER", "")


def _config_digest():
    """Stable digest of every knob that changes the compiled program;
    ledger entries are comparable only within one digest."""
    import hashlib

    key = repr((PHASE, KFAC, LONG_SEQ, LOCAL_BATCH, REMAT,
                RNG_IMPL, ATTN, N_DEVICES,
                # kfac capture mode changes the train-step program; keep
                # the digest stable for non-kfac configs
                KFAC_CAPTURE if KFAC else ""))
    if PACK:
        # Packing changes the compiled step (extra arrays, packed heads).
        key += f"+pack{PACK_K}"
    if SERVE:
        # The serve leg compiles inference forwards, not the train step.
        key += (f"+serve{SERVE_BATCH}x{SERVE_BUCKETS}"
                + ("+spack" if SERVE_PACK else ""))
    if SERVE_SATURATION:
        # The saturation leg compiles inside its replica subprocesses.
        key += "+servesat"
    if KERNELS:
        # The kernels leg compiles serve forwards (four engine variants),
        # not the train step.
        key += f"+kernels{KERNELS_BATCH}x{KERNELS_BUCKETS}v{KERNELS_VOCAB}"
    if ASYNC:
        # The async-checkpoint leg compiles nothing heavy (the snapshot
        # identity only).
        key += f"+async{ASYNC_STATE_MB}"
    if MESH_SWEEP:
        # The mesh sweep compiles one tiny train step per product on a
        # forced-host mesh; keyed on the product list so its ledger
        # digests never collide with a real training config's.
        key += f"+mesh{MESH_SPECS}"
    return hashlib.sha1(key.encode()).hexdigest()[:12]


# BENCH_SEQ overrides the sequence length for long-context runs (the
# reference hard-caps at max_position_embeddings=512; this framework's
# fused attention is O(S) memory, and 'sp' ring attention shards S across
# chips). vs_baseline then uses a FLOP-proportional courtesy scaling of the
# phase-2 anchor (72 * 512/S) — the reference cannot run the shape at all.
LONG_SEQ = int(os.environ.get("BENCH_SEQ", "0"))
LOCAL_BATCH = int(os.environ.get(
    "BENCH_LOCAL_BATCH",
    str(max(1, 28 * 512 // LONG_SEQ)) if LONG_SEQ
    else ("28" if _P2 else "56")))
REMAT = os.environ.get("BENCH_REMAT", "dots")
RNG_IMPL = os.environ.get("BENCH_RNG_IMPL", "rbg")
ATTN = os.environ.get("BENCH_ATTN", "pallas" if (_P2 or LONG_SEQ) else "xla")
if PHASE not in (1, 2):
    raise ValueError(f"BENCH_PHASE must be 1|2, got {PHASE}")
if REMAT not in ("none", "dots", "full"):
    raise ValueError(f"BENCH_REMAT must be none|dots|full, got {REMAT!r}")
if ATTN not in ("xla", "pallas", "ring"):
    raise ValueError(f"BENCH_ATTN must be xla|pallas|ring, got {ATTN!r}")
if RNG_IMPL not in ("rbg", "threefry2x32"):
    raise ValueError(f"BENCH_RNG_IMPL must be rbg|threefry2x32, got {RNG_IMPL!r}")
if PACK and ATTN == "ring":
    raise ValueError(
        "BENCH_PACK does not compose with BENCH_ATTN=ring (the block-"
        "diagonal mask is not implemented over the sharded seq axis)")
if LONG_SEQ and (LONG_SEQ < 128 or LONG_SEQ % 128 != 0):
    raise ValueError(
        f"BENCH_SEQ must be a positive multiple of 128 (tile alignment for "
        f"the fused attention kernel), got {LONG_SEQ}")
SEQ_LEN = LONG_SEQ or (512 if _P2 else 128)
MAX_PRED = (max(20, SEQ_LEN * 80 // 512) if LONG_SEQ
            else (80 if _P2 else 20))  # max_predictions_per_seq (BASELINE.md)
ACCUM = 1
WARMUP_STEPS = int(os.environ.get("BENCH_WARMUP_STEPS", "3"))
MEASURE_STEPS = int(os.environ.get("BENCH_MEASURE_STEPS", "20"))
# BENCH_DEVICES=N restricts the mesh to the first N devices of a
# SINGLE-PROCESS run (an intra-host sweep; multi-host pods sweep by
# launching with fewer hosts), giving the BASELINE.md scaling-efficiency
# curve (seq/s/chip at N vs at the base size). 0 = all devices.
N_DEVICES = int(os.environ.get("BENCH_DEVICES", "0"))
CONFIG_DIGEST = _config_digest()  # all digest inputs are defined above


def _child_main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_default_prng_impl", RNG_IMPL)
    if jax.devices()[0].platform != "tpu":
        # A seq/s or an MFU from anything else is not a device number.
        sys.exit(f"bench.py measures training on a TPU; JAX found "
                 f"{jax.devices()[0].platform!r} "
                 f"({jax.devices()[0].device_kind})")
    from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from bert_pytorch_tpu import optim, pretrain
    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.parallel import MeshConfig, create_mesh, logical_axis_rules

    config = BertConfig.from_json_file(
        os.path.join(REPO_ROOT, "configs", "bert_large_uncased_config.json"))
    if config.vocab_size % 8 != 0:
        config.vocab_size += 8 - (config.vocab_size % 8)
    if LONG_SEQ:
        config.max_position_embeddings = SEQ_LEN

    devices = jax.devices()
    if N_DEVICES:
        # Config errors print a marker and exit 2.
        if N_DEVICES < 0 or N_DEVICES > len(devices):
            print(f"BENCH_CONFIG_ERROR: BENCH_DEVICES={N_DEVICES} outside "
                  f"[1, {len(devices)}]")
            sys.exit(2)
        if jax.process_count() > 1:
            # Slicing the global device list would hand some processes a
            # mesh with none of their addressable chips; pod scaling
            # sweeps should vary the JOB size (hosts) instead.
            print("BENCH_CONFIG_ERROR: BENCH_DEVICES only supports "
                  "single-process runs; on a multi-host pod, sweep by "
                  "launching with fewer hosts")
            sys.exit(2)
        devices = devices[:N_DEVICES]
    n_chips = len(devices)
    if ATTN == "ring":
        # Context parallelism: the sequence axis shards across the chips
        # and K/V blocks rotate over ICI (ops/ring.py). Single-chip runs
        # can't exercise the rotation — require a real seq axis.
        if n_chips < 2:
            raise ValueError(
                "BENCH_ATTN=ring needs >=2 chips (the sequence axis shards "
                "across the mesh); on one chip use the fused 'pallas' kernel")
        mesh = create_mesh(MeshConfig(data=1, seq=n_chips), devices=devices)
        rules = logical_axis_rules("sp")
    else:
        mesh = create_mesh(MeshConfig(data=-1), devices=devices)
        rules = logical_axis_rules("dp")
    model = BertForPreTraining(config, dtype=jnp.bfloat16, remat=REMAT,
                               attention_backend=ATTN)
    schedule = (optim.warmup_poly_schedule(4e-3, 0.128, 1563) if _P2
                else optim.warmup_poly_schedule(6e-3, 0.2843, 7038))
    tx = optim.lamb(schedule, weight_decay_mask=optim.no_decay_mask)

    # Batch scales with the DATA shards only (under 'ring' the chips hold
    # sequence shards, not batch shards).
    data_shards = mesh.shape["data"] * mesh.shape["fsdp"]
    global_batch = LOCAL_BATCH * data_shards * ACCUM
    sample = (jnp.zeros((1, SEQ_LEN), jnp.int32),) * 3
    rng = np.random.default_rng(0)
    eff_max_pred = MAX_PRED * PACK_K if PACK else MAX_PRED
    if PACK:
        # Mixed-length synthetic samples FFD-packed into exactly
        # global_batch full rows (the runner's on-the-fly path,
        # data/packing.py) — what a Wikipedia-style shard looks like to
        # the train step after packing.
        from bert_pytorch_tpu.data.packing import first_fit_decreasing

        lengths: list = []
        while True:
            lengths.extend(
                int(x) for x in rng.integers(8, SEQ_LEN + 1, 512))
            packs = first_fit_decreasing(lengths, SEQ_LEN, PACK_K)
            if len(packs) >= global_batch:
                break
        packs = packs[:global_batch]
        host = {
            "input_ids": np.zeros((global_batch, SEQ_LEN), np.int32),
            "segment_ids": np.zeros((global_batch, SEQ_LEN), np.int32),
            "input_mask": np.zeros((global_batch, SEQ_LEN), np.int32),
            "masked_lm_labels": np.full(
                (global_batch, SEQ_LEN), -1, np.int32),
            "next_sentence_labels": np.full(
                (global_batch, PACK_K), -1, np.int32),
            "sequence_ids": np.zeros((global_batch, SEQ_LEN), np.int32),
            "cls_positions": np.zeros((global_batch, PACK_K), np.int32),
        }
        for r, pack in enumerate(packs):
            offset = 0
            for k, i in enumerate(pack):
                n = min(lengths[i], SEQ_LEN - offset)
                span = slice(offset, offset + n)
                host["input_ids"][r, span] = rng.integers(
                    0, config.vocab_size, n)
                host["segment_ids"][r, span] = rng.integers(0, 2, n)
                host["input_mask"][r, span] = 1
                host["masked_lm_labels"][r, span] = np.where(
                    rng.random(n) < 0.15,
                    rng.integers(0, config.vocab_size, n), -1)
                host["sequence_ids"][r, span] = k + 1
                host["next_sentence_labels"][r, k] = int(rng.integers(0, 2))
                host["cls_positions"][r, k] = offset
                offset += n
        pack_efficiency = float(host["input_mask"].sum()) / (
            global_batch * SEQ_LEN)
    else:
        host = {
            "input_ids": rng.integers(
                0, config.vocab_size, (global_batch, SEQ_LEN)).astype(np.int32),
            "segment_ids": rng.integers(0, 2, (global_batch, SEQ_LEN)).astype(np.int32),
            "input_mask": np.ones((global_batch, SEQ_LEN), np.int32),
            "masked_lm_labels": np.where(
                rng.random((global_batch, SEQ_LEN)) < 0.15,
                rng.integers(0, config.vocab_size, (global_batch, SEQ_LEN)),
                -1).astype(np.int32),
            "next_sentence_labels": rng.integers(0, 2, (global_batch,)).astype(np.int32),
        }
        pack_efficiency = None

    batch_spec = {"input_ids": 3, "segment_ids": 3, "input_mask": 3,
                  "masked_lm_labels": 3,
                  "next_sentence_labels": 3 if PACK else 2}
    if PACK:
        batch_spec.update({"sequence_ids": 3, "cls_positions": 3})
    with mesh:
        shardings = pretrain.state_shardings(mesh, model, rules, sample)
        b_shardings = pretrain.batch_shardings(
            mesh, batch_spec, seq_sharded=ATTN == "ring")
        state = pretrain.make_init_fn(model, tx, sample, shardings)(
            jax.random.PRNGKey(0))

        kfac_obj = kfac_state = kfac_shardings = None
        kfac_fused = KFAC and KFAC_CAPTURE == "train"
        if KFAC:
            # The fused-capture twin keeps the bench remat (its microbatch-0
            # backward shares the training step's memory budget); the
            # stats-pass twin runs a small decoupled batch.
            tapped = BertForPreTraining(
                config, dtype=jnp.bfloat16,
                remat=REMAT if kfac_fused else "none",
                attention_backend=ATTN, kfac_tap=True)
            apply_loss, tap_shape_fn = pretrain.make_kfac_fns(
                tapped, next_sentence=True, max_pred_per_seq=eff_max_pred)
            kfac_obj = optim.KFAC(apply_loss, tap_shape_fn)
            _st = max(1, global_batch // 16)
            stats_mb = {k: v[::_st][:16] for k, v in host.items()}
            kfac_state = kfac_obj.init(state.params, stats_mb)
            kfac_shardings = optim.kfac_state_shardings(mesh, kfac_state)
            kfac_state = jax.device_put(kfac_state, kfac_shardings)

        step = pretrain.make_train_step(
            model, tx, schedule=schedule, next_sentence=True,
            shardings=shardings, batch_shardings_=b_shardings,
            max_pred_per_seq=eff_max_pred,
            kfac=kfac_obj, kfac_shardings=kfac_shardings,
            kfac_capture_model=tapped if kfac_fused else None,
            kfac_factor_interval=10,
            kfac_inv_interval=100 if kfac_fused else 0)

        # Compile observability (telemetry/compile_events.py): the warmup
        # compile is attributed to the bench step, so the result can state
        # whether this run was cold (real XLA compile) or warm (persistent
        # cache hit).
        from bert_pytorch_tpu.telemetry import CompileMonitor
        sink = None
        if TELEMETRY_JSONL:
            from bert_pytorch_tpu.utils.logging import JSONLHandler
            sink = JSONLHandler(TELEMETRY_JSONL, overwrite=False)
        # Static cost attribution only when there is a sink to keep it:
        # 'auto' never pays an un-cached extra backend compile
        # (telemetry/memory.py), and the bench always enables the
        # persistent cache, so memory_analysis costs a deserialize. An
        # unknown env value means 'off' — a typo must not kill a
        # bench run after the compile already ran.
        from bert_pytorch_tpu.telemetry.memory import COST_MODES
        cost_mode = os.environ.get(
            "BENCH_COST_ANALYSIS", "auto" if sink else "off")
        if cost_mode not in COST_MODES:
            print(f"BENCH_COST_ANALYSIS={cost_mode!r} unknown; "
                  "disabling cost attribution", file=sys.stderr)
            cost_mode = "off"
        monitor = CompileMonitor(
            emit=sink.write_record if sink else lambda rec: None,
            cost_analysis=cost_mode)
        step = monitor.instrument(step, "bench_step")

        batch = pretrain.put_batch(
            pretrain.stack_microbatches(host, ACCUM), b_shardings)

        def run_one(state, kfac_state, global_step):
            if kfac_fused:
                # Factor capture rides microbatch 0's backward; both the
                # factor and inverse cadences are cond-gated in-jit.
                state, metrics, kfac_state = step(state, batch, kfac_state)
            elif kfac_obj is not None:
                if global_step % 10 == 0:
                    # Strided rows so every data shard contributes to the
                    # statistics (the runner's pattern; a [:16] head-slice
                    # would sample only shard 0's data on multi-chip runs).
                    stride = max(1, batch["input_ids"].shape[1] // 16)
                    kfac_state = kfac_obj.update_factors(
                        kfac_state, state.params,
                        {k: v[0][::stride][:16] for k, v in batch.items()},
                        jax.random.fold_in(jax.random.PRNGKey(17), global_step))
                if global_step % 100 == 0:
                    kfac_state = kfac_obj.update_inverses(kfac_state)
                state, metrics = step(state, batch, kfac_state)
            else:
                state, metrics = step(state, batch)
            return state, kfac_state, metrics

        for i in range(WARMUP_STEPS):
            state, kfac_state, metrics = run_one(state, kfac_state, i + 100)
            jax.block_until_ready(metrics)

        # Chained dispatch: each step consumes the previous step's donated
        # state, so waiting on the FINAL step's metrics waits for the whole
        # chain. A per-step wait would serialize a host<->device round trip
        # into every step and keep the host from running ahead.
        start = time.perf_counter()
        for i in range(MEASURE_STEPS):
            state, kfac_state, metrics = run_one(state, kfac_state, i)
        jax.block_until_ready(metrics)
        elapsed = time.perf_counter() - start

    seq_per_sec = MEASURE_STEPS * global_batch / elapsed
    seq_per_sec_chip = seq_per_sec / n_chips
    from bert_pytorch_tpu.utils import flops as flops_util
    flops_per_seq = flops_util.bert_train_flops_per_seq(
        config, SEQ_LEN, eff_max_pred, next_sentence=True)
    model_flops_util = flops_util.mfu(
        seq_per_sec_chip, flops_per_seq, devices[0].device_kind)
    result = _result_json(
        seq_per_sec_chip, mfu=model_flops_util, n_chips=n_chips)
    if PACK:
        # Padding-aware accounting (docs/telemetry.md): rows/s barely
        # moves under packing; real tokens/s is the number that ~doubles.
        result["padding_efficiency"] = round(pack_efficiency, 4)
        result["real_tokens_per_sec_chip"] = round(
            seq_per_sec_chip * SEQ_LEN * pack_efficiency, 2)
    compile_events = [e for e in monitor.events if e["kind"] == "compile"]
    if compile_events:
        result["compile"] = {
            "events": len(compile_events),
            "cache": compile_events[0]["cache"],
            "compile_s": round(
                sum(e["compile_s"] for e in compile_events), 2),
        }
    if sink is not None:
        # Summary + memory watermark records so the offline regression
        # gate (tools/telemetry_report.py) can diff seq/s, MFU, and peak
        # device memory between this artifact and a committed baseline.
        from bert_pytorch_tpu.telemetry.memory import MemorySampler

        sampler = MemorySampler(emit=sink.write_record)
        sampler.sample(MEASURE_STEPS)
        sampler.flush(MEASURE_STEPS)
        sink.write_record({
            "kind": "run_summary", "tag": "telemetry",
            "step": MEASURE_STEPS, "steps": MEASURE_STEPS,
            "metric": result["metric"],
            "training_seq_per_sec": round(seq_per_sec, 2),
            "seq_per_sec_chip": round(seq_per_sec_chip, 2),
            "mfu": round(model_flops_util, 4),
        })
        sink.close()
    print(json.dumps(result))


def _serve_child_main():
    """BENCH_SERVE leg: replay a synthetic request trace through the
    online-inference engine (docs/serving.md) and print one JSON line with
    latency percentiles, request throughput, and batch occupancy.

    BENCH_SERVE_QUANT=1 switches to the INFERENCE-FAST-PATH comparison
    (docs/serving.md "Inference fast path"): the SAME trace replays twice
    — an fp32 engine, then a quantized one (BENCH_SERVE_QUANT_MODE,
    default int8) — and the result stamps per-leg p50/p95 + cold_start_s
    + weight bytes, the p50 ratio, and the warm-restart proof: a THIRD
    engine start against the now-populated persistent compile cache must
    perform ZERO cold compiles, measured by the cache counter events
    (telemetry/compile_events.py — wall clock proves nothing). On this
    CPU CI box XLA has no fast s8 GEMM, so int8 p50 typically LOSES here;
    the latency win is an MXU property stamped by on-chip captures, while
    the weight-bytes ratio and the zero-cold-restart hold anywhere.
    """
    import json as _json
    import tempfile
    import threading

    from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache

    # min_compile_secs=0: persist the seconds-scale serve executables too
    # (the warm-restart leg depends on every forward being cached).
    enable_compile_cache(min_compile_secs=0.0)
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.data.tokenization import BertTokenizer
    from bert_pytorch_tpu.serve import (Batcher, InferenceEngine,
                                        ServeTelemetry, ServingService)
    from bert_pytorch_tpu.telemetry import CompileMonitor
    from bert_pytorch_tpu.tools.make_synthetic_data import (
        make_request_trace, write_trace_vocab)

    config = BertConfig.from_json_file(
        os.path.join(REPO_ROOT, "configs", "bert_base_config.json"))
    if config.vocab_size % 8 != 0:
        config.vocab_size += 8 - (config.vocab_size % 8)

    tmp = tempfile.mkdtemp(prefix="bench_serve_")
    # Trace text uses the small covering vocab (token ids stay tiny); the
    # MODEL keeps its real 30k vocab, so per-request FLOPs are realistic.
    vocab = write_trace_vocab(os.path.join(tmp, "vocab.txt"))
    trace = make_request_trace(
        os.path.join(tmp, "requests.jsonl"), SERVE_REQUESTS, seed=0,
        rate_rps=SERVE_RATE)
    tokenizer = BertTokenizer(vocab, do_lower_case=True)

    sink = None
    if TELEMETRY_JSONL:
        from bert_pytorch_tpu.utils.logging import JSONLHandler

        sink = JSONLHandler(TELEMETRY_JSONL, overwrite=False)
    emit = sink.write_record if sink else (lambda rec: None)
    buckets = [int(b) for b in SERVE_BUCKETS.split(",")]
    pack_k = int(os.environ.get("BENCH_SERVE_PACK_K", "4"))
    lines = [_json.loads(line) for line in open(trace)]

    def build_service(quantize, monitor):
        import argparse

        from bert_pytorch_tpu.serve.cli import build_tracer

        engine = InferenceEngine(
            config, tokenizer,
            tasks={"fill_mask": {}, "classify": {"labels": ["0", "1"]},
                   "squad": {}, "ner": {"labels": ["O", "B-LOC", "B-PER"]}},
            buckets=buckets, max_batch_size=SERVE_BATCH,
            max_requests_per_pack=pack_k if SERVE_PACK else 1,
            dtype=jnp.bfloat16, monitor=monitor, quantize=quantize)
        telemetry = ServeTelemetry(emit=emit, window=64)
        # Request tracing rides every serve leg so the perf trajectory
        # records WHERE serve time goes (queue vs execute vs postprocess),
        # not just how much (docs/serving.md "Request tracing & metrics").
        tracer = build_tracer(
            argparse.Namespace(
                trace_sample_rate=float(
                    os.environ.get("BENCH_SERVE_TRACE_RATE", "0.1")),
                slo_p99_ms=float(
                    os.environ.get("BENCH_SERVE_SLO_MS", "0")),
                slo_error_budget=float(
                    os.environ.get("BENCH_SERVE_SLO_BUDGET", "0.01"))),
            emit=emit, window=64)
        return ServingService(
            engine,
            Batcher(max_batch_size=SERVE_BATCH, max_wait_ms=5.0,
                    max_requests_per_pack=engine.max_requests_per_pack),
            telemetry, tracer=tracer, dispatch_mode=SERVE_DISPATCH)

    def replay(service):
        t_warm = time.perf_counter()
        service.start()  # warms every (task, bucket[, packed]) forward
        warmup_s = time.perf_counter() - t_warm
        errors: list = []
        t0 = time.perf_counter()

        def worker(chunk):
            for line in chunk:
                if SERVE_RATE > 0:
                    delay = t0 + line["arrival_s"] - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                try:
                    service.submit(line["task"], line["payload"],
                                   timeout=300)
                except Exception as exc:  # stamped, not fatal
                    errors.append(f"{type(exc).__name__}: {exc}")

        n_workers = min(32, max(4, SERVE_BATCH * 4))
        threads = [threading.Thread(target=worker,
                                    args=(lines[i::n_workers],),
                                    daemon=True)
                   for i in range(n_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        # include_phases=False: the phase rollup is taken ONCE below,
        # after stop() — computing it here too would sort the tracer's
        # whole sample history while the dispatch thread still runs.
        snap = service.telemetry.snapshot(include_phases=False)
        service.stop()
        # After stop(): run-level phase rollup survives the drain, and
        # the tracer's partial serve_phase windows are flushed by it.
        snap["phases"] = service.tracer.phase_snapshot() or {}
        return snap, wall, warmup_s, errors

    def phase_stamp(snap):
        """Trace-derived latency-decomposition stamp for the result JSON:
        queue-wait share + per-phase p95s (serve/tracing.py)."""
        phases = snap.get("phases") or {}
        return {
            "queue_wait_share": phases.get("queue_wait_share"),
            "phase_p95_ms": {
                name: phases.get(f"{name}_p95_ms")
                for name in ("queue", "assembly", "execute", "postprocess")
            },
        }

    quant_mode = os.environ.get("BENCH_SERVE_QUANT_MODE", "int8")
    if os.environ.get("BENCH_SERVE_QUANT", "0") == "1":
        legs = {}
        for mode in (None, quant_mode):
            tag = mode or "fp32"
            monitor = CompileMonitor(emit=emit)
            service = build_service(mode, monitor)
            snap, wall, _, errors = replay(service)
            startup = service.engine.startup or {}
            legs[tag] = {
                "latency_p50_ms": snap.get("latency_p50_ms"),
                "latency_p95_ms": snap.get("latency_p95_ms"),
                "req_per_sec": round(SERVE_REQUESTS / wall, 2),
                "cold_start_s": startup.get("cold_start_s"),
                "compiles_cold": startup.get("compiles_cold"),
                "compiles_warm": startup.get("compiles_warm"),
                "weight_bytes": startup.get("weight_bytes"),
                "serve_errors": len(errors),
            }
            legs[tag].update(phase_stamp(snap))
        # Warm-restart proof: a fresh engine against the persisted AOT
        # cache — the cache counter events must report zero cold
        # compiles (every forward is a persistent-cache hit).
        monitor = CompileMonitor(emit=emit)
        warm_engine = build_service(quant_mode, monitor).engine
        warm_engine.warmup()
        warm_startup = warm_engine.startup or {}
        fp32_leg, quant_leg = legs["fp32"], legs[quant_mode]
        p50_ratio = None
        if fp32_leg["latency_p50_ms"] and quant_leg["latency_p50_ms"]:
            p50_ratio = round(
                fp32_leg["latency_p50_ms"] / quant_leg["latency_p50_ms"], 3)
        bytes_ratio = None
        if fp32_leg["weight_bytes"] and quant_leg["weight_bytes"]:
            bytes_ratio = round(
                fp32_leg["weight_bytes"] / quant_leg["weight_bytes"], 2)
        result = {
            "metric": f"bert_base_serve_{quant_mode}_p50_ms",
            "value": quant_leg["latency_p50_ms"],
            "unit": "ms",
            "n_requests": SERVE_REQUESTS,
            "quant_mode": quant_mode,
            "fp32": fp32_leg,
            quant_mode: quant_leg,
            # >1 = the quantized leg is faster at the median (expected on
            # TPU; on this CPU box s8 GEMMs lose — documented above).
            "p50_speedup": p50_ratio,
            "weight_bytes_ratio": bytes_ratio,
            "second_start_cold_compiles": warm_startup.get("compiles_cold"),
            "second_start_warm_compiles": warm_startup.get("compiles_warm"),
            "second_start_cold_start_s": warm_startup.get("cold_start_s"),
            "buckets": buckets,
            "batch_size": SERVE_BATCH,
            # ok = the CPU-provable invariants: zero-cold warm restart +
            # the quantized weights actually shrank.
            "ok": bool(warm_startup.get("compiles_cold") == 0
                       and (bytes_ratio or 0) > 1.5),
        }
        if sink is not None:
            sink.write_record({
                "kind": "run_summary", "tag": "telemetry",
                "step": SERVE_REQUESTS, "steps": SERVE_REQUESTS,
                "metric": result["metric"]})
            sink.close()
        print(_json.dumps(result))
        return

    monitor = CompileMonitor(emit=emit)
    service = build_service(None, monitor)
    telemetry = service.telemetry
    engine = service.engine
    snap, wall, warmup_s, errors = replay(service)

    metric = "bert_base_serve{}_req_per_sec".format(
        "_packed" if SERVE_PACK else "")
    result = {
        "metric": metric,
        "value": round(SERVE_REQUESTS / wall, 2),
        "unit": "req/s",
        "n_requests": SERVE_REQUESTS,
        "latency_p50_ms": snap.get("latency_p50_ms"),
        "latency_p95_ms": snap.get("latency_p95_ms"),
        "latency_p99_ms": snap.get("latency_p99_ms"),
        "device_p50_ms": snap.get("device_p50_ms"),
        "batch_occupancy": snap.get("batch_occupancy"),
        **phase_stamp(snap),
        "warmup_s": round(warmup_s, 2),
        "cold_start_s": (engine.startup or {}).get("cold_start_s"),
        "serve_errors": len(errors),
        "buckets": buckets,
        "batch_size": SERVE_BATCH,
        "pack": pack_k if SERVE_PACK else 1,
    }
    if SERVE_RATE > 0:
        result["arrival_rate_rps"] = SERVE_RATE
    if errors:
        result["error_sample"] = errors[0][:200]
    compile_events = [e for e in monitor.events if e["kind"] == "compile"]
    if compile_events:
        result["compile"] = {
            "events": len(compile_events),
            "cache": compile_events[0]["cache"],
            "compile_s": round(
                sum(e["compile_s"] for e in compile_events), 2),
        }
    if sink is not None:
        # The metric stamp lets the regression gate refuse diffing a serve
        # artifact against a training baseline (_attach_regression).
        sink.write_record({
            "kind": "run_summary", "tag": "telemetry",
            "step": SERVE_REQUESTS, "steps": SERVE_REQUESTS,
            "metric": metric})
        sink.close()
    print(_json.dumps(result))


def _kernels_child_main():
    """BENCH_KERNELS leg: baseline vs fused-epilogue vs int8-attention
    vs measured-autotune engines on one trace (docs/serving.md
    "Raw-speed kernels").

    Four engines replay the same synthetic request trace through the
    direct plan/stage/execute/demux/postprocess path (no HTTP/batcher —
    the kernels are the thing under test, not the dispatch plane), each
    with cost attribution on, so every leg stamps: latency p50/p95 per
    dispatched batch, the fill_mask forward's output/accessed bytes
    from its compile_cost record (fused engines must move fewer bytes
    off the device), and cold-start/weight stats. The autotuned leg
    measures geometry at warmup and persists the winners JSON next to
    the AOT compile cache; a FIFTH engine start then proves the warm
    restart: winners loaded + every forward a persistent-cache hit —
    ``second_start_cold_compiles == 0`` with autotune winners present.
    """
    import json as _json
    import tempfile

    from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache(min_compile_secs=0.0)
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.data.tokenization import BertTokenizer
    from bert_pytorch_tpu.serve import InferenceEngine
    from bert_pytorch_tpu.serve.batcher import Request
    from bert_pytorch_tpu.telemetry import CompileMonitor
    from bert_pytorch_tpu.tools.make_synthetic_data import (
        make_request_trace, write_trace_vocab)

    tmp = tempfile.mkdtemp(prefix="bench_kernels_")
    vocab_path = write_trace_vocab(os.path.join(tmp, "vocab.txt"))
    trace = make_request_trace(os.path.join(tmp, "requests.jsonl"),
                               KERNELS_REQUESTS, seed=0)
    tokenizer = BertTokenizer(vocab_path, do_lower_case=True)
    lines = [_json.loads(line) for line in open(trace)]
    buckets = [int(b) for b in KERNELS_BUCKETS.split(",")]
    # Small-but-real model: the trace tokenizer's tiny covering vocab
    # keeps token ids valid while the MODEL vocab stays large enough
    # that the fill_mask [B, S, V] plane is the dominant output (the
    # bytes the fused epilogue exists to not move).
    config = BertConfig(
        vocab_size=KERNELS_VOCAB, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=max(buckets), type_vocab_size=2,
        next_sentence=True, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    tasks = {"fill_mask": {}, "classify": {"labels": ["0", "1"]},
             "squad": {}, "ner": {"labels": ["O", "B-LOC", "B-PER"]}}
    winners_path = os.path.join(cache_dir, "pallas_autotune.json")

    sink = None
    if TELEMETRY_JSONL:
        from bert_pytorch_tpu.utils.logging import JSONLHandler

        sink = JSONLHandler(TELEMETRY_JSONL, overwrite=False)
    emit = sink.write_record if sink else (lambda rec: None)

    def build(**kw):
        monitor = CompileMonitor(emit=emit, cost_analysis="auto")
        engine = InferenceEngine(
            config, tokenizer, tasks, buckets=buckets,
            max_batch_size=KERNELS_BATCH, dtype=jnp.float32,
            monitor=monitor, **kw)
        engine.warmup()
        return engine

    def fill_mask_cost(engine):
        """output/accessed bytes of the fill_mask forward the engine
        actually dispatches, joined from the compile_cost records the
        monitor attributed at warmup. Fused engines also warm the
        unfused slot-overflow fallback — the comparison wants the
        steady-state (fused) variant, not the sum of both."""
        costs = {e["fn"]: e for e in engine.monitor.events
                 if e.get("kind") == "compile_cost"
                 and e.get("fn", "").startswith("serve_fill_mask_")}
        fused = {fn: e for fn, e in costs.items() if "_fused" in fn}
        chosen = fused or costs
        out_bytes = sum(int(e.get("output_bytes", 0))
                        for e in chosen.values())
        accessed = sum(int(e.get("bytes_accessed", 0))
                       for e in chosen.values())
        return out_bytes, accessed

    def replay(engine):
        lats = []
        by_task = {}
        for line in lines:
            by_task.setdefault(line["task"], []).append(line["payload"])
        for task, payloads in by_task.items():
            spec = engine.tasks[task]
            todo = [Request(task,
                            spec.handler.prepare(p, engine.max_len()), p)
                    for p in payloads]
            while todo:
                t0 = time.perf_counter()
                plan = engine.plan_batch(todo[:KERNELS_BATCH],
                                         packed=False)
                outputs, info = engine.execute(task, plan)
                for req, out in zip(plan.requests, outputs):
                    spec.handler.postprocess(req.features, out,
                                             req.payload)
                wall = time.perf_counter() - t0
                lats.extend([wall * 1000.0] * len(plan.requests))
                todo = todo[KERNELS_BATCH:] + list(plan.leftover)
        lats.sort()

        def pctl(q):
            return round(lats[min(len(lats) - 1,
                                  int(q * len(lats)))], 2) if lats else None

        return {"latency_p50_ms": pctl(0.50), "latency_p95_ms": pctl(0.95)}

    legs = {}
    # The winners registry is process-global (ops/pallas/autotune.py):
    # start clean, and keep the heuristic int8 leg BEFORE the autotuned
    # one — a populated registry would silently retune it.
    from bert_pytorch_tpu.ops.pallas import autotune as autotune_lib

    autotune_lib.clear_winners()
    plans = (
        ("baseline", {}),
        ("fused", {"fuse_epilogues": True}),
        ("int8_attn", {"fuse_epilogues": True,
                       "attention_backend": "pallas_infer_int8"}),
        ("autotuned", {"fuse_epilogues": True,
                       "attention_backend": "pallas_infer_int8",
                       "autotune": "measure",
                       "autotune_cache": winners_path}),
    )
    for tag, kw in plans:
        engine = build(**kw)
        leg = replay(engine)
        startup = engine.startup or {}
        out_bytes, accessed = fill_mask_cost(engine)
        leg.update({
            "cold_start_s": startup.get("cold_start_s"),
            "compiles_cold": startup.get("compiles_cold"),
            "compiles_warm": startup.get("compiles_warm"),
            "weight_bytes": startup.get("weight_bytes"),
            "fill_mask_output_bytes": out_bytes or None,
            "fill_mask_bytes_accessed": accessed or None,
        })
        legs[tag] = leg

    # Warm-restart proof WITH autotune winners present: same settings as
    # the autotuned leg, winners loaded from the persisted file — every
    # forward must be a persistent-cache hit (counter events, not wall
    # clock: the PR-8 authority).
    warm_engine = build(fuse_epilogues=True,
                        attention_backend="pallas_infer_int8",
                        autotune="load", autotune_cache=winners_path)
    warm = warm_engine.startup or {}

    def bytes_ratio(a, b):
        if legs[a]["fill_mask_output_bytes"] and \
                legs[b]["fill_mask_output_bytes"]:
            return round(legs[a]["fill_mask_output_bytes"]
                         / legs[b]["fill_mask_output_bytes"], 2)
        return None

    ratio = bytes_ratio("baseline", "fused")
    result = {
        "metric": "serve_kernels_fill_mask_output_bytes_ratio",
        "value": ratio,
        "unit": "x (unfused/fused output bytes)",
        "n_requests": KERNELS_REQUESTS,
        "buckets": buckets,
        "batch_size": KERNELS_BATCH,
        "model_vocab": KERNELS_VOCAB,
        "legs": legs,
        "autotune_winners_file": winners_path,
        "second_start_cold_compiles": warm.get("compiles_cold"),
        "second_start_warm_compiles": warm.get("compiles_warm"),
        # ok = the CPU-provable invariants: the fused epilogue moved
        # measurably fewer bytes AND the autotuned warm restart was
        # entirely cache-served.
        "ok": bool((ratio or 0) > 1.5 and warm.get("compiles_cold") == 0),
    }
    if sink is not None:
        sink.write_record({
            "kind": "run_summary", "tag": "telemetry",
            "step": KERNELS_REQUESTS, "steps": KERNELS_REQUESTS,
            "metric": result["metric"]})
        sink.close()
    print(_json.dumps(result))


def _serve_saturation_child_main():
    """BENCH_SERVE_SATURATION leg: the ROADMAP saturation curve — a
    closed-loop req/s vs p99 sweep through the REAL fleet (supervisor-
    owned ``run_server.py`` replica subprocesses behind the router), at
    1 and 2 supervised replicas, pipelined vs serial dispatch legs
    replaying the same trace (docs/serving.md "Continuous batching").

    The parent stays jax-free (supervisor/router/synthetic-data load by
    file path, like tools/chaos_serve.py): all compilation happens
    inside the replica subprocesses, which share one persistent AOT
    cache — the first replica of the first fleet compiles, every later
    fleet warms from the cache, so four fleets cost one warmup. A small
    2-layer model keeps each point dispatch-bound, which is the thing
    under test: the curve separates the dispatch planes, not the model.

    A CPU harness: the supervisor starts one ``run_server.py`` PROCESS per
    replica, and a chip belongs to one process at a time, so two such
    replicas cannot share a TPU. Until replicas can live in one process
    (each pinned to its own device), this leg measures host dispatch on
    the CPU backend and nothing about the device.
    """
    import http.client
    import importlib.util
    import json as _json
    import socket
    import tempfile
    import threading
    import urllib.parse

    def _load(name, *parts):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO_ROOT, *parts))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    supervisor_mod = _load("_sat_supervisor",
                           "bert_pytorch_tpu", "serve", "supervisor.py")
    router_mod = _load("_sat_router",
                       "bert_pytorch_tpu", "serve", "router.py")
    synth = _load("_sat_synth",
                  "bert_pytorch_tpu", "tools", "make_synthetic_data.py")

    replicas_list = [int(n) for n in os.environ.get(
        "BENCH_SERVE_SAT_REPLICAS", "1,2").split(",") if n.strip()]
    modes = [m.strip() for m in os.environ.get(
        "BENCH_SERVE_SAT_MODES", "pipelined,serial").split(",")
        if m.strip()]
    workers_list = [int(n) for n in os.environ.get(
        "BENCH_SERVE_SAT_WORKERS", "2,6").split(",") if n.strip()]
    point_requests = int(os.environ.get("BENCH_SERVE_SAT_REQUESTS", "48"))
    warmup_s = float(os.environ.get("BENCH_SERVE_SAT_WARMUP_S", "240"))

    workdir = tempfile.mkdtemp(prefix="bench_servesat_")
    vocab_path = synth.write_trace_vocab(os.path.join(workdir, "vocab.txt"))
    vocab = 5 + len(synth.TRACE_WORDS)
    vocab += (8 - vocab % 8) % 8
    config_path = os.path.join(workdir, "model.json")
    with open(config_path, "w") as f:
        _json.dump({
            "vocab_size": vocab, "hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "intermediate_size": 128,
            "max_position_embeddings": 64, "type_vocab_size": 2,
            "next_sentence": True, "mask_token_id": 4,
            "hidden_dropout_prob": 0.0,
            "attention_probs_dropout_prob": 0.0,
        }, f)

    phrases = ("paris is big", "the river runs through london",
               "william shakespeare wrote hamlet", "england is old",
               "the capital of france is paris")

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    def spawn(spec):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("BENCH_CHILD", None)  # the replica is run_server, not us
        if spec.env:
            env.update(spec.env)
        log = open(os.path.join(
            workdir, f"replica_{spec.index}.log"), "ab")
        return subprocess.Popen(spec.cmd, env=env, stdout=log,
                                stderr=subprocess.STDOUT)

    def post(url, payload, timeout_s):
        parsed = urllib.parse.urlsplit(url)
        conn = http.client.HTTPConnection(parsed.hostname, parsed.port,
                                          timeout=timeout_s)
        try:
            conn.request("POST", "/v1/classify",
                         body=_json.dumps(payload).encode("utf-8"),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            return resp.status
        finally:
            conn.close()

    def burst(url, total, workers):
        """Closed-loop burst: ``workers`` concurrent clients, each
        firing its next request the moment the previous answers —
        offered load scales with the worker count, which is the sweep
        axis of the saturation curve."""
        lock = threading.Lock()
        issued = [0]
        outcomes = []

        def worker():
            while True:
                with lock:
                    if issued[0] >= total:
                        return
                    issued[0] += 1
                    seq = issued[0]
                payload = {"text": phrases[seq % len(phrases)]}
                t0 = time.monotonic()
                try:
                    status = post(url, payload, timeout_s=30.0)
                except Exception:
                    status = None
                with lock:
                    outcomes.append(
                        (status, time.monotonic() - t0))

        t0 = time.monotonic()
        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return outcomes, time.monotonic() - t0

    def pctl(sorted_vals, frac):
        if not sorted_vals:
            return None
        idx = min(len(sorted_vals) - 1,
                  int(frac * (len(sorted_vals) - 1) + 0.5))
        return sorted_vals[idx]

    legs = []
    for n_replicas in replicas_list:
        for mode in modes:
            shared_args = [
                "--model_config_file", config_path,
                "--vocab_file", vocab_path,
                "--tasks", "classify", "--classify_labels", "neg,pos",
                "--buckets", "16,32", "--max_batch_size", "4",
                "--max_wait_ms", "5", "--dtype", "float32",
                "--trace_sample_rate", "0", "--telemetry_window", "32",
                "--request_timeout_s", "20",
                "--dispatch_mode", mode,
            ]
            specs = []
            for i in range(n_replicas):
                out_dir = os.path.join(
                    workdir, f"fleet_{n_replicas}{mode[0]}_replica_{i}")
                os.makedirs(out_dir, exist_ok=True)
                port = free_port()
                specs.append(supervisor_mod.ReplicaSpec(
                    index=i, port=port,
                    cmd=supervisor_mod.run_server_command(
                        port, out_dir, shared_args),
                    heartbeat_file=os.path.join(out_dir, "heartbeat.json")))
            sup = supervisor_mod.Supervisor(
                specs, emit=lambda rec: None, spawn=spawn,
                startup_grace_s=warmup_s, poll_interval_s=0.25,
                drain_grace_s=15.0)
            router = router_mod.Router(
                [s.url for s in specs], emit=lambda rec: None,
                scrape_interval_s=0.25, deadline_s=20.0,
                brownout_queue_depth=4096)
            router_server = router_mod.make_router_server(router, port=0)
            url = "http://%s:%d" % router_server.server_address[:2]
            leg = {"replicas": n_replicas, "dispatch_mode": mode,
                   "points": []}
            try:
                sup.start()
                router.start()
                threading.Thread(target=router_server.serve_forever,
                                 daemon=True).start()
                deadline = time.monotonic() + warmup_s
                while time.monotonic() < deadline and \
                        router.healthy_count() < n_replicas:
                    time.sleep(0.25)
                if router.healthy_count() < n_replicas:
                    leg["error"] = "fleet never became healthy"
                    legs.append(leg)
                    continue
                for workers in workers_list:
                    outcomes, wall = burst(url, point_requests, workers)
                    ok = [lat for status, lat in outcomes
                          if status is not None and 200 <= status < 300]
                    lat = sorted(lat * 1000.0 for lat in ok)
                    leg["points"].append({
                        "workers": workers,
                        "requests": len(outcomes),
                        "ok": len(ok),
                        "failures": len(outcomes) - len(ok),
                        # Goodput, not offered load: a failure-heavy
                        # point must not outscore an all-ok one in the
                        # headline max (failures ride alongside).
                        "req_per_sec": round(len(ok) / wall, 2),
                        "p50_ms": round(pctl(lat, 0.50), 2) if lat else None,
                        "p99_ms": round(pctl(lat, 0.99), 2) if lat else None,
                    })
            finally:
                # Each teardown step gets its own guard: a replica that
                # wedges sup.stop() must not leak the previous leg's
                # router server + scrape thread into the later legs.
                for teardown in (sup.stop, router_server.shutdown,
                                 router.stop):
                    try:
                        teardown()
                    except Exception:
                        pass
            legs.append(leg)

    # The headline value: best pipelined req/s at the largest sweep
    # point; the serial twin rides alongside so the curve carries its
    # own A/B (pipelined should hold lower p99 at equal offered load).
    def best(mode):
        points = [p for leg in legs if leg["dispatch_mode"] == mode
                  for p in leg.get("points", []) if p["ok"]]
        return max((p["req_per_sec"] for p in points), default=None)

    result = {
        "metric": "serve_saturation_req_per_sec",
        "value": best("pipelined"),
        "unit": "req/s",
        "requests_per_point": point_requests,
        "workers_sweep": workers_list,
        "serial_best_req_per_sec": best("serial"),
        "legs": legs,
    }
    print(json.dumps(result))


def _async_child_main():
    """BENCH_ASYNC leg: checkpoint-step p95 vs steady-state p95, blocking
    vs async device-snapshot saves, on an injected large synthetic state.

    The stall under test is host-side (D2H fetch + msgpack + disk), so the
    leg is meaningful on any backend — the CPU-reproducible counterpart of
    the production win, measured through the same StepTimer/ckpt_step
    telemetry the runners emit (docs/telemetry.md). Steps are paced
    sleeps: the point is the ratio between a step that carried a save and
    one that didn't, not the step time itself.
    """
    import json as _json
    import shutil
    import tempfile

    import jax.numpy as jnp

    from bert_pytorch_tpu.telemetry.report import summarize_records
    from bert_pytorch_tpu.telemetry.step_timer import StepTimer
    from bert_pytorch_tpu.utils import checkpoint as ckpt

    n_leaves = 8
    leaf_elems = ASYNC_STATE_MB * (1 << 20) // 4 // n_leaves
    state = {"model": {f"w{i}": jnp.ones((leaf_elems,), jnp.float32)
                       for i in range(n_leaves)},
             "epoch": 0}

    def run_mode(async_write: bool):
        tmp = tempfile.mkdtemp(prefix="bench_async_")
        timer = StepTimer(window=10, sync_every=0)
        records = []
        try:
            for step in range(1, ASYNC_STEPS + 1):
                timer.data_start()
                timer.data_end()
                time.sleep(ASYNC_STEP_MS / 1000.0)
                timer.dispatch_end()
                rec = timer.step_done(step)
                if rec:
                    records.append(rec)
                if step % ASYNC_CKPT_EVERY == 0:
                    t0 = time.perf_counter()
                    ckpt.save_checkpoint(tmp, step, state, keep=2,
                                         async_write=async_write)
                    timer.note_ckpt_stall(time.perf_counter() - t0)
            ckpt.wait_for_pending_save(tmp)
            rec = timer.flush(ASYNC_STEPS)
            if rec:
                records.append(rec)
        finally:
            ckpt.wait_for_pending_save()
            shutil.rmtree(tmp, ignore_errors=True)
        for rec in records:
            rec.update({"kind": "step_window", "tag": "telemetry"})
        return records

    sync_records = run_mode(async_write=False)
    async_records = run_mode(async_write=True)
    sync_sum = summarize_records(sync_records)
    async_sum = summarize_records(async_records)
    steady = async_sum.get("step_p95_s") or 1e-9
    sync_ratio = (sync_sum.get("ckpt_step_p95_s") or 0.0) / (
        sync_sum.get("step_p95_s") or 1e-9)
    async_ratio = (async_sum.get("ckpt_step_p95_s") or 0.0) / steady
    metric = "ckpt_step_p95_over_steady_async"
    result = {
        "metric": metric,
        "value": round(async_ratio, 3),
        "unit": "x steady-state step p95",
        "sync_ratio": round(sync_ratio, 3),
        "sync_ckpt_step_p95_s": sync_sum.get("ckpt_step_p95_s"),
        "async_ckpt_step_p95_s": async_sum.get("ckpt_step_p95_s"),
        "step_p95_s": async_sum.get("step_p95_s"),
        "state_mb": ASYNC_STATE_MB,
        "steps": ASYNC_STEPS,
        "ckpt_every": ASYNC_CKPT_EVERY,
        # The acceptance shape: async within 20% of steady state while
        # blocking stays a clear multiple (tests/test_async_hotpath.py
        # asserts the same through the report gating path).
        "ok": bool(async_ratio <= 1.2 < sync_ratio),
    }
    if TELEMETRY_JSONL:
        from bert_pytorch_tpu.utils.logging import JSONLHandler

        sink = JSONLHandler(TELEMETRY_JSONL, overwrite=False)
        for rec in async_records:
            sink.write_record(rec)
        sink.write_record({
            "kind": "run_summary", "tag": "telemetry",
            "step": ASYNC_STEPS, "steps": ASYNC_STEPS, "metric": metric,
            "ckpt_step_p95_s": async_sum.get("ckpt_step_p95_s")})
        sink.close()
    print(_json.dumps(result))


def _mesh_child_main():
    """BENCH_MESH leg: step time across composed strategy products on
    a forced-host 8-device CPU mesh (docs/parallelism.md).

    Every product steps the SAME tiny model with the SAME global batch
    through the one-mesh path — ``MeshSpec.parse`` -> derived rules ->
    composed collectives — so the numbers rank the parallelism overhead,
    not the model. Each captured product appends its own perf-ledger
    entry under a distinct config digest (products are only comparable
    with themselves across time); the printed result carries the full
    per-product table.
    """
    import hashlib
    import json as _json

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bert_pytorch_tpu import optim, pretrain
    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.parallel import (
        MeshSpec,
        MeshSpecError,
        create_mesh,
        logical_axis_rules,
    )
    from bert_pytorch_tpu.telemetry import ledger as ledger_mod

    seq, global_batch, n_mb, max_pred = 128, 16, 4, 20
    config = BertConfig.from_dict({
        "vocab_size": 1024, "hidden_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 512,
        "max_position_embeddings": seq, "type_vocab_size": 2,
        "hidden_dropout_prob": 0.1, "attention_probs_dropout_prob": 0.1,
        "next_sentence": True,
    })
    model = BertForPreTraining(config, dtype=jnp.float32)
    schedule = optim.warmup_poly_schedule(1e-3, 0.25, 1000)
    sample = (jnp.zeros((1, seq), jnp.int32),) * 3
    rng = np.random.default_rng(0)
    host_flat = {
        "input_ids": rng.integers(
            0, config.vocab_size, (global_batch, seq)).astype(np.int32),
        "segment_ids": rng.integers(0, 2, (global_batch, seq)).astype(np.int32),
        "input_mask": np.ones((global_batch, seq), np.int32),
        "masked_lm_labels": np.where(
            rng.random((global_batch, seq)) < 0.15,
            rng.integers(0, config.vocab_size, (global_batch, seq)),
            -1).astype(np.int32),
        "next_sentence_labels": rng.integers(
            0, 2, (global_batch,)).astype(np.int32),
    }
    batch_dims = {"input_ids": 3, "segment_ids": 3, "input_mask": 3,
                  "masked_lm_labels": 3, "next_sentence_labels": 2}

    products, captured = [], 0
    for text in [s.strip() for s in MESH_SPECS.split(";") if s.strip()]:
        try:
            spec = MeshSpec.parse(text)
            spec.validate(n_devices=len(jax.devices()))
        except MeshSpecError as e:
            products.append({"spec": text, "skipped": f"invalid spec: {e}"})
            continue
        entry = {"spec": spec.canonical()}
        try:
            mesh = create_mesh(spec.mesh_config())
            rules = logical_axis_rules(spec)
            tx = optim.lamb(schedule, weight_decay_mask=optim.no_decay_mask)
            pipe = spec.pipe > 1
            # pp consumes explicit microbatches; dp/fsdp take one stacked
            # macrobatch (ACCUM=1) — same sequences per optimizer step.
            accum = n_mb if pipe else 1
            with mesh:
                shardings = pretrain.state_shardings(
                    mesh, model, rules, sample)
                b_shardings = pretrain.batch_shardings(
                    mesh, batch_dims, seq_sharded=spec.seq > 1)
                state = pretrain.make_init_fn(model, tx, sample, shardings)(
                    jax.random.PRNGKey(0))
                if pipe:
                    step = pretrain.make_pp_train_step(
                        model, tx, mesh, schedule=schedule,
                        next_sentence=True, shardings=shardings,
                        batch_shardings_=b_shardings,
                        max_pred_per_seq=max_pred)
                else:
                    step = pretrain.make_train_step(
                        model, tx, schedule=schedule, next_sentence=True,
                        shardings=shardings, batch_shardings_=b_shardings,
                        max_pred_per_seq=max_pred)
                batch = pretrain.put_batch(
                    pretrain.stack_microbatches(host_flat, accum),
                    b_shardings)
                for _ in range(MESH_WARMUP):
                    state, metrics = step(state, batch)
                    _ = float(metrics["loss"])
                start = time.perf_counter()
                for _ in range(MESH_STEPS):
                    state, metrics = step(state, batch)
                _ = float(metrics["loss"])  # forces the chained dispatch
                elapsed = time.perf_counter() - start
        except Exception as e:  # per-product: record, keep sweeping
            entry["skipped"] = f"{type(e).__name__}: {e}"
            products.append(entry)
            continue
        step_s = elapsed / MESH_STEPS
        seq_per_sec_chip = global_batch / step_s / len(jax.devices())
        entry.update({
            "step_ms_p50": round(step_s * 1000, 2),
            "seq_per_sec_chip": round(seq_per_sec_chip, 2),
        })
        products.append(entry)
        captured += 1
        if LEDGER_PATH:
            # Distinct digest per product: entries are only comparable
            # within one (config, product) pair across time.
            digest = hashlib.sha1(
                f"{CONFIG_DIGEST}|{spec.canonical()}".encode()
            ).hexdigest()[:12]
            try:
                ledger_mod.append_entry(
                    LEDGER_PATH, "mesh",
                    {"step_ms_p50": entry["step_ms_p50"],
                     "seq_per_sec_per_chip": entry["seq_per_sec_chip"]},
                    digest=digest,
                    extra={"metric": "mesh_product_step",
                           "mesh_spec": spec.canonical()})
                print(f"perf ledger: appended mesh [{digest}] "
                      f"{spec.canonical()}", file=sys.stderr)
            except Exception as exc:  # advisory, like the parent's append
                print(f"perf ledger append failed: {exc}", file=sys.stderr)

    if not captured:
        print("BENCH_CONFIG_ERROR: no mesh product captured: "
              + "; ".join(f"{p['spec']}: {p.get('skipped')}"
                          for p in products))
        sys.exit(2)
    best = max(p["seq_per_sec_chip"] for p in products
               if "seq_per_sec_chip" in p)
    print(_json.dumps({
        "metric": "mesh_products_seq_per_sec_chip",
        "value": round(best, 2),
        "unit": "seq/s/chip (best product)",
        "vs_baseline": 1.0,
        "products": products,
        "captured": captured,
        "steps": MESH_STEPS,
        "global_batch": global_batch,
    }))


def _metric_name_and_anchor():
    kfac_tag = "_kfac" if KFAC else ""
    pack_tag = "_packed" if PACK else ""
    if MESH_SWEEP:
        # No external anchor: products are compared against each other
        # (and longitudinally via the per-product ledger entries).
        return ("mesh_products_seq_per_sec_chip", 1.0)
    if KERNELS:
        # Anchor 1.0 like the serve legs: no external baseline exists;
        # the child prints its own richer result.
        return ("serve_kernels_fill_mask_output_bytes_ratio", 1.0)
    if SERVE:
        # No external anchor exists for the serve leg. The child prints
        # its own richer result.
        if os.environ.get("BENCH_SERVE_QUANT", "0") == "1":
            mode = os.environ.get("BENCH_SERVE_QUANT_MODE", "int8")
            return (f"bert_base_serve_{mode}_p50_ms", 1.0)
        return ("bert_base_serve{}_req_per_sec".format(
            "_packed" if SERVE_PACK else ""), 1.0)
    if LONG_SEQ:
        return (f"bert_large_seq{SEQ_LEN}{kfac_tag}{pack_tag}_seq_per_sec",
                A100_PHASE2_SEQ_PER_SEC * 512.0 / SEQ_LEN)
    return (f"bert_large_phase{PHASE}{kfac_tag}{pack_tag}_seq_per_sec",
            A100_PHASE2_SEQ_PER_SEC if _P2 else A100_PHASE1_SEQ_PER_SEC)


def _result_json(seq_per_sec_chip, mfu=None, n_chips=None):
    name, anchor = _metric_name_and_anchor()
    out = {
        "metric": name,
        "value": round(seq_per_sec_chip, 2),
        "unit": "seq/s/chip",
        "vs_baseline": round(seq_per_sec_chip / anchor, 4),
    }
    if KFAC:
        out["kfac_capture"] = KFAC_CAPTURE
    if mfu is not None:
        out["mfu"] = round(mfu, 4)
    if n_chips is not None:
        out["n_chips"] = n_chips  # scaling sweeps (BENCH_DEVICES) read this
    return out


def _telemetry_offset():
    """Byte size of the append-mode telemetry sink RIGHT NOW — taken
    immediately before the child starts, so an earlier invocation's
    records never leak into the tail the regression gate scores."""
    if TELEMETRY_JSONL and os.path.exists(TELEMETRY_JSONL):
        try:
            return os.path.getsize(TELEMETRY_JSONL)
        except OSError:
            return 0
    return 0


def _attach_regression(result, offset=0):
    """Offline regression gate: when this run wrote a telemetry artifact
    and a previous committed one exists, diff them with
    tools/telemetry_report.py and attach the verdict. The bench result
    must always print, so the report's nonzero exit becomes a field
    (CI/the capture harness gate on it), never a bench failure.

    ``offset`` is the artifact's byte size when this invocation started:
    the sink is append-mode (capture passes accumulate evidence across
    runs), so the verdict must be computed over THIS invocation's records
    only — older runs' windows/memory records would otherwise pollute the
    maxima."""
    if not TELEMETRY_JSONL or not os.path.exists(TELEMETRY_JSONL):
        return result
    baseline = TELEMETRY_BASELINE
    if (not baseline or not os.path.exists(baseline)
            or os.path.abspath(baseline) == os.path.abspath(TELEMETRY_JSONL)):
        return result
    tool = os.path.join(REPO_ROOT, "tools", "telemetry_report.py")
    try:
        run_path = TELEMETRY_JSONL
        tmp_tail = None
        if offset:
            import tempfile

            with open(TELEMETRY_JSONL, "rb") as f:
                f.seek(offset)
                tail = f.read()
            fd, tmp_tail = tempfile.mkstemp(suffix=".jsonl")
            with os.fdopen(fd, "wb") as f:
                f.write(tail)
            run_path = tmp_tail
        try:
            # --last-run: both artifacts are append-mode accumulations
            # (this invocation's tail can hold several runs; the
            # committed baseline can hold several legs) — score each
            # side's final run only.
            proc = subprocess.run(
                [sys.executable, tool, run_path, baseline, "--json",
                 "--last-run"],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                timeout=120)
        finally:
            if tmp_tail:
                os.unlink(tmp_tail)
        verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception as exc:  # the gate is advisory; never break the bench
        print(f"telemetry regression gate failed: {exc}", file=sys.stderr)
        return result
    # Different bench legs (phase2, seq2048, kfac)
    # share the default baseline path; diffing step time or peak memory
    # across configurations is meaningless — refuse, don't flag.
    run_metric = verdict.get("run", {}).get("metric")
    base_metric = verdict.get("baseline", {}).get("metric")
    if run_metric and base_metric and run_metric != base_metric:
        result["regression"] = {
            "verdict": "n/a",
            "baseline": os.path.basename(baseline),
            "note": f"baseline is {base_metric}, this run is "
                    f"{run_metric}; not comparable",
        }
        return result
    result["regression"] = {
        "verdict": verdict.get("verdict"),
        "baseline": os.path.basename(baseline),
        "regressions": [
            {k: r.get(k) for k in ("metric", "base", "new", "change")}
            for r in verdict.get("regressions", [])],
    }
    if verdict.get("verdict") == "regression":
        names = ", ".join(
            r.get("metric", "?") for r in verdict.get("regressions", []))
        print(f"bench REGRESSION vs {baseline}: {names}", file=sys.stderr)
    return result


def _ledger_leg():
    """Ledger leg name for the active bench configuration — entries are
    only comparable within a leg, so each child flavor gets its own."""
    if SERVE_SATURATION:
        return "serve_saturation"
    if SERVE:
        return "serve"
    if KERNELS:
        return "kernels"
    if ASYNC:
        return "async"
    if MESH_SWEEP:
        return "mesh"
    return "train"


def _append_ledger(result):
    """Append this capture's headline metrics to the perf ledger
    (advisory like the regression gate: a ledger failure must never
    break the bench result line). The ledger module is stdlib-only and
    loaded by file path — the parent stays jax-free."""
    if not LEDGER_PATH or result.get("error"):
        return
    try:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "_bench_ledger",
            os.path.join(REPO_ROOT, "bert_pytorch_tpu", "telemetry",
                         "ledger.py"))
        ledger = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ledger)
        metrics = {}
        for src, dst, scale in (
                ("mfu", "mfu", 1.0),
                ("latency_p50_ms", "serve_p50_ms", 1.0),
                ("latency_p99_ms", "serve_p99_ms", 1.0),
                ("cold_start_s", "cold_start_s", 1.0),
                ("padding_efficiency", "padding_efficiency", 1.0),
                # Direction-less extras: recorded for the trajectory
                # (perf_ledger.py show), not gated by the drift check.
                ("value", "headline", 1.0),
                ("seq_per_sec_per_chip", "seq_per_sec_per_chip", 1.0)):
            v = result.get(src)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                metrics[dst] = float(v) * scale
        rec = ledger.append_entry(
            LEDGER_PATH, _ledger_leg(), metrics,
            digest=_config_digest(),
            extra={"metric": result.get("metric")})
        if rec is not None:
            print(f"perf ledger: appended {rec['leg']} "
                  f"[{rec['config_digest']}] to {LEDGER_PATH}",
                  file=sys.stderr)
    except Exception as exc:
        print(f"perf ledger append failed: {exc}", file=sys.stderr)


def _metric_line_index(lines):
    """Index of the last line of ``lines`` that is a JSON object with a
    "metric" key, or None. The result line must stay findable under
    kilobytes of runtime teardown logging printed after it."""
    for i in range(len(lines) - 1, -1, -1):
        try:
            cand = json.loads(lines[i])
        except ValueError:
            continue
        if isinstance(cand, dict) and "metric" in cand:
            return i
    return None


def main():
    """Parent: start the benchmark child once and pass it through.

    The parent never imports JAX (module docstring). The child's standard
    error is inherited and its standard output is relayed as printed. A
    child that exits non-zero, or prints no result line, makes this
    process exit non-zero: nothing is retried, probed or substituted. On
    success the result line comes last, with the advisory regression
    verdict attached (_attach_regression), and goes to the ledger when
    BENCH_LEDGER names one.
    """
    env = dict(os.environ)
    env["BENCH_CHILD"] = "1"
    if PACK:
        # The child is respawned WITHOUT argv, so the --pack_sequences
        # command-line spelling must be forwarded as the env knob.
        env["BENCH_PACK"] = "1"
        env.setdefault("BENCH_PACK_K", str(PACK_K))
    tele_offset = _telemetry_offset()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env,
        stdout=subprocess.PIPE, text=True)
    lines = proc.stdout.splitlines()
    at = _metric_line_index(lines) if proc.returncode == 0 else None
    if at is None:
        sys.stdout.write(proc.stdout)
        if proc.returncode == 0:
            sys.exit("bench child exited 0 without printing a result line")
        sys.exit(proc.returncode)
    result = _attach_regression(json.loads(lines.pop(at)), tele_offset)
    _append_ledger(result)
    for line in lines:
        print(line)
    print(json.dumps(result))


if __name__ == "__main__":
    if os.environ.get("BENCH_CHILD") == "1":
        if ASYNC:
            _async_child_main()
        elif MESH_SWEEP:
            _mesh_child_main()
        elif KERNELS:
            _kernels_child_main()
        elif SERVE_SATURATION:
            _serve_saturation_child_main()
        elif SERVE:
            _serve_child_main()
        else:
            _child_main()
    else:
        main()
