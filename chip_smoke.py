#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             # one TPU chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: the mesh trainer only

Drives the two main paths through the entry points a user would call —
``run_pretraining.py`` and ``run_server.py`` — at BERT-large width
(configs/bert_large_uncased_config.json, all 24 layers, random weights from
``--seed``), and checks what comes out. Phases, one JSON line each on stdout:

* ``kernels`` — every Pallas kernel against the XLA reference path at 16
  heads x 64, seq 128 and 512, padded and packed, forward and gradients; the
  hardware-PRNG dropout checks; kernels compiled, not interpreted.
* ``train`` — phase-1 shape: a few steps, a checkpoint, a second invocation
  that resumes from it with the train step served by the persistent compile
  cache. Then phase-2 shape with ``--attention_backend auto``: the compiled
  step must hold the fused kernel; peak device bytes are printed.
* ``serve`` — the server warms up, answers /v1/fill_mask and another head
  over HTTP, reports 0 compiles after warm-up, drains on SIGTERM with the
  graceful exit code and schema-clean telemetry; a second start against the
  same cache performs 0 cold compiles and gives the same answer.
* ``mesh`` (``--chips 4`` only, and then no other phase) —
  ``--mesh dp=4`` and ``--mesh dp=2,fsdp=2`` against a one-device run with
  accumulation on the same data: losses within tolerance, state and batch
  on four distinct devices.

One process for each chip: this parent never imports JAX (a parent that had
touched JAX would hold the chip its children need), and every phase that
starts a runner is one child at a time, waited for to its end. The device in
the last line is what the children reported.

It fails — exits non-zero and prints no ``"ok": true`` — when any phase
fails, when JAX finds no TPU, and anywhere but in a checkout of the
repository. The last line of stdout, on success and only then, is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

All inputs are generated from ``--seed`` with
``python -m bert_pytorch_tpu.tools.make_synthetic_data``; nothing is read
from the network or from outside the checkout. The compile cache is where
utils/compile_cache.py puts it: ``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

# ---------------------------------------------------------------------------
# What is run. tests/test_chip_smoke.py rehearses the control flow on the CPU
# by patching these constants (a tiny model, the CPU as the expected device);
# the runners get no switch for it.

MODEL_CONFIG = os.path.join(REPO, "configs", "bert_large_uncased_config.json")
# What every child must report of itself (ops/pallas/common.py device_report).
EXPECT = {"platform": "tpu", "kernels": "compiled"}

KERNELS = {
    "heads": 16, "depth": 64, "hidden": 1024, "batch": 4,
    "seqs": [128, 512],
    # Largest difference allowed between a Pallas kernel and the XLA
    # reference path on N(0,1) inputs in bf16, for each array relative to
    # max(1, its largest reference magnitude). Both sides round to bf16 (8
    # bits of mantissa: half an ulp is 2e-3 relative, 1.6e-2 at magnitude 4)
    # after fp32 accumulation in a different order; gradients pass through
    # two such roundings. The int8 kernel quantizes QK^T per head on top of
    # it (docs/serving.md "Raw-speed kernels"). Measured on a v5e: attention
    # 0.016, int8 0.031, layer norm 0.016 (CHANGES.md, PR 22).
    "tol": {"fwd": 3e-2, "grad": 3e-2, "infer": 3e-2, "infer_int8": 6e-2,
            "layer_norm": 3e-2, "layer_norm_grad": 3e-2},
}

RECIPE1 = os.path.join(REPO, "configs", "bert_pretraining_phase1_config.json")
RECIPE2 = os.path.join(REPO, "configs", "bert_pretraining_phase2_config.json")
# One fixed batch for each shape: the recipes' own per-chip batch.
# steps is 4 so that the resume step is a multiple of the grad-stats cadence
# and the resumed train step is the program the first run compiled.
PHASE1 = {"seq_len": 128, "local_batch": 64, "steps": 4, "resume_steps": 2}
PHASE2 = {"seq_len": 512, "local_batch": 32, "steps": 3}
FIRST_LOSS_TOL = 0.5  # around ln(vocab) + ln 2: random weights, MLM + NSP
# The compile events of the resumed run's train step: served from the
# persistent cache, and compiled nowhere.
RESUMED_STEP_COMPILE = ["hit"]

SERVE = {"buckets": "32,128,512", "tasks": "fill_mask,classify",
         "dtype": "bfloat16", "fill_mask_requests": 5}

MESH = {"specs": ["dp=4", "dp=2,fsdp=2"], "local_batch": 64, "steps": 3,
        # Same parameters (threefry init does not depend on the layout), same
        # rows, no dropout: what is left between a one-device run with
        # accumulation and a mesh run is bf16 rounding under another
        # reduction order, and the mean over microbatches against the mean
        # over one batch. With no warm-up the losses move (11.3, 13.7, 12.2
        # on a v5e); measured there: 0.0099 for dp=4, 0.0025 for dp=2,fsdp=2.
        "loss_tol": 0.05}
# How a child on a four-chip host is shown ONE device, from outside.
# (libtpu reads the *_PROCESS_* names; the *_HOST_* ones are their older
# spellings, which the machine may come with set for the whole host.)
ONE_DEVICE_ENV = {"TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0",
                  "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                  "TPU_PROCESS_BOUNDS": "1,1,1",
                  "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
                  "TPU_HOST_BOUNDS": "1,1,1"}
ALL_DEVICES_ENV: dict = {}

EXIT_PREEMPTED = 75  # utils/preemption.py: a SIGTERM drain that completed
CHILD_TIMEOUT_S = 900


class PhaseFailed(Exception):
    """A check did not hold; the message says which."""


def check(condition, message: str) -> None:
    if not condition:
        raise PhaseFailed(message)


# ---------------------------------------------------------------------------
# children


def child_env(extra=None) -> dict:
    env = dict(os.environ, PYTHONUNBUFFERED="1")
    env.update(extra or {})
    return env


def run_child(argv, log_path, env=None, timeout=CHILD_TIMEOUT_S) -> int:
    """Run one child to its end, output to ``log_path``; returns its code.
    A child that outlives ``timeout`` is killed and counts as failed."""
    with open(log_path, "ab") as log:
        proc = subprocess.Popen(argv, cwd=REPO, env=child_env(env),
                                stdout=log, stderr=subprocess.STDOUT)
        try:
            return proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise PhaseFailed(
                f"{os.path.basename(argv[1])} still running after "
                f"{timeout}s; killed (log: {log_path})") from None


def tail(path, n=25) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def read_jsonl(path) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def make_data(out_dir, seed, *extra) -> None:
    log = os.path.join(os.path.dirname(out_dir), "make_data.log")
    rc = run_child(
        [sys.executable, "-m", "bert_pytorch_tpu.tools.make_synthetic_data",
         "--output_dir", out_dir, "--seed", str(seed), *extra], log)
    check(rc == 0, f"make_synthetic_data exited {rc}:\n{tail(log)}")


def model_vocab() -> int:
    with open(MODEL_CONFIG) as f:
        vocab = json.load(f)["vocab_size"]
    return vocab + -vocab % 8  # the runners pad it for the MXU


def check_device(report: dict, devices: dict, count: int) -> None:
    """A child's account of itself against EXPECT, and against what the
    children before it reported (the last line quotes it)."""
    for key, want in EXPECT.items():
        check(report.get(key) == want,
              f"child reports {key}={report.get(key)!r}, wanted {want!r} "
              f"(full report: {report})")
    check(report.get("device_count") == count,
          f"child sees {report.get('device_count')} device(s), wanted {count}")
    if count != devices["chips"]:
        return  # the one-device comparison of --chips 4
    seen = {"platform": report["platform"], "kind": report["device_kind"],
            "count": report["device_count"]}
    check(devices.setdefault("device", seen) == seen,
          f"children disagree on the device: {seen} != {devices['device']}")


# ---------------------------------------------------------------------------
# phase: kernels (one child of this file, the only code here that imports JAX)


def phase_kernels(work, seed, devices) -> dict:
    out = os.path.join(work, "kernels.json")
    log = os.path.join(work, "kernels.log")
    spec = dict(KERNELS, seed=seed, expect=EXPECT)
    rc = run_child([sys.executable, os.path.abspath(__file__),
                    "--child-kernels", json.dumps(spec), out], log)
    check(os.path.exists(out),
          f"kernels child exited {rc} without a report:\n{tail(log)}")
    with open(out) as f:
        result = json.load(f)
    check_device(result["device"], devices, 1)
    check(not result["failures"], "; ".join(result["failures"]))
    check(rc == 0, f"kernels child exited {rc}:\n{tail(log)}")
    return {"checks": len(result["errors"]), "max_err": result["errors"],
            "dropout": result["dropout"],
            "interpret_mode": result["interpret_mode"]}


def kernels_child(spec_json: str, out_path: str) -> int:
    """Pallas kernels against the XLA reference, on whatever JAX finds."""
    spec = json.loads(spec_json)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bert_pytorch_tpu import ops
    from bert_pytorch_tpu.ops.attention import make_attention_bias
    from bert_pytorch_tpu.ops.pallas.attention import flash_attention
    from bert_pytorch_tpu.ops.pallas.common import (device_report,
                                                    interpret_mode)

    report = device_report()
    result = {"device": report, "interpret_mode": interpret_mode(),
              "errors": {}, "failures": [], "dropout": "not run"}

    def finish() -> int:
        with open(out_path, "w") as f:
            json.dump(result, f)
        return 1 if result["failures"] else 0

    if any(report.get(k) != v for k, v in spec["expect"].items()):
        # Not the device that was asked for: say so and stop, rather than
        # grind through BERT-large shapes in the interpreter.
        result["failures"].append(
            f"wanted {spec['expect']}, running on {report}")
        return finish()

    heads, depth, batch = spec["heads"], spec["depth"], spec["batch"]
    tol = spec["tol"]
    rng = np.random.default_rng(spec["seed"])

    def worst(got, want) -> float:
        """Largest |got - want| over the arrays, each relative to
        max(1, largest |want|) of its array."""
        def one(g, w):
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            return float(jnp.max(jnp.abs(g - w))
                         / jnp.maximum(1.0, jnp.max(jnp.abs(w))))
        return max(one(g, w) for g, w in zip(
            jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))

    def record(name, got, want, bound) -> None:
        finite = all(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32))))
                     for x in jax.tree_util.tree_leaves(got))
        err = worst(got, want)
        result["errors"][name] = round(err, 5)
        if not finite or not err <= bound:
            result["failures"].append(
                f"{name}: max err {err:.4g} > {bound} (finite={finite})")

    for seq in spec["seqs"]:
        shape = (batch, seq, heads, depth)
        q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                   for _ in range(3))
        # padded: the tail of every row is padding; packed: three
        # sequences and a padded tail in every row
        mask = np.ones((batch, seq), np.int32)
        mask[:, seq - seq // 8:] = 0
        ids = np.zeros((batch, seq), np.int32)
        cuts = [0, seq // 4, seq // 2, seq - seq // 8]
        for n, (lo, hi) in enumerate(zip(cuts, cuts[1:])):
            ids[:, lo:hi] = n + 1
        layouts = {
            "padded": (mask, dict(
                bias=make_attention_bias(jnp.asarray(mask)))),
            "packed": (ids > 0, dict(
                bias=make_attention_bias(None, sequence_ids=jnp.asarray(ids)),
                sequence_ids=jnp.asarray(ids))),
        }
        for layout, (valid, kwargs) in layouts.items():
            # what a padding QUERY attends to is nobody's business: the
            # model masks those rows out of every loss, so they are
            # masked out of the comparison
            valid = jnp.asarray(valid, jnp.bfloat16)[:, :, None, None]

            def attend(backend, q, k, v, kwargs=kwargs, valid=valid):
                return valid * ops.dot_product_attention(
                    q, k, v, backend=backend, **kwargs)

            def loss(backend):
                return lambda q, k, v: jnp.sum(jnp.tanh(
                    attend(backend, q, k, v).astype(jnp.float32)))

            want = jax.jit(lambda q, k, v: attend("xla", q, k, v))(q, k, v)
            want_grads = jax.jit(jax.grad(loss("xla"), (0, 1, 2)))(q, k, v)
            tag = f"seq{seq}_{layout}"
            record(f"flash_attention_fwd_{tag}", jax.jit(
                lambda q, k, v: attend("pallas", q, k, v))(q, k, v),
                want, tol["fwd"])
            record(f"flash_attention_grad_{tag}", jax.jit(
                jax.grad(loss("pallas"), (0, 1, 2)))(q, k, v),
                want_grads, tol["grad"])
            for backend in ("pallas_infer", "pallas_infer_int8"):
                record(f"flash_attention_{backend[7:]}_{tag}", jax.jit(
                    lambda q, k, v, b=backend: attend(b, q, k, v))(q, k, v),
                    want, tol[backend[7:]])

        x = jnp.asarray(rng.normal(size=(batch, seq, spec["hidden"])),
                        jnp.bfloat16)
        scale, bias = (jnp.asarray(rng.normal(size=(spec["hidden"],)),
                                   jnp.float32) for _ in range(2))

        # a fixed linear functional of the output: its cotangent does not
        # depend on how the output rounded, so what is compared is the
        # backward pass and not cos() of a one-ulp difference
        weight = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

        def ln(backend):
            return lambda x, s, b: ops.layer_norm(x, s, b, backend=backend)

        def ln_loss(backend):
            return lambda x, s, b: jnp.sum(
                ln(backend)(x, s, b).astype(jnp.float32) * weight)

        record(f"layer_norm_fwd_seq{seq}",
               jax.jit(ln("pallas"))(x, scale, bias),
               jax.jit(ln("xla"))(x, scale, bias), tol["layer_norm"])
        record(f"layer_norm_grad_seq{seq}",
               jax.jit(jax.grad(ln_loss("pallas"), (0, 1, 2)))(x, scale, bias),
               jax.jit(jax.grad(ln_loss("xla"), (0, 1, 2)))(x, scale, bias),
               tol["layer_norm_grad"])

    if not interpret_mode():
        # In-kernel dropout from the hardware PRNG (tests/test_ops.py can
        # only skip this off a TPU): the same key gives the same masks,
        # fresh and split keys differ, and the mean over keys approaches
        # the dense result (keep-rate and rescaling are right).
        seq = spec["seqs"][0]
        shape = (batch, seq, heads, depth)
        q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                   for _ in range(3))
        mask = np.ones((batch, seq), np.int32)
        mask[:, seq - 5:] = 0
        bias = make_attention_bias(jnp.asarray(mask))
        base = flash_attention(q, k, v, bias=bias)
        drop = jax.jit(lambda key: flash_attention(
            q, k, v, bias=bias, dropout_rate=0.1, dropout_rng=key))
        problems = []
        for impl in ("threefry2x32", "rbg"):
            with jax.default_prng_impl(impl):
                key = jax.random.PRNGKey(7)
                d1, d2 = drop(key), drop(key)
                d3 = drop(jax.random.PRNGKey(8))
                s1, s2 = jax.random.split(key)
                if not bool(jnp.all(d1 == d2)):
                    problems.append(f"{impl}: same key, different masks")
                if not bool(jnp.any(d1 != d3)):
                    problems.append(f"{impl}: fresh key, same masks")
                if not bool(jnp.any(drop(s1) != drop(s2))):
                    problems.append(f"{impl}: split keys, same masks")
                if not bool(jnp.any(d1 != base)):
                    problems.append(f"{impl}: dropout changed nothing")
        mean = sum(drop(jax.random.PRNGKey(i)) for i in range(32)) / 32
        rel = float(jnp.abs(mean - base).mean() / jnp.abs(base).mean())
        if not rel < 0.1:
            problems.append(f"mean over 32 keys is {rel:.3f} off the dense "
                            "result (keep-rate or rescaling wrong)")
        result["dropout"] = {"deterministic": not problems,
                             "mean_rel_err": round(rel, 4)}
        result["failures"] += [f"dropout: {p}" for p in problems]
    return finish()


# ---------------------------------------------------------------------------
# phase: train


def pretrain_argv(data, out, recipe, local_batch, global_batch, steps, seed,
                  *extra) -> list:
    return [sys.executable, os.path.join(REPO, "run_pretraining.py"),
            "--input_dir", data, "--output_dir", out,
            "--model_config_file", MODEL_CONFIG, "--config_file", recipe,
            "--local_batch_size", str(local_batch),
            "--global_batch_size", str(global_batch),
            "--steps", str(steps), "--seed", str(seed),
            "--rng_impl", "rbg", "--remat", "dots",
            "--disable_tensorboard", *extra]


def run_trainer(argv, out, label, env=None) -> dict:
    """One run_pretraining.py invocation to its end; returns what its own
    telemetry says of THIS invocation (the JSONL appends across resumes)."""
    os.makedirs(out, exist_ok=True)
    log = os.path.join(out, f"{label}.log")
    jsonl = os.path.join(out, "pretraining_telemetry.jsonl")
    before = len(read_jsonl(jsonl)) if os.path.exists(jsonl) else 0
    started = time.monotonic()
    rc = run_child(argv, log, env=env)
    check(rc == 0, f"run_pretraining.py ({label}) exited {rc}:\n{tail(log)}")
    records = read_jsonl(jsonl)[before:]
    summary = [r for r in records if r.get("kind") == "run_summary"]
    check(len(summary) == 1, f"{label}: no run_summary record")
    train = [r for r in records if r.get("tag") == "train"]
    memory = [r for r in records
              if r.get("kind") == "memory" and r.get("memory_supported")]
    return {
        "label": label, "seconds": round(time.monotonic() - started, 1),
        "summary": summary[0],
        "steps": [r["step"] for r in train],
        "losses": [r["step_loss"] for r in train],
        "compiles": [r for r in records if r.get("kind") == "compile"],
        "costs": [r for r in records if r.get("kind") == "compile_cost"],
        "resumes": [r for r in records if r.get("kind") == "resume"],
        # device.memory_stats() as the trainer's telemetry sampled it
        "peak_bytes_in_use": max(
            (r["peak_bytes_in_use"] for r in memory), default=None),
        "bytes_limit": max((r["bytes_limit"] for r in memory), default=None),
    }


def check_losses(run, first_loss=None) -> None:
    check(run["losses"] and all(math.isfinite(x) for x in run["losses"]),
          f"{run['label']}: non-finite or missing losses {run['losses']}")
    if first_loss is not None:
        check(abs(run["losses"][0] - first_loss) <= FIRST_LOSS_TOL,
              f"{run['label']}: first loss {run['losses'][0]:.3f} is not "
              f"within {FIRST_LOSS_TOL} of ln(vocab)+ln 2 = {first_loss:.3f}")


def step_compiles(run) -> list:
    return [c["cache"] for c in run["compiles"] if c["fn"] == "train_step"]


def brief(run) -> dict:
    step = next((c for c in run["costs"] if c["fn"] == "train_step"), {})
    return {"seconds": run["seconds"], "steps": run["steps"],
            "losses": [round(x, 4) for x in run["losses"]],
            "train_step_compile": step_compiles(run),
            "compile_events": len(run["compiles"]),
            # the allocator's high-water mark (on a v5e it does not count
            # the running program's temporaries) beside what the compiled
            # step says it needs: arguments + temporaries is what has to
            # fit under bytes_limit
            "peak_bytes_in_use": run["peak_bytes_in_use"],
            "bytes_limit": run["bytes_limit"],
            "step_argument_bytes": step.get("argument_bytes"),
            "step_temp_bytes": step.get("temp_bytes")}


def phase_train(work, seed, devices) -> dict:
    first_loss = math.log(model_vocab()) + math.log(2)
    data1, out1 = os.path.join(work, "data_p1"), os.path.join(work, "p1")
    make_data(data1, seed, "--seq_len", str(PHASE1["seq_len"]),
              "--vocab_size", str(model_vocab()), "--num_shards", "2",
              "--samples_per_shard", str(
                  PHASE1["local_batch"]
                  * (PHASE1["steps"] + PHASE1["resume_steps"])))
    batch = PHASE1["local_batch"]
    first = run_trainer(pretrain_argv(
        data1, out1, RECIPE1, batch, batch, PHASE1["steps"], seed),
        out1, "phase1")
    check_device(first["summary"], devices, 1)
    check_losses(first, first_loss)
    check(first["steps"] == list(range(1, PHASE1["steps"] + 1)),
          f"phase1 steps {first['steps']}")
    check(not first["resumes"], "phase1 resumed from a stale checkpoint")

    # The second invocation: same command, same output directory.
    resumed = run_trainer(pretrain_argv(
        data1, out1, RECIPE1, batch, batch, PHASE1["resume_steps"], seed,
        "--skip_final_checkpoint"), out1, "phase1_resume")
    check_device(resumed["summary"], devices, 1)
    check_losses(resumed)
    check([r["step"] for r in resumed["resumes"]] == [PHASE1["steps"]],
          f"resume records {resumed['resumes']}, wanted one at step "
          f"{PHASE1['steps']}")
    end = PHASE1["steps"] + PHASE1["resume_steps"]
    check(resumed["steps"] == list(range(PHASE1["steps"] + 1, end + 1)),
          f"the resume did not continue the step count: {resumed['steps']}")
    # The compile events are the authority on cold against warm
    # (telemetry/compile_events.py): the first run compiled the step and
    # wrote it, the second was served from the persistent cache.
    check(step_compiles(resumed) == RESUMED_STEP_COMPILE,
          f"the resumed train step was not served from the persistent "
          f"compile cache: {step_compiles(resumed)} "
          f"(first run: {step_compiles(first)})")

    data2, out2 = os.path.join(work, "data_p2"), os.path.join(work, "p2")
    make_data(data2, seed + 1, "--seq_len", str(PHASE2["seq_len"]),
              "--vocab_size", str(model_vocab()), "--num_shards", "2",
              "--samples_per_shard",
              str(PHASE2["local_batch"] * PHASE2["steps"]))
    batch = PHASE2["local_batch"]
    second = run_trainer(pretrain_argv(
        data2, out2, RECIPE2, batch, batch, PHASE2["steps"], seed,
        # the recipe continues phase 1's checkpoint and names the kernel;
        # here phase 2 starts fresh and the runner's default picks
        "--previous_phase_end_step", "0", "--attention_backend", "auto",
        "--skip_final_checkpoint"), out2, "phase2")
    check_device(second["summary"], devices, 1)
    check_losses(second, first_loss)
    kernels = [c.get("tpu_custom_calls") for c in second["costs"]
               if c["fn"] == "train_step"]
    if EXPECT["kernels"] == "compiled":
        check(kernels and kernels[0], "phase2: the compiled train step holds "
              f"no Mosaic custom call (compile_cost: {second['costs']})")
        check(second["peak_bytes_in_use"],
              "phase2: no peak_bytes_in_use in the memory records")
    return {"first_loss_expected": round(first_loss, 4),
            "phase1": brief(first), "phase1_resume": brief(resumed),
            "phase2": dict(brief(second), tpu_custom_calls=kernels)}


# ---------------------------------------------------------------------------
# phase: serve


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(port, path, payload=None, timeout=60):
    """(status, parsed JSON body)."""
    data = None if payload is None else json.dumps(payload).encode()
    request = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data)
    try:
        with urllib.request.urlopen(request, timeout=timeout) as reply:
            return reply.status, json.loads(reply.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read() or b"{}")


class Server:
    """One run_server.py child: started, waited for, always stopped."""

    def __init__(self, work, name, vocab, seed):
        self.out = os.path.join(work, name)
        os.makedirs(self.out, exist_ok=True)
        self.log = os.path.join(self.out, "server.log")
        self.port = free_port()
        argv = [sys.executable, os.path.join(REPO, "run_server.py"),
                "--model_config_file", MODEL_CONFIG, "--vocab_file", vocab,
                "--tasks", SERVE["tasks"], "--classify_labels", "neg,pos",
                "--buckets", SERVE["buckets"], "--dtype", SERVE["dtype"],
                "--seed", str(seed), "--port", str(self.port),
                "--output_dir", self.out]
        self._log = open(self.log, "ab")
        self.proc = subprocess.Popen(argv, cwd=REPO, env=child_env(),
                                     stdout=self._log,
                                     stderr=subprocess.STDOUT)

    def wait_ready(self, timeout=CHILD_TIMEOUT_S) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            check(self.proc.poll() is None,
                  f"run_server.py exited {self.proc.returncode} before it "
                  f"served:\n{tail(self.log)}")
            try:
                status, health = http(self.port, "/healthz", timeout=5)
            except (OSError, ValueError):
                time.sleep(1.0)
                continue
            check(status == 200 and health.get("warmed"),
                  f"/healthz {status}: {health}")
            return
        raise PhaseFailed(f"server not ready after {timeout}s:\n"
                          f"{tail(self.log)}")

    def drain(self) -> int:
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise PhaseFailed("server ignored SIGTERM for 120s") from None

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._log.close()

    def logged(self, needle) -> str:
        with open(self.log, errors="replace") as f:
            for line in f:
                if needle in line:
                    return line.strip()
        return ""


def check_fill_mask(status, body) -> None:
    check(status == 200, f"/v1/fill_mask answered {status}: {body}")
    slots = body.get("masks")
    check(slots and all(slots), f"fill_mask: no predictions in {body}")
    for slot in slots:
        scores = [entry["score"] for entry in slot]
        check(all(math.isfinite(s) and 0.0 < s <= 1.0 for s in scores)
              and sum(scores) <= 1.0 + 1e-3
              and scores == sorted(scores, reverse=True),
              f"fill_mask scores are not ordered probabilities: {scores}")


def phase_serve(work, seed, devices) -> dict:
    trace_dir = os.path.join(work, "trace")
    make_data(trace_dir, seed, "--requests", "64", "--max_words", "400")
    vocab = os.path.join(trace_dir, "vocab.txt")
    trace = read_jsonl(os.path.join(trace_dir, "requests.jsonl"))
    # a handful of fill_mask requests that between them span the buckets
    # (the trace's lengths are short-biased), and one of another head
    fill = sorted((r["payload"] for r in trace if r["task"] == "fill_mask"),
                  key=lambda p: len(p["text"]))
    n = SERVE["fill_mask_requests"]
    fill = [fill[i * (len(fill) - 1) // (n - 1)] for i in range(n)]
    other = next(r["payload"] for r in trace if r["task"] == "classify")
    report = {}

    started = time.monotonic()
    server = Server(work, "serve1", vocab, seed)
    try:
        server.wait_ready()
        report["cold_start_seconds"] = round(time.monotonic() - started, 1)
        answers = []
        for payload in fill:
            status, body = http(server.port, "/v1/fill_mask", payload)
            check_fill_mask(status, body)
            answers.append(body)
        status, body = http(server.port, "/v1/classify", other)
        check(status == 200 and body.get("label") in ("neg", "pos")
              and abs(sum(body["scores"].values()) - 1.0) < 1e-3,
              f"/v1/classify answered {status}: {body}")
        status, stats = http(server.port, "/statsz")
        check(status == 200, f"/statsz answered {status}")
        check_device(stats, devices, 1)
        check(stats["compiles"] == 0,
              f"{stats['compiles']} compile(s) after warm-up")
        check(stats["requests"] == len(fill) + 1 and stats["errors"] == 0,
              f"/statsz counts {stats['requests']} requests, "
              f"{stats['errors']} errors; sent {len(fill) + 1}")
        rc = server.drain()
        check(rc == EXIT_PREEMPTED,
              f"SIGTERM drain exited {rc}, wanted {EXIT_PREEMPTED}:\n"
              f"{tail(server.log)}")
    finally:
        server.close()
    report.update({
        "requests": stats["requests"],
        "warmup_compiles": stats.get("warmup_compiles"),
        "warmup_compiles_cold": stats.get("warmup_compiles_cold"),
        "latency_p50_ms": stats.get("latency_p50_ms"),
        "tokenizer": server.logged("tokenizer back end"),
    })
    check(report["tokenizer"], "the server never said which tokenizer "
                               "back end serves")
    jsonl = os.path.join(server.out, "serve_telemetry.jsonl")
    lint = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_telemetry_schema.py"), jsonl],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    check(lint.returncode == 0,
          f"serve telemetry fails the schema lint:\n{lint.stdout[-2000:]}")
    kinds = {r.get("kind") for r in read_jsonl(jsonl)}
    check({"serve_cold_start", "serve_summary"} <= kinds,
          f"serve telemetry lacks records: {sorted(k for k in kinds if k)}")

    # Second start, same cache: every forward is a persistent-cache hit,
    # and the same request gets the same answer.
    started = time.monotonic()
    server = Server(work, "serve2", vocab, seed)
    try:
        server.wait_ready()
        report["warm_start_seconds"] = round(time.monotonic() - started, 1)
        status, body = http(server.port, "/v1/fill_mask", fill[0])
        check_fill_mask(status, body)
        top = [[e["id"] for e in slot][:1] for slot in body["masks"]]
        was = [[e["id"] for e in slot][:1] for slot in answers[0]["masks"]]
        check(top == was, f"the second start answers {top}, the first {was}")
        status, stats = http(server.port, "/statsz")
        check(stats.get("warmup_compiles_cold") == 0
              and stats.get("warmup_compiles", 0) > 0,
              f"second start: {stats.get('warmup_compiles_cold')} cold of "
              f"{stats.get('warmup_compiles')} warm-up compiles")
        check(server.drain() == EXIT_PREEMPTED, "second drain failed")
    finally:
        server.close()
    report["second_start_compiles_cold"] = stats["warmup_compiles_cold"]
    return report


# ---------------------------------------------------------------------------
# phase: mesh (--chips 4)


def phase_mesh(work, seed, devices) -> dict:
    # BERT-large as configured, with dropout off (MESH["loss_tol"] says why).
    with open(MODEL_CONFIG) as f:
        config = json.load(f)
    config.update(hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    no_dropout = os.path.join(work, "model_no_dropout.json")
    with open(no_dropout, "w") as f:
        json.dump(config, f)

    local, steps = MESH["local_batch"], MESH["steps"]
    global_batch = 4 * local
    data = os.path.join(work, "data_mesh")
    make_data(data, seed, "--seq_len", str(PHASE1["seq_len"]),
              "--vocab_size", str(model_vocab()), "--num_shards", "2",
              "--samples_per_shard", str(global_batch * steps))

    def run(spec, env, count):
        out = os.path.join(work, "mesh_" + spec.replace("=", "").replace(
            ",", "_"))
        result = run_trainer(pretrain_argv(
            data, out, RECIPE1, local, global_batch, steps, seed,
            "--mesh", spec, "--model_config_file", no_dropout,
            "--rng_impl", "threefry2x32",
            # no warm-up: the learning rate is the recipe's from the first
            # step, so that by the third step the losses have moved and a
            # wrong gradient reduction would show
            "--warmup_proportion", "0", "--skip_final_checkpoint"),
            out, spec, env=env)
        check_device(result["summary"], devices, count)
        check_losses(result)
        check(len(result["losses"]) == steps, f"{spec}: {result['steps']}")
        return result

    # What the mesh runs are compared with: ONE device (shown to the child
    # from outside, by its environment), the same global batch by
    # accumulation.
    single = run("dp=1", ONE_DEVICE_ENV, 1)
    report = {"dp=1": brief(single)}
    for spec in MESH["specs"]:
        sharded = run(spec, ALL_DEVICES_ENV, 4)
        diffs = [abs(a - b)
                 for a, b in zip(sharded["losses"], single["losses"])]
        check(max(diffs) <= MESH["loss_tol"],
              f"{spec}: losses {sharded['losses']} against one device "
              f"{single['losses']}: off by {max(diffs):.4f} > "
              f"{MESH['loss_tol']}")
        summary = sharded["summary"]
        placed = {key: summary.get(key) for key in (
            "params_devices", "opt_state_devices", "batch_devices",
            "params_share_on_first_device")}
        check(all(placed[key] == 4 for key in (
            "params_devices", "opt_state_devices", "batch_devices")),
              f"{spec}: not on four distinct devices: {placed}")
        share = placed["params_share_on_first_device"]
        want = 0.5 if "fsdp=2" in spec else 1.0
        check(abs(share - want) < 0.1,
              f"{spec}: the first device holds {share:.0%} of the "
              f"parameters, wanted about {want:.0%}")
        report[spec] = dict(brief(sharded), placed=placed,
                            max_loss_diff=round(max(diffs), 5))
    return report


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    if argv[:1] == ["--child-kernels"]:
        return kernels_child(argv[1], argv[2])
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, default=1, choices=[1, 4],
                        help="4: the mesh phase and its one-device "
                             "comparison, and no other phase")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--phases", default="",
                        help="comma-separated subset of the phases, for "
                             "finding a fault without paying for the rest")
    parser.add_argument("--keep", action="store_true",
                        help="keep the work directory (logs, telemetry)")
    args = parser.parse_args(argv)

    phases = ({"mesh": phase_mesh} if args.chips == 4 else
              {"kernels": phase_kernels, "train": phase_train,
               "serve": phase_serve})
    wanted = [p for p in args.phases.split(",") if p] or list(phases)
    unknown = [p for p in wanted if p not in phases]
    if unknown:
        parser.error(f"no phase {unknown} with --chips {args.chips}; "
                     f"there are {list(phases)}")

    work = tempfile.mkdtemp(prefix="chip_smoke_")
    devices: dict = {"chips": args.chips}
    failed = []
    try:
        for name in wanted:
            started = time.monotonic()
            line = {"phase": name}
            try:
                line.update(phases[name](work, args.seed, devices), ok=True)
            except PhaseFailed as exc:
                line.update(ok=False, error=str(exc))
                failed.append(name)
            line["seconds"] = round(time.monotonic() - started, 1)
            print(json.dumps(line), flush=True)
            if failed and "device" not in devices:
                break  # not the device that was asked for: nothing to add
    finally:
        if args.keep:
            print(f"work directory kept: {work}", file=sys.stderr)
        else:
            shutil.rmtree(work, ignore_errors=True)
    if failed or set(wanted) != set(phases):
        # a subset is for finding faults; only the whole is the proof
        print(json.dumps({"failed": failed, "ran": wanted,
                          "complete": set(wanted) == set(phases)}))
        return 1 if failed else 2
    print(json.dumps({"ok": True, "device": devices["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
