"""End-to-end pretraining runner tests on the virtual 8-device CPU mesh.

The TPU-world analog of the reference's Gloo CPU harness (SURVEY.md §4):
full config -> data -> model -> LAMB -> checkpoint -> logging flow, plus the
resume and phase-switch behaviors of SURVEY §5.4.
"""

import json
import os

import numpy as np
import pytest

import run_pretraining
from bert_pytorch_tpu.tools.make_synthetic_data import make_shard
from bert_pytorch_tpu.utils import checkpoint as ckpt

# End-to-end runner tests (compile + train on the virtual 8-device mesh, many
# minutes on a throttled CPU host): outside the tier-1 wallclock budget. Run
# explicitly with `-m slow`; tier-1 keeps the telemetry CPU smoke run
# (tests/test_telemetry.py) as the fast end-to-end pretraining guard.
pytestmark = pytest.mark.slow

VOCAB = 1000


@pytest.fixture()
def workdir(tmp_path):
    data_dir = tmp_path / "data"
    out_dir = tmp_path / "out"
    data_dir.mkdir()
    for i in range(2):
        make_shard(str(data_dir / f"shard_{i}.hdf5"), 64, 32, VOCAB, seed=i)
    model_config = {
        "vocab_size": VOCAB,
        "hidden_size": 32,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "intermediate_size": 64,
        "max_position_embeddings": 32,
        "type_vocab_size": 2,
        "next_sentence": True,
        "mask_token_id": 4,
    }
    config_path = tmp_path / "model.json"
    config_path.write_text(json.dumps(model_config))
    return {"data": str(data_dir), "out": str(out_dir), "model": str(config_path)}


def _args(workdir, **overrides):
    argv = [
        "--input_dir", workdir["data"],
        "--output_dir", workdir["out"],
        "--model_config_file", workdir["model"],
        "--global_batch_size", "32",
        "--local_batch_size", "2",
        "--max_steps", "8",
        "--steps", "3",
        "--learning_rate", "1e-3",
        "--warmup_proportion", "0.25",
        "--num_steps_per_checkpoint", "100",
        "--dtype", "float32",
        "--seed", "7",
    ]
    for key, value in overrides.items():
        if value is True:  # bare store_true flag
            argv += [f"--{key}"]
        else:
            argv += [f"--{key}", str(value)]
    return run_pretraining.parse_arguments(argv)


def test_smoke_train_with_accumulation(workdir):
    # 8 data shards x local_bs 2 = global microbatch 16; gbs 32 -> accum 2.
    result = run_pretraining.main(_args(workdir))
    assert result["global_step"] == 3
    assert np.isfinite(result["loss"])
    # loss should be near ln(vocab) + ln(2) at start
    assert 4.0 < result["loss"] < 10.0
    # final checkpoint written
    assert ckpt.find_resume_step(os.path.join(workdir["out"], "pretrain_ckpts")) == 3
    # log sinks exist
    assert os.path.exists(os.path.join(workdir["out"], "pretraining.txt"))
    assert os.path.exists(os.path.join(workdir["out"], "pretraining_metrics.csv"))


def test_compile_cache_populates_and_restart_resumes(
        workdir, tmp_path, monkeypatch, persistent_cache):
    """--compile_cache_dir wires JAX's persistent cache into the runner:
    the train-step executable lands in the directory (threshold dropped to
    0 here — tiny-model compiles are under the production 10s bar) and a
    run resumed from its checkpoint finds it there: the resumed step is
    the SAME program (the resume step enters it only modulo the grad-stats
    cadence, telemetry/model_stats.py), so its compile is a cache hit."""
    from bert_pytorch_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(compile_cache, "MIN_COMPILE_TIME_SECS", 0.0)
    cache = tmp_path / "xla_cache"
    run_pretraining.main(
        _args(workdir, steps=4, compile_cache_dir=str(cache)))
    entries = list(cache.iterdir())
    assert entries, "no executables were persisted"
    result = run_pretraining.main(
        _args(workdir, steps=2, compile_cache_dir=str(cache)))
    assert result["global_step"] == 6
    with open(os.path.join(
            workdir["out"], "pretraining_telemetry.jsonl")) as f:
        step_compiles = [
            rec["cache"] for rec in map(json.loads, f)
            if rec.get("kind") == "compile" and rec["fn"] == "train_step"]
    assert step_compiles == ["miss", "hit"]


def test_resume_continues_and_losses_drop(workdir):
    run_pretraining.main(_args(workdir))
    result2 = run_pretraining.main(_args(workdir, steps=2))
    assert result2["global_step"] == 5
    out_dir = os.path.join(workdir["out"], "pretrain_ckpts")
    assert ckpt.find_resume_step(out_dir) == 5


def test_checkpoint_sampler_index_matches_trained_samples(workdir):
    """The saved sampler position must equal the samples actually TRAINED,
    not the loader's read-ahead position: the DataLoader queue plus
    device_prefetch stage batches ahead of the train step, and saving the
    live index would skip that buffered-but-untrained data on resume (a
    latent defect of the reference, whose DataLoader workers run ahead of
    its checkpoints the same way, reference src/dataset.py:401-425)."""
    run_pretraining.main(_args(workdir, steps=3))
    out = os.path.join(workdir["out"], "pretrain_ckpts")
    step = ckpt.find_resume_step(out)
    saved = ckpt.load_checkpoint(ckpt.checkpoint_path(out, step))
    # dataset: 128 samples; 3 steps x global_batch 32 trained = 96 < 128,
    # while the pipelines have buffered well past 96 by save time.
    assert int(saved["sampler"]["index"]) == 3 * 32


def test_phase_switch_resets_optimizer_count(workdir):
    run_pretraining.main(_args(workdir, steps=4, max_steps=4))
    out_dir = os.path.join(workdir["out"], "pretrain_ckpts")
    assert ckpt.find_resume_step(out_dir) == 4
    # Phase 2: new schedule, previous_phase_end_step=4.
    result = run_pretraining.main(
        _args(workdir, steps=2, max_steps=4, previous_phase_end_step=4,
              learning_rate="2e-3", warmup_proportion="0.5"))
    # global step restarts from 0 within phase 2 and runs 2 steps
    assert result["global_step"] == 2
    # checkpoint names continue the global numbering (4 + 2)
    assert ckpt.find_resume_step(out_dir) == 6


def test_checkpoint_retention(workdir):
    run_pretraining.main(
        _args(workdir, steps=6, max_steps=8, num_steps_per_checkpoint=1,
              keep_checkpoints=3))
    out_dir = os.path.join(workdir["out"], "pretrain_ckpts")
    files = sorted(f for f in os.listdir(out_dir) if f.endswith(".msgpack"))
    assert len(files) == 3
    assert ckpt.find_resume_step(out_dir) == 6


def test_masked_position_head_matches_full_head():
    """The masked-positions MLM path (decoder on [B,P] gathered positions)
    must give the same loss as the full [B,S,V] path."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining, pretraining_loss

    cfg = BertConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32)
    model = BertForPreTraining(cfg, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    B, S, P = 4, 16, 5
    ids = jnp.asarray(rng.integers(0, 128, (B, S), dtype=np.int32))
    types = jnp.zeros((B, S), jnp.int32)
    mask = jnp.ones((B, S), jnp.int32)
    labels = np.full((B, S), -1, np.int32)
    for b in range(B):
        pos = rng.choice(S, size=rng.integers(1, P), replace=False)
        labels[b, pos] = rng.integers(0, 128, len(pos))
    labels = jnp.asarray(labels)
    nsp = jnp.asarray(rng.integers(0, 2, (B,), dtype=np.int32))

    variables = model.init(jax.random.PRNGKey(0), ids, types, mask)
    full_logits, nsp_logits = model.apply(variables, ids, types, mask)
    full_loss = pretraining_loss(full_logits, nsp_logits, labels, nsp)

    is_masked = (labels != -1).astype(jnp.int32)
    _, positions = jax.lax.top_k(is_masked, P)
    glabels = jnp.take_along_axis(labels, positions, axis=1)
    m_logits, nsp_logits2 = model.apply(
        variables, ids, types, mask, True, positions)
    m_loss = pretraining_loss(m_logits, nsp_logits2, glabels, nsp)
    assert m_logits.shape == (B, P, 128)
    np.testing.assert_allclose(float(m_loss), float(full_loss), rtol=1e-5)


def test_kfac_end_to_end(workdir):
    """Runner with --kfac: preconditioned steps, preconditioner in the
    checkpoint, and resume restoring it (reference run_pretraining.py:320-355,
    519-520)."""
    argv = [
        "--input_dir", workdir["data"],
        "--output_dir", workdir["out"],
        "--model_config_file", workdir["model"],
        "--global_batch_size", "32",
        "--local_batch_size", "2",
        "--max_steps", "8",
        "--steps", "3",
        "--learning_rate", "1e-3",
        "--warmup_proportion", "0.25",
        "--num_steps_per_checkpoint", "100",
        "--dtype", "float32",
        "--seed", "7",
        "--kfac",
        "--kfac_factor_interval", "1",
        "--kfac_inv_interval", "2",
    ]
    result = run_pretraining.main(run_pretraining.parse_arguments(argv))
    assert result["global_step"] == 3
    assert np.isfinite(result["loss"])
    ckpt_dir = os.path.join(workdir["out"], "pretrain_ckpts")
    loaded = ckpt.load_checkpoint(ckpt.checkpoint_path(ckpt_dir, 3))
    assert "preconditioner" in loaded
    assert int(loaded["preconditioner"]["count"]) == 3
    # resume picks the preconditioner back up and keeps training
    result2 = run_pretraining.main(
        run_pretraining.parse_arguments(argv + ["--steps", "2"]))
    assert result2["global_step"] == 5
    assert np.isfinite(result2["loss"])


def test_roberta_path_no_nsp(workdir, tmp_path):
    """next_sentence=False (the RoBERTa config path,
    configs/roberta_pretraining_config.json): no token-type embeddings, no
    pooler/NSP head, MLM-only loss."""
    model_config = json.loads(open(workdir["model"]).read())
    model_config["next_sentence"] = False
    config_path = tmp_path / "roberta.json"
    config_path.write_text(json.dumps(model_config))
    args = _args({**workdir, "model": str(config_path)},
                 lr_decay="linear", warmup_proportion="0.06")
    result = run_pretraining.main(args)
    assert result["global_step"] == 3
    assert np.isfinite(result["loss"])
    # NSP-free loss is pure MLM cross-entropy: ~ln(vocab)
    assert 4.0 < result["loss"] < 9.0
    loaded = ckpt.load_checkpoint(ckpt.checkpoint_path(
        os.path.join(workdir["out"], "pretrain_ckpts"), 3))
    assert "seq_relationship" not in loaded["model"]
    assert "token_type_embeddings" not in loaded["model"]["bert"]["embeddings"]
    assert "pooler" not in loaded["model"]["bert"]


def test_convergence_memorization():
    """End-to-end learning signal: LAMB + schedule + masking + model memorize
    a fixed batch to ~100% MLM accuracy — catches optimizer/loss/labeling
    plumbing bugs no smoke test sees."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu import optim, pretrain
    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining
    from bert_pytorch_tpu.parallel import (
        MeshConfig, create_mesh, logical_axis_rules)

    config = BertConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32, next_sentence=True)
    model = BertForPreTraining(config, dtype=jnp.float32)
    mesh = create_mesh(MeshConfig(data=-1))
    rules = logical_axis_rules("dp")
    schedule = optim.warmup_poly_schedule(8e-3, 0.05, 300)
    tx = optim.lamb(schedule, weight_decay_mask=optim.no_decay_mask)
    S, B = 16, 16
    sample = (jnp.zeros((1, S), jnp.int32),) * 3
    rng = np.random.default_rng(0)
    host = {
        "input_ids": rng.integers(5, 128, (B, S)).astype(np.int32),
        "segment_ids": np.zeros((B, S), np.int32),
        "input_mask": np.ones((B, S), np.int32),
        "next_sentence_labels": rng.integers(0, 2, (B,)).astype(np.int32),
    }
    host["masked_lm_labels"] = np.where(
        rng.random((B, S)) < 0.3, host["input_ids"], -1).astype(np.int32)
    with mesh:
        shardings = pretrain.state_shardings(mesh, model, rules, sample)
        b_shardings = pretrain.batch_shardings(
            mesh, {"input_ids": 3, "segment_ids": 3, "input_mask": 3,
                   "masked_lm_labels": 3, "next_sentence_labels": 2})
        state = pretrain.make_init_fn(model, tx, sample, shardings)(
            jax.random.PRNGKey(0))
        step = pretrain.make_train_step(
            model, tx, schedule=schedule, next_sentence=True,
            shardings=shardings, batch_shardings_=b_shardings)
        batch = pretrain.put_batch(
            pretrain.stack_microbatches(host, 1), b_shardings)
        for i in range(300):
            state, metrics = step(state, batch)
            if i % 25 == 0:  # periodic sync: keep the CPU in-process
                float(metrics["loss"])  # collective queue shallow
    assert float(metrics["mlm_accuracy"]) > 0.95
    assert float(metrics["loss"]) < 1.0


def test_validation_pass(workdir, tmp_path):
    """--val_input_dir runs a held-out MLM eval at the configured cadence
    and logs tag=val records (beyond the reference, which never evaluates
    during pretraining)."""
    val_dir = tmp_path / "valdata"
    val_dir.mkdir()
    make_shard(str(val_dir / "val_0.hdf5"), 32, 32, VOCAB, seed=99)
    log_prefix = str(tmp_path / "vallog")
    result = run_pretraining.main(_args(
        workdir, steps=2, val_input_dir=str(val_dir),
        num_steps_per_eval=1, eval_batches=2, log_prefix=log_prefix))
    assert np.isfinite(result["loss"])
    text = open(log_prefix + ".txt").read()
    assert "tag: val" in text
    assert "mlm_accuracy" in text


@pytest.mark.slow  # ~90s subprocess; the cross-process half is also
# covered by the chaos harness (tier-1) and the in-process term-injection
# test (tests/test_fault_tolerance.py) — run with -m slow
def test_sigterm_graceful_checkpoint(workdir):
    """Preemption handling (beyond the reference's die-and-resubmit fault
    model): SIGTERM mid-run makes the runner stop at the next
    term-check step, write the normal final checkpoint, and exit with
    the distinct EXIT_PREEMPTED code (75: "checkpointed cleanly,
    resubmit me" — docs/fault_tolerance.md) — and the checkpoint
    resumes."""
    import signal
    import subprocess
    import sys
    import time as _time

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    argv = [
        sys.executable,
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "run_pretraining.py"),
        "--input_dir", workdir["data"],
        "--output_dir", workdir["out"],
        "--model_config_file", workdir["model"],
        "--global_batch_size", "4", "--local_batch_size", "4",
        "--max_steps", "100000", "--steps", "100000",
        "--learning_rate", "1e-3", "--warmup_proportion", "0.25",
        "--num_steps_per_checkpoint", "100000",
        "--term_check_steps", "1", "--log_steps", "1",
        "--dtype", "float32", "--seed", "7",
    ]
    proc = subprocess.Popen(argv, env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    log_path = os.path.join(workdir["out"], "pretraining_metrics.csv")
    deadline = _time.monotonic() + 240
    try:
        # Wait until a couple of steps have actually trained.
        while _time.monotonic() < deadline:
            if os.path.exists(log_path) and sum(
                    1 for _ in open(log_path)) >= 3:
                break
            _time.sleep(1.0)
        else:
            raise AssertionError("runner never reached step 2")
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    from bert_pytorch_tpu.utils.preemption import EXIT_PREEMPTED

    assert proc.returncode == EXIT_PREEMPTED, (proc.returncode, out[-2000:])
    assert "termination signal" in out, out[-2000:]
    ckpt_dir = os.path.join(workdir["out"], "pretrain_ckpts")
    stopped_at = ckpt.find_resume_step(ckpt_dir)
    assert stopped_at is not None and 1 <= stopped_at < 100000
    # The checkpoint is a normal one: a resume run continues from it.
    result = run_pretraining.main(_args(
        workdir, steps=1, max_steps=100000, term_check_steps=0))
    assert result["global_step"] == stopped_at + 1
    assert not result["terminated_by_signal"]


def test_check_batch_process_locality(monkeypatch):
    """Batch shards whose pipe/model replicas span processes must be
    rejected: the per-process loaders would feed the same global rows
    different data (silent cross-rank divergence)."""
    import dataclasses

    import jax

    from bert_pytorch_tpu import pretrain

    @dataclasses.dataclass(frozen=True)
    class Dev:
        process_index: int

    def mesh_of(proc_grid):
        # proc_grid: nested list shaped [data, fsdp, pipe, seq, model]
        class FakeMesh:
            pass
        m = FakeMesh()
        m.devices = np.vectorize(Dev)(np.asarray(proc_grid))
        return m

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    # 2 hosts, pipe intra-host: data axis splits hosts -> OK
    ok = [[[[[0]], [[0]]]], [[[[1]], [[1]]]]]  # [2,1,2,1,1]
    pretrain.check_batch_process_locality(mesh_of(ok))
    # pipe spans hosts: shard (0,0) replicated on processes 0 and 1 -> raise
    bad = [[[[[0]], [[1]]]], [[[[0]], [[1]]]]]
    with pytest.raises(ValueError, match="conflicting data"):
        pretrain.check_batch_process_locality(mesh_of(bad))
    # single process: never raises
    monkeypatch.setattr(jax, "process_count", lambda: 1)
    pretrain.check_batch_process_locality(mesh_of(bad))
