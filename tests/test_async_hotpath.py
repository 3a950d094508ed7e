"""Async hot-path suite (ISSUE 6; docs/telemetry.md "async the hot path").

Covers the two overlapped phases end to end on CPU:

* async checkpointing — per-directory pending-save keying, device-snapshot
  donation safety, and the acceptance comparison: a blocking save writes
  on the loop's thread before it returns, an async save's write runs on
  its own thread beside the steps that follow (by count, on the loop's own
  clock: no host time is asserted), and the loop that waits for its writes
  is named by the telemetry-report regression path;
* double-buffered device prefetch — a fast producer drives data_wait p50
  to ~0, a slow producer still attributes the stall to data_wait, and a
  slow staging function reports as the h2d_wait sub-phase (always <= the
  data_wait it is part of — the schema lint invariant).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from bert_pytorch_tpu.data.device_prefetch import DevicePrefetcher
from bert_pytorch_tpu.telemetry import schema as tschema
from bert_pytorch_tpu.telemetry import report as treport
from bert_pytorch_tpu.telemetry.runner import TrainTelemetry
from bert_pytorch_tpu.telemetry.step_timer import StepTimer
from bert_pytorch_tpu.utils import checkpoint as ckpt
from bert_pytorch_tpu.utils.logging import JSONLHandler


# ---------------------------------------------------------------------------
# async checkpointing: pending-save registry + device snapshot


def test_pending_saves_keyed_per_directory(tmp_path, monkeypatch):
    """Two save targets in one process must not share a pending slot: a
    wait on one directory leaves the other's write untouched, and a
    failure surfaces for its own directory only."""
    import threading

    dir_a, dir_b = str(tmp_path / "a"), str(tmp_path / "b")
    release_b = threading.Event()
    real_write = ckpt._write_and_prune

    def gated_write(state, output_dir, step, keep):
        if output_dir == dir_b:
            assert release_b.wait(10.0)
        real_write(state, output_dir, step, keep)

    monkeypatch.setattr(ckpt, "_write_and_prune", gated_write)
    state = {"model": {"w": np.ones((8,), np.float32)}}
    ckpt.save_checkpoint(dir_a, 1, state, async_write=True)
    ckpt.save_checkpoint(dir_b, 2, state, async_write=True)
    # Joining A must complete without B's gate ever opening.
    ckpt.wait_for_pending_save(dir_a)
    assert ckpt.find_resume_step(dir_a) == 1
    assert ckpt.find_resume_step(dir_b) is None  # still gated
    release_b.set()
    ckpt.wait_for_pending_save()  # joins ALL remaining
    assert ckpt.find_resume_step(dir_b) == 2


def test_pending_save_error_stays_with_its_directory(tmp_path, monkeypatch):
    dir_a, dir_b = str(tmp_path / "a"), str(tmp_path / "b")
    real_write = ckpt._write_and_prune

    def failing_for_a(state, output_dir, step, keep):
        if output_dir == dir_a:
            raise OSError("disk full")
        real_write(state, output_dir, step, keep)

    monkeypatch.setattr(ckpt, "_write_and_prune", failing_for_a)
    state = {"model": {"w": np.ones((8,), np.float32)}}
    ckpt.save_checkpoint(dir_a, 1, state, async_write=True)
    ckpt.save_checkpoint(dir_b, 1, state, async_write=True)
    ckpt.wait_for_pending_save(dir_b)  # B is healthy: no raise
    with pytest.raises(RuntimeError, match="async checkpoint write failed"):
        ckpt.wait_for_pending_save(dir_a)
    ckpt.wait_for_pending_save()  # error consumed; all joined


def test_failed_async_write_does_not_block_emergency_save(tmp_path,
                                                          monkeypatch):
    """A stale periodic-write failure must not cost the CURRENT state:
    the next (emergency) sync save writes its checkpoint FIRST, then
    re-raises the background failure — durability before diagnostics
    (docs/fault_tolerance.md)."""
    real_write = ckpt._write_and_prune

    def failing_once(state, output_dir, step, keep):
        if step == 1:
            raise OSError("disk full")
        real_write(state, output_dir, step, keep)

    monkeypatch.setattr(ckpt, "_write_and_prune", failing_once)
    state = {"model": {"w": np.ones((8,), np.float32)}}
    ckpt.save_checkpoint(str(tmp_path), 1, state, async_write=True)
    with pytest.raises(RuntimeError, match="async checkpoint write failed"):
        ckpt.save_checkpoint(str(tmp_path), 2, state)  # emergency: sync
    # The raise reported the OLD failure; the NEW state landed anyway.
    assert ckpt.find_resume_step(str(tmp_path), verify=True) == 2


def test_async_snapshot_survives_donated_device_buffers(tmp_path):
    """The tentpole invariant: save_checkpoint(async_write=True) returns
    after a DEVICE-side snapshot, so the train loop may immediately donate
    the live buffers to the next step without corrupting the write."""
    import jax
    import jax.numpy as jnp

    state = {"model": {"w": jnp.full((64, 64), 3.0)}, "epoch": 5}
    ckpt.save_checkpoint(str(tmp_path), 7, state, async_write=True)
    # Donate-and-overwrite the source buffer, as the next train step does.
    bump = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x * -1.0, t),
                   donate_argnums=0)
    mutated = bump(state["model"])
    jax.block_until_ready(mutated)
    ckpt.wait_for_pending_save(str(tmp_path))
    loaded = ckpt.load_checkpoint(ckpt.checkpoint_path(str(tmp_path), 7))
    np.testing.assert_array_equal(loaded["model"]["w"],
                                  np.full((64, 64), 3.0))
    assert int(loaded["epoch"]) == 5


# ---------------------------------------------------------------------------
# device prefetch: data_wait attribution


def _drive_loop(tmp_path, producer_sleep_s, stage_sleep_s, consumer_sleep_s,
                n_items=12, window=10, depth=2):
    """Run a synthetic loop through TrainTelemetry.timed with an attached
    DevicePrefetcher; return the step_window records (schema-validated)."""
    jsonl = str(tmp_path / "telemetry.jsonl")

    def source():
        for i in range(n_items):
            if producer_sleep_s:
                time.sleep(producer_sleep_s)
            yield {"x": np.full((4,), i)}

    def stage(item):
        if stage_sleep_s:
            time.sleep(stage_sleep_s)
        return item

    tele = TrainTelemetry(jsonl_path=jsonl, window=window, sync_every=0)
    prefetcher = DevicePrefetcher(source(), stage=stage, depth=depth)
    tele.attach_prefetcher(prefetcher)
    step = 0
    for _ in tele.timed(iter(prefetcher)):
        if consumer_sleep_s:
            time.sleep(consumer_sleep_s)
        tele.dispatch_done()
        step += 1
        tele.step_done(step, None)
    tele.finish(step)
    tele.close()
    assert tschema.validate_file(jsonl) == []
    return [rec for rec in map(json.loads, open(jsonl))
            if rec.get("kind") == "step_window"]


def test_prefetch_fast_producer_drives_data_wait_to_zero(tmp_path):
    """With the producer ahead of the loop, the consumer never waits:
    data_wait p50 ~ 0 even though featurization takes real time per item
    (it hides behind the consumer's step)."""
    windows = _drive_loop(tmp_path, producer_sleep_s=0.004,
                          stage_sleep_s=0.0, consumer_sleep_s=0.02)
    assert windows, "no window record emitted"
    assert windows[0]["data_wait_p50_s"] < 0.004
    # h2d fields ride along (prefetcher attached), bounded by data_wait.
    assert windows[0]["h2d_wait_p50_s"] <= windows[0]["data_wait_p50_s"]


def test_prefetch_slow_producer_still_attributes_data_wait(tmp_path):
    """A producer slower than the loop is a real stall and must stay
    attributed to data_wait (not hidden), with only a small h2d share."""
    windows = _drive_loop(tmp_path, producer_sleep_s=0.03,
                          stage_sleep_s=0.0, consumer_sleep_s=0.0)
    w = windows[0]
    assert w["data_wait_p50_s"] >= 0.015
    assert w["h2d_wait_p50_s"] <= 0.5 * w["data_wait_p50_s"]


def test_prefetch_slow_staging_reports_as_h2d_subphase(tmp_path):
    """When the H2D staging call is the bottleneck, the wait lands in
    data_wait AND is attributed to the h2d_wait sub-phase."""
    windows = _drive_loop(tmp_path, producer_sleep_s=0.0,
                          stage_sleep_s=0.02, consumer_sleep_s=0.0)
    w = windows[0]
    assert w["data_wait_p50_s"] >= 0.01
    assert w["h2d_wait_p50_s"] >= 0.5 * w["data_wait_p50_s"]
    assert w["h2d_wait_p95_s"] <= w["data_wait_p95_s"]


def test_prefetch_inline_depth_zero_same_contract(tmp_path):
    windows = _drive_loop(tmp_path, producer_sleep_s=0.0,
                          stage_sleep_s=0.01, consumer_sleep_s=0.0,
                          depth=0)
    w = windows[0]
    assert w["h2d_wait_p50_s"] >= 0.005
    assert w["h2d_wait_p50_s"] <= w["data_wait_p50_s"]


def test_prefetch_propagates_producer_error():
    def source():
        yield 1
        raise RuntimeError("shard exploded")

    p = DevicePrefetcher(source(), stage=lambda x: x, depth=2)
    it = iter(p)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="shard exploded"):
        next(it)


# ---------------------------------------------------------------------------
# acceptance: an async save's write runs beside the loop, and the report
# names a loop that waits for its writes

STEP_S, WRITE_S = 0.45, 1.0  # on the loop's own clock below, not the host's
_ATOMIC_WRITE = ckpt._atomic_write


def _ckpt_run(jsonl_path, async_write, state, monkeypatch, n_steps=12,
              every=4):
    """A training loop with periodic saves on a clock of its OWN: a step
    costs ``STEP_S`` of it and a write made on the loop's thread ``WRITE_S``,
    a write made on any other thread nothing (and that write is held until
    ``every - 1`` more steps are done). Emits real step_window records
    (through the same StepTimer + ckpt_step accounting as the trainer's
    loop). Returns (the threads that wrote, whether each save's file was
    there when the save returned, the steps that finished while a write was
    still to come)."""
    import shutil
    import tempfile
    import threading

    now = [0.0]
    loop = threading.current_thread()
    writers, gates = [], {}  # gates: a checkpoint's path -> its write may go
    def write(path, blob):
        writers.append(threading.current_thread().name)
        if threading.current_thread() is loop:
            now[0] += WRITE_S
        else:
            assert gates[path].wait(60.0)
        _ATOMIC_WRITE(path, blob)

    monkeypatch.setattr(ckpt, "_atomic_write", write)
    sink = JSONLHandler(jsonl_path, overwrite=False)
    timer = StepTimer(window=8, sync_every=0, clock=lambda: now[0])
    out_dir = tempfile.mkdtemp(prefix="ckpt_accept_")
    key = ckpt._pending_key(out_dir)
    written_on_return, overlapped = [], 0
    try:
        for step in range(1, n_steps + 1):
            timer.data_start()
            timer.data_end()
            now[0] += STEP_S
            timer.dispatch_end()
            rec = timer.step_done(step)
            if rec:
                sink.write_record(rec)
            if async_write and not all(g.is_set() for g in gates.values()):
                assert ckpt._pending_saves[key].is_alive()  # a write is held
                overlapped += 1
            if step % every == every - 1:
                for gate in gates.values():
                    gate.set()
            if step % every == 0:
                gates[ckpt.checkpoint_path(out_dir, step)] = threading.Event()
                t0 = now[0]
                path = ckpt.save_checkpoint(out_dir, step, state, keep=2,
                                            async_write=async_write)
                timer.note_ckpt_stall(now[0] - t0)
                written_on_return.append(os.path.exists(path))
        for gate in gates.values():
            gate.set()
        ckpt.wait_for_pending_save(out_dir)
        assert ckpt.find_resume_step(out_dir, verify=True) == n_steps
        rec = timer.flush(n_steps)
        if rec:
            sink.write_record(rec)
        sink.write_record({"kind": "run_summary", "tag": "telemetry",
                           "step": n_steps, "steps": n_steps})
    finally:
        for gate in gates.values():
            gate.set()
        ckpt.wait_for_pending_save()
        shutil.rmtree(out_dir, ignore_errors=True)
        sink.close()
    return writers, written_on_return, overlapped


def test_async_save_writes_beside_the_loop_and_report_gates(tmp_path,
                                                            monkeypatch):
    """ISSUE 6 acceptance, by what the two paths DO and not by the host's
    clock (how long a stall lasts is a chip cell's to measure): a blocking
    save writes on the loop's thread and its file is there when it returns;
    an async save returns with nothing written, its write runs on a
    ``ckpt-write-*`` thread while three more steps finish, and the
    checkpoint lands whole. On the loop's own clock the blocking run's
    checkpoint steps then cost a step and a write, the async run's a step
    — and diffing the blocking run against the async baseline trips the
    telemetry-report regression gate BY NAME."""
    import jax.numpy as jnp

    state = {"model": {f"w{i}": jnp.ones((1000,), jnp.float32)
                       for i in range(6)}, "epoch": 1}
    sync_jsonl = str(tmp_path / "sync_telemetry.jsonl")
    async_jsonl = str(tmp_path / "async_telemetry.jsonl")
    writers, written, overlapped = _ckpt_run(
        sync_jsonl, False, state, monkeypatch)
    assert set(writers) == {"MainThread"} and len(writers) == 3
    assert written == [True] * 3 and overlapped == 0
    writers, written, overlapped = _ckpt_run(
        async_jsonl, True, state, monkeypatch)
    assert writers == ["ckpt-write-4", "ckpt-write-8", "ckpt-write-12"]
    assert written == [False] * 3  # the foreground made no write
    assert overlapped == 2 * 3     # steps 5-7 and 9-11 ran beside a write
    for path in (sync_jsonl, async_jsonl):
        assert tschema.validate_file(path) == []

    def ratios(summary):
        return (summary["ckpt_step_p95_s"] / summary["step_p95_s"], summary)

    sync_ratio, sync_sum = ratios(treport.summarize_file(sync_jsonl))
    async_ratio, async_sum = ratios(treport.summarize_file(async_jsonl))
    assert sync_sum["ckpt_steps"] == async_sum["ckpt_steps"] == 3
    assert sync_ratio == pytest.approx((STEP_S + WRITE_S) / STEP_S, rel=1e-3)
    assert async_ratio == pytest.approx(1.0, rel=1e-3)

    # Injected-regression gating path: blocking run vs async baseline
    # must exit nonzero and NAME the checkpoint-step regression.
    regressions, _ = treport.compare(async_sum, sync_sum)
    assert any(r["metric"] == "ckpt_step_p95_s" for r in regressions), (
        regressions)
    rc = treport.main([sync_jsonl, async_jsonl])
    assert rc == 1
    # And the async run against itself is clean.
    assert treport.main([async_jsonl, async_jsonl]) == 0
