"""Telemetry layer tests (docs/telemetry.md, ISSUE 1).

Covers the five telemetry pieces in isolation — JSONL sink round-trip +
schema pin, StepTimer decomposition under a fake clock, sentinel
abort-after-K, compile-event emission on a forced persistent-cache miss,
heartbeat advance/resume — the logging satellites (CSV widening,
is_primary vs verbose, stepless TensorBoard records, init closing
handlers), the schema lint over the committed bench artifacts, and the
acceptance CPU smoke: a >=20-step synthetic pretraining run whose JSONL
stream must hold the step-time decomposition, MFU, a compile event with
cache status, and an advancing heartbeat.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bert_pytorch_tpu import telemetry
from bert_pytorch_tpu.telemetry import schema as tschema
from bert_pytorch_tpu.telemetry.profiler import parse_profile_spec
from bert_pytorch_tpu.telemetry.sentinels import (FailureSentinel, Heartbeat,
                                                  NonFiniteError)
from bert_pytorch_tpu.telemetry.step_timer import StepTimer
from bert_pytorch_tpu.utils import logging as logging_util

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeClock:
    """Manually-advanced clock for deterministic timer tests."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


# -- JSONL sink + schema ------------------------------------------------


def test_schema_version_pinned():
    # Consumers dispatch on this; bump KNOWN_VERSIONS when it changes.
    assert tschema.SCHEMA_VERSION == 1
    assert tschema.SCHEMA_VERSION in tschema.KNOWN_VERSIONS


def test_jsonl_sink_round_trip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    sink = logging_util.JSONLHandler(path)
    sink.write_record({"kind": "run_summary", "tag": "telemetry",
                       "step": 3, "steps": 3, "note": "hi"})
    sink.write_record({"tag": "train", "step": 4, "loss": 1.25})
    sink.close()
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2
    for rec in lines:
        assert rec["schema"] == tschema.SCHEMA_VERSION
        assert "ts" in rec
    assert lines[0]["note"] == "hi"
    assert lines[1]["loss"] == 1.25
    assert tschema.validate_file(path) == []


def test_jsonl_sink_nonfinite_becomes_null(tmp_path):
    path = str(tmp_path / "nan.jsonl")
    sink = logging_util.JSONLHandler(path)
    sink.write_record({"tag": "train", "step": 1, "loss": float("nan"),
                       "grad_norm": float("inf")})
    sink.close()
    raw = open(path).read()
    assert "NaN" not in raw and "Infinity" not in raw
    rec = json.loads(raw)
    assert rec["loss"] is None and rec["grad_norm"] is None
    assert tschema.validate_file(path) == []


def test_schema_rejects_bad_records(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"schema": 999, "ts": 0}) + "\n")
        f.write(json.dumps({"schema": 1, "ts": 0, "kind": "mystery"}) + "\n")
        f.write(json.dumps({"schema": 1, "ts": 0, "kind": "sentinel"}) + "\n")
        f.write('{"loss": NaN}\n')
        f.write("not json at all\n")
        window = {"schema": 1, "ts": 0, "kind": "step_window", "step": 1,
                  "window_steps": 1, "synced_steps": 1, "steps_per_sec": 1.0,
                  "mfu": 0.0}
        window.update({f"{p}_{s}_s": 0.0 for p in
                       ("data_wait", "host", "device", "step")
                       for s in ("p50", "p95", "max")})
        f.write(json.dumps({**window, "loader": {"batches": 1}}) + "\n")
    errors = tschema.validate_file(path)
    linenos = [lineno for lineno, _ in errors]
    assert 1 in linenos  # unknown version
    assert 2 in linenos  # unknown kind
    assert 3 in linenos  # missing required keys
    assert 4 in linenos  # NaN spelling
    assert 5 in linenos  # invalid JSON
    assert 6 in linenos  # malformed nested loader gauges


def test_check_telemetry_schema_tool(tmp_path):
    """The tier-1 lint: committed artifacts pass; a malformed file fails."""
    tool = os.path.join(REPO_ROOT, "tools", "check_telemetry_schema.py")
    proc = subprocess.run([sys.executable, tool], capture_output=True,
                          text=True, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr

    bad = tmp_path / "BROKEN_r99.jsonl"
    bad.write_text('{"metric": "x", "value": NaN}\n')
    proc = subprocess.run([sys.executable, tool, str(bad)],
                          capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 1
    assert "non-finite" in proc.stdout


# -- logging satellites -------------------------------------------------


def test_csv_handler_widens_on_new_keys(tmp_path):
    path = str(tmp_path / "m.csv")
    h = logging_util.CSVHandler(path)
    h.write_record({"tag": "train", "step": 1, "loss": 1.0})
    h.write_record({"tag": "train", "step": 2, "loss": 0.9, "mfu": 0.41})
    h.write_record({"tag": "eval", "step": 2, "eval_loss": 2.0})
    h.close()
    import csv

    rows = list(csv.DictReader(open(path)))
    assert set(rows[0].keys()) == {"tag", "step", "loss", "mfu", "eval_loss"}
    assert rows[0]["loss"] == "1.0" and rows[0]["mfu"] == ""  # blank-filled
    assert rows[1]["mfu"] == "0.41"
    assert rows[2]["eval_loss"] == "2.0" and rows[2]["loss"] == ""


def test_csv_handler_append_resume_keeps_prior_header(tmp_path):
    """A resumed (append-mode) session must treat the FILE's header as the
    base column set: widening may not demote the old header to a data row
    or zip prior rows against the wrong columns."""
    path = str(tmp_path / "m.csv")
    h = logging_util.CSVHandler(path)
    h.write_record({"tag": "train", "step": 1, "loss": 1.0})
    h.close()

    h2 = logging_util.CSVHandler(path)  # restart: different first record
    h2.write_record({"tag": "train", "step": 2, "loss": 0.8, "mfu": 0.3})
    h2.close()
    import csv

    rows = list(csv.DictReader(open(path)))
    assert set(rows[0].keys()) == {"tag", "step", "loss", "mfu"}
    assert [r["step"] for r in rows] == ["1", "2"]  # no header-as-data row
    assert rows[0]["loss"] == "1.0" and rows[0]["mfu"] == ""
    assert rows[1]["mfu"] == "0.3"


def test_is_primary_separate_from_verbose(tmp_path, capsys):
    """A quiet (verbose=False) rank-0 run still writes its file artifacts;
    a non-primary rank writes none even when verbose."""
    quiet_path = str(tmp_path / "quiet.txt")
    h = logging_util.FileHandler(quiet_path, verbose=False, is_primary=True)
    h.write_message("kept")
    h.close()
    assert open(quiet_path).read().strip() == "kept"

    nonprimary_path = str(tmp_path / "nonprimary.txt")
    h = logging_util.FileHandler(nonprimary_path, verbose=True,
                                 is_primary=False)
    h.write_message("dropped")
    h.close()
    assert not os.path.exists(nonprimary_path)

    stream = logging_util.StreamHandler(verbose=False, is_primary=True)
    stream.write_message("silent")
    assert capsys.readouterr().out == ""

    # Backward compatibility: is_primary defaults to verbose.
    legacy = logging_util.FileHandler(str(tmp_path / "legacy.txt"),
                                      verbose=False)
    assert legacy._f is None


def test_logger_init_closes_replaced_handlers(tmp_path):
    lg = logging_util.Logger()
    f = logging_util.FileHandler(str(tmp_path / "a.txt"))
    lg.init([f])
    assert f._f is not None
    lg.init([logging_util.StreamHandler(verbose=False)])
    assert f._f is None  # closed by re-init, not leaked
    lg.close()


def test_tensorboard_handler_skips_stepless_records(recwarn):
    h = logging_util.TensorBoardHandler.__new__(logging_util.TensorBoardHandler)
    logging_util.Handler.__init__(h, verbose=True, is_primary=True)
    h._warned_stepless = False

    class FakeWriter:
        def __init__(self):
            self.scalars = []

        def add_scalar(self, tag, value, step):
            self.scalars.append((tag, value, step))

        def flush(self):
            pass

    h._writer = FakeWriter()
    h.write_record({"tag": "train", "loss": 1.0})  # stepless: skipped
    assert h._writer.scalars == []
    assert any("without 'step'" in str(w.message) for w in recwarn.list)
    h.write_record({"tag": "train", "step": 7, "loss": 1.0})
    assert h._writer.scalars == [("train/loss", 1.0, 7)]


# -- step timer ---------------------------------------------------------


def test_step_timer_decomposition_fake_clock():
    clock = FakeClock()
    timer = StepTimer(window=3, sync_every=1, clock=clock)
    for _ in range(2):
        for _ in range(3):
            timer.data_start()
            clock.advance(0.10)  # data wait
            timer.data_end()
            clock.advance(0.02)  # host dispatch
            timer.dispatch_end()
            assert timer.should_sync()
            clock.advance(0.30)  # device tail
            timer._t_device1 = clock()  # what device_sync records
            record = timer.step_done(step=timer._step_index + 1)
        assert record is not None, "window must close every 3rd step"
        assert record["window_steps"] == 3
        assert record["synced_steps"] == 3
        assert record["data_wait_p50_s"] == pytest.approx(0.10)
        assert record["host_p50_s"] == pytest.approx(0.02)
        assert record["device_p50_s"] == pytest.approx(0.30)
        # Monotonicity: the step total equals the component sum (each
        # component is a difference of successive clock reads).
        assert record["step_p50_s"] == pytest.approx(0.42)
        assert record["step_max_s"] >= record["step_p50_s"]
        assert record["steps_per_sec"] == pytest.approx(1 / 0.42, rel=1e-3)


def test_step_timer_unsynced_steps_have_no_device_sample():
    clock = FakeClock()
    timer = StepTimer(window=4, sync_every=2, clock=clock)
    for _ in range(4):
        timer.data_start()
        clock.advance(0.01)
        timer.data_end()
        clock.advance(0.01)
        timer.dispatch_end()
        if timer.should_sync():
            clock.advance(0.5)
            timer._t_device1 = clock()
        record = timer.step_done(step=timer._step_index + 1)
    assert record["window_steps"] == 4
    assert record["synced_steps"] == 2  # steps 0 and 2 per the cadence
    # Sampled cadence: each device sample is a multi-step backlog, so MFU
    # must fall back to the wall basis instead of deflating by the cadence.
    timer2 = StepTimer(window=2, sync_every=2, clock=clock, seq_per_step=8,
                       flops_per_seq=1e12, device_kind="TPU v4")
    for _ in range(2):
        timer2.data_start()
        timer2.data_end()
        clock.advance(1.0)  # 1 s of wall per step, in the host segment
        timer2.dispatch_end()
        if timer2.should_sync():
            timer2._t_device1 = clock()
        record2 = timer2.step_done(step=timer2._step_index + 1)
    assert record2["mfu_basis"] == "wall"
    # 2 steps * 8 seq over 2 s wall on a 275 Tflop/s chip.
    assert record2["mfu"] == pytest.approx(8e12 / 275e12, rel=1e-3)


def test_step_timer_mfu_from_device_time():
    clock = FakeClock()
    # 8 seq per step, 1e12 flops/seq, 1 s device time per step on a chip
    # with 275 Tflop/s peak (v4): MFU = 8e12 / 275e12 per step.
    timer = StepTimer(window=2, sync_every=1, clock=clock, seq_per_step=8,
                      flops_per_seq=1e12, device_kind="TPU v4", n_devices=1)
    for _ in range(2):
        timer.data_start()
        timer.data_end()
        timer.dispatch_end()
        clock.advance(1.0)
        timer._t_device1 = clock()
        record = timer.step_done(step=timer._step_index + 1)
    assert record["mfu"] == pytest.approx(8e12 / 275e12, rel=1e-3)
    assert record["mfu_basis"] == "device"  # every step synced
    # CPU (no peak): MFU is absent — not measured — never 0.0 or a bogus
    # number; an unknown TPU kind is an error, not an assumed peak.
    cpu_timer = StepTimer(window=1, clock=clock, seq_per_step=8,
                          flops_per_seq=1e12, device_kind="cpu")
    cpu_timer.data_start()
    cpu_timer.data_end()
    cpu_timer.dispatch_end()
    clock.advance(1.0)
    cpu_timer._t_device1 = clock()
    cpu_record = cpu_timer.step_done(1)
    assert "mfu" not in cpu_record and cpu_record["mfu_basis"] == "none"


def test_step_timer_flush_partial_window():
    clock = FakeClock()
    timer = StepTimer(window=100, clock=clock)
    timer.data_start()
    clock.advance(0.1)
    timer.data_end()
    timer.dispatch_end()
    assert timer.step_done(1) is None  # window not full
    record = timer.flush(1)
    assert record is not None and record["window_steps"] == 1
    assert timer.flush(1) is None  # nothing left


# -- sentinels + heartbeat ----------------------------------------------


def test_sentinel_abort_after_k_consecutive():
    emitted = []
    s = FailureSentinel(policy="abort", patience=3, emit=emitted.append)
    assert s.observe(1, finite=1.0)
    assert not s.observe(2, finite=0.0, loss=float("nan"))
    assert s.observe(3, finite=1.0)  # recovery resets the streak
    s.observe(4, finite=0.0)
    s.observe(5, finite=0.0)
    with pytest.raises(NonFiniteError):
        s.observe(6, finite=0.0)
    assert s.total_nonfinite == 4
    assert [r["consecutive_nonfinite"] for r in emitted] == [1, 1, 2, 3]
    assert all(r["kind"] == "sentinel" for r in emitted)


def test_sentinel_continue_never_raises():
    emitted = []
    s = FailureSentinel(policy="continue", patience=1, emit=emitted.append)
    for step in range(5):
        s.observe(step, finite=0.0)
    assert len(emitted) == 5


def test_sentinel_rejects_unknown_policy():
    with pytest.raises(ValueError):
        FailureSentinel(policy="explode")


def test_heartbeat_advances_and_resumes(tmp_path):
    path = str(tmp_path / "hb.json")
    hb = Heartbeat(path)
    hb.beat(1, last_loss=2.5)
    first = Heartbeat.read(path)
    hb.beat(2)  # no loss this beat: last known loss is retained
    second = Heartbeat.read(path)
    assert (first["counter"], second["counter"]) == (1, 2)
    assert second["step"] == 2 and second["last_loss"] == 2.5
    assert second["wallclock"] >= first["wallclock"]

    # A restarted run resumes the monotonic counter from the file.
    hb2 = Heartbeat(path)
    hb2.beat(3)
    assert Heartbeat.read(path)["counter"] == 3

    assert Heartbeat.read(str(tmp_path / "absent.json")) is None
    assert Heartbeat(None).path is None  # disabled: beat() is a no-op
    Heartbeat(None).beat(1)
    # Non-primary ranks never write.
    assert Heartbeat(str(tmp_path / "np.json"), is_primary=False).path is None


# -- profiler spec ------------------------------------------------------


def test_parse_profile_spec():
    assert parse_profile_spec(None) is None
    assert parse_profile_spec("") is None
    assert parse_profile_spec("0") is None
    assert parse_profile_spec(0) is None
    assert parse_profile_spec("5") == (2, 7)  # legacy steady-state window
    assert parse_profile_spec(5) == (2, 7)
    assert parse_profile_spec("3:10") == (3, 10)
    for bad in ("0:5", "7:3", "4:4"):
        with pytest.raises(ValueError):
            parse_profile_spec(bad)


# -- compile events -----------------------------------------------------


# ``persistent_cache`` is the conftest fixture: the cache on, in a
# directory of the test's own, every compile persisted.


def test_compile_event_on_forced_cache_miss(persistent_cache):
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.telemetry.compile_events import CompileMonitor

    emitted = []
    monitor = CompileMonitor(emit=emitted.append)
    # A fresh (never-jitted) program against an empty persistent cache:
    # a real XLA compile plus a cache miss must be attributed to the call.
    fn = monitor.instrument(jax.jit(lambda x: x * 3.5 + x ** 2), "probe")
    out = fn(jnp.arange(7, dtype=jnp.float32))
    assert out.shape == (7,)
    assert len(emitted) == 1
    rec = emitted[0]
    assert rec["kind"] == "compile" and rec["fn"] == "probe"
    assert rec["cache"] == "miss"
    assert rec["compile_s"] > 0
    assert rec["backend_compile_s"] > 0
    assert len(rec["shapes_digest"]) == 12
    assert tschema.validate_record(
        {"schema": tschema.SCHEMA_VERSION, "ts": 0.0, **rec}) == []

    # Same shapes again: the in-process executable serves it — no event.
    fn(jnp.arange(7, dtype=jnp.float32))
    assert len(emitted) == 1

    # New shapes: new digest, new event.
    fn(jnp.arange(9, dtype=jnp.float32))
    assert len(emitted) == 2
    assert emitted[1]["shapes_digest"] != emitted[0]["shapes_digest"]


def test_compile_event_cache_hit(persistent_cache):
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.telemetry.compile_events import CompileMonitor

    emitted = []
    monitor = CompileMonitor(emit=emitted.append)

    # Two DISTINCT function objects with identical programs: the second
    # can't reuse the in-process executable (different jit cache key) but
    # lowers to the same HLO, so it hits the persistent cache instead of
    # compiling — the warm-start path the runners rely on. Lambdas, not
    # defs: the cache key covers the HLO module, whose name comes from the
    # Python function name, and both lambdas lower as "jit__lambda_".
    monitor.instrument(
        jax.jit(lambda x: jnp.sin(x) * 2.0 + jnp.cos(x)), "cold")(
            jnp.ones((5,)))
    monitor.instrument(
        jax.jit(lambda x: jnp.sin(x) * 2.0 + jnp.cos(x)), "warm")(
            jnp.ones((5,)))
    assert [r["fn"] for r in emitted] == ["cold", "warm"]
    assert emitted[0]["cache"] == "miss"
    # The hit call may still compile tiny auxiliary modules (constant
    # conversions), so backend_compile_s isn't asserted to be zero — the
    # cache counters, not the durations, carry the warm/cold verdict.
    assert emitted[1]["cache"] == "hit"


def test_shapes_digest_stability():
    import jax.numpy as jnp

    from bert_pytorch_tpu.telemetry.compile_events import shapes_digest

    a = shapes_digest(((jnp.ones((2, 3)),), {"n": 4}))
    b = shapes_digest(((jnp.zeros((2, 3)),), {"n": 4}))  # values don't matter
    c = shapes_digest(((jnp.ones((2, 4)),), {"n": 4}))  # shapes do
    d = shapes_digest(((jnp.ones((2, 3)),), {"n": 5}))  # static args do
    assert a == b
    assert a != c and a != d


# -- TrainTelemetry facade ----------------------------------------------


def test_train_telemetry_loop_protocol(tmp_path):
    import jax.numpy as jnp

    path = str(tmp_path / "tele.jsonl")
    clock = FakeClock()
    tele = telemetry.TrainTelemetry(
        jsonl_path=path, window=2, clock=clock,
        heartbeat_path=str(tmp_path / "hb.json"), sentinel_policy="continue")
    batches = iter([jnp.ones((2,)), jnp.ones((2,)), jnp.ones((2,))])
    step = 0
    for batch in tele.timed(batches):
        step += 1
        clock.advance(0.01)
        tele.dispatch_done()
        loss = jnp.asarray(1.0 if step < 3 else float("nan"))
        tele.step_done(step, {"loss": loss})
    tele.finish(step, summary={"note": "done"})
    tele.close()

    kinds = {}
    for line in open(path):
        rec = json.loads(line)
        kinds.setdefault(rec["kind"], []).append(rec)
    assert len(kinds["step_window"]) == 2  # one full window + the flush
    assert kinds["step_window"][0]["window_steps"] == 2
    # Step 3's NaN loss trips the host-side fallback sentinel.
    assert kinds["sentinel"][0]["step"] == 3
    assert kinds["run_summary"][0]["note"] == "done"
    hb = Heartbeat.read(str(tmp_path / "hb.json"))
    assert hb["step"] == 3 and hb["counter"] == 4  # 3 steps + finish
    assert tschema.validate_file(path) == []


# -- acceptance: CPU smoke pretraining run ------------------------------


@pytest.fixture()
def pretrain_workdir(tmp_path):
    from bert_pytorch_tpu.tools.make_synthetic_data import make_shard

    data_dir = tmp_path / "data"
    data_dir.mkdir()
    for i in range(2):
        make_shard(str(data_dir / f"shard_{i}.hdf5"), 64, 32, 1000, seed=i)
    model_config = {
        "vocab_size": 1000, "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 64,
        "max_position_embeddings": 32, "type_vocab_size": 2,
        "next_sentence": True, "mask_token_id": 4,
    }
    config_path = tmp_path / "model.json"
    config_path.write_text(json.dumps(model_config))
    return {"data": str(data_dir), "out": str(tmp_path / "out"),
            "model": str(config_path)}


@pytest.mark.slow
def test_pretraining_smoke_emits_telemetry(pretrain_workdir):
    """ISSUE 1 acceptance: >=20 synthetic CPU steps must leave a JSONL
    stream holding the per-window step-time decomposition, MFU, a compile
    event with cache status, and a heartbeat file that advanced.

    Slow-gated (ISSUE 14 budget fix; ~47-100s on the throttled box: a
    full runner compile+run): the key invariant — the telemetry facade
    leaves a SCHEMA-CLEAN artifact with step_window/sentinel/
    run_summary records and an advancing heartbeat — is carried tier-1
    by the cheap in-process ``test_train_telemetry_loop_protocol``
    above (fake clock, no jit); this E2E additionally proves
    run_pretraining.py plumbs it and runs under ``-m slow``."""
    import run_pretraining

    args = run_pretraining.parse_arguments([
        "--input_dir", pretrain_workdir["data"],
        "--output_dir", pretrain_workdir["out"],
        "--model_config_file", pretrain_workdir["model"],
        "--global_batch_size", "16", "--local_batch_size", "2",
        "--max_steps", "22", "--steps", "22",
        "--learning_rate", "1e-3", "--warmup_proportion", "0.25",
        "--num_steps_per_checkpoint", "100", "--dtype", "float32",
        "--seed", "7", "--telemetry_window", "10",
        "--telemetry_sync_every", "1",
    ])
    result = run_pretraining.main(args)
    assert result["global_step"] == 22

    jsonl = os.path.join(pretrain_workdir["out"],
                         "pretraining_telemetry.jsonl")
    assert tschema.validate_file(jsonl) == []
    kinds = {}
    for line in open(jsonl):
        rec = json.loads(line)
        kinds.setdefault(rec.get("kind", "metric"), []).append(rec)

    windows = kinds["step_window"]
    assert len(windows) >= 2  # 22 steps / window 10
    for w in windows:
        for key in ("data_wait_p50_s", "data_wait_p95_s", "data_wait_max_s",
                    "host_p50_s", "host_p95_s", "host_max_s",
                    "device_p50_s", "device_p95_s", "device_max_s",
                    "step_p50_s", "steps_per_sec"):
            assert key in w, f"window record missing {key}"
        assert w["synced_steps"] == w["window_steps"]  # --telemetry_sync_every 1
        # CPU: no peak, so MFU is absent ("not measured"), not 0.0
        assert "mfu" not in w and w["mfu_basis"] == "none"
    # The device-prefetch loader feeds its queue gauges into the windows.
    assert any("loader" in w for w in windows)

    compiles = kinds["compile"]
    assert any(r["fn"] == "train_step" for r in compiles)
    assert all(r["cache"] in ("hit", "miss", "uncached", "jit")
               for r in compiles)
    # The step-0 compile dominates; it must be visible, not folded into
    # step time.
    assert max(r["compile_s"] for r in compiles) > 0

    # ISSUE 2: in-jit grad-health on the sync cadence (1 here, so every
    # step), with per-layer-group norms and the stacked-encoder
    # per-layer vector.
    health = kinds["grad_health"]
    assert len(health) >= 20
    for rec in health[:3]:
        assert rec["grad_norm"] > 0 and rec["param_norm"] > 0
        assert 0 < rec["update_ratio"] < 1
        assert "bert/encoder" in rec["groups"]
        assert "bert/embeddings" in rec["groups"]
        for vals in rec["groups"].values():
            assert set(vals) == {"grad_norm", "param_norm", "update_ratio"}
        assert len(rec["per_layer_grad_norm"]) == 2  # num_hidden_layers
    # The in-jit global grad norm must agree with the step's own metric.
    train_recs = [r for r in kinds["metric"] if r.get("tag") == "train"]
    by_step = {r["step"]: r for r in train_recs}
    probe = health[5]
    assert probe["grad_norm"] == pytest.approx(
        by_step[probe["step"]]["grad_norm"], rel=1e-4)

    # ISSUE 2: memory observability on CPU = exactly ONE unsupported
    # note (never a per-step storm), and one-shot static cost
    # attribution joined to the compile event's digest.
    mem = kinds["memory"]
    assert len(mem) == 1 and mem[0]["memory_supported"] is False
    costs = kinds["compile_cost"]
    assert any(r["fn"] == "train_step" for r in costs)
    cost = next(r for r in costs if r["fn"] == "train_step")
    assert cost["shapes_digest"] in {c["shapes_digest"] for c in compiles}
    assert cost["flops"] > 0
    assert cost["analysis"] == "compiled"  # CPU: the extra compile is cheap
    assert cost["temp_bytes"] >= 0 and cost["argument_bytes"] > 0
    # No divergence warnings on a healthy run.
    assert "divergence" not in kinds

    hb = Heartbeat.read(
        os.path.join(pretrain_workdir["out"], "heartbeat.json"))
    assert hb is not None
    assert hb["step"] == 22
    assert hb["counter"] >= 22  # advanced across (at least) every step
    assert np.isfinite(hb["last_loss"])

    assert kinds["run_summary"][0]["steps"] == 22

    # The ordinary train records share the sink (tag/step/loss... records
    # with no "kind"): the artifact is single-file parseable.
    assert any(r.get("tag") == "train" for r in kinds["metric"])


@pytest.mark.slow
def test_pretraining_resume_keeps_grad_health_cadence(pretrain_workdir):
    """A checkpoint-resumed run whose resume step is NOT a multiple of
    the sampled sync cadence must still emit grad_health records: the
    in-jit due gate is rebased on the run-start optimizer count
    (stats_phase), matching the host's run-local sync index.

    Slow-gated (~36s: two full pretraining runs): the rebasing invariant
    itself is tier-1-covered at the step level by
    tests/test_model_stats.py (phase-offset due-gate cases); this E2E
    proves the runner plumbs the run-start count through and runs under
    ``-m slow``."""
    import run_pretraining

    def run(steps):
        args = run_pretraining.parse_arguments([
            "--input_dir", pretrain_workdir["data"],
            "--output_dir", pretrain_workdir["out"],
            "--model_config_file", pretrain_workdir["model"],
            "--global_batch_size", "16", "--local_batch_size", "2",
            "--max_steps", "20", "--steps", str(steps),
            "--num_steps_per_checkpoint", "100", "--dtype", "float32",
            "--seed", "7", "--telemetry_window", "5",
            "--telemetry_sync_every", "4",  # sampled cadence
        ])
        return run_pretraining.main(args)

    assert run(6)["global_step"] == 6   # final checkpoint at step 6
    assert run(6)["global_step"] == 12  # resumes; 6 % 4 != 0
    jsonl = os.path.join(pretrain_workdir["out"],
                         "pretraining_telemetry.jsonl")
    health = [json.loads(line) for line in open(jsonl)]
    health = [r for r in health if r.get("kind") == "grad_health"]
    first = [r for r in health if r["step"] <= 6]
    resumed = [r for r in health if r["step"] > 6]
    assert first, "fresh run emitted no grad_health"
    assert resumed, ("resumed run emitted no grad_health — the due gate "
                     "drifted off the run-local sync cadence")


@pytest.mark.slow
def test_pretraining_sentinel_abort_flag(pretrain_workdir):
    """--sentinel_policy abort is accepted and a healthy run completes.

    Slow-gated (~24s for a full compile+run that asserts only flag
    acceptance): the sentinel abort BEHAVIOR is tier-1-covered by the
    FailureSentinel unit tests above and the fault-tolerance in-process
    injection tests; runs under ``-m slow``."""
    import run_pretraining

    args = run_pretraining.parse_arguments([
        "--input_dir", pretrain_workdir["data"],
        "--output_dir", pretrain_workdir["out"],
        "--model_config_file", pretrain_workdir["model"],
        "--global_batch_size", "16", "--local_batch_size", "2",
        "--max_steps", "2", "--steps", "2",
        "--num_steps_per_checkpoint", "100", "--dtype", "float32",
        "--sentinel_policy", "abort", "--sentinel_patience", "1",
    ])
    result = run_pretraining.main(args)
    assert result["global_step"] == 2
