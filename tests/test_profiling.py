"""Profiling-plane tests (ISSUE 17, docs/observability.md "Profiling
plane"): the stdlib host thread
sampler (bounded, self-excluding), the arm-at-boundary capture
controller (idle -> armed -> active -> idle, the double-arm 409 guard),
the process-wide trace latch, ``POST /profilez`` on BOTH HTTP planes
(trainer introspection hub + serving replica) with live-server status
codes, the collector's coordinated fleet-wide trigger, telemetry-report's
``--format json``, the router heartbeat, and the schema fixtures for the
``profile_window`` record kind.

The jax-trace-artifact proof (real ``jax.profiler`` trace directory
with nonzero bytes) is slow-gated at the bottom."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from bert_pytorch_tpu.telemetry import profiler, schema
from bert_pytorch_tpu.telemetry.collector import FleetCollector, Target
from bert_pytorch_tpu.telemetry.introspect import (IntrospectionHub,
                                                   start_debug_server)
from bert_pytorch_tpu.telemetry.sampler import (CaptureController,
                                                ThreadSampler)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "fixtures", "telemetry")
REPORT_TOOL = os.path.join(REPO_ROOT, "tools", "telemetry_report.py")
TOOLS_DIR = os.path.join(REPO_ROOT, "tools")


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def _busy_thread(stop: threading.Event) -> threading.Thread:
    """A named worker the sampler is guaranteed to catch mid-frame."""

    def spin():
        while not stop.is_set():
            sum(i * i for i in range(200))
            time.sleep(0.001)

    t = threading.Thread(target=spin, name="busy-worker", daemon=True)
    t.start()
    return t


def _post(url: str, body: dict, timeout: float = 5.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode("utf-8"),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode("utf-8"))
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read().decode("utf-8") or "{}")


def _get(url: str):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, json.loads(resp.read().decode("utf-8"))


def _stamped(rec: dict) -> dict:
    """What the JSONL sink would add before writing."""
    out = dict(rec)
    out.setdefault("schema", 1)
    out.setdefault("ts", 1754600000.0)
    return out


# ---------------------------------------------------------------------------
# telemetry/sampler.py: ThreadSampler


def test_sampler_attributes_busy_thread_and_is_bounded():
    stop = threading.Event()
    _busy_thread(stop)
    try:
        sampler = ThreadSampler(interval_s=0.002, max_samples=500,
                                max_duration_s=5.0)
        sampler.start()
        time.sleep(0.15)
        sampler.stop()
        folded = sampler.result(top_k=10)
    finally:
        stop.set()
    assert 0 < folded["samples"] <= 500
    assert folded["top_frames"], "a live process must yield frames"
    share_sum = 0.0
    for row in folded["top_frames"]:
        assert row["samples"] >= 1
        assert row["samples"] <= folded["samples"]
        assert 0 < row["share"] <= 1
        assert row["frame"] and row["stack"]
        share_sum += row["share"]
    assert share_sum <= 1.0 + 1e-6
    # The sampler never profiles itself.
    assert all("telemetry-sampler" not in row["frame"]
               for row in folded["top_frames"])
    assert any(t for t in folded["threads"])


def test_sampler_max_samples_bound_and_one_shot():
    sampler = ThreadSampler(interval_s=0.001, max_samples=3,
                            max_duration_s=5.0)
    sampler.start()
    time.sleep(0.1)
    sampler.stop()
    assert sampler.result()["samples"] <= 3
    with pytest.raises(RuntimeError):
        sampler.start()


# ---------------------------------------------------------------------------
# telemetry/sampler.py: CaptureController state machine


def test_controller_full_cycle_emits_schema_clean_record():
    clock = FakeClock()
    emitted = []
    ctrl = CaptureController(source="trainer", covered_unit="steps",
                             emit=emitted.append, clock=clock)
    assert ctrl.status()["phase"] == "idle"
    ok, payload = ctrl.arm(duration_s=0.2, sample_interval_s=0.002)
    assert ok and payload["armed"] and payload["source"] == "trainer"
    assert ctrl.status()["phase"] == "armed"

    stop = threading.Event()
    _busy_thread(stop)
    try:
        assert ctrl.tick(100) is None          # armed -> active
        assert ctrl.status()["phase"] == "active"
        assert ctrl.tick(105) is None          # not expired yet
        time.sleep(0.1)                        # real time for the sampler
        clock.advance(0.5)                     # past the deadline
        record = ctrl.tick(112)
    finally:
        stop.set()
    assert record is not None and emitted == [record]
    assert record["kind"] == "profile_window"
    assert record["trigger"] == "ondemand"
    assert record["covered"] == 12 and record["covered_unit"] == "steps"
    assert record["samples"] > 0 and record["top_frames"]
    assert record["trace_path"] == "" and record["trace_bytes"] == 0
    assert schema.validate_record(_stamped(record)) == []
    status = ctrl.status()
    assert status["phase"] == "idle" and status["captures"] == 1
    assert status["last"]["top_frame"]
    # The plane is reusable: a second arm from idle succeeds.
    ok, _ = ctrl.arm(duration_s=0.1)
    assert ok


def test_controller_double_arm_refused_with_phase_bad_params_without():
    """The 409 discriminator: a busy refusal carries the blocking phase,
    a bad parameter does not — the HTTP planes map exactly on that."""
    ctrl = CaptureController(source="replica", covered_unit="requests",
                             clock=FakeClock())
    ok, _ = ctrl.arm(duration_s=0.5)
    assert ok
    ok, payload = ctrl.arm(duration_s=0.5)
    assert not ok and payload["phase"] == "armed"
    ctrl.tick(0)
    ok, payload = ctrl.arm(duration_s=0.5)
    assert not ok and payload["phase"] == "active"
    # Parameter refusals: no "phase" key.
    for kwargs in ({"duration_s": "soon"}, {"duration_s": -1.0},
                   {"max_samples": "lots"}):
        ok, payload = ctrl.arm(**kwargs)
        assert not ok and "error" in payload and "phase" not in payload


def test_controller_caps_runaway_duration():
    ctrl = CaptureController(source="trainer", clock=FakeClock())
    ok, payload = ctrl.arm(duration_s=1e9)
    assert ok
    from bert_pytorch_tpu.telemetry.sampler import MAX_DURATION_S
    assert payload["duration_s"] == MAX_DURATION_S


# ---------------------------------------------------------------------------
# telemetry/profiler.py: the process-wide trace latch


def test_trace_latch_is_exclusive_and_releases():
    assert not profiler.trace_active()
    assert profiler._acquire_trace()
    try:
        assert profiler.trace_active()
        assert not profiler._acquire_trace()  # refused, not raised
    finally:
        profiler._release_trace()
    assert not profiler.trace_active()
    assert profiler._acquire_trace()
    profiler._release_trace()


# ---------------------------------------------------------------------------
# POST /profilez on the trainer introspection plane (live server)


def test_profilez_live_trainer_debug_server(tmp_path):
    emitted = []
    hub = IntrospectionHub(process="unit")
    hub.capture = CaptureController(source="trainer", covered_unit="steps",
                                    emit=emitted.append)
    server = start_debug_server(hub, port=0)
    stop = threading.Event()
    _busy_thread(stop)
    try:
        host, port = server.server_address[:2]
        base = f"http://{host}:{port}"
        code, body = _post(f"{base}/profilez",
                           {"duration_s": 0.15, "sample_interval_s": 0.002})
        assert code == 200 and body["armed"]
        # Second arm while armed: 409, naming the blocking phase.
        code, body = _post(f"{base}/profilez", {"duration_s": 0.15})
        assert code == 409 and body["phase"] == "armed"
        # /statsz shows the capture status sub-object.
        code, stats = _get(f"{base}/statsz")
        assert code == 200 and stats["profile"]["phase"] == "armed"
        # Bad parameter: 400, not 409.
        code, body = _post(f"{base}/profilez", {"duration_s": "soon"})
        assert code == 400 and "error" in body
        # Drive the boundary like the train loop does.
        hub.capture.tick(7)
        time.sleep(0.3)
        record = hub.capture.tick(19)
        assert record is not None and record["covered"] == 12
        assert record["top_frames"], "host-frame table must be non-empty"
        assert schema.validate_record(_stamped(record)) == []
        code, stats = _get(f"{base}/statsz")
        assert stats["profile"]["phase"] == "idle"
        assert stats["profile"]["captures"] == 1
        # Idle again: a new arm succeeds.
        code, body = _post(f"{base}/profilez", {"duration_s": 0.1})
        assert code == 200
    finally:
        stop.set()
        server.shutdown()
        server.server_close()
    assert len(emitted) == 1


def test_profilez_404_when_no_controller_attached():
    hub = IntrospectionHub(process="bare")
    server = start_debug_server(hub, port=0)
    try:
        host, port = server.server_address[:2]
        code, body = _post(f"http://{host}:{port}/profilez",
                           {"duration_s": 0.1})
        assert code == 404 and "error" in body
    finally:
        server.shutdown()
        server.server_close()


def test_train_telemetry_wires_capture_to_hub_and_ticks_it(tmp_path):
    """TrainTelemetry builds the controller, attaches it to the hub, and
    ticks it at every step boundary — armed captures complete through
    the normal step loop and land in the run's JSONL sink."""
    from bert_pytorch_tpu.telemetry.runner import TrainTelemetry

    jsonl = tmp_path / "train_telemetry.jsonl"
    hub = IntrospectionHub(process="unit")
    tele = TrainTelemetry(jsonl_path=str(jsonl), window=10, sync_every=1,
                          introspect=hub)
    try:
        assert hub.capture is tele.capture
        ok, _ = tele.capture.arm(duration_s=0.1, sample_interval_s=0.002)
        assert ok
        for step in (1, 2):
            tele.timer.data_start()
            tele.timer.data_end()
            tele.dispatch_done()
            if step == 2:
                time.sleep(0.2)
            tele.step_done(step, {"loss": 2.0})
    finally:
        tele.close()
    records = [json.loads(line) for line in open(jsonl)]
    windows = [r for r in records if r.get("kind") == "profile_window"]
    assert len(windows) == 1
    assert windows[0]["source"] == "trainer"
    assert windows[0]["covered_unit"] == "steps"
    assert schema.validate_file(str(jsonl)) == []


# ---------------------------------------------------------------------------
# POST /profilez on a serving replica (live HTTP server, no engine work)


def test_profilez_live_replica_http_server(tmp_path):
    from bert_pytorch_tpu.serve import (Batcher, ServeTelemetry,
                                        ServingService, make_server)

    emitted = []
    capture = CaptureController(source="replica", covered_unit="requests",
                                emit=emitted.append)
    # __init__ never touches the engine; the capture plane needs only
    # the HTTP front end + the telemetry counters.
    service = ServingService(object(), Batcher(max_batch_size=2),
                             ServeTelemetry(), capture=capture)
    server = make_server(service, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    stop = threading.Event()
    _busy_thread(stop)
    try:
        port = server.server_address[1]
        base = f"http://127.0.0.1:{port}"
        code, body = _post(f"{base}/profilez",
                           {"duration_s": 0.15, "sample_interval_s": 0.002,
                            "trigger": "fleet"})
        assert code == 200 and body["covered_unit"] == "requests"
        code, body = _post(f"{base}/profilez", {"duration_s": 0.1})
        assert code == 409 and body["phase"] == "armed"
        code, stats = _get(f"{base}/statsz")
        assert code == 200 and stats["profile"]["phase"] == "armed"
        code, body = _post(f"{base}/profilez", {"duration_s": []})
        assert code == 400
        # Drive the dispatch boundary the way the service loops do.
        service._capture_tick()
        time.sleep(0.3)
        service._capture_tick()
    finally:
        stop.set()
        server.shutdown()
        server.server_close()
    assert len(emitted) == 1
    record = emitted[0]
    assert record["source"] == "replica" and record["trigger"] == "fleet"
    assert record["top_frames"]
    assert schema.validate_record(_stamped(record)) == []


# ---------------------------------------------------------------------------
# telemetry/collector.py: the coordinated fleet-wide trigger


def test_collector_trigger_profile_hits_every_capture_plane(tmp_path):
    out = tmp_path / "timeline.jsonl"
    targets = [Target("pretrain", "trainer", "http://t:9100"),
               Target("r0", "replica", "http://r0:8001"),
               Target("r1", "replica", "http://r1:8002"),
               Target("front", "router", "http://front:8100")]
    coll = FleetCollector(targets, out_path=str(out))
    calls = []

    def post(url, path, body, timeout_s):
        calls.append((url, path, dict(body)))
        if "r1" in url:
            raise OSError("connection refused")
        return 200, json.dumps({"armed": True,
                                "duration_s": body["duration_s"]})

    records = coll.trigger_profile(duration_s=1.5, post=post)
    coll.close()
    # Routers have no capture plane: three posts, not four.
    assert len(calls) == 3
    assert all(path == "/profilez" for _, path, _ in calls)
    assert all(body["duration_s"] == 1.5 and body["trigger"] == "fleet"
               for _, _, body in calls)
    by_target = {r["target"]: r for r in records}
    assert set(by_target) == {"pretrain", "r0", "r1"}
    assert by_target["pretrain"]["ok"] and by_target["r0"]["ok"]
    assert not by_target["r1"]["ok"] and by_target["r1"]["error"]
    assert all(r["probe"] == "profilez" for r in records)
    # The trigger records land in the timeline, schema-clean.
    assert schema.validate_file(str(out)) == []
    written = [json.loads(line) for line in open(out)]
    assert sum(1 for r in written if r.get("probe") == "profilez") == 3


def test_obs_collect_cli_profile_flag(tmp_path):
    """--profile arms the fleet before the pass loop; an unreachable
    target is reported, the trigger record still lands, the timeline
    still lints."""
    out = tmp_path / "timeline.jsonl"
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS_DIR, "obs_collect.py"),
         "--target", "replica:r0=http://127.0.0.1:9",
         "--out", str(out), "--passes", "1", "--interval_s", "0.05",
         "--scrape_timeout_s", "0.2",
         "--profile", "--profile_duration_s", "0.5"],
        capture_output=True, text=True, timeout=60, cwd=TOOLS_DIR)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "profile: armed 0/1" in proc.stdout
    assert "r0" in proc.stderr
    written = [json.loads(line) for line in open(out)]
    triggers = [r for r in written if r.get("probe") == "profilez"]
    assert len(triggers) == 1 and triggers[0]["ok"] is False
    assert schema.validate_file(str(out)) == []


# ---------------------------------------------------------------------------
# telemetry-report: --format json and the profile section


def _window(step, p50, mfu=0.4):
    rec = {"schema": 1, "ts": 0.0, "kind": "step_window",
           "tag": "telemetry", "step": step, "window_steps": 10,
           "synced_steps": 10, "steps_per_sec": round(1.0 / p50, 4),
           "mfu": mfu, "mfu_basis": "device"}
    for prefix in ("data_wait", "host", "device", "step"):
        base = p50 if prefix == "step" else p50 / 10
        rec[f"{prefix}_p50_s"] = base
        rec[f"{prefix}_p95_s"] = base * 1.5
        rec[f"{prefix}_max_s"] = base * 2
    return rec


def _run_artifact(path, p50=0.1, mfu=0.4):
    records = [_window(10, p50, mfu), _window(20, p50, mfu),
               _window(30, p50, mfu),
               {"schema": 1, "ts": 0.0, "kind": "run_summary",
                "tag": "telemetry", "step": 30, "steps": 30,
                "training_seq_per_sec": round(8 / p50, 2), "mfu": mfu}]
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return str(path)


def _report(*args):
    return subprocess.run(
        [sys.executable, REPORT_TOOL, *args],
        capture_output=True, text=True, timeout=60, cwd=TOOLS_DIR)


def test_report_format_json_stable_contract(tmp_path):
    """--format json prints the check_all contract: one versioned object
    with rc both inside and as the exit code."""
    clean = _run_artifact(tmp_path / "clean.jsonl", p50=0.1)
    proc = _report(clean, "--format", "json")
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    obj = json.loads(proc.stdout)
    assert obj["version"] == 1
    assert obj["rc"] == proc.returncode == 0
    assert obj["verdict"] == "ok"
    assert obj["regressions"] == []
    assert isinstance(obj["checks"], list)
    assert obj["run"]["step_p50_s"] == 0.1
    # A regression against a baseline flows into the same shape with rc=1.
    slow = _run_artifact(tmp_path / "slow.jsonl", p50=0.14)
    proc = _report(slow, clean, "--format", "json")
    obj = json.loads(proc.stdout)
    assert proc.returncode == 1 and obj["rc"] == 1
    assert any(r["label"] == "step-time p50" for r in obj["regressions"])
    assert obj["baseline"]["step_p50_s"] == 0.1


def test_report_profile_section_joins_host_and_device(tmp_path):
    """The report names the dominant host frame and the heaviest
    compiled fn out of profile_window + compile_cost records."""
    from bert_pytorch_tpu.telemetry import report

    path = tmp_path / "run.jsonl"
    records = [
        _window(10, 0.1),
        {"schema": 1, "ts": 1.0, "kind": "profile_window",
         "tag": "profile", "source": "trainer", "trigger": "ondemand",
         "covered": 12, "covered_unit": "steps", "duration_s": 2.0,
         "sample_interval_s": 0.01, "samples": 100,
         "top_frames": [
             {"frame": "MainThread:train_loop.py:step", "samples": 60,
              "share": 0.6, "stack": "x"},
             {"frame": "writer:runner.py:write_record", "samples": 20,
              "share": 0.2, "stack": "y"}],
         "trace_path": "out/profile/ondemand_1", "trace_bytes": 4096},
        {"schema": 1, "ts": 2.0, "kind": "compile_cost",
         "tag": "telemetry", "fn": "train_step", "shapes_digest": "abc",
         "analysis": "jaxpr", "flops": 9e12, "bytes_accessed": 1e9},
        {"schema": 1, "ts": 3.0, "kind": "compile_cost",
         "tag": "telemetry", "fn": "eval_step", "shapes_digest": "def",
         "analysis": "jaxpr", "flops": 1e10, "bytes_accessed": 1e8},
    ]
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    summary = report.summarize_file(str(path))
    assert summary["profile_windows"] == 1
    assert summary["profile_samples"] == 100
    assert summary["profile_trace_bytes"] == 4096
    assert summary["profile_critical_host"] == \
        "MainThread:train_loop.py:step"
    assert summary["profile_critical_device"] == "train_step"
    text = report.format_summary(summary)
    assert "MainThread:train_loop.py:step" in text


# ---------------------------------------------------------------------------
# schema fixtures for the profile_window kind


def test_profile_window_fixtures_lint_as_expected():
    good = os.path.join(FIXTURES, "profile_window_good.jsonl")
    bad = os.path.join(FIXTURES, "profile_window_bad.jsonl")
    assert schema.validate_file(good) == []
    errors = schema.validate_file(bad)
    assert len(errors) >= 10
    text = " ".join(err for _, err in errors)
    assert "trigger must be one of" in text
    assert "covered_unit must be one of" in text
    assert "exceeds the capture's total samples" in text
    assert "shares sum to" in text
    assert "trace_path must be a string" in text
    proc = subprocess.run(
        [sys.executable,
         os.path.join(TOOLS_DIR, "check_telemetry_schema.py"), good, bad],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    assert "profile_window_good.jsonl: ok" in proc.stdout
    assert "trigger must be one of" in proc.stdout


# ---------------------------------------------------------------------------
# serve/router.py: the router heartbeat


def test_router_writes_resumable_heartbeat_with_routed_requests(tmp_path):
    from bert_pytorch_tpu.serve import Router
    from bert_pytorch_tpu.telemetry.sentinels import Heartbeat

    hb = tmp_path / "router_heartbeat.json"

    def mk_router():
        return Router(
            ["http://127.0.0.1:1"],
            scrape=lambda url: {"dispatch_alive": True, "queue_depth": 0},
            transport=lambda url, task, payload, deadline_s: (200, {}),
            heartbeat_file=str(hb))

    router = mk_router()
    router.scrape_once()
    status, _, _ = router.handle("classify", {"text": "x"})
    assert status == 200
    assert router._maybe_beat(0.0) > 0.0  # interval elapsed: beats
    payload = Heartbeat.read(str(hb))
    assert payload["step"] == 1 and payload["counter"] == 1
    router.stop()  # final flush beats again
    payload = Heartbeat.read(str(hb))
    assert payload["counter"] == 2
    # Resumable: a restarted router continues the counter, never resets.
    router2 = mk_router()
    router2.stop()
    payload = Heartbeat.read(str(hb))
    assert payload["counter"] == 3 and payload["step"] == 0


# ---------------------------------------------------------------------------
# slow-gated: a real jax.profiler trace artifact on disk


@pytest.mark.slow
def test_ondemand_capture_writes_real_trace_artifact(tmp_path):
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.telemetry.profiler import ProfilerWindow

    trace_root = str(tmp_path / "profile")
    emitted = []
    ctrl = CaptureController(
        source="trainer", covered_unit="steps",
        window=ProfilerWindow(None, trace_root, enabled=True),
        trace_dir=trace_root, emit=emitted.append)
    ok, _ = ctrl.arm(duration_s=0.5, sample_interval_s=0.005)
    assert ok
    x = jnp.ones((256, 256))
    assert ctrl.tick(0) is None
    deadline = time.time() + 10.0
    step = 0
    record = None
    while record is None and time.time() < deadline:
        for _ in range(5):
            x = jnp.tanh(x @ x.T / 256.0)
        x.block_until_ready()
        step += 1
        record = ctrl.tick(step, sync_target=x)
    assert record is not None, "capture never completed"
    assert record["trace_path"].startswith(trace_root)
    assert os.path.isdir(record["trace_path"])
    assert record["trace_bytes"] > 0
    assert record["samples"] > 0
    assert schema.validate_record(_stamped(record)) == []
    # The latch is released: a fresh window can begin again.
    assert profiler._acquire_trace()
    profiler._release_trace()
