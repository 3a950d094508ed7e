"""Offline telemetry report + regression gate (ISSUE 2 acceptance,
docs/telemetry.md): summary aggregation over synthetic artifacts, the
baseline-diff verdict (including the injected +25% step-time regression
that must exit nonzero and NAME the regression), and the CLI surface."""

import json
import os
import subprocess
import sys

import pytest

from bert_pytorch_tpu.telemetry import report

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO_ROOT, "tools", "telemetry_report.py")


def _window(step, p50, p95=None, steps=10, sps=None, mfu=0.4):
    rec = {"schema": 1, "ts": 0.0, "kind": "step_window", "tag": "telemetry",
           "step": step, "window_steps": steps, "synced_steps": steps,
           "steps_per_sec": sps if sps is not None else round(1.0 / p50, 4),
           "mfu": mfu, "mfu_basis": "device"}
    for prefix in ("data_wait", "host", "device", "step"):
        base = p50 if prefix == "step" else p50 / 10
        rec[f"{prefix}_p50_s"] = base
        rec[f"{prefix}_p95_s"] = p95 if (p95 and prefix == "step") \
            else base * 1.5
        rec[f"{prefix}_max_s"] = base * 2
    return rec


def _artifact(path, p50=0.1, mfu=0.4, peak=1000, grad_max=1.5,
              divergences=0, nonfinite=0):
    records = [
        _window(10, p50 * 1.2, p95=p50 * 30, mfu=mfu),  # cold: compile tail
        _window(20, p50, mfu=mfu),
        _window(30, p50, mfu=mfu),
        {"schema": 1, "ts": 0.0, "kind": "compile", "tag": "telemetry",
         "fn": "train_step", "shapes_digest": "abc123", "compile_s": 3.0,
         "backend_compile_s": 2.5, "cache": "miss"},
        {"schema": 1, "ts": 0.0, "kind": "memory", "tag": "telemetry",
         "step": 30, "memory_supported": True, "samples": 3, "n_devices": 1,
         "bytes_in_use": peak - 100, "bytes_in_use_max": peak - 50,
         "peak_bytes_in_use": peak, "bytes_limit": 4000},
        {"schema": 1, "ts": 0.0, "kind": "grad_health", "tag": "telemetry",
         "step": 30, "grad_norm": grad_max, "param_norm": 10.0,
         "update_ratio": 0.002, "groups": {}},
        {"schema": 1, "ts": 0.0, "kind": "run_summary", "tag": "telemetry",
         "step": 30, "steps": 30, "training_seq_per_sec": round(8 / p50, 2),
         "mfu": mfu},
    ]
    for i in range(divergences):
        records.append({"schema": 1, "ts": 0.0, "kind": "divergence",
                        "tag": "telemetry", "step": 25 + i,
                        "reason": "grad_norm_spike", "value": 99.0,
                        "threshold": 9.0, "policy": "continue"})
    for i in range(nonfinite):
        records.append({"schema": 1, "ts": 0.0, "kind": "sentinel",
                        "tag": "telemetry", "step": 28 + i, "finite": 0,
                        "loss": None, "consecutive_nonfinite": i + 1,
                        "policy": "continue"})
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return str(path)


def test_summarize_aggregates(tmp_path):
    summary = report.summarize_file(_artifact(tmp_path / "a.jsonl", p50=0.1))
    assert summary["steps"] == 30
    assert summary["windows"] == 3
    # weighted median over window p50s: two steady windows dominate
    assert summary["step_p50_s"] == pytest.approx(0.1)
    # p95 excludes the first (compile-tail) window
    assert summary["step_p95_s"] == pytest.approx(0.15)
    assert summary["mfu"] == pytest.approx(0.4)
    assert summary["compiles"] == 1 and summary["cold_start"] is True
    assert summary["peak_bytes_in_use"] == 1000
    assert summary["grad_norm_max"] == pytest.approx(1.5)
    assert summary["training_seq_per_sec"] == pytest.approx(80.0)
    assert summary["nonfinite_steps"] == 0
    assert summary["divergence_warnings"] == 0


def test_summarize_mfu_excludes_cold_window(tmp_path):
    """Like p95, the MFU aggregate must skip the first window: a cold
    run's step-0 compile halves that window's wall-basis MFU and would
    read as a regression against a warm baseline."""
    path = tmp_path / "cold.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps(_window(10, 0.2, mfu=0.2)) + "\n")  # cold
        f.write(json.dumps(_window(20, 0.1, mfu=0.4)) + "\n")
        f.write(json.dumps(_window(30, 0.1, mfu=0.4)) + "\n")
    summary = report.summarize_file(str(path))
    assert summary["mfu"] == pytest.approx(0.4)


def test_last_run_trims_append_mode_artifact(tmp_path):
    """Append-mode artifacts accumulate runs (capture legs, retries);
    --last-run must score only the segment after the penultimate
    run_summary, so one leg's windows can't poison another's verdict."""
    def _summary(metric):
        return {"schema": 1, "ts": 0.0, "kind": "run_summary",
                "tag": "telemetry", "step": 30, "steps": 30,
                "metric": metric}

    path = tmp_path / "accumulated.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps(_window(10, 0.1)) + "\n")   # fast leg
        f.write(json.dumps(_summary("phase1")) + "\n")
        f.write(json.dumps(_window(10, 0.5)) + "\n")   # slow leg
        f.write(json.dumps(_summary("seq2048")) + "\n")
    last = report.summarize_file(str(path), last_run=True)
    assert last["metric"] == "seq2048"
    assert last["step_p50_s"] == pytest.approx(0.5)
    blended = report.summarize_file(str(path))
    assert blended["step_p50_s"] != pytest.approx(0.5)  # why --last-run exists
    # fewer than two run_summary records: nothing to trim
    single = _artifact(tmp_path / "single.jsonl", p50=0.1)
    assert report.summarize_file(single, last_run=True)["steps"] == 30


def test_compare_clean_runs_pass(tmp_path):
    base = report.summarize_file(_artifact(tmp_path / "b.jsonl", p50=0.1))
    new = report.summarize_file(_artifact(tmp_path / "n.jsonl", p50=0.104))
    regressions, checks = report.compare(base, new)
    assert regressions == []
    assert any(c["verdict"] == "ok" for c in checks)


def test_compare_catches_each_axis(tmp_path):
    base = report.summarize_file(_artifact(tmp_path / "b.jsonl"))
    cases = {
        "step_p50_s": dict(p50=0.125),            # +25% step time
        "mfu": dict(mfu=0.3),                     # -25% MFU
        "peak_bytes_in_use": dict(peak=1200),     # +20% peak memory
        "grad_norm_max": dict(grad_max=4.0),      # >2x grad envelope
        "divergence_warnings": dict(divergences=2),
        "nonfinite_steps": dict(nonfinite=1),
    }
    for metric, kwargs in cases.items():
        new = report.summarize_file(
            _artifact(tmp_path / f"{metric}.jsonl", **kwargs))
        regressions, _ = report.compare(base, new)
        assert metric in [r["metric"] for r in regressions], metric


def test_cli_summary_and_missing_file(tmp_path, capsys):
    path = _artifact(tmp_path / "a.jsonl")
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "steps_per_sec" in out
    assert report.main([str(tmp_path / "absent.jsonl")]) == 2


def test_cli_injected_step_time_regression_exits_nonzero(tmp_path):
    """The ISSUE 2 acceptance shape: a +25% step-time copy of the same
    run must exit nonzero with the regression NAMED, via the repo-root
    tool in a fresh process (no jax import needed)."""
    base = _artifact(tmp_path / "base.jsonl", p50=0.1)
    slow = _artifact(tmp_path / "slow.jsonl", p50=0.125)
    proc = subprocess.run(
        [sys.executable, TOOL, slow, base],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "REGRESSION" in proc.stdout
    assert "step-time p50" in proc.stdout
    # same artifact against itself: clean exit
    proc = subprocess.run(
        [sys.executable, TOOL, base, base],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_json_verdict(tmp_path, capsys):
    base = _artifact(tmp_path / "base.jsonl", p50=0.1)
    slow = _artifact(tmp_path / "slow.jsonl", p50=0.2)
    assert report.main([slow, base, "--json"]) == 1
    verdict = json.loads(capsys.readouterr().out)
    assert verdict["verdict"] == "regression"
    assert "step_p50_s" in [r["metric"] for r in verdict["regressions"]]


def test_cli_tolerance_knobs(tmp_path):
    base = _artifact(tmp_path / "base.jsonl", p50=0.1)
    mild = _artifact(tmp_path / "mild.jsonl", p50=0.115)  # +15%
    assert report.main([mild, base]) == 1                 # default 10%
    assert report.main([mild, base, "--step-tol", "0.2"]) == 0
