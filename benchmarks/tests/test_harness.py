import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks import run as bench_run

ROOT = bench_run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_the_contract():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    cells = bench["workloads"]
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    configs = {c["name"] for c in bench["configs"]}
    assert configs == {c["config"] for c in cells}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and all(0 < m["bound"] <= 0.1 for m in e2e.values())
    for entry in cells + bench["configs"] + bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
        for text in ("why", "layer", "source"):
            if text in entry:
                assert 1 <= len(entry[text]) <= 200 and "\n" not in entry[text]
    for c in cells:
        assert c["chips"] in (1, 4) and NAME.match(c["traffic"])
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics",
                                           m["name"] + ".py"))
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        assert held["source"] == c["source"]


def test_every_cell_loads_from_data_alone():
    for cell in _bench()["workloads"]:
        ctx = bench_run.context(ROOT, cell["name"])
        assert os.path.exists(ctx["kind_file"])
        assert set(ctx["mix"]["check"]["limits"]) >= {
            "loss_gap_first", "loss_gap_later", "grad_global_norm_gap",
            "grad_norm_gap_worst_leaf", "delta_norm_gap_worst_leaf",
            "head_grad_rel_diff", "all_grad_rel_diff", "feed_faults"}


def test_a_configuration_a_cell_and_a_metric_are_added_as_files_only(tmp_path):
    """What a later PR does: new files and new entries, no edit elsewhere."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _bench()
    before = {p: open(os.path.join(dp, p), "rb").read()
              for dp, _, fs in os.walk(root / "benchmarks") for p in fs}
    (root / "benchmarks/configs/new-model.json").write_text(json.dumps(
        {"source": "https://example.org/new", "hidden_size": 8, "reduced": []}))
    (root / "benchmarks/traffic/new-mix.json").write_text(json.dumps(
        {"kind": "train", "seq_len": 16}))
    (root / "benchmarks/metrics/new_metric.train.py").write_text(
        "def read(ctx):\n    return ctx.get('answer')\n")
    bench["configs"].append({"name": "new-model", "source": "https://example.org/new",
                             "file": "benchmarks/configs/new-model.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "new-cell", "config": "new-model",
                               "traffic": "new-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "new_metric.train", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "data (loader, device prefetch)",
                               "moves": "train_tokens_per_s",
                               "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    ctx = bench_run.context(str(root), "new-cell")
    assert ctx["config"]["hidden_size"] == 8 and ctx["mix"]["seq_len"] == 16
    assert ctx["readers"]["new_metric.train"]({"answer": 42}) == 42
    assert ctx["readers"]["new_metric.train"]({}) is None
    assert ctx["kind_file"].endswith("kinds/train.py")
    old = bench_run.context(str(root), "train-large-phase1")
    assert "new_metric.train" not in old["readers"]
    after = {p: open(os.path.join(dp, p), "rb").read()
             for dp, _, fs in os.walk(root / "benchmarks") for p in fs}
    assert all(after[p] == before[p] for p in before)


def _run_cell(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "train-large-phase1",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    done = _run_cell(ROOT, {"BENCH_RUN": "7"})
    assert done.returncode != 0
    assert '"correct"' not in done.stdout and "no TPU" in done.stderr


def test_without_the_program_the_run_fails_and_prints_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    done = _run_cell(str(tmp_path))
    assert done.returncode != 0 and '"correct"' not in done.stdout


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        bench_run.context(ROOT, "no-such-cell")
