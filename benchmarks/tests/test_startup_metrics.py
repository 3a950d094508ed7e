"""The seven set-up metrics (ISSUE 36): ``trace/startup.py`` over a recorded
telemetry file, the entries of ``BENCHMARK.json``, and a file from before the
``startup`` record.

``recorded_startup.jsonl`` is the trainer's whole telemetry file of one traced
run of ``train-laguna-s-seq8192`` on the chip (PR 36, seed 3600000101, the run
that compiled the step: ``cache`` "miss"; its ``startup`` record without the
``first_sync_step`` field, which the program wrote then and dropped before the
PR was done); ``recorded_no_startup.jsonl`` is a tiny BERT run of PR 35's
program on the CPU.
"""

import json
import os

import pytest

import benchmarks.run as bench_run
from benchmarks.trace import scopes, startup

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(HERE, "recorded_startup.jsonl")
OLD = os.path.join(HERE, "recorded_no_startup.jsonl")
METRICS = {
    "setup_before_main_s.train": ("s", "entry (imports, chip runtime start-up)"),
    "setup_prepare_s.train": ("s", "run_pretraining (the loop)"),
    "setup_state_init_s.train": ("s", "pretrain (the jitted step)"),
    "setup_step_lower_s.train": ("s", "pretrain (the jitted step)"),
    "setup_step_executable_s.train":
        ("s", "compile cache (executables built or loaded)"),
    "setup_first_update_s.train": ("s", "run_pretraining (the loop)"),
    "setup_unattributed_pct.train": ("%", "trace (the reduction itself)"),
}
# By hand from the file's four records of the step (its lines 1, 2, 3, 5):
#   compile       trace 11.4813  lower 2.9027  compile-or-load 70.3242
#   compile_cost  analysis 0.7373, trace 0.0001, lower 0, compile-or-load 0
#   startup       main entered 17.758656; setup 0.003281 + model 0.001132 +
#                 optimizer 0.000033 + data 0.588985 + step_build 0.003055;
#                 state_init 18.352214 -> 45.443915; first update 0.005055 +
#                 87.770124 + 0.000469; unattributed 0.000822 of 133.223313
#   compile       (the probe's second check update, AFTER the record)
#                 trace 0.014  lower 0.0649  compile-or-load 5.2496
BY_HAND = {
    "setup_before_main_s.train": 17.758656,
    "setup_prepare_s.train": 0.596486,
    "setup_state_init_s.train": 27.091701,
    "setup_step_lower_s.train": 11.4813 + 2.9027 + 0.7373 + 0.014 + 0.0649,
    "setup_step_executable_s.train": 70.3242 + 5.2496,
    # less what lies inside the first call: both records before the startup
    "setup_first_update_s.train":
        87.775648 - (11.4813 + 2.9027 + 70.3242) - 0.7373,
    "setup_unattributed_pct.train": 100 * 0.000822 / 133.223313,
}


def _reader(name):
    return bench_run.load_module(
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"),
        "metric_" + name.replace(".", "_")).read


@pytest.fixture
def run_of(tmp_path, monkeypatch):
    """A reader's context for a run whose work directory holds ``source`` as
    the trainer's telemetry file beside a trace, found as ``scopes.py`` finds
    the trace."""
    def make(source):
        work = tmp_path / "bench_train_x"
        (work / "trace" / "plugins" / "profile" / "t").mkdir(parents=True)
        (work / "out").mkdir()
        trace = work / "trace" / "plugins" / "profile" / "t" / "h.xplane.pb"
        trace.write_bytes(b"")
        with open(source, "rb") as f:
            (work / "out" / "pretraining_telemetry.jsonl").write_bytes(f.read())
        monkeypatch.setattr(scopes, "newest_trace",
                            lambda under=None: str(trace))
        startup._reductions.clear()
        return {"summary": {"busy_s": 1.0}, "updates": 3}
    return make


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_recorded_file_reduces_to_the_values_taken_by_hand(
        run_of, name, capsys):
    value = _reader(name)(run_of(RECORDED))
    assert value == pytest.approx(BY_HAND[name], abs=1e-6)
    printed = [line for line in capsys.readouterr().out.splitlines()
               if line.startswith("startup: ")]
    assert len(printed) == 1    # the records read, once a run
    assert json.loads(printed[0][len("startup: "):])["reduced"]["lowerings"] == 2


def test_the_parts_before_the_first_update_add_up_to_it():
    found = startup.reduce_startup(startup.read_records(RECORDED))
    after = 0.014 + 0.0649 + 5.2496     # the one record after the startup's
    parts = sum(found[k] for k in (
        "before_main_s", "prepare_s", "state_init_s", "step_lower_s",
        "step_executable_s", "first_update_s")) - after
    unattributed = found["unattributed_pct"] / 100 * \
        found["time_to_first_update_s"]
    assert parts + unattributed == pytest.approx(
        found["time_to_first_update_s"], abs=1e-4)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_every_new_metric_has_its_entry_its_file_and_the_six_cells(name):
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    unit, layer = METRICS[name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": "host_clock",
        "layer": layer, "moves": "setup_s",
        "workloads": [w["name"] for w in bench["workloads"][:6]]}
    assert "setup_s" in [m["name"] for m in bench["end_to_end"]]
    assert callable(_reader(name))
    for cell in entry["workloads"]:
        assert name in bench_run.context(ROOT, cell)["readers"]


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_run_from_before_the_record_reports_nothing(run_of, name, capsys):
    assert _reader(name)(run_of(OLD)) is None
    assert "startup:" not in capsys.readouterr().out


def test_nothing_to_read_is_not_an_error(monkeypatch, tmp_path):
    startup._reductions.clear()
    assert startup.for_run({}) is None                  # an empty context
    monkeypatch.setattr(scopes, "newest_trace", lambda under=None: None)
    ctx = {"summary": {"busy_s": 1.0}, "updates": 3}
    assert startup.for_run(ctx) is None                 # no trace to go by
    lone = tmp_path / "somewhere" / "h.xplane.pb"       # no trace directory
    assert startup.telemetry_file(str(lone)) is None
    (tmp_path / "trace").mkdir()                        # no out/ beside it
    assert startup.telemetry_file(str(tmp_path / "trace" / "h.pb")) is None
