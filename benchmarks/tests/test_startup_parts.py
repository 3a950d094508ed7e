"""The five metrics of what start-up traced (ISSUE 51):
``trace/startup_parts.py`` over a recorded telemetry file, the entries of
``BENCHMARK.json``, and files from before ``trace_parts``.

``recorded_startup_parts.jsonl`` is the trainer's whole telemetry file of one
traced run of ``train-laguna-s-seq8192`` on the chip (PR 51, call 1, seed
3000000019, the run that compiled the step and the init program: ``cache``
"miss" in both). ``recorded_startup.jsonl`` (PR 36's program) has the
``startup`` record and no ``trace_parts``; ``recorded_no_startup.jsonl``
(PR 35's) has neither: both give ``None`` for all five, as the parent's
program does under this PR's benchmark files.
"""

import json
import os

import pytest

import benchmarks.run as bench_run
from benchmarks.trace import scopes, startup, startup_parts

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(HERE, "recorded_startup_parts.jsonl")
BEFORE = (os.path.join(HERE, "recorded_startup.jsonl"),
          os.path.join(HERE, "recorded_no_startup.jsonl"))
MODELS = "models (the families' Python, at trace time)"
OPS = "ops (kernels and fusions, taken whole)"
STEP = "pretrain (the jitted step)"
METRICS = {
    "setup_trace_model_s.train": ("s", MODELS),
    "setup_trace_kernels_s.train": ("s", OPS),
    "setup_kernel_builds.train": ("count", OPS),
    "setup_trace_other_s.train": ("s", STEP),
    "setup_init_program_s.train": ("s", STEP),
}
# By hand from the file's four records of the two programs (its lines 1, 2, 3
# and 6; the startup record is line 4):
#   compile init_state   trace 3.6893  lower 1.1407  compile-or-load 23.9847
#   compile train_step   trace 8.4234: modules 0.8203 + 0.2327 + 0.1158 +
#       0.1014 + 0.0589 + 0.0294 + 0.0011 + 0 = 1.3596; kernels 0.1161 +
#       0.1011 + 0.1905 + 0.2395 + 0.301 + 0.3215 + 0.7925 + 0.5987 + 0.1518
#       = 2.8127 over 2 + 2 + 6 + 3 + 3 + 9 + 56 + 40 + 16 = 137 builds;
#       optimizer 0.3902; other 3.8609
#   compile_cost train_step   trace 0.0001, all of it other
#   compile train_step (the probe's second check update)   trace 0.0143, other
BY_HAND = {
    "setup_trace_model_s.train": 1.3596,
    "setup_trace_kernels_s.train": 2.8127,
    "setup_kernel_builds.train": 137,
    "setup_trace_other_s.train": 0.3902 + 3.8609 + 0.0001 + 0.0143,
    "setup_init_program_s.train": 3.6893 + 1.1407 + 23.9847,
}
TRACE_S = 8.4234 + 0.0001 + 0.0143


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
        startup_parts._reductions.clear()
        return {"summary": {"busy_s": 1.0}, "updates": 3}
    return make


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_recorded_file_reduces_to_the_values_taken_by_hand(
        run_of, name, capsys):
    value = _reader(name)(run_of(RECORDED))
    assert value == pytest.approx(BY_HAND[name], abs=1e-6)
    assert isinstance(value, float)
    printed = [line for line in capsys.readouterr().out.splitlines()
               if line.startswith("startup_parts: ")]
    assert len(printed) == 1    # the records read, once a run
    line = json.loads(printed[0][len("startup_parts: "):])
    # the kernels' table whole: name -> [builds, seconds]
    assert line["kernels"]["rotary_turn"] == [40, 0.5987]
    assert line["kernels"]["gmm"][0] == 56 and len(line["kernels"]) == 9
    assert (line["optimizer_s"], line["outside_trace_s"]) == (0.3902, 0.0)
    assert line["init"] == [{"trace_s": 3.6893, "lower_s": 1.1407,
                             "backend_compile_s": 23.9847,
                             "cache_load_s": 0.0, "cache": "miss"}]


def test_the_three_trace_metrics_add_up_to_the_steps_trace_s():
    found = startup_parts.reduce_parts(startup_parts.read_records(RECORDED))
    assert found["trace_s"] == pytest.approx(TRACE_S, abs=1e-9)
    assert (found["trace_model_s"] + found["trace_kernels_s"]
            + found["trace_other_s"]) == pytest.approx(TRACE_S, abs=1e-9)
    # and the older reader's step_lower_s holds them: trace + lower + analysis
    older = startup.reduce_startup(startup.read_records(RECORDED))
    assert older["step_lower_s"] == pytest.approx(
        TRACE_S + 3.2224 + 0.0656 + 0.72 - 0.0001, abs=1e-6)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_every_new_metric_has_its_entry_its_file_and_the_ten_cells(name):
    bench = bench_run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    [entry] = [m for m in bench["per_layer"] if m["name"] == name]
    unit, layer = METRICS[name]
    every = [w["name"] for w in bench["workloads"]]
    # the ten cells of PR 51, and whichever cells were appended since
    cells = entry.pop("workloads")
    assert cells[:10] == every[:10] and cells == every[:len(cells)]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": "host_clock",
        "layer": layer, "moves": "setup_s"}
    assert callable(_reader(name))
    for cell in cells:
        context = bench_run.context(ROOT, cell)
        assert name in context["readers"] and context["units"][name] == unit
    # a layer BENCHMARK.json already names is spelled as it is there
    if layer != MODELS:
        assert layer in {m["layer"] for m in bench["per_layer"]
                         if m["name"] not in METRICS}


@pytest.mark.parametrize("source", BEFORE,
                         ids=["before_trace_parts", "before_startup"])
@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_run_from_before_the_field_reports_nothing(
        run_of, name, source, capsys):
    assert _reader(name)(run_of(source)) is None
    assert "startup_parts:" not in capsys.readouterr().out


def test_nothing_to_read_is_not_an_error(monkeypatch):
    startup_parts._reductions.clear()
    assert startup_parts.for_run({}) is None            # an empty context
    monkeypatch.setattr(scopes, "newest_trace", lambda under=None: None)
    ctx = {"summary": {"busy_s": 1.0}, "updates": 3}
    assert startup_parts.for_run(ctx) is None           # no trace to go by
    assert startup_parts.reduce_parts([]) is None
