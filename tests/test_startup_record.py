"""The trainer's start-up has a record of its own (ISSUE 36; docs/telemetry.md
"Start-up"): ``run_pretraining.main`` keeps its ``startup:*`` spans until the
first update and then emits ONE ``kind="startup"`` record, and the ``compile``
/ ``compile_cost`` records say where a first call's time went.

Two tiny runs of ``main`` (three updates each): one that syncs on the
telemetry cadence at update 1, one with the cadence off, whose only syncs are
``main``'s barrier on update 1 and the fetch of a logged update. No test
compares a duration with a threshold on the host's clock: they compare the
record's stamps with each other.
"""

import copy
import json
import os
import re
import subprocess
import sys

import jax
import pytest

from bert_pytorch_tpu.telemetry import profiler, schema
from bert_pytorch_tpu.telemetry.profiler import SPANS, STARTUP

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = 3


def _records(out_dir):
    with open(os.path.join(out_dir, "pretraining_telemetry.jsonl")) as f:
        return [json.loads(line) for line in f]


def _tiny_run(tmp, out_name, *extra):
    """Runs ``main``; returns (records of its telemetry file, how often
    ``jax.block_until_ready`` was called, how many spans the store held when
    ``main`` returned and after a later ``startup:*`` span)."""
    import run_pretraining
    from bert_pytorch_tpu.tools.make_synthetic_data import make_shard

    data = tmp / "data"
    if not data.exists():
        data.mkdir()
        for i in range(2):
            make_shard(str(data / f"shard_{i}.hdf5"), 128, 32, 1000, seed=i)
        (tmp / "model.json").write_text(json.dumps({
            "vocab_size": 1000, "hidden_size": 32, "num_hidden_layers": 2,
            "num_attention_heads": 4, "intermediate_size": 64,
            "max_position_embeddings": 32, "type_vocab_size": 2,
            "next_sentence": True, "mask_token_id": 4}))
    blocks = []
    real_block, real_open = jax.block_until_ready, profiler.startup_open
    stores = []

    def counting(tree):
        blocks.append(1)
        return real_block(tree)

    def opening():
        stores.append(real_open())
        return stores[-1]

    jax.block_until_ready = counting
    profiler.startup_open = run_pretraining.telemetry.startup_open = opening
    try:
        run_pretraining.main(run_pretraining.parse_arguments([
            "--input_dir", str(data), "--output_dir", str(tmp / out_name),
            "--model_config_file", str(tmp / "model.json"),
            "--global_batch_size", "32", "--local_batch_size", "2",
            "--max_steps", str(STEPS), "--steps", str(STEPS),
            "--learning_rate", "1e-3", "--dtype", "float32", "--seed", "7",
            "--skip_final_checkpoint", "--disable_tensorboard", *extra]))
    finally:
        jax.block_until_ready = real_block
        profiler.startup_open = real_open
        run_pretraining.telemetry.startup_open = real_open
    [store] = stores
    held = len(store.spans)
    with profiler.span("startup:setup"):
        pass
    return {"records": _records(str(tmp / out_name)), "blocks": len(blocks),
            "held": held, "held_later": len(store.spans),
            "path": str(tmp / out_name / "pretraining_telemetry.jsonl")}


@pytest.fixture(scope="module")
def cadence_run(tmp_path_factory):
    """Device syncs on the telemetry cadence at updates 1 and 3."""
    return _tiny_run(tmp_path_factory.mktemp("startup"), "out",
                     "--telemetry_sync_every", "2")


@pytest.fixture(scope="module")
def fetch_run(tmp_path_factory):
    """No cadence: after ``main``'s barrier on update 1 the loop syncs
    only where it fetches a logged update, the second."""
    return _tiny_run(tmp_path_factory.mktemp("startup_fetch"), "out",
                     "--telemetry_sync_every", "0", "--log_steps", "2")


def _startup(run):
    [record] = [r for r in run["records"] if r.get("kind") == "startup"]
    return record


def test_a_run_emits_one_startup_record_that_passes_the_schema_tool(
        cadence_run):
    record = _startup(cadence_run)
    assert record["origin"] == "proc_stat"  # the suite runs on Linux
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_telemetry_schema.py"),
         cadence_run["path"]], capture_output=True, text=True)
    assert done.returncode == 0, done.stdout + done.stderr


def test_the_phases_are_the_spans_main_writes_and_nest_by_parent(cadence_run):
    record = _startup(cadence_run)
    names = [p["name"] for p in record["phases"]]
    # no checkpoint to read in a fresh run
    assert names == [n for n in SPANS if n.startswith(STARTUP)
                     and n != "startup:restore"]
    parents = {p["name"]: p["parent"] for p in record["phases"]}
    assert parents.pop("startup:backend") == "startup:setup"
    assert set(parents.values()) == {None}
    by_name = {p["name"]: p for p in record["phases"]}
    for p in record["phases"]:
        assert p["start_s"] <= p["end_s"]
        if p["parent"] is not None:
            outer = by_name[p["parent"]]
            assert outer["start_s"] <= p["start_s"]
            assert p["end_s"] <= outer["end_s"]


def test_sibling_phases_follow_each_other_inside_the_start_up(cadence_run):
    record = _startup(cadence_run)
    top = [p for p in record["phases"] if p["parent"] is None]
    assert record["main_entered_s"] <= top[0]["start_s"]
    for before, after in zip(top, top[1:]):
        assert before["end_s"] <= after["start_s"]
    first_update = (record["first_batch_wait_s"] + record["first_call_s"]
                    + record["first_sync_s"])
    assert top[-1]["end_s"] <= record["time_to_first_update_s"] - first_update


def test_the_parts_add_up_to_the_time_to_the_first_update(cadence_run):
    record = _startup(cadence_run)
    named = sum(p["end_s"] - p["start_s"] for p in record["phases"]
                if p["parent"] is None)
    parts = (named + record["first_batch_wait_s"] + record["first_call_s"]
             + record["first_sync_s"] + record["unattributed_s"])
    whole = record["time_to_first_update_s"] - record["main_entered_s"]
    assert parts == pytest.approx(whole, abs=1e-4)
    assert record["unattributed_s"] >= -1e-4


def test_the_record_is_emitted_at_the_loops_own_first_sync(cadence_run):
    record = _startup(cadence_run)
    kinds = [r.get("kind") or r.get("tag") for r in cadence_run["records"]]
    # after the first call's compile record, before update 1 is logged
    assert kinds.index("compile") < kinds.index("startup")
    assert kinds.index("startup") < kinds.index("train")
    # the loop's own syncs and no other: update 1's barrier before the
    # throughput clock starts, and the cadence's at updates 1 and 3
    assert cadence_run["blocks"] == 3
    # the init program's and the step's, both compiled (the suite's cache
    # is off)
    assert (record["compiles"], record["compiles_cold"],
            record["compiles_warm"]) == (2, 2, 0)


def test_without_a_cadence_the_start_up_still_ends_at_update_one(fetch_run):
    record = _startup(fetch_run)
    assert fetch_run["blocks"] == 1     # update 1's barrier alone: none added
    logged = [r for r in fetch_run["records"] if r.get("tag") == "train"]
    assert [r["step"] for r in logged] == [2]
    assert schema.validate_record(record) == []
    # the record is written at update 1's barrier, before update 2 is fed:
    # ahead of every window and of the first logged update
    kinds = [r.get("kind") or r.get("tag") for r in fetch_run["records"]]
    assert kinds.index("compile") < kinds.index("startup")
    assert kinds.index("startup") < kinds.index("train")
    assert (record["compiles"], record["compiles_cold"]) == (2, 2)


def test_the_store_is_closed_at_the_first_update(cadence_run, fetch_run):
    for run in (cadence_run, fetch_run):
        assert run["held"] == len(_startup(run)["phases"])
        assert run["held_later"] == run["held"]
    assert profiler._startup is None
    # and a span outside any run is a plain annotation
    assert isinstance(profiler.span("startup:setup"),
                      jax.profiler.TraceAnnotation)


def test_a_first_call_says_where_its_time_went(cadence_run):
    [compiled] = [r for r in cadence_run["records"]
                  if r.get("kind") == "compile" and r["fn"] == "train_step"]
    assert compiled["trace_s"] > 0 and compiled["lower_s"] > 0
    assert compiled["backend_compile_s"] > 0
    assert compiled["cache_load_s"] == 0    # the suite's cache is off
    # every part lies inside the call. Each on its own: the parts are not
    # disjoint (a small program compiled while the step is traced counts
    # under trace_s and under backend_compile_s), so their sum may pass the
    # whole by as much as the scheduler gives that compile; 2e-4 is the two
    # roundings to four places.
    for part in ("trace_s", "lower_s", "backend_compile_s"):
        assert compiled[part] <= compiled["compile_s"] + 2e-4, part


def test_the_cost_record_carries_the_time_of_its_analysis(cadence_run):
    [cost] = [r for r in cadence_run["records"]
              if r.get("kind") == "compile_cost"]
    assert cost["analysis"] == "compiled"
    assert cost["analysis_s"] > 0
    for key in ("trace_s", "lower_s", "backend_compile_s", "cache_load_s"):
        assert 0 <= cost[key] <= cost["analysis_s"] + 1e-3


def test_a_trace_inside_another_is_counted_once():
    from bert_pytorch_tpu.telemetry import compile_events as ce

    call = ce._new_call()
    ce._tls.call = call
    try:
        trace = "/jax/core/compile/jaxpr_trace_duration"
        ce._on_span(trace, 1.0, 1.5)          # inner, ends first
        ce._on_span(trace, 2.0, 2.25)         # inner
        ce._on_span(trace, 0.5, 3.0)          # the outer one holds both
        ce._on_span(trace, 4.0, 5.0)          # one after it
        ce._on_span("/jax/core/compile/jaxpr_to_mlir_module_duration", 5, 7)
        ce._on_span("/some/other/event", 0.0, 100.0)
        ce._on_duration("/jax/compilation_cache/cache_retrieval_time_sec", .5)
    finally:
        ce._tls.call = None
    assert ce._split(call) == {
        "trace_s": 3.5, "lower_s": 2.0, "backend_compile_s": 0.0,
        "cache_load_s": 0.5,
        # no hook booked anything: the whole of it is JAX's own
        "trace_parts": {"modules": {}, "kernels": {}, "optimizer_s": 0.0,
                        "other_s": 3.5, "outside_trace_s": 0.0}}


_WARM_SCRIPT = """
import json, sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from bert_pytorch_tpu.telemetry import CompileMonitor
from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache
enable_compile_cache({bar})
records = []
monitor = CompileMonitor(emit=records.append)
f = monitor.instrument(jax.jit(lambda x: jnp.tanh(x @ x).sum()), {name!r})
f(jnp.ones((64, 64)))
print(json.dumps(records[0]))
"""


def _two_starts(tmp_path, bar, name):
    """The first record of two processes, one after the other, that share a
    cache directory and call the same jitted function under a monitor."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    found = []
    for _ in range(2):
        done = subprocess.run(
            [sys.executable, "-c",
             _WARM_SCRIPT.format(repo=REPO, bar=bar, name=name)],
            env=env, capture_output=True, text=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        found.append(json.loads(done.stdout.strip().splitlines()[-1]))
    return found


def test_a_second_process_loads_from_a_warm_cache_and_says_so(tmp_path):
    cold, warm = _two_starts(tmp_path, "min_compile_secs=0.0", "f")
    assert (cold["cache"], cold["cache_load_s"]) == ("miss", 0)
    assert warm["cache"] == "hit"
    assert warm["cache_load_s"] > 0
    # the load is inside JAX's compile-or-load call, not beside it
    assert warm["cache_load_s"] <= warm["backend_compile_s"] + 1e-3
    assert warm["trace_s"] > 0 and warm["lower_s"] > 0


def test_a_program_under_the_persistence_bar_compiles_at_every_start(
        tmp_path):
    """ROADMAP S14d's coin landing wrong, as the init program's record shows
    it: compiled in less than the trainer's bar of 10 s, so never written,
    and the next start compiles it again."""
    first, second = _two_starts(tmp_path, "", "init_state")
    for record in (first, second):
        assert (record["fn"], record["cache"]) == ("init_state", "uncached")
        assert record["backend_compile_s"] > 0 and record["cache_load_s"] == 0


def test_the_init_program_has_its_record_ahead_of_the_steps(cadence_run):
    compiles = [r for r in cadence_run["records"]
                if r.get("kind") == "compile"]
    assert [r["fn"] for r in compiles[:2]] == ["init_state", "train_step"]
    init, step = compiles[:2]
    assert init["cache"] == "uncached"      # the suite's cache is off
    assert init["trace_s"] > 0 and init["backend_compile_s"] > 0
    for record in (init, step):
        assert schema.validate_record(record) == []
        # the model's modules were entered under the interceptor in both
        # (which of its classes make the twelve kept is the clock's to say)
        assert any(name.startswith("Bert")
                   for name in record["trace_parts"]["modules"])
        assert record["trace_parts"]["outside_trace_s"] == 0
    assert init["trace_parts"]["optimizer_s"] == 0      # tx.init is no update
    assert step["trace_parts"]["optimizer_s"] > 0


def test_the_record_says_when_the_program_began_to_be_imported(cadence_run):
    record = _startup(cadence_run)
    assert 0 <= record["package_imported_s"] <= record["main_entered_s"]
    imported = record["imported_in_first_call"]
    assert imported["modules"] >= len(imported["packages"]) >= 0


def _written_names():
    """Every literal name a ``span("...")`` call of the program writes."""
    found = {}
    sources = [os.path.join(REPO, "run_pretraining.py")]
    for folder, _, files in os.walk(os.path.join(REPO, "bert_pytorch_tpu")):
        sources += [os.path.join(folder, f) for f in files
                    if f.endswith(".py")]
    for path in sources:
        with open(path, encoding="utf-8") as f:
            for name in re.findall(r'\bspan\(\s*"([a-z_]+:[a-z_0-9]+)"',
                                   f.read()):
                found.setdefault(name, []).append(os.path.relpath(path, REPO))
    return found


def test_every_name_the_program_writes_is_listed_and_documented():
    written = _written_names()
    assert set(written) == set(SPANS), (
        sorted(set(written) ^ set(SPANS)), written)
    with open(os.path.join(REPO, "docs", "telemetry.md")) as f:
        docs = f.read()
    assert [n for n in SPANS if f"`{n}`" not in docs] == []
    for name in (n for n in SPANS if n.startswith(STARTUP)):
        assert "run_pretraining.py" in written[name], name


@pytest.mark.parametrize("fault, says", [
    ("sum", "add up"), ("outside", "lies outside"),
    ("overlap", "overlaps"), ("child", "lies outside"),
    ("origin", "origin"), ("clock", "clock"), ("compiles", "exceeds"),
    ("imported_late", "package_imported_s"),
    ("imports", "imported_in_first_call")])
def test_the_schema_refuses_a_start_up_that_does_not_hold_together(
        cadence_run, fault, says):
    record = copy.deepcopy(_startup(cadence_run))
    assert schema.validate_record(record) == []
    by_name = {p["name"]: p for p in record["phases"]}
    if fault == "sum":
        record["unattributed_s"] += 0.5
    elif fault == "outside":
        by_name["startup:setup"]["start_s"] = record["main_entered_s"] - 1.0
    elif fault == "overlap":
        by_name["startup:model"]["start_s"] = \
            by_name["startup:setup"]["start_s"]
    elif fault == "child":
        by_name["startup:backend"]["end_s"] = \
            by_name["startup:setup"]["end_s"] + 1.0
    elif fault == "origin":
        record["origin"] = "guessed"
    elif fault == "clock":
        del record["clock"]["time_ns"]
    elif fault == "compiles":
        record["compiles_warm"] = record["compiles"] + 1
    elif fault == "imported_late":
        record["package_imported_s"] = record["main_entered_s"] + 1.0
    elif fault == "imports":
        record["imported_in_first_call"] = {"modules": 0, "packages": ["x"]}
    errors = schema.validate_record(record)
    assert any(says in e for e in errors), errors
