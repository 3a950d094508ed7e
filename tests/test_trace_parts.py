"""What a traced function's host time was spent on (ISSUE 51;
docs/telemetry.md "Start-up"): the hooks of ``utils/trace_parts.py`` book
module methods, kernel builds and the optimizer's update into the monitored
call on the stack, and ``telemetry/compile_events.py`` turns the book into the
record's ``trace_parts``. No test compares a duration with a threshold on the
host's clock: times are read from a scripted clock, and a real trace is held
to counts and to its own sum.
"""

import contextlib
import copy
import importlib.util
import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.telemetry import CompileMonitor, TrainTelemetry, schema
from bert_pytorch_tpu.telemetry import compile_events as ce
from bert_pytorch_tpu.telemetry import profiler
from bert_pytorch_tpu.utils import trace_parts

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE = "/jax/core/compile/jaxpr_trace_duration"

spec = importlib.util.spec_from_file_location(
    "_lowered_steps_for_parts",
    os.path.join(REPO_ROOT, "tools", "lowered_steps.py"))
tool = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tool)  # the small sizes at which the kernels are taken


class Script:
    """A clock that reads out a script, one stamp a read."""

    def __init__(self, *stamps):
        self.stamps = list(stamps)

    def __call__(self):
        return self.stamps.pop(0)


class Inner(nn.Module):
    @nn.compact
    def __call__(self, x):
        with trace_parts.kernel_build("planted_kernel"):
            return x + 1


class Outer(nn.Module):
    @nn.compact
    def __call__(self, x):
        return Inner()(x) * 2


@contextlib.contextmanager
def monitored(clock=None):
    call = ce._new_call(clock)
    before = ce._current_call()
    ce._tls.call = call
    try:
        yield call
    finally:
        ce._tls.call = before


def test_the_four_parts_add_up_to_trace_s_and_a_build_is_booked_once():
    # reads, in order: Outer enters 1, Inner enters 2, the build enters 3 and
    # ends 6, Inner ends 7, Outer ends 9; the optimizer 10 to 12; one more
    # build, 20 to 21, after the only trace span [0, 14] has ended
    clock = Script(1.0, 2.0, 3.0, 6.0, 7.0, 9.0, 10.0, 12.0, 20.0, 21.0)
    with monitored(clock) as call:
        with trace_parts.modules():
            assert Outer().apply({}, jnp.zeros(())) == 2
        with trace_parts.optimizer():
            pass
        ce._on_span(TRACE, 0.0, 14.0)
        with trace_parts.kernel_build("planted_kernel"):
            pass
    assert clock.stamps == []
    split = ce._split(call)
    assert split["trace_s"] == 14.0
    assert split["trace_parts"] == {
        # Inner: 5 s whole less the build's 3; Outer: 8 s less Inner's 5
        "modules": {"Outer": {"calls": 1, "self_s": 3.0},
                    "Inner": {"calls": 1, "self_s": 2.0}},
        "kernels": {"planted_kernel": {"builds": 1, "build_s": 3.0}},
        "optimizer_s": 2.0,
        "other_s": 4.0,            # 14 - (3 + 2) - 3 - 2
        "outside_trace_s": 1.0,    # the build no trace span holds
    }
    record = {"schema": 1, "ts": 0.0, "kind": "compile", "fn": "f",
              "shapes_digest": "0", "compile_s": 22.0, "cache": "jit", **split}
    assert schema.validate_record(record) == []


def test_more_classes_than_are_kept_go_into_one_row():
    stamps = []
    for i in range(ce.KEPT_CLASSES + 3):   # class i takes i + 1 seconds
        stamps += [100.0 * i, 100.0 * i + i + 1]
    with monitored(Script(*stamps)) as call:
        for i in range(ce.KEPT_CLASSES + 3):
            with trace_parts._book().interval(trace_parts.MODULE,
                                              f"Class{i}"):
                pass
        ce._on_span(TRACE, 0.0, 5000.0)
    rows = ce._split(call)["trace_parts"]["modules"]
    assert len(rows) == ce.KEPT_CLASSES + 1
    assert rows[ce.OTHER_CLASSES] == {"calls": 3, "self_s": 1.0 + 2.0 + 3.0}
    assert "Class2" not in rows and "Class3" in rows


def test_with_no_monitored_call_the_hooks_book_nothing(monkeypatch):
    assert ce._current_call() is None
    made = []
    monkeypatch.setattr(trace_parts, "Book",
                        lambda *a: made.append(a) or pytest.fail("a book"))
    for hook in (trace_parts.modules(), trace_parts.optimizer(),
                 trace_parts.kernel_build("planted_kernel")):
        assert isinstance(hook, contextlib.nullcontext)
    with trace_parts.modules():
        assert Outer().apply({}, jnp.zeros(())) == 2
    assert made == []


def _decoder_step(family, monkeypatch):
    """The family's train step at ``tools/lowered_steps.py``'s sizes with the
    kernels taken as on the chip, twice over, the state's shapes, a batch's,
    and the builds really made, counted where they are made: {name: times}."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend

    from bert_pytorch_tpu.ops import moe
    from bert_pytorch_tpu.ops.pallas import attention, common

    for module in (common, attention, moe):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    reached = {}
    real_call = pl.pallas_call

    def counting_call(kernel, *args, **kwargs):
        name = kwargs.get("name")
        if name in SITE_NAMES:
            reached[name] = reached.get(name, 0) + 1
        return real_call(kernel, *args, **kwargs)

    def counting(name, real):
        def call(*args, **kwargs):
            reached[name] = reached.get(name, 0) + 1
            return real(*args, **kwargs)
        return call

    monkeypatch.setattr(pl, "pallas_call", counting_call)
    monkeypatch.setattr(backend, "gmm", counting("gmm", backend.gmm))
    monkeypatch.setattr(backend, "tgmm", counting("tgmm", backend.tgmm))
    model = tool.family_model(family, "full", "pallas")
    tx = optim.adamw(1e-3, max_grad_norm=1.0,
                     weight_decay_mask=optim.no_decay_mask)
    state = jax.eval_shape(
        pretrain.make_init_fn(model, tx, (jnp.zeros((1, 16), jnp.int32),),
                              None), jax.random.PRNGKey(0))
    steps = [pretrain.make_train_step(model, tx, next_sentence=False)
             for _ in range(2)]
    reached.clear()     # the init program's forward pass reached some too
    return steps, state, {"input_ids": tool.ids(2, 1, tool.SEQ)}, reached


def _jaxpr(step, state, batch):
    """The traced step as text, less the addresses of function objects."""
    return re.sub(r" at 0x[0-9a-f]+", "", str(step.trace(state, batch).jaxpr))


# the names this repo's own ``pallas_call`` sites give their kernels in the
# two families below (megablox's inner calls bear others)
SITE_NAMES = {
    "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_window_fwd",
    "flash_window_bwd_dq", "flash_window_bwd_dkv", "flash_gated_fwd",
    "flash_gated_bwd_dq", "flash_gated_bwd_dkv", "rotary_turn",
    "delta_rule_fwd", "delta_rule_bwd", "gdn_mix_fwd", "gdn_mix_bwd",
    "gated_norm_fwd", "gated_norm_bwd"}


@pytest.fixture(scope="module")
def traced():
    """family -> (the record of its step traced through a monitor, the builds
    counted at the sites, hook entries of the first and of a second call)."""
    found = {}

    def trace(family):
        if family not in found:
            patch = pytest.MonkeyPatch()
            try:
                (step, other), state, batch, reached = _decoder_step(
                    family, patch)
                entered = []
                real_book = trace_parts._book
                patch.setattr(trace_parts, "_book",
                              lambda: entered.append(1) or real_book())
                records = []
                monitor = CompileMonitor(emit=records.append)
                fn = monitor.instrument(
                    lambda s, b: _jaxpr(step, s, b), "train_step")
                first = fn(state, batch)
                entries = len(entered)
                second = fn(state, batch)
                found[family] = dict(
                    records=records, reached=dict(reached), entries=entries,
                    entries_later=len(entered) - entries,
                    # JAX keeps a jitted function's trace, so it is a step
                    # built alike that is traced with no call on the stack
                    same=(first == second == _jaxpr(other, state, batch)))
            finally:
                patch.undo()
        return found[family]

    return trace


# a family whose kernels have no jitted entry point, and one whose mixer's
# kernels have (ops/delta_rule.py, ops/gdn_mix.py)
FAMILIES = {
    "laguna": ("GatedAttention",
               {"flash_fwd", "flash_window_fwd", "flash_window_bwd_dq",
                "rotary_turn", "gmm", "tgmm"}),
    "qwen3_next": ("GatedDeltaNet",
                   {"delta_rule_fwd", "delta_rule_bwd", "gdn_mix_fwd",
                    "gated_norm_bwd", "flash_gated_fwd", "gmm", "tgmm"}),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_decoder_step_says_what_its_trace_was_spent_on(traced, family):
    run = traced(family)
    attention_class, kernels = FAMILIES[family]
    [record] = run["records"]
    assert schema.validate_record({"schema": 1, "ts": 0.0, **record}) == []
    parts = record["trace_parts"]
    assert attention_class in parts["modules"]
    assert parts["modules"][attention_class]["calls"] >= 1
    assert kernels <= set(parts["kernels"])
    # one build a site reached, no more and no fewer
    assert {name: row["builds"] for name, row in parts["kernels"].items()} \
        == run["reached"]
    assert parts["optimizer_s"] > 0 and parts["outside_trace_s"] == 0
    assert record["trace_s"] > 0 and parts["other_s"] >= 0


def test_a_jitted_entry_point_is_built_once_a_shape(traced):
    """Three delta-rule layers share one build of the backward kernel and two
    of the forward one (the primal body's and the forward rule's); the
    rotary kernel, with no jitted entry point, is built at every call."""
    builds = {name: row["builds"] for name, row in
              traced("qwen3_next")["records"][0]["trace_parts"]
              ["kernels"].items()}
    assert (builds["delta_rule_fwd"], builds["delta_rule_bwd"]) == (2, 1)
    assert builds["rotary_turn"] == 6      # q and k, three times, one layer
    laguna = {name: row["builds"] for name, row in
              traced("laguna")["records"][0]["trace_parts"]["kernels"].items()}
    assert laguna["rotary_turn"] == 30     # five layers, six each


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_second_call_books_nothing_and_the_program_is_the_same(
        traced, family):
    run = traced(family)
    assert run["entries"] > 0
    assert run["entries_later"] == 0    # no hook was reached: nothing traced
    assert len(run["records"]) == 1     # and no second record
    # traced under the interceptor, and with no monitored call on the stack
    assert run["same"]


@pytest.mark.parametrize("planted, cache", [
    ("hit", "hit"), ("miss", "miss"), ("under_the_bar", "uncached"),
    ("nothing", "jit")])
def test_the_init_programs_record_says_what_the_cache_did(planted, cache):
    def init_fn(key):
        if planted == "hit":
            ce._on_event(ce._CACHE_HIT_EVENT)
            ce._on_duration(ce._CACHE_LOAD_EVENT, 0.5)
        elif planted == "miss":       # compiled, and written to the cache
            ce._on_duration(ce._BACKEND_COMPILE_EVENTS[0], 12.0)
            ce._on_event(ce._CACHE_MISS_EVENT)
        elif planted == "under_the_bar":   # compiled, under the bar: no write
            ce._on_duration(ce._BACKEND_COMPILE_EVENTS[0], 3.0)
        return key

    held = []
    out = CompileMonitor(emit=held.append).instrument(
        init_fn, "init_state")(jax.random.PRNGKey(0))
    assert out.shape == (2,)
    [record] = held
    assert (record["fn"], record["cache"]) == ("init_state", cache)
    assert set(record) >= {"trace_s", "lower_s", "backend_compile_s",
                           "cache_load_s", "trace_parts"}
    assert schema.validate_record({"schema": 1, "ts": 0.0, **record}) == []


class _ListSink:
    def __init__(self):
        self.records = []

    def write_record(self, record):
        self.records.append(record)

    def close(self):
        pass


def test_an_import_inside_the_first_call_is_named(tmp_path, monkeypatch):
    package = tmp_path / "planted_lazy_pkg"
    package.mkdir()
    (package / "__init__.py").write_text("from planted_lazy_pkg import sub\n")
    (package / "sub.py").write_text("VALUE = 1\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    sink = _ListSink()
    tele = TrainTelemetry(sink=sink, window=2)
    store = profiler.startup_open()
    try:
        for update, _ in enumerate(tele.timed(iter([1, 2])), 1):
            if update == 1:
                import planted_lazy_pkg  # noqa: F401  (the planted import)
            else:
                import planted_lazy_pkg.sub  # noqa: F401  (cached: nothing)
            tele.dispatch_done()
            if update == 1:
                tele.first_update_done(store)
    finally:
        store.close()
        for name in ("planted_lazy_pkg", "planted_lazy_pkg.sub"):
            sys.modules.pop(name, None)
    [record] = [r for r in sink.records if r.get("kind") == "startup"]
    assert record["imported_in_first_call"] == {
        "modules": 2, "packages": ["planted_lazy_pkg"]}
    assert 0 <= record["package_imported_s"]
    assert tele._modules_before is None     # two snapshots, once a run


def test_many_imports_are_counted_whole_and_named_up_to_twenty():
    before = {"a"}
    after = before | {f"pkg{i:02d}.mod" for i in range(30)} | {"pkg00"}
    found = profiler.imported_between(before, after)
    assert found["modules"] == 31
    assert found["packages"] == [f"pkg{i:02d}" for i in range(20)]


SOUND = {"schema": 1, "ts": 0.0, "kind": "compile", "fn": "train_step",
         "shapes_digest": "0", "compile_s": 9.0, "cache": "hit",
         "trace_s": 6.0, "trace_parts": {
             "modules": {"A": {"calls": 2, "self_s": 1.5}},
             "kernels": {"k": {"builds": 3, "build_s": 2.0}},
             "optimizer_s": 0.5, "other_s": 2.0, "outside_trace_s": 0.25}}


@pytest.mark.parametrize("fault, says", [
    ("sum", "add up"), ("negative", "negative"), ("count", "must hold"),
    ("table", "must hold"), ("no_trace_s", "must hold")])
def test_the_schema_refuses_parts_that_do_not_hold_together(fault, says):
    assert schema.validate_record(SOUND) == []
    record = copy.deepcopy(SOUND)
    parts = record["trace_parts"]
    if fault == "sum":
        parts["other_s"] = 3.0
    elif fault == "negative":
        parts["modules"]["A"]["self_s"] = -0.5
        parts["other_s"] = 4.0      # the sum still holds
    elif fault == "count":
        parts["kernels"]["k"]["builds"] = 0
    elif fault == "table":
        del parts["kernels"]
    elif fault == "no_trace_s":
        del record["trace_s"]
    errors = schema.validate_record(record)
    assert any(says in e for e in errors), errors
    cost = dict(copy.deepcopy(SOUND), kind="compile_cost", analysis="lowered")
    assert schema.validate_record(cost) == []
