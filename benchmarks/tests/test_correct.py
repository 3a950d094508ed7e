"""How ``correct`` is decided, at a size a test run can hold (bert_small's
widths, 2 layers, a few rows, on the CPU): the reference against the program
in float32; the control (the reference in the precision below, in the
program's place) fails; a run whose timed path is broken underneath comes out
not correct. Limits here are fitted to float32, not the cells' limits."""

import json
import tempfile

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.reference import bert_f32, compare
from benchmarks.rehearse import cpu_cell

FLOAT32_LIMITS = {"loss_gap_first": 1e-4, "loss_gap_later": 1e-4,
                  "grad_global_norm_gap": 1e-4, "grad_norm_gap_worst_leaf": 1e-4,
                  "head_grad_rel_diff": 1e-4, "all_grad_rel_diff": 1e-4,
                  "delta_norm_gap_worst_leaf": 1e-3, "feed_faults": 0}


def _broken_step_maker(fault):
    """``pretrain.make_train_step`` with one fault planted under the harness:
    every step it builds (the timed one and the check's) carries it."""
    import jax
    from bert_pytorch_tpu import pretrain

    real = pretrain.make_train_step

    def make_broken(*args, **kwargs):
        step = real(*args, **kwargs)

        def stuck(state, batch):  # returns its state unchanged: never learns
            kept = jax.tree_util.tree_map(lambda a: a + 0, state.params)
            new_state, metrics = step(state, batch)
            return new_state.replace(params=kept), metrics

        def wrong_rate(state, batch):  # the update is half as large again
            start = jax.tree_util.tree_map(lambda a: a + 0, state.params)
            new_state, metrics = step(state, batch)
            moved = jax.tree_util.tree_map(
                lambda a, b: a + 1.5 * (b - a), start, new_state.params)
            return new_state.replace(params=moved), metrics

        def dropped_micro_batch(state, batch):  # the last one never arrives
            return step(state, jax.tree_util.tree_map(
                lambda a: a.at[-1].set(a[0]), batch))

        broken = {"state_unchanged": stuck, "update_half_as_large_again": wrong_rate,
                  "micro_batch_dropped": dropped_micro_batch}[fault]
        broken.lower = step.lower
        return broken

    return make_broken


def _tiny_run(monkeypatch=None, fault=None, limits=FLOAT32_LIMITS,
              seed=2 ** 31 + 77):
    tmp = tempfile.mkdtemp()
    ctx = cpu_cell.tiny_context("train-large-phase1", seed, 0.3, tmp)
    ctx["mix"]["trainer_args"] = ["--dtype", "float32"]
    if limits is not None:  # None: the cell's own limits
        ctx["mix"]["check"] = dict(ctx["mix"]["check"], limits=limits)
    ctx["controls"] = ["fp8"]
    if fault:
        from bert_pytorch_tpu import pretrain
        monkeypatch.setattr(pretrain, "make_train_step", _broken_step_maker(fault))
    kind = bench_run.load_module(ctx["kind_file"], "kind_under_test")
    return kind.measure(ctx)


@pytest.fixture(scope="module")
def sound():
    return _tiny_run()


def test_reference_agrees_with_the_program_in_float32(sound):
    assert sound["correct"] is True
    readings = sound["readings"]
    assert readings["loss_gap_first"] < 1e-5 and readings["loss_gap_later"] < 1e-5
    assert readings["head_grad_rel_diff"] < 1e-5
    assert readings["all_grad_rel_diff"] < 1e-5
    assert readings["delta_norm_gap_worst_leaf"] < 1e-4
    assert readings["grad_global_norm_gap"] < 1e-5
    assert readings["grad_norm_gap_worst_leaf"] < 1e-5
    assert readings["feed_faults"] == 0
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    assert sound["compiles_in_window"] == 0
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    json.dumps(sound)  # the result line is plain JSON


def test_the_control_in_the_precision_below_fails(sound):
    control = dict(sound["controls"]["fp8"], feed_faults=0.0)
    correct, lines = compare.judge(control, FLOAT32_LIMITS)
    assert correct is False and any("FAILS" in line for line in lines)
    assert control["head_grad_rel_diff"] > 1e-3  # first order in the error
    assert control["head_grad_rel_diff"] > \
        100 * sound["readings"]["head_grad_rel_diff"]


def test_the_window_times_dropout_and_the_check_runs_without(sound):
    assert sound["dropout"] == {"timed": [0.1, 0.1], "check": [0.0, 0.0]}


@pytest.mark.parametrize("fault, number", [
    ("state_unchanged", "delta_norm_gap_worst_leaf"),
    ("update_half_as_large_again", "delta_norm_gap_worst_leaf"),
    ("micro_batch_dropped", "loss_gap_first"),
])
def test_a_planted_fault_is_not_correct_at_the_cells_own_limits(
        monkeypatch, fault, number):
    """The numbers a lower precision hardly moves are each held against a
    fault; the limits are the cell's, not ones fitted to float32."""
    limits = bench_run.context(bench_run.ROOT, "train-large-phase1")[
        "mix"]["check"]["limits"]
    broken = _tiny_run(monkeypatch, fault=fault, limits=None)
    assert broken["correct"] is False
    assert broken["readings"][number] > limits[number]


def test_a_sound_run_passes_at_the_cells_own_limits():
    assert _tiny_run(limits=None)["correct"] is True


def test_a_compile_inside_the_window_fails_the_run(monkeypatch):
    kind = bench_run.load_module(
        bench_run.context(bench_run.ROOT, "train-large-phase1")["kind_file"],
        "kind_compiles")
    real = kind.Probes.on_step

    def on_step(self, *args):
        if self.window_open:
            self.on_compile("/jax/core/compile/backend_compile_duration")
        return real(self, *args)

    monkeypatch.setattr(kind.Probes, "on_step", on_step)
    tmp = tempfile.mkdtemp()
    ctx = cpu_cell.tiny_context("train-large-phase1", 2 ** 31 + 78, 0.3, tmp)
    with pytest.raises(RuntimeError, match="compiled inside the window"):
        kind.measure(ctx)


def test_identical_readings_pass_and_each_number_has_its_limit():
    norms = {"a": np.array([1.0, 2.0]), "b": np.array(3.0)}
    head = {name: np.array(1.0) for name in compare.HEAD}
    same = {"loss": [1.0, 2.0], "grad_global_norm": 4.0,
            "grad_norms": dict(norms, **head), "delta_norms": dict(norms, **head),
            "grad_diff_norms": {k: 0.0 * v for k, v in dict(norms, **head).items()}}
    numbers = dict(compare.numbers(same, same), feed_faults=0.0)
    assert compare.judge(numbers, FLOAT32_LIMITS)[0] is True
    assert all(v == 0 for v in numbers.values())
    with pytest.raises(KeyError):
        compare.judge(numbers, {"loss_gap_first": 1.0})
    nan = dict(numbers, loss_gap_first=float("nan"))
    assert compare.judge(nan, FLOAT32_LIMITS)[0] is False


def test_seeds_past_31_bits_give_distinct_weights():
    c = bert_f32.sizes({"vocab_size": 64, "hidden_size": 8, "num_hidden_layers": 1,
                        "num_attention_heads": 2, "intermediate_size": 16,
                        "max_position_embeddings": 8, "type_vocab_size": 2,
                        "initializer_range": 0.02})
    a = bert_f32.seeded_params(bert_f32.key_from_seed(2 ** 31 + 5), c)
    b = bert_f32.seeded_params(bert_f32.key_from_seed(2 ** 31 + 5), c)
    other = bert_f32.seeded_params(bert_f32.key_from_seed(5), c)
    assert (np.asarray(a["word_emb"]) == np.asarray(b["word_emb"])).all()
    assert (np.asarray(a["word_emb"]) != np.asarray(other["word_emb"])).any()


def test_published_lamb_takes_one_trust_ratio_per_layer_and_the_program_one_per_stack():
    """The departure ``Recipe.stacked_trust_ratio`` names: with layers whose
    weights differ in size, published LAMB moves each layer by lr * its own
    norm; one ratio for the stack moves the stack by lr * the stack's norm."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    start = (rng.normal(size=(2, 4, 4)) * [[[1.0]], [[5.0]]]).astype(np.float32)
    g = {"layer.wq": jnp.asarray(rng.normal(size=(2, 4, 4)), jnp.float32) * 1e-3}
    norms = {}
    for stacked in (False, True):
        recipe = bert_f32.Recipe(1.0, 0.5, 10, weight_decay=0.0,
                                 stacked_trust_ratio=stacked)
        fresh = lambda a: {"layer.wq": jnp.array(a)}  # the update donates them
        new_p, *_ = bert_f32.make_lamb_update(recipe)(
            fresh(start), fresh(0 * start), fresh(0 * start), g, 0.1, 1.0)
        change = np.asarray(new_p["layer.wq"]) - start
        norms[stacked] = np.sqrt((change ** 2).sum(axis=(1, 2)))
    weights = np.sqrt((start ** 2).sum(axis=(1, 2)))
    np.testing.assert_allclose(norms[False], 0.1 * weights, rtol=1e-4)
    np.testing.assert_allclose(np.sqrt((norms[True] ** 2).sum()),
                               0.1 * np.sqrt((weights ** 2).sum()), rtol=1e-4)
    assert abs(norms[True][0] / norms[False][0] - 1) > 0.5
