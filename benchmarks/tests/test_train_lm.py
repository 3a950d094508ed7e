"""The ``train_lm`` kind, its generator, its FLOP counts and its readers, on
the CPU: the generator's rows, the scope rules of ``scopes_lm.json`` on op
names as the program writes them, the readers on a small synthetic trace (and
on none, and on a BERT trace: nothing to read, no raise), and how ``correct``
is decided at a size a test can hold: sound in float32, the control failing,
and three faults planted under the harness (the experts' terms left out, the
recurrence's decay dropped, the causal mask dropped) each coming out not
correct."""

import json
import os
import tempfile

import numpy as np
import pytest

import benchmarks.run as bench_run
from benchmarks.rehearse import cpu_cell_lm
from benchmarks.trace import flops_lm, reduce, scopes, scopes_lm
from benchmarks.traffic import generate_lm

ROOT = bench_run.ROOT
CELL = "train-nemotron-nano-seq8192"
LM_METRICS = (
    "ssm_device_ms.train", "ssd_scan_device_ms.train", "moe_device_ms.train",
    "moe_dispatch_device_ms.train", "moe_expert_mfu_pct.train",
    "causal_attention_device_ms.train", "flash_causal_roofline_pct.train",
    "lm_head_device_ms.train", "lm_unattributed_device_pct.train")


def _reader(name):
    return bench_run.load_module(
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"),
        "metric_" + name.replace(".", "_")).read


def _cell():
    return bench_run.context(ROOT, CELL)


# -- the generator ---------------------------------------------------------------

def test_rows_are_full_seeded_and_inside_the_slice():
    mix = dict(_cell()["mix"], seq_len=512, sequences=256)
    rows = generate_lm.make_rows(mix, 16384, 2 ** 31 + 5)
    assert rows.shape == (256, 512) and rows.dtype == np.int32
    assert rows.min() >= 0 and rows.max() < 16384
    np.testing.assert_array_equal(rows, generate_lm.make_rows(mix, 16384, 2 ** 31 + 5))
    assert (rows != generate_lm.make_rows(mix, 16384, 2 ** 31 + 6)).mean() > 0.9
    eod = mix["documents"]["eod_id"]
    ends = np.flatnonzero(rows.reshape(-1) == eod)
    lengths = np.diff(ends) - 1
    spec = mix["documents"]
    assert lengths.min() >= spec["min_tokens"] and lengths.max() <= spec["max_tokens"]
    # log-normal about the median: a third to three times it, mostly
    assert 300 < np.median(lengths) < 3000


def test_the_feed_check_knows_its_rows(tmp_path):
    mix = dict(_cell()["mix"], seq_len=64, sequences=16)
    known = generate_lm.write_shards(mix, 512, 9, str(tmp_path))
    rows = generate_lm.make_rows(mix, 512, 9)
    fed = rows[:8].reshape(4, 2, 64)
    assert generate_lm.check_fed_rows(fed, known, 512) == []
    foreign = fed.copy()
    foreign[0, 0, 3] += 1
    assert "not one of the generated rows" in generate_lm.check_fed_rows(
        foreign, known, 512)[0]
    twice = np.concatenate([fed[:1], fed[:1]])
    assert any("repeats" in f for f in generate_lm.check_fed_rows(twice, known, 512))


# -- FLOPs -----------------------------------------------------------------------

def test_model_flops_are_the_issues_arithmetic():
    ctx = _cell()
    parts = flops_lm.forward_flops_per_token(ctx["config"], 8192)
    total = sum(parts.values())
    assert total == pytest.approx(0.7176e9, rel=1e-3)
    shares = {k: round(100 * v / total) for k, v in parts.items()}
    assert shares == {"ssm": 45, "experts": 27, "attention": 16, "head": 12}
    assert flops_lm.train_flops_per_update(ctx["config"], ctx["mix"], 1) == (
        pytest.approx(3 * 32768 * total))
    # the program's own copy agrees (it may drift later; the yardstick may not)
    from bert_pytorch_tpu.config import load_model_config
    from bert_pytorch_tpu.utils import flops as program_flops
    assert program_flops.nemotron_h_train_flops_per_seq(
        load_model_config(ctx["config_file"]), 8192) == pytest.approx(
            3 * 8192 * total)
    work, traffic = flops_lm.flash_causal_call(ctx["config"], ctx["mix"], "flash_fwd")
    assert work == pytest.approx(2 * 2 * 128 * 8192 * 8193 / 2 * 32)
    assert work / 197e12 > traffic / 819e9  # compute-bound at this length


# -- the rules -------------------------------------------------------------------

SCAN = "jit(step_fn)/micro_batches/while/body/closed_call/"
FWD = SCAN + "jvp(NemotronHForCausalLM)/"
BWD = SCAN + "transpose(jvp(NemotronHForCausalLM))/"


@pytest.mark.parametrize("op_name,instruction,expected", [
    (FWD + "layers_0/checkpoint/layers_0/mixer/ssm_mixer/ssd_scan/dot_general",
     "%fusion.3", ("forward", "ssd_scan")),
    (BWD + "layers_0/checkpoint/rematted_computation/layers_0/mixer/ssm_mixer/"
     "ssm_in_proj/in_proj/dot_general", "%fusion.9", ("recompute", "ssm_in_proj")),
    (BWD + "layers_2/checkpoint/layers_2/mixer/ssm_mixer/ssm_gate_norm/mul",
     "%fusion.10", ("backward", "ssm_gate_norm")),
    (FWD + "layers_2/checkpoint/layers_2/mixer/ssm_mixer/split", "%slice.4",
     ("forward", "ssm_other")),
    (FWD + "layers_1/checkpoint/layers_1/mixer/moe/moe_experts/ragged_dot",
     "%ragged-dot-none.2", ("forward", "moe_experts")),
    (BWD + "layers_1/checkpoint/layers_1/mixer/moe/moe_combine/while/body/"
     "scatter-add", "%fusion.77", ("backward", "moe_combine")),
    (FWD + "layers_1/checkpoint/layers_1/mixer/moe/moe_shared/shared_up/"
     "dot_general", "%fusion.5", ("forward", "moe_shared")),
    (FWD + "layers_1/checkpoint/layers_1/mixer/moe/moe_route/top_k", "%sort.1",
     ("forward", "moe_route")),
    (BWD + "layers_5/checkpoint/rematted_computation/layers_5/mixer/"
     "attention_core/flash_fwd/pallas_call", "%flash_fwd.3",
     ("recompute", "attention_core")),
    (FWD + "layers_5/checkpoint/layers_5/mixer/q_proj/dot_general", "%fusion.8",
     ("forward", "attention_proj")),
    (FWD + "layers_3/checkpoint/layers_3/norm/mul", "%fusion.2",
     ("forward", "norm")),
    (BWD + "while/body/checkpoint/lm_head/dot_general", "%fusion.1",
     ("backward", "lm_head")),
    (FWD + "while/body/checkpoint/lm_loss/reduce_max", "%fusion.6",
     ("forward", "lm_loss")),
    ("jit(step_fn)/optimizer/clip/mul", "%fusion.40", ("optimizer", "optimizer")),
    (SCAN + "grad_accumulate/add", "%fusion.41", ("other", "accumulate")),
    (None, "%copy.3", ("other", "unnamed_copies")),
    (None, "%while.3", ("other", None)),
])
def test_pass_and_part_rules_of_the_family(op_name, instruction, expected):
    assert scopes.classify(op_name, instruction, scopes_lm.rules()) == expected


def test_the_rules_name_only_scopes_the_program_writes():
    from bert_pytorch_tpu import pretrain

    table = scopes_lm.rules()
    named = {f for rule in table["part"] for f in rule["fragments"]}
    scope_like = {f for f in named if f.replace("_", "").isalpha()}
    written = set(pretrain.SCOPES) | set(pretrain.CAUSAL_LM_SCOPES)
    # beside the scopes: a module name and the kernels' kinds
    assert scope_like <= written | {"final_norm", "flash_", "gmm", "tgmm"}
    assert set(table["kernels"]) == set(flops_lm.FLASH_MATMULS)


# -- the readers -------------------------------------------------------------------

def _family_planes():
    ms = 1e6
    ops = [
        ["%fusion.1", 0.0, 4 * ms, FWD + "layers_0/mixer/ssm_mixer/ssd_scan/dot"],
        ["%fusion.2", 4 * ms, 2 * ms,
         FWD + "layers_0/mixer/ssm_mixer/ssm_in_proj/in_proj/dot_general"],
        ["%ragged-dot-none.1", 6 * ms, 2 * ms,
         FWD + "layers_1/mixer/moe/moe_experts/ragged_dot"],
        ["%fusion.3", 8 * ms, 1 * ms, FWD + "layers_1/mixer/moe/moe_route/top_k"],
        ["%fusion.4", 9 * ms, 1 * ms,
         FWD + "layers_1/mixer/moe/moe_shared/shared_up/dot_general"],
        ["%flash_fwd.1", 10 * ms, 6 * ms,
         FWD + "layers_5/mixer/attention_core/flash_fwd/pallas_call"],
        ["%fusion.5", 16 * ms, 3 * ms, FWD + "while/body/checkpoint/lm_head/dot"],
        ["%copy.1", 19 * ms, 1 * ms, None],
    ]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": reduce.OPS_LINE, "events": ops}]}]


@pytest.fixture
def traced(monkeypatch):
    def with_planes(planes):
        monkeypatch.setattr(scopes_lm, "_reductions", {})
        monkeypatch.setattr(scopes, "newest_trace", lambda under=None: "a.xplane.pb")
        monkeypatch.setattr(scopes, "read_xspace", lambda path: planes)
        ctx = _cell()
        return {"summary": {"busy_s": 20e-3}, "updates": 2, "chips": 1,
                "peak_flops": 197e12, "device_kind": "TPU v5 lite",
                "config": ctx["config"], "mix": ctx["mix"],
                "counters": {"moe_local_slots": 49152.0}, "trace_dir": "x"}
    return with_planes


def test_readers_on_a_small_trace_of_the_family(traced):
    ctx = traced(_family_planes())
    read = lambda name: _reader(name)(ctx)
    assert read("ssm_device_ms.train") == pytest.approx(3.0)
    assert read("ssd_scan_device_ms.train") == pytest.approx(2.0)
    assert read("moe_device_ms.train") == pytest.approx(2.0)
    assert read("moe_dispatch_device_ms.train") == pytest.approx(0.5)
    assert read("causal_attention_device_ms.train") == pytest.approx(3.0)
    assert read("lm_head_device_ms.train") == pytest.approx(1.5)
    assert read("lm_unattributed_device_pct.train") == pytest.approx(0.0)
    # 49152 slots an update: 3 x 4 x 2688 x 1856 x 49152 FLOPs in 1 ms
    want = 100 * 3 * 4 * 2688 * 1856 * 49152 / (1e-3 * 197e12)
    assert read("moe_expert_mfu_pct.train") == pytest.approx(want)
    # one forward call of 550 GFLOP in 6 ms
    work, _ = flops_lm.flash_causal_call(ctx["config"], ctx["mix"], "flash_fwd")
    assert read("flash_causal_roofline_pct.train") == pytest.approx(
        100 * work / 197e12 / 6e-3)
    assert read("flash_causal_roofline_pct.train") < 100


def test_a_trace_without_the_family_gives_nothing_and_does_not_raise(traced):
    bert = [{"name": "/device:TPU:0", "lines": [{"name": reduce.OPS_LINE, "events": [
        ["%fusion.1", 0.0, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(BertForPreTraining)/bert/encoder/layers/output/dot_general"],
        ["%fusion.3", 5e6, 2e6, "jit(step_fn)/optimizer/lamb/mul"]]}]}]
    ctx = traced(bert)
    assert [_reader(name)(ctx) for name in LM_METRICS] == [None] * len(LM_METRICS)


@pytest.mark.parametrize("name", LM_METRICS)
def test_every_reader_returns_none_on_an_empty_context(name):
    assert _reader(name)({}) is None


def test_every_new_metric_has_its_entry_for_the_new_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in LM_METRICS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "train_tokens_per_s"
    assert entries["collective_exposed_ms.train"]["workloads"] == [
        "train-large-phase1-dp4"]
    readers = _cell()["readers"]
    assert set(LM_METRICS) <= set(readers)
    assert {"data_wait_ms.train", "host_dispatch_ms.train", "device_step_ms.train",
            "step_mfu_pct.train", "device_idle_pct.train"} <= set(readers)


# -- correct -----------------------------------------------------------------------

FLOAT32_LIMITS = {"loss_gap_first": 1e-4, "loss_gap_later": 1e-4,
                  "grad_global_norm_gap": 1e-3, "grad_norm_gap_worst_leaf": 1e-3,
                  "head_grad_rel_diff": 1e-3, "all_grad_rel_diff": 1e-3,
                  "delta_norm_gap_worst_leaf": 2e-2, "feed_faults": 0}


def _plant(monkeypatch, fault):
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import nemotron_h
    from bert_pytorch_tpu.ops import moe, ssm

    if fault == "experts_left_out":
        real = moe.held_experts
        monkeypatch.setattr(moe, "held_experts", lambda *a, **k: (
            lambda out: (jnp.zeros_like(out[0]), out[1]))(real(*a, **k)))
    elif fault == "decay_dropped":
        real = ssm.ssd_chunked_scan
        monkeypatch.setattr(ssm, "ssd_chunked_scan", lambda x, dt, a, *rest:
                            real(x, dt, jnp.zeros_like(a), *rest))
    elif fault == "causal_mask_dropped":
        real = nemotron_h.dot_product_attention
        monkeypatch.setattr(
            nemotron_h, "dot_product_attention",
            lambda *a, causal=True, **k: real(*a, causal=False, **k))


def _tiny_run(monkeypatch=None, fault=None, controls=()):
    tmp = tempfile.mkdtemp()
    ctx = cpu_cell_lm.tiny_context(CELL, 2 ** 31 + 77, 0.3, tmp)
    ctx["mix"]["trainer_args"] = ["--dtype", "float32", "--remat", "full"]
    ctx["mix"]["check"] = dict(ctx["mix"]["check"], limits=FLOAT32_LIMITS)
    ctx["controls"] = list(controls)
    if fault:
        _plant(monkeypatch, fault)
    kind = bench_run.load_module(ctx["kind_file"], "kind_under_test")
    return kind.measure(ctx)


def test_sound_in_float32_and_the_control_fails():
    result = _tiny_run(controls=["fp8"])
    assert result["correct"] is True and result["failed"] == 0
    assert result["dropped_slots"] == 0 and result["compiles_in_window"] == 0
    assert result["counters"]["moe_local_slots"] > 0
    assert result["readings"]["routing_flip_share"] < 0.01
    control = result["controls"]["fp8"]
    assert any(control[k] > FLOAT32_LIMITS[k] for k in control)
    assert control["head_grad_rel_diff"] > 10 * result["readings"]["head_grad_rel_diff"]


@pytest.mark.parametrize("fault", ["experts_left_out", "decay_dropped",
                                   "causal_mask_dropped"])
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    assert _tiny_run(monkeypatch, fault)["correct"] is False


def test_a_program_without_the_family_is_told_so_at_once(monkeypatch):
    from bert_pytorch_tpu import config as program_config

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    monkeypatch.delattr(program_config, "MODEL_FAMILIES")
    with pytest.raises(SystemExit, match="unknown model_type 'nemotron_h'"):
        kind.run(dict(_cell(), seed=1, seconds=1.0, trace=False, started=0.0))


# -- the rules on what the chip really wrote -----------------------------------------

def test_the_recorded_chip_ops_fall_where_the_rules_say():
    """The heaviest device ops of a traced run on the chip, by name: the rules
    place all but a few percent, every family part is there, and the grouped
    products' kernels (which keep no scope of their own on some builds) land
    under ``moe_experts``."""
    with open(os.path.join(os.path.dirname(__file__),
                           "recorded_scopes_lm.json")) as f:
        recorded = json.load(f)
    table, parts, passes = scopes_lm.rules(), {}, {}
    for op_name, kind, ms in recorded["ops"]:
        op_pass, part = scopes.classify(op_name, kind, table)
        parts[part or "unattributed"] = parts.get(part or "unattributed", 0) + ms
        passes[op_pass] = passes.get(op_pass, 0) + ms
    seen = sum(parts.values())
    assert seen > 0.9 * recorded["total_ms"]
    assert parts.get("unattributed", 0) < 0.01 * seen
    assert 0.03 * seen < parts["unnamed_copies"] < 0.06 * seen
    for part in ("ssd_scan", "ssm_in_proj", "ssm_conv", "ssm_gate_norm",
                 "ssm_out_proj", "moe_route", "moe_dispatch", "moe_experts",
                 "moe_combine", "moe_shared", "attention_core",
                 "attention_proj", "lm_head", "lm_loss", "norm", "optimizer",
                 "accumulate"):
        assert parts.get(part, 0) > 0, part
    assert set(passes) <= set(scopes.PASSES)
    assert all(passes[p] > 0 for p in ("forward", "backward", "recompute",
                                       "optimizer"))
    # the state-space mixers are the largest share, as by FLOPs
    ssm = sum(v for k, v in parts.items() if k.startswith(("ssm_", "ssd_")))
    assert ssm == max(ssm, parts["moe_shared"] + parts["moe_experts"],
                      parts["attention_core"], parts["lm_head"])
