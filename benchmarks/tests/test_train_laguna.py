"""The ``train_laguna`` kind, its FLOP counts, its rules and its readers, on
the CPU: the counts against the issue's arithmetic (the windowed kernels' over
the BAND), the scope rules of ``scopes_laguna.json`` on op names as the program
writes them, the readers on a small synthetic trace (and on none, and on the
other decoder's trace: nothing to read, no raise), and how ``correct`` is
decided at a size a test can hold: sound in float32, the fp8 control failing,
and four faults planted under the harness (the window dropped, the rotary
table dropped, the gate dropped, the gated product's ``up`` left out) each
coming out not correct."""

import json
import os
import tempfile

import pytest

import benchmarks.run as bench_run
from benchmarks.rehearse import cpu_cell_laguna
from benchmarks.trace import flops_laguna, reduce, scopes, scopes_laguna

ROOT = bench_run.ROOT
CELL = "train-laguna-s-seq8192"
NEW_METRICS = (
    "window_attention_device_ms.train", "full_attention_device_ms.train",
    "flash_window_roofline_pct.train", "attention_proj_device_ms.train",
    "dense_mlp_device_ms.train", "glu_expert_mfu_pct.train",
    "laguna_unattributed_device_pct.train")
SHARED_METRICS = (
    "fwd_device_ms.train", "bwd_device_ms.train", "recompute_device_ms.train",
    "optimizer_device_ms.train", "sync_idle_ms.train",
    "loop_work_idle_ms.train", "moe_device_ms.train",
    "moe_dispatch_device_ms.train", "lm_head_device_ms.train")


def _reader(name):
    return bench_run.load_module(
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"),
        "metric_" + name.replace(".", "_")).read


def _cell():
    return bench_run.context(ROOT, CELL)


# -- the entries -------------------------------------------------------------------

def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["moves"] == "train_tokens_per_s"
    for name in SHARED_METRICS:
        assert entries[name]["workloads"][-1] == CELL
    for name in ("moe_expert_mfu_pct.train", "causal_attention_device_ms.train",
                 "flash_causal_roofline_pct.train", "ssm_device_ms.train",
                 "lm_unattributed_device_pct.train"):  # need an edit to read it
        assert CELL not in entries[name]["workloads"]
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["chips"], cell["traffic"]) == (
        CELL, 1, "lm-seq8192-laguna")
    config = bench["configs"][-1]
    assert config["name"] == cell["config"] == "laguna-s-2.1"
    ctx = _cell()
    assert ctx["mix"]["kind"] == "train_laguna"
    assert ctx["config"]["model_type"] == "laguna"
    assert set(config["reduced"]) == set(ctx["config"]["reduced"])
    assert set(NEW_METRICS) | set(SHARED_METRICS) <= set(ctx["readers"])
    assert {"data_wait_ms.train", "host_dispatch_ms.train", "device_step_ms.train",
            "step_mfu_pct.train", "device_idle_pct.train"} <= set(ctx["readers"])
    # every width is the published one
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(json.loads(line) for line in f
                         if '"Laguna-S-2.1"' in line)["config"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert ctx["config"][key] == value, key


# -- FLOPs -----------------------------------------------------------------------

def test_model_flops_are_the_issues_arithmetic():
    ctx = _cell()
    parts = {k: v / 1e6 for k, v in
             flops_laguna.forward_flops_per_token(ctx["config"], 8192).items()}
    assert parts["attention_proj"] == pytest.approx(277.8, abs=0.1)
    assert parts["attention_full"] == pytest.approx(100.7, abs=0.1)
    assert parts["attention_window"] == pytest.approx(27.4, abs=0.1)
    assert parts["dense_mlp"] == pytest.approx(226.5, abs=0.1)
    assert parts["experts"] == pytest.approx(105.4, abs=0.1)
    assert parts["head"] == pytest.approx(77.1, abs=0.1)
    total = sum(parts.values())
    assert total == pytest.approx(816, rel=2e-3)
    assert flops_laguna.train_flops_per_update(ctx["config"], ctx["mix"], 1) == (
        pytest.approx(3 * 32768 * total * 1e6))
    # the program's own copy agrees (it may drift later; the yardstick may not)
    from bert_pytorch_tpu.config import load_model_config
    from bert_pytorch_tpu.utils import flops as program_flops
    assert program_flops.causal_lm_train_flops_per_seq(
        load_model_config(ctx["config_file"]), 8192) == pytest.approx(
            3 * 8192 * total * 1e6)


def test_a_windowed_call_counts_the_band_not_the_triangle():
    ctx = _cell()
    assert flops_laguna.band_pairs(8192, None) == 8192 * 8193 / 2
    assert flops_laguna.band_pairs(8192, 512) == 512 * 8192 - 512 * 511 / 2
    assert flops_laguna.band_pairs(8, 3) == 1 + 2 + 3 * 6
    work, traffic = flops_laguna.flash_window_call(
        ctx["config"], ctx["mix"], "flash_window_fwd")
    assert work == pytest.approx(2 * 2 * 128 * (512 * 8192 - 512 * 511 / 2) * 36)
    assert traffic == 4 * 36 * 8192 * 128 * 2
    assert work < 0.13 * 2 * 2 * 128 * (8192 * 8193 / 2) * 36  # an eighth of it
    # at a window of one tile the call is as near memory-bound as compute-bound
    assert 0.5 < (work / 197e12) / (traffic / 819e9) < 2
    dkv, _ = flops_laguna.flash_window_call(
        ctx["config"], ctx["mix"], "flash_window_bwd_dkv")
    assert dkv == 2 * work
    assert flops_laguna.routed_expert_train_flops(ctx["config"], 1000.0) == (
        3 * 6 * 3072 * 1024 * 1000.0)


# -- the rules -------------------------------------------------------------------

SCAN = "jit(step_fn)/micro_batches/while/body/closed_call/"
FWD = SCAN + "jvp(LagunaForCausalLM)/"
BWD = SCAN + "transpose(jvp(LagunaForCausalLM))/"


@pytest.mark.parametrize("op_name,instruction,expected", [
    (FWD + "layers_1/checkpoint/layers_1/attn/attention_core/flash_window_fwd/"
     "pallas_call", "%flash_window_fwd.3", ("forward", "window_attention")),
    (BWD + "layers_1/checkpoint/layers_1/attn/attention_core/"
     "flash_window_bwd_dkv/pallas_call", "%flash_window_bwd_dkv.1",
     ("backward", "window_attention")),
    (BWD + "layers_4/checkpoint/rematted_computation/layers_4/attn/"
     "attention_core/flash_fwd/pallas_call", "%flash_fwd.3",
     ("recompute", "full_attention")),
    (BWD + "layers_0/checkpoint/layers_0/attn/attention_core/flash_bwd_dq/"
     "pallas_call", "%flash_bwd_dq.2", ("backward", "full_attention")),
    (FWD + "layers_2/checkpoint/layers_2/attn/attention_core/transpose",
     "%fusion.7", ("forward", "attention_core")),
    (FWD + "layers_2/checkpoint/layers_2/attn/attn_qkv/q_proj/dot_general",
     "%fusion.8", ("forward", "attn_qkv")),
    (FWD + "layers_2/checkpoint/layers_2/attn/attn_rope/mul", "%fusion.9",
     ("forward", "attn_rope")),
    (BWD + "layers_2/checkpoint/layers_2/attn/attn_gate/g_proj/dot_general",
     "%fusion.10", ("backward", "attn_gate")),
    (FWD + "layers_2/checkpoint/layers_2/attn/attn_out/o_proj/dot_general",
     "%fusion.11", ("forward", "attn_out")),
    (FWD + "layers_0/checkpoint/layers_0/mlp/dense_mlp/gate_up_proj/"
     "dot_general", "%fusion.12", ("forward", "dense_mlp")),
    (FWD + "layers_1/checkpoint/layers_1/mlp/moe/moe_experts/while/body/gmm",
     "%gmm.2", ("forward", "moe_experts")),
    (FWD + "layers_1/checkpoint/layers_1/mlp/moe/moe_shared/shared_up/"
     "dot_general", "%fusion.5", ("forward", "moe_shared")),
    (FWD + "layers_1/checkpoint/layers_1/mlp/moe/moe_route/top_k", "%sort.1",
     ("forward", "moe_route")),
    (FWD + "layers_3/checkpoint/layers_3/mlp_norm/mul", "%fusion.2",
     ("forward", "norm")),
    (FWD + "layers_3/checkpoint/layers_3/attn_norm/mul", "%fusion.2",
     ("forward", "norm")),
    (BWD + "while/body/checkpoint/lm_head/dot_general", "%fusion.1",
     ("backward", "lm_head")),
    ("jit(step_fn)/optimizer/clip/mul", "%fusion.40", ("optimizer", "optimizer")),
    (SCAN + "grad_accumulate/add", "%fusion.41", ("other", "accumulate")),
    (None, "%copy.3", ("other", "unnamed_copies")),
    (None, "%while.3", ("other", None)),
])
def test_pass_and_part_rules_of_the_family(op_name, instruction, expected):
    assert scopes.classify(op_name, instruction, scopes_laguna.rules()) == expected


def test_the_rules_name_only_scopes_the_program_writes():
    from bert_pytorch_tpu import pretrain

    table = scopes_laguna.rules()
    named = {f for rule in table["part"] for f in rule["fragments"]}
    scope_like = {f for f in named if f.replace("_", "").isalpha()}
    written = set(pretrain.SCOPES) | set(pretrain.LAGUNA_SCOPES)
    # beside the scopes: module names and the kernels' kinds
    assert scope_like <= written | {
        "attn_norm", "mlp_norm", "final_norm", "flash_window_", "flash_fwd",
        "flash_bwd_", "gmm", "tgmm"}
    assert set(table["kernels"]) == (set(flops_laguna.WINDOW_KERNELS)
                                     | set(flops_laguna.WINDOW_KERNELS.values()))


# -- the readers -------------------------------------------------------------------

def _family_planes():
    ms = 1e6
    ops = [
        ["%flash_window_fwd.1", 0.0, 2 * ms,
         FWD + "layers_1/attn/attention_core/flash_window_fwd/pallas_call"],
        ["%flash_fwd.1", 2 * ms, 6 * ms,
         FWD + "layers_0/attn/attention_core/flash_fwd/pallas_call"],
        ["%fusion.1", 8 * ms, 3 * ms, FWD + "layers_1/attn/attn_qkv/q_proj/dot"],
        ["%fusion.2", 11 * ms, 1 * ms, FWD + "layers_1/attn/attn_rope/mul"],
        ["%fusion.3", 12 * ms, 1 * ms, FWD + "layers_1/attn/attn_gate/mul"],
        ["%fusion.4", 13 * ms, 1 * ms, FWD + "layers_1/attn/attn_out/o_proj/dot"],
        ["%fusion.5", 14 * ms, 4 * ms, FWD + "layers_0/mlp/dense_mlp/dot"],
        ["%gmm.1", 18 * ms, 1 * ms, FWD + "layers_1/mlp/moe/moe_experts/gmm"],
        ["%fusion.6", 19 * ms, 1 * ms, FWD + "layers_1/mlp/moe/moe_route/top_k"],
        ["%while.1", 20 * ms, 1 * ms, None],
    ]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": reduce.OPS_LINE, "events": ops}]}]


@pytest.fixture
def traced(monkeypatch):
    def with_planes(planes):
        from benchmarks.trace import scopes_lm

        monkeypatch.setattr(scopes_laguna, "_reductions", {})
        monkeypatch.setattr(scopes_lm, "_reductions", {})
        monkeypatch.setattr(scopes, "newest_trace", lambda under=None: "a.xplane.pb")
        monkeypatch.setattr(scopes, "read_xspace", lambda path: planes)
        ctx = _cell()
        return {"summary": {"busy_s": 21e-3}, "updates": 2, "chips": 1,
                "peak_flops": 197e12, "device_kind": "TPU v5 lite",
                "config": ctx["config"], "mix": ctx["mix"],
                "counters": {"moe_local_slots": 10240.0}, "trace_dir": "x"}
    return with_planes


def test_readers_on_a_small_trace_of_the_family(traced):
    ctx = traced(_family_planes())
    read = lambda name: _reader(name)(ctx)
    assert read("window_attention_device_ms.train") == pytest.approx(1.0)
    assert read("full_attention_device_ms.train") == pytest.approx(3.0)
    assert read("attention_proj_device_ms.train") == pytest.approx(3.0)
    assert read("dense_mlp_device_ms.train") == pytest.approx(2.0)
    assert read("laguna_unattributed_device_pct.train") == pytest.approx(
        100 * 1 / 21)
    # 10240 slots an update: 3 x 6 x 3072 x 1024 x 10240 FLOPs in 0.5 ms
    want = 100 * 3 * 6 * 3072 * 1024 * 10240 / (0.5e-3 * 197e12)
    assert read("glu_expert_mfu_pct.train") == pytest.approx(want)
    # one windowed forward call over the band in 2 ms; the full call not in it
    work, traffic = flops_laguna.flash_window_call(
        ctx["config"], ctx["mix"], "flash_window_fwd")
    least = max(work / 197e12, traffic / 819e9)
    assert read("flash_window_roofline_pct.train") == pytest.approx(
        100 * least / 2e-3)
    assert read("flash_window_roofline_pct.train") < 100
    # the readers shared with the other decoder read the same trace rightly
    assert read("moe_device_ms.train") == pytest.approx(1.0)
    assert read("moe_dispatch_device_ms.train") == pytest.approx(0.5)


def test_a_trace_without_the_family_gives_nothing_and_does_not_raise(traced):
    other = [{"name": "/device:TPU:0", "lines": [{"name": reduce.OPS_LINE, "events": [
        ["%fusion.1", 0.0, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(NemotronHForCausalLM)/layers_0/mixer/ssm_mixer/ssd_scan/dot"],
        ["%flash_fwd.1", 5e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(NemotronHForCausalLM)/layers_5/mixer/attention_core/flash_fwd"],
        ["%fusion.3", 10e6, 2e6, "jit(step_fn)/optimizer/clip/mul"]]}]}]
    ctx = traced(other)
    assert [_reader(name)(ctx) for name in NEW_METRICS] == [None] * len(NEW_METRICS)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_reader_returns_none_on_an_empty_context(name):
    assert _reader(name)({}) is None


# -- correct -----------------------------------------------------------------------

FLOAT32_LIMITS = {"loss_gap_first": 1e-4, "loss_gap_later": 1e-4,
                  "grad_global_norm_gap": 1e-3, "grad_norm_gap_worst_leaf": 1e-3,
                  "head_grad_rel_diff": 1e-3, "all_grad_rel_diff": 1e-3,
                  "delta_norm_gap_worst_leaf": 2e-2, "feed_faults": 0}


def _plant(monkeypatch, fault):
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import laguna

    if fault == "window_dropped":
        real = laguna.dot_product_attention
        monkeypatch.setattr(laguna, "dot_product_attention",
                            lambda *a, window=None, **k: real(*a, **k))
    elif fault == "rotary_dropped":
        monkeypatch.setattr(laguna.rope, "apply_rotary", lambda x, cos, sin: x)
    elif fault == "gate_dropped":
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: jnp.ones_like(x))
    elif fault == "up_left_out":
        # silu(gate) alone: the product's other factor set to one
        real = jnp.split
        monkeypatch.setattr(jnp, "split", lambda x, n, axis=0: (
            lambda parts: [parts[0], jnp.ones_like(parts[1])]
            if n == 2 and axis == -1 else parts)(real(x, n, axis=axis)))


def _tiny_run(monkeypatch=None, fault=None, controls=()):
    tmp = tempfile.mkdtemp()
    ctx = cpu_cell_laguna.tiny_context(CELL, 2 ** 31 + 77, 0.3, tmp)
    ctx["mix"]["trainer_args"] = ["--dtype", "float32", "--remat", "full"]
    ctx["mix"]["check"] = dict(ctx["mix"]["check"], limits=FLOAT32_LIMITS)
    ctx["controls"] = list(controls)
    kind = bench_run.load_module(ctx["kind_file"], "kind_under_test")
    if not fault:
        return kind.measure(ctx)
    # the fault lives in the PROGRAM alone: the reference runs after the
    # trainer has returned, with the program's modules as they were
    real_drive = kind.drive

    def drive_with_the_fault(*a, **k):
        with monkeypatch.context() as planted:
            _plant(planted, fault)
            return real_drive(*a, **k)

    monkeypatch.setattr(kind, "drive", drive_with_the_fault)
    return kind.measure(ctx)


def test_sound_in_float32_and_the_control_fails():
    result = _tiny_run(controls=["fp8"])
    assert result["correct"] is True and result["failed"] == 0
    assert result["dropped_slots"] == 0 and result["compiles_in_window"] == 0
    assert result["counters"]["moe_local_slots"] > 0
    assert result["counters"]["attn_window_tiles_run"] > 0
    assert result["readings"]["routing_flip_share"] < 0.01
    control = result["controls"]["fp8"]
    assert any(control[k] > FLOAT32_LIMITS[k] for k in control)
    assert control["head_grad_rel_diff"] > 10 * result["readings"]["head_grad_rel_diff"]


@pytest.mark.parametrize("fault", ["window_dropped", "rotary_dropped",
                                   "gate_dropped", "up_left_out"])
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    assert _tiny_run(monkeypatch, fault)["correct"] is False


def test_a_program_without_the_family_is_told_so_at_once(monkeypatch):
    from bert_pytorch_tpu import config as program_config

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    monkeypatch.setattr(program_config, "MODEL_FAMILIES", {
        k: v for k, v in program_config.MODEL_FAMILIES.items() if k != "laguna"})
    with pytest.raises(SystemExit, match="unknown model_type 'laguna'"):
        kind.run(dict(_cell(), seed=1, seconds=1.0, trace=False, started=0.0))
