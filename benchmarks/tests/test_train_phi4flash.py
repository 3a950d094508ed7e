"""The ``train_phi4flash`` kind, its FLOP counts, its kernels' operation and
byte counts, its rules and its readers, on the CPU: the counts against the
issue's arithmetic, the scope rules of ``scopes_phi4flash.json`` on op names as
the program writes them, the readers on a small synthetic trace (and on none,
and on another decoder's trace: nothing to read, no raise), and how
``correct`` is decided at a size a test can hold: sound in float32, the
lower-precision control failing, and four faults planted under the harness
(the window dropped, the ``lam A2`` term dropped, the scan's decay dropped, the
memory read one position late) each coming out not correct."""

import json
import os
import tempfile

import pytest

import benchmarks.run as bench_run
from benchmarks.rehearse import cpu_cell_phi4flash
from benchmarks.trace import (flops_phi4flash, reduce, scopes,
                              scopes_phi4flash)

ROOT = bench_run.ROOT
CELL = "train-phi4-mini-flash-seq8192"
NEW_METRICS = (
    "s6_mixer_device_ms.train", "selective_scan_device_ms.train",
    "selective_scan_roofline_pct.train", "gmu_device_ms.train",
    "diff_window_attention_device_ms.train",
    "diff_full_attention_device_ms.train", "flash_diff_roofline_pct.train",
    "phi_attention_proj_device_ms.train", "phi_mlp_device_ms.train",
    "phi_unattributed_device_pct.train")
SHARED_METRICS = (
    "fwd_device_ms.train", "bwd_device_ms.train", "recompute_device_ms.train",
    "optimizer_device_ms.train", "sync_idle_ms.train",
    "loop_work_idle_ms.train", "lm_head_device_ms.train")


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
        assert entries[name]["source"] == "device_trace"
    # (found by name, never by place: the next cell is appended after this one)
    for name in SHARED_METRICS:
        assert CELL in entries[name]["workloads"]
    for name in ("ssm_device_ms.train", "moe_device_ms.train",
                 "dense_mlp_device_ms.train", "attention_proj_device_ms.train",
                 "window_attention_device_ms.train",
                 "laguna_unattributed_device_pct.train"):  # not this family's
        assert CELL not in entries[name]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "lm-seq8192-phi4flash")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["name"] == "phi-4-mini-flash-reasoning"
    assert config["reduced"] == ["num_hidden_layers", "vocab_size",
                                 "num_attention_heads", "num_key_value_heads"]
    ctx = _cell()
    assert ctx["mix"]["kind"] == "train_phi4flash"
    assert ctx["config"]["model_type"] == "phi4flash"
    assert config["reduced"] == ctx["config"]["reduced"]
    assert config["source"] == ctx["config"]["source"]
    assert set(NEW_METRICS) | set(SHARED_METRICS) <= set(ctx["readers"])
    assert {"data_wait_ms.train", "host_dispatch_ms.train", "device_step_ms.train",
            "step_mfu_pct.train", "device_idle_pct.train"} <= set(ctx["readers"])
    # every number of the catalog's entry under its key; no width is reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # (not on a machine without the guides)
        with open(catalog) as f:
            entry = next(json.loads(line) for line in f
                         if '"Phi-4-mini-flash-reasoning"' in line)
        assert config["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            if key not in config["reduced"]:
                assert ctx["config"][key] == value, key
    # the decoder cells' traffic, unchanged but for the kind and the limits
    other = bench_run.context(ROOT, "train-laguna-s-seq8192")["mix"]
    for key in set(other) - {"kind", "check"}:
        assert ctx["mix"][key] == other[key], key
    assert ctx["mix"]["check"]["updates"] == other["check"]["updates"]


# -- FLOPs, operations, bytes --------------------------------------------------------

def test_model_flops_are_the_issues_arithmetic():
    ctx = _cell()
    parts = {k: v / 1e6 for k, v in flops_phi4flash.forward_flops_per_token(
        ctx["config"], 8192).items()}
    assert parts["mlp"] == pytest.approx(943.7, abs=0.1)
    assert parts["s6_proj"] == pytest.approx(164.5, abs=0.1)
    assert parts["gmu"] == pytest.approx(52.4, abs=0.1)
    # 20 of the 40 heads (the stated fallback): half of 104.9, 125.8 and 7.6
    assert parts["attention_proj"] == pytest.approx(52.4, abs=0.1)
    assert parts["attention_full"] == pytest.approx(62.9, abs=0.1)
    assert parts["attention_window"] == pytest.approx(3.8, abs=0.1)
    assert parts["head"] == pytest.approx(128.5, abs=0.1)
    total = sum(parts.values())
    assert total == pytest.approx(1408.3, rel=1e-3)
    assert flops_phi4flash.train_flops_per_update(
        ctx["config"], ctx["mix"], 1) == pytest.approx(3 * 32768 * total * 1e6)
    # the new mechanisms are about 24% of it (30% with the heads whole)
    new = sum(parts[k] for k in ("s6_proj", "gmu", "attention_proj",
                                 "attention_full", "attention_window"))
    assert 0.22 < new / total < 0.26
    # the program's own copy agrees (it may drift later; the yardstick may not)
    from bert_pytorch_tpu.config import load_model_config
    from bert_pytorch_tpu.utils import flops as program_flops
    assert program_flops.causal_lm_train_flops_per_seq(
        load_model_config(ctx["config_file"]), 8192) == pytest.approx(
            3 * 8192 * total * 1e6)


def test_a_scan_call_counts_its_operands_once_and_no_state():
    ctx = _cell()
    ops, traffic = flops_phi4flash.selective_scan_call(
        ctx["config"], ctx["mix"], "selective_scan_fwd")
    assert ops == 7 * 8192 * 5120 * 16
    assert traffic == 4 * 8192 * (3 * 5120 + 2 * 16)
    # the states, were they written, would be sixteen times the operands
    assert 8192 * 5120 * 16 * 4 > 5 * traffic
    back_ops, back_traffic = flops_phi4flash.selective_scan_call(
        ctx["config"], ctx["mix"], "selective_scan_bwd")
    assert back_ops == 22 * 8192 * 5120 * 16
    assert back_traffic == 4 * 8192 * (5 * 5120 + 4 * 16)
    # by peaks.json's two peaks the bytes bound it, ten times over
    assert (traffic / 819e9) > 10 * (ops / 197e12)


def test_a_differential_call_counts_keys_of_64_and_values_of_128():
    ctx = _cell()
    triangle, band = 8192 * 8193 / 2, 512 * 8192 - 512 * 511 / 2
    work, traffic = flops_phi4flash.flash_diff_call(
        ctx["config"], ctx["mix"], "flash_diff_fwd")
    assert work == pytest.approx((2 * 64 + 2 * 128) * triangle * 20)
    assert traffic == (2 * 64 + 2 * 128) * 20 * 8192 * 2
    cross, _ = flops_phi4flash.flash_diff_call(
        ctx["config"], ctx["mix"], "flash_diff_cross_fwd")
    assert cross == work
    windowed, _ = flops_phi4flash.flash_diff_call(
        ctx["config"], ctx["mix"], "flash_diff_window_fwd")
    assert windowed == pytest.approx(384 * band * 20)
    dq, dq_bytes = flops_phi4flash.flash_diff_call(
        ctx["config"], ctx["mix"], "flash_diff_bwd_dq")
    dkv, dkv_bytes = flops_phi4flash.flash_diff_call(
        ctx["config"], ctx["mix"], "flash_diff_window_bwd_dkv")
    assert dq == pytest.approx(512 * triangle * 20)
    assert dkv == pytest.approx(768 * band * 20)
    assert dq_bytes == (3 * 64 + 2 * 128) * 20 * 8192 * 2
    assert dkv_bytes == (3 * 64 + 3 * 128) * 20 * 8192 * 2
    assert set(flops_phi4flash.DIFF_KERNELS) | set(
        flops_phi4flash.SCAN_KERNELS) == set(scopes_phi4flash.rules()["kernels"])


# -- the rules -------------------------------------------------------------------

SCAN = "jit(step_fn)/micro_batches/while/body/closed_call/"
FWD = SCAN + "jvp(PhiFlashForCausalLM)/"
BWD = SCAN + "transpose(jvp(PhiFlashForCausalLM))/"


@pytest.mark.parametrize("op_name,instruction,expected", [
    (FWD + "layers_0/checkpoint/layers_0/mixer/s6_mixer/selective_scan/"
     "selective_scan_fwd/pallas_call", "%selective_scan_fwd.3",
     ("forward", "selective_scan")),
    (BWD + "layers_2/checkpoint/layers_2/mixer/s6_mixer/selective_scan/"
     "selective_scan_bwd/pallas_call", "%selective_scan_bwd.1",
     ("backward", "selective_scan")),
    (BWD + "layers_2/checkpoint/rematted_computation/layers_2/mixer/s6_mixer/"
     "selective_scan/broadcast_in_dim", "%fusion.3",
     ("recompute", "selective_scan")),
    (FWD + "layers_0/checkpoint/layers_0/mixer/s6_mixer/s6_in_proj/in_proj/"
     "dot_general", "%fusion.4", ("forward", "s6_in_proj")),
    (FWD + "layers_0/checkpoint/layers_0/mixer/s6_mixer/s6_conv/ssm_conv/mul",
     "%fusion.5", ("forward", "s6_conv")),
    (FWD + "layers_0/checkpoint/layers_0/mixer/s6_mixer/s6_dt/dt_proj/"
     "dot_general", "%fusion.6", ("forward", "s6_dt")),
    (BWD + "layers_0/checkpoint/layers_0/mixer/s6_mixer/s6_gate/mul",
     "%fusion.7", ("backward", "s6_gate")),
    (FWD + "layers_0/checkpoint/layers_0/mixer/s6_mixer/s6_out_proj/out_proj/"
     "dot_general", "%fusion.8", ("forward", "s6_out_proj")),
    (FWD + "layers_0/checkpoint/layers_0/mixer/s6_mixer/exp", "%fusion.9",
     ("forward", "s6_other")),
    (FWD + "layers_4/checkpoint/layers_4/mixer/gmu/in_proj/dot_general",
     "%fusion.10", ("forward", "gmu")),
    (FWD + "layers_1/checkpoint/layers_1/mixer/attention_core/"
     "flash_diff_window_fwd/pallas_call", "%flash_diff_window_fwd.3",
     ("forward", "diff_window_attention")),
    (BWD + "layers_3/checkpoint/layers_3/mixer/attention_core/"
     "flash_diff_bwd_dkv/pallas_call", "%flash_diff_bwd_dkv.1",
     ("backward", "diff_full_attention")),
    (BWD + "layers_5/checkpoint/rematted_computation/layers_5/mixer/"
     "attention_core/flash_diff_cross_fwd/pallas_call",
     "%flash_diff_cross_fwd.3", ("recompute", "diff_cross_attention")),
    (FWD + "layers_3/checkpoint/layers_3/mixer/attention_core/concatenate",
     "%fusion.11", ("forward", "attention_core")),
    (FWD + "layers_3/checkpoint/layers_3/mixer/attn_qkv/Wqkv/dot_general",
     "%fusion.12", ("forward", "attn_qkv")),
    (FWD + "layers_3/checkpoint/layers_3/mixer/attn_diff/subln/mul",
     "%fusion.13", ("forward", "attn_diff")),
    (FWD + "layers_5/checkpoint/layers_5/mixer/attn_out/out_proj/dot_general",
     "%fusion.14", ("forward", "attn_out")),
    (FWD + "layers_0/checkpoint/layers_0/mlp/dense_mlp/fc1/dot_general",
     "%fusion.15", ("forward", "dense_mlp")),
    (FWD + "layers_3/checkpoint/layers_3/norm1/mul", "%fusion.2",
     ("forward", "norm")),
    (FWD + "layers_3/checkpoint/layers_3/norm2/mul", "%fusion.2",
     ("forward", "norm")),
    (BWD + "while/body/checkpoint/lm_head/dot_general", "%fusion.1",
     ("backward", "lm_head")),
    ("jit(step_fn)/optimizer/clip/mul", "%fusion.40", ("optimizer", "optimizer")),
    (SCAN + "grad_accumulate/add", "%fusion.41", ("other", "accumulate")),
    (None, "%copy.3", ("other", "unnamed_copies")),
    (None, "%while.3", ("other", None)),
])
def test_pass_and_part_rules_of_the_family(op_name, instruction, expected):
    assert scopes.classify(
        op_name, instruction, scopes_phi4flash.rules()) == expected


def test_the_rules_name_only_scopes_the_program_writes():
    from bert_pytorch_tpu import pretrain

    table = scopes_phi4flash.rules()
    named = {f.strip("/") for rule in table["part"] for f in rule["fragments"]}
    scope_like = {f for f in named if f.replace("_", "").isalnum()
                  and not f.startswith(("flash_", "norm", "final_norm"))}
    written = set(pretrain.SCOPES) | set(pretrain.PHI_FLASH_SCOPES)
    assert scope_like <= written | {"optimizer", "step_metrics", "layers_"}, (
        scope_like - written)
    # the shared head reader's rules place the tied head's pieces too
    from benchmarks.trace import scopes_lm
    assert scopes.classify(BWD + "while/body/checkpoint/lm_head/dot_general",
                           "%fusion.1", scopes_lm.rules()) == (
                               "backward", "lm_head")


# -- the readers -------------------------------------------------------------------

def _family_planes():
    ms = 1e6
    mixer = FWD + "layers_0/mixer/s6_mixer/"
    ops = [
        ["%selective_scan_fwd.1", 0.0, 6 * ms,
         mixer + "selective_scan/selective_scan_fwd/pallas_call"],
        ["%fusion.1", 6 * ms, 2 * ms, mixer + "selective_scan/broadcast_in_dim"],
        ["%fusion.2", 8 * ms, 4 * ms, mixer + "s6_in_proj/in_proj/dot_general"],
        ["%fusion.3", 12 * ms, 1 * ms, mixer + "s6_gate/mul"],
        ["%fusion.4", 13 * ms, 1 * ms, FWD + "layers_4/mixer/gmu/in_proj/dot"],
        ["%flash_diff_window_fwd.1", 14 * ms, 2 * ms,
         FWD + "layers_1/mixer/attention_core/flash_diff_window_fwd/pallas_call"],
        ["%flash_diff_fwd.1", 16 * ms, 6 * ms,
         FWD + "layers_3/mixer/attention_core/flash_diff_fwd/pallas_call"],
        ["%flash_diff_cross_fwd.1", 22 * ms, 6 * ms,
         FWD + "layers_5/mixer/attention_core/flash_diff_cross_fwd/pallas_call"],
        ["%fusion.5", 28 * ms, 3 * ms, FWD + "layers_3/mixer/attn_qkv/Wqkv/dot"],
        ["%fusion.6", 31 * ms, 1 * ms, FWD + "layers_3/mixer/attn_diff/mul"],
        ["%fusion.7", 32 * ms, 2 * ms, FWD + "layers_3/mixer/attn_out/out_proj/dot"],
        ["%fusion.8", 34 * ms, 8 * ms, FWD + "layers_0/mlp/dense_mlp/fc1/dot"],
        ["%fusion.9", 42 * ms, 2 * ms, FWD + "while/body/lm_head/dot_general"],
        ["%while.1", 44 * ms, 1 * ms, None],
    ]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": reduce.OPS_LINE, "events": ops}]}]


@pytest.fixture
def traced(monkeypatch):
    def with_planes(planes):
        from benchmarks.trace import scopes_lm

        monkeypatch.setattr(scopes_phi4flash, "_reductions", {})
        monkeypatch.setattr(scopes_lm, "_reductions", {})
        monkeypatch.setattr(scopes, "newest_trace", lambda under=None: "a.xplane.pb")
        monkeypatch.setattr(scopes, "read_xspace", lambda path: planes)
        ctx = _cell()
        return {"summary": {"busy_s": 45e-3}, "updates": 2, "chips": 1,
                "peak_flops": 197e12, "device_kind": "TPU v5 lite",
                "config": ctx["config"], "mix": ctx["mix"],
                "counters": {"scan_chunks_run": 512.0}, "trace_dir": "x"}
    return with_planes


def test_readers_on_a_small_trace_of_the_family(traced):
    ctx = traced(_family_planes())
    read = lambda name: _reader(name)(ctx)
    assert read("selective_scan_device_ms.train") == pytest.approx(4.0)
    assert read("s6_mixer_device_ms.train") == pytest.approx(6.5)
    assert read("gmu_device_ms.train") == pytest.approx(0.5)
    assert read("diff_window_attention_device_ms.train") == pytest.approx(1.0)
    assert read("diff_full_attention_device_ms.train") == pytest.approx(6.0)
    assert read("phi_attention_proj_device_ms.train") == pytest.approx(3.0)
    assert read("phi_mlp_device_ms.train") == pytest.approx(4.0)
    assert read("phi_unattributed_device_pct.train") == pytest.approx(100 / 45)
    # one forward scan call; the least time by the bytes, over the WHOLE scope
    ops, traffic = flops_phi4flash.selective_scan_call(
        ctx["config"], ctx["mix"], "selective_scan_fwd")
    assert read("selective_scan_roofline_pct.train") == pytest.approx(
        100 * max(ops / 197e12, traffic / 819e9) / 8e-3)
    # one call each of three differential kernels in 14 ms of their own
    least = sum(max(w / 197e12, t / 819e9) for w, t in (
        flops_phi4flash.flash_diff_call(ctx["config"], ctx["mix"], k)
        for k in ("flash_diff_window_fwd", "flash_diff_fwd",
                  "flash_diff_cross_fwd")))
    assert read("flash_diff_roofline_pct.train") == pytest.approx(
        100 * least / 14e-3)
    assert read("flash_diff_roofline_pct.train") < 100
    assert read("selective_scan_roofline_pct.train") < 100
    # the reader shared with the other decoders reads the same trace rightly
    assert read("lm_head_device_ms.train") == pytest.approx(1.0)


def test_a_trace_without_the_family_gives_nothing_and_does_not_raise(traced):
    other = [{"name": "/device:TPU:0", "lines": [{"name": reduce.OPS_LINE, "events": [
        ["%fusion.1", 0.0, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(NemotronHForCausalLM)/layers_0/mixer/ssm_mixer/ssd_scan/dot"],
        ["%flash_fwd.1", 5e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/attn/attention_core/flash_fwd"],
        ["%fusion.2", 10e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/attn/attn_qkv/q_proj/dot_general"],
        ["%fusion.3", 15e6, 2e6, "jit(step_fn)/optimizer/clip/mul"]]}]}]
    ctx = traced(other)
    assert [_reader(name)(ctx) for name in NEW_METRICS] == [None] * len(NEW_METRICS)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_reader_returns_none_on_an_empty_context(name):
    assert _reader(name)({}) is None


# -- correct -----------------------------------------------------------------------

FLOAT32_LIMITS = {"loss_gap_first": 1e-4, "loss_gap_later": 1e-4,
                  # (the lambdas' gradients are one cancelling sum each: 1e-3
                  # of the median tensor's norm is their float32 rounding)
                  "grad_global_norm_gap": 1e-3, "grad_norm_gap_worst_leaf": 5e-3,
                  "head_grad_rel_diff": 1e-3, "all_grad_rel_diff": 1e-3,
                  "delta_norm_gap_worst_leaf": 2e-2, "feed_faults": 0}


def _plant(monkeypatch, fault):
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import phi4flash

    if fault == "window_dropped":
        real = phi4flash.differential_attention
        monkeypatch.setattr(phi4flash, "differential_attention",
                            lambda *a, window=None, **k: real(*a, **k))
    elif fault == "lambda_term_dropped":
        real = phi4flash.differential_attention
        monkeypatch.setattr(
            phi4flash, "differential_attention",
            lambda *a, **k: (lambda a1, a2: (a1, 0 * a2))(*real(*a, **k)))
    elif fault == "scan_decay_dropped":
        real = phi4flash.ssm.selective_scan
        monkeypatch.setattr(
            phi4flash.ssm, "selective_scan",
            lambda u, dt, a, b, c, **k: real(u, dt, 0 * a, b, c, **k))
    elif fault == "memory_shifted":
        real = phi4flash.GatedMemoryUnit.__call__
        monkeypatch.setattr(
            phi4flash.GatedMemoryUnit, "__call__",
            lambda self, x, memory: real(self, x, jnp.roll(memory, 1, axis=1)))


def _tiny_run(monkeypatch=None, fault=None, controls=()):
    tmp = tempfile.mkdtemp()
    ctx = cpu_cell_phi4flash.tiny_context(CELL, 2 ** 31 + 77, 0.3, tmp)
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
    assert result["compiles_in_window"] == 0
    assert result["counters"]["scan_chunks_run"] > 0
    assert result["counters"]["attn_window_tiles_run"] > 0
    assert result["counters"]["memory_readers"] == 1.0
    assert result["counters"]["shared_kv_readers"] == 1.0
    control = result["controls"]["fp8"]
    assert any(control[k] > FLOAT32_LIMITS[k] for k in control)
    assert control["head_grad_rel_diff"] > 10 * result["readings"]["head_grad_rel_diff"]


@pytest.mark.parametrize("fault", ["window_dropped", "lambda_term_dropped",
                                   "scan_decay_dropped", "memory_shifted"])
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    assert _tiny_run(monkeypatch, fault)["correct"] is False


def test_a_program_without_the_family_is_told_so_at_once(monkeypatch):
    from bert_pytorch_tpu import config as program_config

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    monkeypatch.setattr(program_config, "MODEL_FAMILIES", {
        k: v for k, v in program_config.MODEL_FAMILIES.items()
        if k != "phi4flash"})
    with pytest.raises(SystemExit, match="unknown model_type 'phi4flash'"):
        kind.run(dict(_cell(), seed=1, seconds=1.0, trace=False, started=0.0))
