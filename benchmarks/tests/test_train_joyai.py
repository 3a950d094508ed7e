"""The ``train_joyai`` kind, its FLOP counts, its core's operation and byte
counts, its rules and its readers, on the CPU: the counts against the issue's
arithmetic, the scope rules of ``scopes_joyai.json`` on op names as the
program writes them, the readers on a small synthetic trace (and on none, and
on another decoder's trace: nothing to read, no raise), and how ``correct`` is
decided at a size a test can hold: sound in float32, the lower-precision
control failing, and four faults planted under the harness (the second term
left out, the module's input moved the wrong way, the latent norms left out,
the shared key's gradient from one head alone) each coming out not correct by
the number that is there for it. The cell and its configuration are found BY
NAME."""

import json
import os
import tempfile

import pytest

import benchmarks.run as bench_run
from benchmarks.rehearse import cpu_cell_joyai, faults_joyai
from benchmarks.trace import flops_joyai, reduce, scopes, scopes_joyai

ROOT = bench_run.ROOT
CELL = "train-joyai-flash-seq8192"
CONFIG = "joyai-llm-flash"
NEW_METRICS = (
    "mla_device_ms.train", "mla_proj_device_ms.train",
    "mla_core_device_ms.train", "flash_mla_roofline_pct.train",
    "mtp_device_ms.train", "joyai_expert_mfu_pct.train",
    "joyai_unattributed_device_pct.train")
SHARED_METRICS = (
    "fwd_device_ms.train", "bwd_device_ms.train", "recompute_device_ms.train",
    "optimizer_device_ms.train", "sync_idle_ms.train",
    "loop_work_idle_ms.train", "moe_device_ms.train",
    "moe_dispatch_device_ms.train", "lm_head_device_ms.train",
    "dense_mlp_device_ms.train",
    "setup_before_main_s.train", "setup_prepare_s.train",
    "setup_state_init_s.train", "setup_step_lower_s.train",
    "setup_step_executable_s.train", "setup_first_update_s.train",
    "setup_unattributed_pct.train")
NEW_NUMBERS = ("mtp_loss_gap", "mtp_grad_rel_diff", "latent_grad_rel_diff")


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
    for name in SHARED_METRICS:
        assert CELL in entries[name]["workloads"]
    for name in ("ssm_device_ms.train", "glu_expert_mfu_pct.train",
                 "flash_causal_roofline_pct.train", "attention_device_ms.train",
                 "sparse_core_device_ms.train", "keye_expert_mfu_pct.train"):
        assert CELL not in entries[name]["workloads"]  # not this family's
    assert len(bench["configs"]) >= 8 and len(bench["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "lm-seq8192-joyai", CONFIG)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "ep_size"]
    ctx = _cell()
    assert ctx["mix"]["kind"] == "train_joyai"
    assert ctx["config"]["model_type"] == "joyai_llm_flash"
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
                         if '"name": "JoyAI-LLM-Flash"' in line)
        assert config["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            if key not in config["reduced"]:
                assert ctx["config"][key] == value, key
        for key in config["reduced"]:
            assert ctx["config"]["published"][key] == entry["config"][key], key
    assert not any(key.endswith(("_dim", "_rank")) or key in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok") for key in config["reduced"])
    # the laguna cell's traffic but for the kind and the limits
    other = bench_run.context(ROOT, "train-laguna-s-seq8192")["mix"]
    for key in set(other) - {"kind", "check"}:
        assert ctx["mix"][key] == other[key], key
    mix = ctx["mix"]
    assert (mix["seq_len"], mix["local_batch_size"],
            mix["global_batch_size_per_chip"], mix["sequences"],
            mix["shards"], mix["trace_updates"]) == (8192, 1, 4, 256, 1, 3)
    assert mix["documents"] == {"median_tokens": 1000, "sigma": 1.0,
                                "min_tokens": 16, "max_tokens": 8192,
                                "eod_id": 0}
    assert mix["trainer_args"] == ["--remat", "full", "--attention_backend",
                                   "auto", "--log_steps", "4"]
    assert mix["recipe"]["max_steps"] == 10000
    assert mix["check"]["updates"] == 2
    assert flops_joyai.micro_batches(mix) == 4
    assert set(mix["check"]["limits"]) == {
        "loss_gap_first", "loss_gap_later", "grad_global_norm_gap",
        "grad_norm_gap_worst_leaf", "delta_norm_gap_worst_leaf",
        "head_grad_rel_diff", "all_grad_rel_diff", "feed_faults", *NEW_NUMBERS}


@pytest.mark.parametrize("name", [
    "all_grad_rel_diff", "head_grad_rel_diff", "grad_global_norm_gap",
    "grad_norm_gap_worst_leaf", "loss_gap_first", *NEW_NUMBERS])
def test_a_limit_lies_between_the_cells_own_two_readings(name):
    """With room on both sides: the largest sound seed passes with half as
    much again to spare, and the control's reading fails by as much (a limit
    copied from a sibling cell can lie above this cell's control)."""
    check = _cell()["mix"]["check"]
    sound, control = (check["readings"][name][k] for k in ("sound", "control"))
    assert 1.5 * sound < check["limits"][name] < control / 1.5


def test_the_numbers_without_a_control_reading():
    """The precision hardly moves two (the control's later loss gap read
    inside the sound seeds' range on the chip): the accepted decoder cells'
    limit for the one, between the reading and the 1.0 of an unchanged state
    for the other."""
    check = _cell()["mix"]["check"]
    assert set(check["limits"]) - set(check["readings"]) == {
        "delta_norm_gap_worst_leaf", "feed_faults", "loss_gap_later"}
    assert check["limits"]["loss_gap_later"] == 0.0012


# -- FLOPs, operations, bytes --------------------------------------------------------

def test_model_flops_are_the_issues_arithmetic():
    ctx = _cell()
    config, mix = ctx["config"], ctx["mix"]
    parts = flops_joyai.forward_flops_per_token(config, 8192)
    total = sum(parts.values())
    assert round(total / 1e6) == 1421
    share = {name: round(100 * value / total) for name, value in parts.items()}
    assert share == {"mla_proj": 30, "mla_core": 47, "dense_mlp": 6,
                     "experts": 6, "mtp_merge": 1, "head": 5, "mtp_head": 5}
    # the module as a whole: its block, W_eh and the second head pass
    module = ((parts["mla_proj"] + parts["mla_core"]) / 8
              + parts["experts"] / 7 + parts["mtp_merge"] + parts["mtp_head"])
    assert round(100 * module / total) == 16
    assert flops_joyai.train_flops_per_update(config, mix, 1) == pytest.approx(
        139.65e12, rel=1e-4)
    assert flops_joyai.expected_local_slots(config, mix) == 57344
    assert (flops_joyai.blocks(config), flops_joyai.expert_layers(config)) == (8, 7)
    # the program's own copy agrees
    from bert_pytorch_tpu.config import load_model_config
    from bert_pytorch_tpu.utils import flops

    assert flops.joyai_forward_flops_per_token(
        load_model_config(ctx["config_file"]), 8192) == parts
    assert flops_joyai.routed_expert_train_flops(config, 1000.0) == (
        3 * 6 * 2048 * 768 * 1000.0)


def test_the_cores_call_counts_the_causal_pairs_and_the_shared_key_once():
    ctx = _cell()
    config, mix = ctx["config"], ctx["mix"]
    pairs = 32 * 8192 * 8193 // 2
    heads_bytes = lambda width: 2 * 8192 * 32 * width
    k_r, lse = 2 * 8192 * 64, 4 * 8192 * 32
    read = heads_bytes(192) + heads_bytes(128) + k_r + heads_bytes(128)
    assert flops_joyai.mla_core_call(config, mix, "flash_mla_fwd") == (
        2.0 * 320 * pairs, read + heads_bytes(128) + lse)
    assert flops_joyai.mla_core_call(config, mix, "flash_mla_bwd_dq") == (
        2.0 * 320 * pairs, read + heads_bytes(128) + 2 * lse + heads_bytes(192))
    assert flops_joyai.mla_core_call(config, mix, "flash_mla_bwd_dkv") == (
        2.0 * 320 * pairs,
        read + heads_bytes(128) + 2 * lse + 2 * heads_bytes(128) + k_r)
    # the three calls of a block are three times its share of ``mla_core``
    parts = flops_joyai.forward_flops_per_token(config, 8192)
    assert 3 * 2.0 * 320 * pairs == pytest.approx(
        3 * 8192 * parts["mla_core"] / 8)
    with pytest.raises(ValueError):
        flops_joyai.mla_core_call(config, mix, "flash_fwd")


# -- the rules -----------------------------------------------------------------------

SCAN = "jit(step_fn)/micro_batches/while/body/closed_call/"
FWD = SCAN + "jvp(JoyAIForCausalLM.streams)/"
BWD = SCAN + "transpose(jvp(JoyAIForCausalLM.streams))/"
REMAT = BWD.replace("transpose(", "rematted_computation/transpose(")
L1 = "layers_1/"
MTP = "mtp/mtp/"


@pytest.mark.parametrize("op_name,instruction,expected", [
    (FWD + L1 + "attention/mla/mla_q_proj/q_a_proj/dot_general", "%fusion.1",
     ("forward", "mla_q_proj")),
    (FWD + L1 + "attention/mla/mla_q_proj/q_a_norm/rsqrt", "%fusion.2",
     ("forward", "mla_q_proj")),
    (BWD + L1 + "attention/mla/mla_kv_proj/kv_b_proj/dot_general", "%fusion.3",
     ("backward", "mla_kv_proj")),
    (FWD + L1 + "attention/mla/attn_rope/mul", "%fusion.4",
     ("forward", "attn_rope")),
    (FWD + "attn_rope/cos", "%fusion.5", ("forward", "attn_rope")),
    (FWD + L1 + "attention/mla/mla_core/attention_core/flash_mla_fwd/pallas_call",
     "%flash_mla_fwd.1", ("forward", "mla_core")),
    (REMAT + L1 + "attention/mla/mla_core/concatenate", "%fusion.6",
     ("recompute", "mla_core")),
    (BWD + L1 + "attention/mla/mla_core/attention_core/flash_mla_bwd_dkv/"
     "pallas_call", "%flash_mla_bwd_dkv.1", ("backward", "mla_core")),
    (BWD + L1 + "attention/mla/attn_out/o_proj/dot_general", "%fusion.7",
     ("backward", "attn_out")),
    (FWD + L1 + "attention/mla/reshape", "%fusion.8", ("forward", "mla_other")),
    (FWD + "layers_0/mlp/dense_mlp/gate_up_proj/dot_general", "%fusion.9",
     ("forward", "dense_mlp")),
    (FWD + L1 + "mlp/moe/moe_route/top_k", "%fusion.10", ("forward", "moe_route")),
    (FWD + L1 + "mlp/moe/while/body/moe_experts/gmm/pallas_call", "%gmm.1",
     ("forward", "moe_experts")),
    (FWD + L1 + "mlp/moe/moe_shared/shared_up/dot_general", "%fusion.11",
     ("forward", "moe_shared")),
    (FWD + MTP + "mtp_merge/eh_proj/dot_general", "%fusion.12",
     ("forward", "mtp_merge")),
    (FWD + MTP + "block/attention/mla/mla_core/attention_core/flash_mla_fwd/"
     "pallas_call", "%flash_mla_fwd.2", ("forward", "mla_core")),
    (FWD + MTP + "final_norm/rsqrt", "%fusion.13", ("forward", "norm")),
    (FWD + MTP + "concatenate", "%fusion.14", ("forward", "mtp_other")),
    (SCAN + "while/body/mtp_head/dot_general", "%fusion.15", ("other", "mtp_head")),
    (SCAN + "while/body/mtp_loss/reduce_sum", "%fusion.16", ("other", "mtp_loss")),
    (SCAN + "while/body/lm_head/dot_general", "%fusion.17", ("other", "lm_head")),
    (FWD + L1 + "attention_norm/rsqrt", "%fusion.18", ("forward", "norm")),
    ("jit(step_fn)/optimizer/clip/mul", "%fusion.19", ("optimizer", "optimizer")),
])
def test_pass_and_part_rules_of_the_family(op_name, instruction, expected):
    assert scopes.classify(op_name, instruction, scopes_joyai.rules()) == expected


@pytest.mark.parametrize("op_name,module", [
    (FWD + MTP + "block/attention/mla/mla_core/attention_core/flash_mla_fwd/"
     "pallas_call", True),
    (FWD + MTP + "mtp_merge/eh_proj/dot_general", True),
    (SCAN + "while/body/mtp_head/dot_general", True),
    (SCAN + "while/body/mtp_loss/reduce_sum", True),
    (SCAN + "while/body/lm_head/dot_general", False),
    (FWD + L1 + "attention/mla/mla_core/attention_core/flash_mla_fwd/pallas_call",
     False)])
def test_the_second_reduction_classes_everything_of_the_module_as_one(
        op_name, module):
    table = scopes_joyai.rules()
    by_module = dict(table, part=table["module_part"] + table["part"])
    part = scopes.classify(op_name, "%fusion.1", by_module)[1]
    assert (part == scopes_joyai.MODULE) == module


def test_the_rules_name_only_scopes_the_program_writes():
    from bert_pytorch_tpu import pretrain

    written = set(pretrain.SCOPES) | set(pretrain.JOYAI_SCOPES)
    rules = scopes_joyai.rules()
    for rule in rules["part"] + rules["module_part"]:
        if rule["name"] in ("norm", "layers", "micro_batch_scan",
                            "unnamed_copies", "accumulate", "moe_other",
                            "mla_other", "mtp_other", "step_metrics",
                            "optimizer"):
            continue
        assert rule["name"] in written, rule["name"]
    assert set(scopes_joyai.FAMILY_PARTS) <= {r["name"] for r in rules["part"]}
    assert set(scopes_joyai.MLA_PARTS) <= {r["name"] for r in rules["part"]}
    assert rules["kernels"] == list(flops_joyai.KERNELS)


# -- the readers -------------------------------------------------------------------

def _family_planes():
    ms = 1e6
    mla = FWD + "layers_1/attention/mla/"
    moe = FWD + "layers_1/mlp/moe/"
    module = FWD + MTP
    ops = [
        ["%fusion.1", 0.0, 2 * ms, mla + "mla_q_proj/q_b_proj/dot_general"],
        ["%fusion.2", 2 * ms, 1 * ms, mla + "mla_kv_proj/kv_b_proj/dot_general"],
        ["%fusion.3", 3 * ms, 1 * ms, mla + "attn_rope/mul"],
        ["%fusion.4", 4 * ms, 1 * ms, mla + "mla_core/concatenate"],
        ["%flash_mla_fwd.1", 5 * ms, 8 * ms,
         mla + "mla_core/attention_core/flash_mla_fwd/pallas_call"],
        ["%flash_mla_bwd_dq.1", 13 * ms, 8 * ms, BWD + "layers_1/attention/mla/"
         "mla_core/attention_core/flash_mla_bwd_dq/pallas_call"],
        ["%fusion.5", 21 * ms, 1 * ms, mla + "attn_out/o_proj/dot_general"],
        ["%fusion.6", 22 * ms, 2 * ms,
         FWD + "layers_0/mlp/dense_mlp/gate_up_proj/dot_general"],
        ["%fusion.7", 24 * ms, 1 * ms, moe + "moe_route/top_k"],
        ["%gmm.1", 25 * ms, 2 * ms, moe + "while/body/moe_experts/gmm/pallas_call"],
        ["%fusion.8", 27 * ms, 1 * ms, moe + "moe_shared/shared_up/dot_general"],
        ["%fusion.9", 28 * ms, 1 * ms, module + "mtp_merge/eh_proj/dot_general"],
        ["%flash_mla_fwd.2", 29 * ms, 4 * ms, module + "block/attention/mla/"
         "mla_core/attention_core/flash_mla_fwd/pallas_call"],
        ["%fusion.10", 33 * ms, 1 * ms, SCAN + "while/body/mtp_head/dot_general"],
        ["%fusion.11", 34 * ms, 2 * ms, SCAN + "while/body/lm_head/dot_general"],
        ["%while.1", 36 * ms, 1 * ms, None],
    ]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": reduce.OPS_LINE, "events": ops}]}]


@pytest.fixture
def traced(monkeypatch):
    def with_planes(planes):
        from benchmarks.trace import scopes_laguna, scopes_lm

        for module in (scopes_joyai, scopes_lm, scopes_laguna):
            monkeypatch.setattr(module, "_reductions", {})
        monkeypatch.setattr(scopes, "newest_trace", lambda under=None: "a.xplane.pb")
        monkeypatch.setattr(scopes, "read_xspace", lambda path: planes)
        ctx = _cell()
        return {"summary": {"busy_s": 37e-3}, "updates": 2, "chips": 1,
                "peak_flops": 197e12, "device_kind": "TPU v5 lite",
                "config": ctx["config"], "mix": ctx["mix"],
                "counters": {"moe_local_slots": 57344.0}, "trace_dir": "x"}
    return with_planes


def test_readers_on_a_small_trace_of_the_family(traced):
    ctx = traced(_family_planes())
    read = lambda name: _reader(name)(ctx)
    assert read("mla_device_ms.train") == pytest.approx(13.0)
    assert read("mla_proj_device_ms.train") == pytest.approx(2.0)
    assert read("mla_core_device_ms.train") == pytest.approx(10.5)
    assert read("mtp_device_ms.train") == pytest.approx(3.0)
    assert read("joyai_unattributed_device_pct.train") == pytest.approx(100 / 37)
    assert read("joyai_expert_mfu_pct.train") == pytest.approx(
        100 * 3 * 6 * 2048 * 768 * 57344 / (1e-3 * 197e12))
    config, mix = ctx["config"], ctx["mix"]

    def floor(kernel):
        work, traffic = flops_joyai.mla_core_call(config, mix, kernel)
        return max(work / 197e12, traffic / 819e9)

    least = 2 * floor("flash_mla_fwd") + floor("flash_mla_bwd_dq")
    assert read("flash_mla_roofline_pct.train") == pytest.approx(
        100 * least / 20e-3)
    # the readers shared with the other decoders read the same trace rightly
    assert read("lm_head_device_ms.train") == pytest.approx(1.0)
    assert read("moe_device_ms.train") == pytest.approx(2.0)
    assert read("moe_dispatch_device_ms.train") == pytest.approx(0.5)
    assert read("dense_mlp_device_ms.train") == pytest.approx(1.0)


def test_a_trace_without_the_family_gives_nothing_and_does_not_raise(traced):
    other = [{"name": "/device:TPU:0", "lines": [{"name": reduce.OPS_LINE, "events": [
        ["%fusion.1", 0.0, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(NemotronHForCausalLM)/layers_0/mixer/ssm_mixer/ssd_scan/dot"],
        ["%flash_fwd.1", 5e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_3/attn/attention_core/flash_fwd"],
        ["%fusion.2", 10e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/attn/attn_rope/mul"],
        ["%gmm.1", 15e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/mlp/moe/while/body/moe_experts/gmm"],
        ["%fusion.4", 22e6, 2e6, "jit(step_fn)/optimizer/clip/mul"]]}]}]
    ctx = traced(other)
    assert [_reader(name)(ctx) for name in NEW_METRICS] == [None] * len(NEW_METRICS)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_reader_returns_none_on_an_empty_context(name):
    assert _reader(name)({}) is None


# -- correct -----------------------------------------------------------------------

FLOAT32_LIMITS = {"loss_gap_first": 1e-4, "loss_gap_later": 3e-4,
                  "grad_global_norm_gap": 1e-3, "grad_norm_gap_worst_leaf": 5e-3,
                  "head_grad_rel_diff": 1e-3, "all_grad_rel_diff": 1e-3,
                  "delta_norm_gap_worst_leaf": 2e-2, "feed_faults": 0,
                  "mtp_loss_gap": 1e-4, "mtp_grad_rel_diff": 2e-3,
                  "latent_grad_rel_diff": 2e-3}


def _tiny_run(fault=None, controls=()):
    tmp = tempfile.mkdtemp()
    ctx = cpu_cell_joyai.tiny_context(CELL, 2 ** 31 + 77, 0.3, tmp)
    ctx["mix"]["trainer_args"] = ["--dtype", "float32", "--remat", "full"]
    ctx["mix"]["check"] = dict(ctx["mix"]["check"], limits=FLOAT32_LIMITS)
    ctx["controls"] = list(controls)
    kind = bench_run.load_module(ctx["kind_file"], "kind_under_test")
    if not fault:
        return kind.measure(ctx)
    # the fault lives in the PROGRAM alone: the reference runs after the
    # trainer has returned, with the program's modules as they were
    return faults_joyai.read(ctx, kind, fault)


def test_sound_in_float32_and_the_control_fails():
    result = _tiny_run(controls=["fp8"])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compiles_in_window"] == 0
    counters = result["counters"]
    assert counters["moe_dropped_slots"] == 0.0
    # (2 layers + the module) x 4 micro-batches of one row x 4 heads x one tile
    assert counters["mla_tiles_run"] == 3 * 4 * 4
    # an expert layer and the module's: 2 x 4 x 64 tokens x 3 slots, about half
    # of them on the 4 of 8 experts held
    assert 0.3 < counters["moe_local_slots"] / (2 * 4 * 64 * 3) < 0.7
    assert 5.0 < counters["mtp_loss"] < 7.5
    readings = result["readings"]
    assert readings["routing_flip_share"] < 0.01
    for name in NEW_NUMBERS:
        assert readings[name] < FLOAT32_LIMITS[name]
    control = result["controls"]["fp8"]
    assert set(NEW_NUMBERS) <= set(control)
    assert any(control[k] > FLOAT32_LIMITS[k] for k in control)
    for name in ("all_grad_rel_diff", "mtp_grad_rel_diff",
                 "latent_grad_rel_diff"):
        assert control[name] > 10 * readings[name], name
    assert control["mtp_loss_gap"] > 3 * readings["mtp_loss_gap"]


@pytest.mark.parametrize("fault, seen_by, times", [
    ("mtp_left_out", "mtp_grad_rel_diff", 10),
    ("mtp_input_shifted_back", "mtp_loss_gap", 10),
    ("latent_norms_left_out", "latent_grad_rel_diff", 10),
    ("shared_key_first_head_only", "latent_grad_rel_diff", 3)])
def test_a_planted_fault_is_not_correct(fault, seen_by, times):
    """... and the number named sees it at this float32 run's limit by ten
    times or more (the shared key's lost gradient by three: at fresh weights
    the scores are near uniform and the keys' gradient small, and the turned
    key's columns are 8 of ``W_kva``'s 40 here)."""
    result = _tiny_run(fault)
    assert result["correct"] is False
    assert result["readings"][seen_by] > times * FLOAT32_LIMITS[seen_by]
    if fault == "mtp_left_out":
        assert result["readings"]["mtp_grad_rel_diff"] == pytest.approx(1.0)


def test_the_kind_is_the_laguna_kind_over_another_family():
    """Nothing of ``train_laguna.py`` is written again but the three numbers
    this family adds and the routing's read: this kind's functions are that
    file's, loaded a second time; the laguna cell's own copy still names its
    own."""
    from benchmarks.kinds import train_laguna
    from benchmarks.reference import joyai_f32, laguna_f32

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    assert kind.base is not train_laguna
    assert kind.base.__file__ == train_laguna.__file__
    assert kind.measure is kind.base.measure and kind.run is kind.base.run
    assert kind.base.compare_with_reference is kind.compare_with_reference
    assert kind.base.Probes is kind.Probes
    assert kind.base.family()[0] is joyai_f32
    assert kind.base.COUNTERS == ("moe_", "mla_", "mtp_")
    assert train_laguna.family()[0] is laguna_f32
    assert train_laguna.COUNTERS == ("moe_", "attn_")
    assert train_laguna.Probes is not kind.Probes
    assert kind.NEW_NUMBERS == NEW_NUMBERS


def test_a_program_without_the_family_is_told_so_at_once(monkeypatch):
    from bert_pytorch_tpu import config as program_config

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    monkeypatch.setattr(program_config, "MODEL_FAMILIES", {
        k: v for k, v in program_config.MODEL_FAMILIES.items()
        if k != "joyai_llm_flash"})
    with pytest.raises(SystemExit, match="unknown model_type 'joyai_llm_flash'"):
        kind.run(dict(_cell(), seed=1, seconds=1.0, trace=False, started=0.0))
