"""The ``train_keye`` kind, its FLOP counts, its indexer's and core's operation
and byte counts, its rules and its readers, on the CPU: the counts against the
issue's arithmetic, the scope rules of ``scopes_keye.json`` on op names as the
program writes them, the readers on a small synthetic trace (and on none, and
on another decoder's trace: nothing to read, no raise), and how ``correct`` is
decided at a size a test can hold: sound in float32, the lower-precision
control failing, and four faults planted under the harness (the KL left out
of the objective, the indexer's input not detached, the top-k one short, a
micro-batch dropped) each coming out not correct. The cell and its
configuration are found BY NAME."""

import json
import os
import tempfile

import pytest

import benchmarks.run as bench_run
from benchmarks.rehearse import cpu_cell_keye, faults_keye
from benchmarks.trace import flops_keye, reduce, scopes, scopes_keye

ROOT = bench_run.ROOT
CELL = "train-keye-vl2-30b-seq16384"
CONFIG = "keye-vl-2.0-30b-a3b"
NEW_METRICS = (
    "sparse_attention_device_ms.train", "indexer_device_ms.train",
    "index_select_device_ms.train", "sparse_core_device_ms.train",
    "indexer_roofline_pct.train", "sparse_core_roofline_pct.train",
    "keye_attention_proj_device_ms.train", "keye_expert_mfu_pct.train",
    "keye_unattributed_device_pct.train")
SHARED_METRICS = (
    "fwd_device_ms.train", "bwd_device_ms.train", "recompute_device_ms.train",
    "optimizer_device_ms.train", "sync_idle_ms.train",
    "loop_work_idle_ms.train", "moe_device_ms.train",
    "moe_dispatch_device_ms.train", "lm_head_device_ms.train",
    "setup_before_main_s.train", "setup_prepare_s.train",
    "setup_state_init_s.train", "setup_step_lower_s.train",
    "setup_step_executable_s.train", "setup_first_update_s.train",
    "setup_unattributed_pct.train")


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
                 "qwen_expert_mfu_pct.train", "flash_gated_roofline_pct.train",
                 "gdn_device_ms.train", "attention_device_ms.train"):
        assert CELL not in entries[name]["workloads"]  # not this family's
    assert len(bench["configs"]) >= 7 and len(bench["workloads"]) >= 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "lm-seq16384-keye", CONFIG)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    ctx = _cell()
    assert ctx["mix"]["kind"] == "train_keye"
    assert ctx["config"]["model_type"] == "KeyeVL2"
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
                         if '"name": "Keye-VL-2.0-30B-A3B"' in line)
        assert config["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            if key not in config["reduced"]:
                assert ctx["config"][key] == value, key
    # the sibling decoders' traffic but for the rows' length, their number a
    # micro-batch and an update, the kind and the limits
    other = bench_run.context(ROOT, "train-laguna-s-seq8192")["mix"]
    for key in set(other) - {"kind", "check", "seq_len", "sequences",
                             "documents", "global_batch_size_per_chip"}:
        assert ctx["mix"][key] == other[key], key
    mix = ctx["mix"]
    assert (mix["seq_len"], mix["local_batch_size"],
            mix["global_batch_size_per_chip"], mix["sequences"]) == (
                16384, 1, 2, 128)
    assert mix["documents"] == dict(other["documents"], max_tokens=16384)
    assert mix["check"]["updates"] == 2
    assert flops_keye.micro_batches(mix) == 2
    limits = mix["check"]["limits"]
    assert set(limits) >= {"loss_gap_first", "all_grad_rel_diff", "feed_faults",
                           "index_grad_rel_diff"}


@pytest.mark.parametrize("name", [
    "all_grad_rel_diff", "head_grad_rel_diff", "grad_global_norm_gap",
    "grad_norm_gap_worst_leaf", "index_grad_rel_diff", "loss_gap_first",
    "loss_gap_later"])
def test_a_limit_lies_between_the_cells_own_two_readings(name):
    """With room on both sides: the largest sound seed passes with half as
    much again to spare, and the control's reading fails by as much (a limit
    copied from a sibling cell can lie above this cell's control)."""
    check = _cell()["mix"]["check"]
    sound, control = (check["readings"][name][k] for k in ("sound", "control"))
    assert 1.5 * sound < check["limits"][name] < control / 1.5


def test_the_numbers_without_a_control_reading_are_the_exact_ones():
    check = _cell()["mix"]["check"]
    assert set(check["limits"]) - set(check["readings"]) == {
        "delta_norm_gap_worst_leaf", "feed_faults", "objective_leak_rel",
        "chosen_pairs_gap"}
    assert check["limits"]["objective_leak_rel"] == 1e-6
    assert check["limits"]["chosen_pairs_gap"] == 1e-6
    # a choice one key short of topk, by arithmetic, at the cell's sizes
    full = flops_keye.chosen_pairs(16384, 2048)
    assert (full - flops_keye.chosen_pairs(16384, 2047)) / full > 4e-4


# -- FLOPs, operations, bytes --------------------------------------------------------

def test_model_flops_are_the_issues_arithmetic():
    ctx = _cell()
    layers = ctx["config"]["num_hidden_layers"]
    parts = {k: v / 1e6 for k, v in flops_keye.forward_flops_per_token(
        ctx["config"], 16384).items()}
    assert parts["attention_proj"] / layers == pytest.approx(37.7, abs=0.1)
    assert parts["indexer_proj"] / layers == pytest.approx(4.5, abs=0.1)
    assert parts["indexer_scores"] / layers == pytest.approx(16.8, abs=0.1)
    assert parts["sparse_core"] / layers == pytest.approx(31.5, abs=0.1)
    assert parts["experts"] / layers == pytest.approx(5.2, abs=0.1)
    assert parts["head"] == pytest.approx(78.1, abs=0.1)
    new = parts["indexer_proj"] + parts["indexer_scores"] + parts["sparse_core"]
    assert new / sum(parts.values()) == pytest.approx(0.50, abs=0.02)
    assert flops_keye.train_flops_per_update(
        ctx["config"], ctx["mix"], 1) == pytest.approx(92.9e12, rel=0.01)
    # the program's own count agrees with the yardstick's
    from bert_pytorch_tpu.config import load_model_config
    from bert_pytorch_tpu.utils import flops

    mine = flops.keye_vl_forward_flops_per_token(
        load_model_config(ctx["config_file"]), 16384)
    for name, value in mine.items():
        assert value / 1e6 == pytest.approx(parts[name], rel=1e-9), name


def test_the_pairs_are_the_models_causal_for_the_scores_chosen_for_the_core():
    assert flops_keye.causal_pairs(16384) == 134_225_920
    assert flops_keye.chosen_pairs(16384, 2048) == 31_458_304
    assert flops_keye.chosen_pairs(100, 2048) == flops_keye.causal_pairs(100)
    ctx = _cell()
    config, mix = ctx["config"], ctx["mix"]
    work, traffic = flops_keye.indexer_call(config, mix, "forward")
    assert work == 2 * 16 * 64 * 134_225_920
    assert traffic == 16384 * (2 * (1024 + 64) + 64) + 16384 * 16384 // 8
    assert flops_keye.indexer_call(config, mix, "recompute") == (work, traffic)
    back, _ = flops_keye.indexer_call(config, mix, "backward")
    assert back == 4 * 16 * 64 * 31_458_304
    work, traffic = flops_keye.sparse_core_call(config, mix, "forward")
    assert work == 4 * 128 * 32 * 31_458_304
    assert traffic == 16384 * 128 * 2 * 72 + 16384 * 16384 // 8
    assert flops_keye.sparse_core_call(config, mix, "backward")[0] == 2 * work
    with pytest.raises(ValueError):
        flops_keye.sparse_core_call(config, mix, "sideways")


# -- the rules -----------------------------------------------------------------------

SCAN = "jit(step_fn)/micro_batches/while/body/closed_call/"
FWD = SCAN + "jvp(KeyeVLForCausalLM.hidden_states)/"
BWD = SCAN + "transpose(jvp(KeyeVLForCausalLM.hidden_states))/"
REMAT = BWD.replace("transpose(", "rematted_computation/transpose(")
L1 = "layers_1/"


@pytest.mark.parametrize("op_name,instruction,expected", [
    (FWD + L1 + "attention/dsa/dsa_index_proj/index_q/dot_general", "%fusion.1",
     ("forward", "dsa_index_proj")),
    (FWD + L1 + "attention/dsa/dsa_select/dsa_select/pallas_call",
     "%dsa_select.1", ("forward", "dsa_select")),
    (FWD + L1 + "attention/dsa/while/body/dsa_scores/dot_general", "%fusion.2",
     ("forward", "dsa_scores")),
    (FWD + L1 + "attention/dsa/dsa_core/dsa_core_fwd/pallas_call",
     "%dsa_core_fwd.3", ("forward", "dsa_core")),
    (BWD + L1 + "attention/dsa/dsa_core/dsa_core_bwd_dkv/pallas_call",
     "%dsa_core_bwd_dkv.3", ("backward", "dsa_core")),
    (BWD + L1 + "attention/dsa/dsa_core/transpose", "%fusion.9",
     ("backward", "dsa_core")),
    (FWD + L1 + "attention/dsa/dsa_index_loss/dsa_index_loss/pallas_call",
     "%dsa_index_loss.2", ("forward", "dsa_index_loss")),
    (FWD + L1 + "attention/dsa/population_count", "%fusion.3",
     ("forward", "dsa_other")),
    (FWD + L1 + "attention/attn_qkv/q_proj/dot_general", "%fusion.4",
     ("forward", "attn_qkv")),
    (FWD + L1 + "attention/attn_qk_norm/q_norm/rsqrt", "%fusion.5",
     ("forward", "attn_qk_norm")),
    (FWD + "attn_rope/cos", "%fusion.6", ("forward", "attn_rope")),
    (BWD + L1 + "attention/attn_out/o_proj/dot_general", "%fusion.7",
     ("backward", "attn_out")),
    (FWD + L1 + "mlp/moe/moe_route/top_k", "%fusion.8", ("forward", "moe_route")),
    (FWD + L1 + "mlp/moe/while/body/moe_experts/gmm/pallas_call", "%gmm.1",
     ("forward", "moe_experts")),
    (FWD + L1 + "attention_norm/rsqrt", "%fusion.10", ("forward", "norm")),
    (SCAN + "while/body/lm_head/dot_general", "%fusion.11", ("other", "lm_head")),
    ("jit(step_fn)/optimizer/clip/mul", "%fusion.12", ("optimizer", "optimizer")),
])
def test_pass_and_part_rules_of_the_family(op_name, instruction, expected):
    assert scopes.classify(op_name, instruction, scopes_keye.rules()) == expected


def test_the_rules_name_only_scopes_the_program_writes():
    from bert_pytorch_tpu import pretrain

    written = set(pretrain.SCOPES) | set(pretrain.KEYE_SCOPES)
    rules = scopes_keye.rules()
    for rule in rules["part"]:
        if rule["name"] in ("norm", "layers", "micro_batch_scan",
                            "unnamed_copies", "accumulate", "moe_other",
                            "dsa_other", "step_metrics", "optimizer"):
            continue
        assert rule["name"] in written, rule["name"]
    assert set(scopes_keye.FAMILY_PARTS) <= {r["name"] for r in rules["part"]}
    assert rules["kernels"] == ["dsa_core_fwd", "dsa_core_bwd_dq",
                                "dsa_core_bwd_dkv"]


# -- the readers -------------------------------------------------------------------

def _family_planes():
    ms = 1e6
    dsa = FWD + "layers_0/attention/dsa/"
    attn = FWD + "layers_0/attention/"
    moe = FWD + "layers_0/mlp/moe/"
    ops = [
        ["%fusion.1", 0.0, 1 * ms, dsa + "dsa_index_proj/index_q/dot_general"],
        ["%dsa_select.1", 1 * ms, 5 * ms, dsa + "dsa_select/dsa_select/pallas_call"],
        ["%dsa_core_fwd.1", 6 * ms, 8 * ms, dsa + "dsa_core/dsa_core_fwd/pallas_call"],
        ["%fusion.2", 14 * ms, 1 * ms, dsa + "dsa_core/transpose"],
        ["%dsa_index_loss.1", 15 * ms, 6 * ms,
         dsa + "dsa_index_loss/dsa_index_loss/pallas_call"],
        ["%fusion.3", 21 * ms, 1 * ms, dsa + "population_count"],
        ["%fusion.4", 22 * ms, 2 * ms, attn + "attn_qkv/q_proj/dot_general"],
        ["%fusion.5", 24 * ms, 1 * ms, attn + "attn_qk_norm/q_norm/rsqrt"],
        ["%fusion.6", 25 * ms, 1 * ms, attn + "attn_out/o_proj/dot_general"],
        ["%fusion.7", 26 * ms, 1 * ms, moe + "moe_route/top_k"],
        ["%fusion.8", 27 * ms, 1 * ms, moe + "moe_dispatch/sort"],
        ["%gmm.1", 28 * ms, 2 * ms, moe + "while/body/moe_experts/gmm/pallas_call"],
        ["%fusion.9", 30 * ms, 2 * ms, FWD + "while/body/lm_head/dot_general"],
        ["%while.1", 32 * ms, 1 * ms, None],
    ]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": reduce.OPS_LINE, "events": ops}]}]


@pytest.fixture
def traced(monkeypatch):
    def with_planes(planes):
        from benchmarks.trace import scopes_lm

        monkeypatch.setattr(scopes_keye, "_reductions", {})
        monkeypatch.setattr(scopes_lm, "_reductions", {})
        monkeypatch.setattr(scopes, "newest_trace", lambda under=None: "a.xplane.pb")
        monkeypatch.setattr(scopes, "read_xspace", lambda path: planes)
        ctx = _cell()
        return {"summary": {"busy_s": 33e-3}, "updates": 2, "chips": 1,
                "peak_flops": 197e12, "device_kind": "TPU v5 lite",
                "config": ctx["config"], "mix": ctx["mix"],
                "counters": {"moe_local_slots": 16000.0}, "trace_dir": "x"}
    return with_planes


def test_readers_on_a_small_trace_of_the_family(traced):
    ctx = traced(_family_planes())
    read = lambda name: _reader(name)(ctx)
    assert read("sparse_attention_device_ms.train") == pytest.approx(11.0)
    assert read("indexer_device_ms.train") == pytest.approx(3.5)
    assert read("index_select_device_ms.train") == pytest.approx(2.5)
    assert read("sparse_core_device_ms.train") == pytest.approx(4.5)
    assert read("keye_attention_proj_device_ms.train") == pytest.approx(2.0)
    assert read("keye_unattributed_device_pct.train") == pytest.approx(100 / 33)
    assert read("keye_expert_mfu_pct.train") == pytest.approx(
        100 * 3 * 6 * 2048 * 768 * 16000 / (1e-3 * 197e12))
    config, mix = ctx["config"], ctx["mix"]
    floor = lambda call: sum(
        max(w / 197e12, b / 819e9) for w, b in (
            call(config, mix, which) for which in flops_keye.PASSES))
    # 2 updates x 9 layers x 2 micro-batches of three passes, over the time
    # the scopes took in the trace
    calls = 2 * config["num_hidden_layers"] * 2
    assert read("sparse_core_roofline_pct.train") == pytest.approx(
        100 * calls * floor(flops_keye.sparse_core_call) / 9e-3)
    assert read("indexer_roofline_pct.train") == pytest.approx(
        100 * calls * floor(flops_keye.indexer_call) / 11e-3)
    # the readers shared with the other decoders read the same trace rightly
    assert read("lm_head_device_ms.train") == pytest.approx(1.0)
    assert read("moe_device_ms.train") == pytest.approx(2.0)
    assert read("moe_dispatch_device_ms.train") == pytest.approx(1.0)


def test_a_trace_without_the_family_gives_nothing_and_does_not_raise(traced):
    other = [{"name": "/device:TPU:0", "lines": [{"name": reduce.OPS_LINE, "events": [
        ["%fusion.1", 0.0, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(NemotronHForCausalLM)/layers_0/mixer/ssm_mixer/ssd_scan/dot"],
        ["%flash_gated_fwd.1", 5e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(Qwen3NextForCausalLM)/layers_3/mixer/attention_core/flash_gated_fwd"],
        ["%fusion.2", 10e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/attn/attn_qkv/q_proj/dot_general"],
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
                  "index_grad_rel_diff": 2e-3, "objective_leak_rel": 1e-6,
                  "chosen_pairs_gap": 1e-6}


def _tiny_run(fault=None, controls=()):
    tmp = tempfile.mkdtemp()
    ctx = cpu_cell_keye.tiny_context(CELL, 2 ** 31 + 77, 0.3, tmp)
    ctx["mix"]["trainer_args"] = ["--dtype", "float32", "--remat", "full"]
    ctx["mix"]["check"] = dict(ctx["mix"]["check"], limits=FLOAT32_LIMITS)
    ctx["controls"] = list(controls)
    kind = bench_run.load_module(ctx["kind_file"], "kind_under_test")
    if not fault:
        return kind.measure(ctx)
    # the fault lives in the PROGRAM alone: the reference runs after the
    # trainer has returned, with the program's modules as they were
    return faults_keye.read(ctx, kind, fault)


def test_sound_in_float32_and_the_control_fails():
    result = _tiny_run(controls=["fp8"])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compiles_in_window"] == 0
    counters = result["counters"]
    assert counters["moe_dropped_slots"] == 0.0
    # 2 layers x 2 micro-batches of one row of 64 at topk 16
    assert counters["dsa_pairs_run"] == 2 * 2 * (136 + 48 * 16)
    assert counters["dsa_scored_pairs_run"] == 2 * 2 * 2080
    assert counters["dsa_index_kl"] > 0
    readings = result["readings"]
    assert readings["routing_flip_share"] < 0.01
    assert readings["selection_flip_share"] < 0.01
    assert readings["index_grad_rel_diff"] < 1e-3
    assert readings["objective_leak_rel"] == 0.0
    assert readings["chosen_pairs_gap"] == 0.0
    control = result["controls"]["fp8"]
    assert any(control[k] > FLOAT32_LIMITS[k] for k in control)
    assert control["all_grad_rel_diff"] > 10 * readings["all_grad_rel_diff"]
    assert control["index_grad_rel_diff"] > 10 * readings["index_grad_rel_diff"]
    assert "selected" not in json.dumps(result["raw"])  # (too large to write)


@pytest.mark.parametrize("fault, seen_by", [
    ("index_loss_left_out", "index_grad_rel_diff"),
    ("indexer_input_not_detached", "objective_leak_rel"),
    ("top_k_one_short", "chosen_pairs_gap"),
    ("micro_batch_dropped", "all_grad_rel_diff")])
def test_a_planted_fault_is_not_correct(fault, seen_by):
    """... and the number named sees it at THE CELL'S limit, not only at this
    float32 run's: it reads what no rounding moves (1.0 of a gradient left
    out or of another batch's, 0 against a count or a leak)."""
    result = _tiny_run(fault)
    assert result["correct"] is False
    limit = _cell()["mix"]["check"]["limits"][seen_by]
    assert result["readings"][seen_by] > 10 * limit


def test_the_kind_is_the_laguna_kind_over_another_family():
    """Nothing of ``train_laguna.py`` is written again but the two numbers
    this family adds: this kind's functions are that file's, loaded a second
    time; the laguna cell's own copy still names its own."""
    from benchmarks.kinds import train_laguna
    from benchmarks.reference import keye_f32, laguna_f32

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    assert kind.base is not train_laguna
    assert kind.base.__file__ == train_laguna.__file__
    assert kind.measure is kind.base.measure and kind.run is kind.base.run
    assert kind.base.compare_with_reference is kind.compare_with_reference
    assert kind.base.Probes is kind.Probes
    assert kind.base.family()[0] is keye_f32
    assert kind.base.COUNTERS == ("moe_", "dsa_")
    assert train_laguna.family()[0] is laguna_f32
    assert train_laguna.COUNTERS == ("moe_", "attn_")
    assert train_laguna.Probes is not kind.Probes


def test_selection_flip_share_counts_the_pairs_one_side_chose_alone():
    import numpy as np

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    mine = np.zeros((1, 4, 2), np.uint8)
    theirs = mine.copy()
    mine[0, 0, 0], theirs[0, 0, 0] = 0b1100, 0b1010   # one key swapped
    mine[0, 1, 1] = theirs[0, 1, 1] = 0b1111
    assert kind.selection_flip_share([mine], [theirs]) == pytest.approx(2 / 12)
    assert kind.selection_flip_share([theirs], [theirs]) == 0.0


def test_a_program_without_the_family_is_told_so_at_once(monkeypatch):
    from bert_pytorch_tpu import config as program_config

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    monkeypatch.setattr(program_config, "MODEL_FAMILIES", {
        k: v for k, v in program_config.MODEL_FAMILIES.items()
        if k != "KeyeVL2"})
    with pytest.raises(SystemExit, match="unknown model_type 'KeyeVL2'"):
        kind.run(dict(_cell(), seed=1, seconds=1.0, trace=False, started=0.0))
