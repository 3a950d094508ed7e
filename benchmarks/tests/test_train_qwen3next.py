"""The ``train_qwen3next`` kind, its FLOP counts, its kernels' and the rule's
operation and byte counts, its rules and its readers, on the CPU: the counts
against the issue's arithmetic, the scope rules of ``scopes_qwen3next.json`` on
op names as the program writes them, the readers on a small synthetic trace
(and on none, and on another decoder's trace: nothing to read, no raise), and
how ``correct`` is decided at a size a test can hold: sound in float32, the
lower-precision control failing, and five faults planted under the harness
(the correction read from the undecayed state, ``beta`` left out, the top-k
weights not renormalised, the shared expert's gate dropped, the rule's state
in bfloat16) each coming out not correct."""

import json
import os
import tempfile

import pytest

import benchmarks.run as bench_run
from benchmarks.rehearse import cpu_cell_qwen3next
from benchmarks.trace import flops_qwen3next, reduce, scopes, scopes_qwen3next

ROOT = bench_run.ROOT
CELL = "train-qwen3-next-80b-seq8192"
NEW_METRICS = (
    "gdn_device_ms.train", "delta_rule_device_ms.train",
    "delta_rule_roofline_pct.train", "gated_attention_device_ms.train",
    "flash_gated_roofline_pct.train", "qwen_attention_proj_device_ms.train",
    "qwen_expert_mfu_pct.train", "qwen_unattributed_device_pct.train")
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
    # (found by name, never by place: the next cell is appended after this one)
    for name in SHARED_METRICS:
        assert CELL in entries[name]["workloads"]
    for name in ("ssm_device_ms.train", "dense_mlp_device_ms.train",
                 "attention_proj_device_ms.train", "glu_expert_mfu_pct.train",
                 "moe_expert_mfu_pct.train", "zaya_expert_mfu_pct.train",
                 "flash_causal_roofline_pct.train",
                 "zaya_unattributed_device_pct.train"):  # not this family's
        assert CELL not in entries[name]["workloads"]
    assert len(bench["configs"]) >= 6 and len(bench["workloads"]) >= 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "lm-seq8192-qwen3next")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["name"] == "qwen3-next-80b-a3b"
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    ctx = _cell()
    assert ctx["mix"]["kind"] == "train_qwen3next"
    assert ctx["config"]["model_type"] == "qwen3_next"
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
                         if '"name": "Qwen3-Next-80B-A3B-Instruct"' in line)
        assert config["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            if key not in config["reduced"]:
                assert ctx["config"][key] == value, key
    # the zaya cell's traffic (4 micro-batches of 2 rows) but for the kind,
    # the limits and the schedule's length, which is the other decoders'
    other = bench_run.context(ROOT, "train-zaya1-8b-seq8192")["mix"]
    for key in set(other) - {"kind", "check", "recipe"}:
        assert ctx["mix"][key] == other[key], key
    assert ctx["mix"]["recipe"] == bench_run.context(
        ROOT, "train-laguna-s-seq8192")["mix"]["recipe"]
    assert ctx["mix"]["check"]["updates"] == 2
    assert (ctx["mix"]["global_batch_size_per_chip"] * ctx["mix"]["seq_len"]
            == 65536)
    assert flops_qwen3next.micro_batches(ctx["mix"]) == 4
    limits = ctx["mix"]["check"]["limits"]
    assert set(limits) >= {"loss_gap_first", "all_grad_rel_diff", "feed_faults"}


# -- FLOPs, operations, bytes --------------------------------------------------------

def test_model_flops_are_the_issues_arithmetic():
    ctx = _cell()
    parts = {k: v / 1e6 for k, v in flops_qwen3next.forward_flops_per_token(
        ctx["config"], 8192).items()}
    assert parts["gdn_proj"] == pytest.approx(202.1, abs=0.1)
    assert parts["delta_rule"] == pytest.approx(15.7, abs=0.1)
    assert parts["attention_proj"] == pytest.approx(54.5, abs=0.1)
    assert parts["attention_core"] == pytest.approx(67.1, abs=0.1)
    assert parts["experts"] == pytest.approx(49.3, abs=0.1)
    assert parts["head"] == pytest.approx(78.1, abs=0.1)
    total = sum(parts.values())
    assert total == pytest.approx(466.9, abs=0.2)
    assert flops_qwen3next.train_flops_per_update(
        ctx["config"], ctx["mix"], 1) == pytest.approx(3 * 65536 * total * 1e6)
    assert 3 * 65536 * total * 1e6 == pytest.approx(91.8e12, rel=2e-3)
    # the shares the cell's ``why`` states
    mixers = parts["gdn_proj"] + parts["delta_rule"]
    attention = parts["attention_proj"] + parts["attention_core"]
    assert mixers / total == pytest.approx(0.47, abs=0.01)
    assert attention / total == pytest.approx(0.26, abs=0.01)
    assert parts["head"] / total == pytest.approx(0.17, abs=0.01)
    # of the whole model the head is 8%
    whole = dict(ctx["config"], num_hidden_layers=48, num_experts=512,
                 ep_size=1, vocab_size=151936)
    all_of_it = flops_qwen3next.forward_flops_per_token(whole, 8192)
    assert all_of_it["head"] / sum(all_of_it.values()) == pytest.approx(
        0.08, abs=0.01)
    # the program's own copy agrees (it may drift later; the yardstick may not)
    from bert_pytorch_tpu.config import load_model_config
    from bert_pytorch_tpu.utils import flops as program_flops
    assert program_flops.causal_lm_train_flops_per_seq(
        load_model_config(ctx["config_file"]), 8192) == pytest.approx(
            3 * 8192 * total * 1e6)


def test_a_flash_call_counts_the_causal_half_of_sixteen_heads_of_256():
    ctx = _cell()
    triangle = 8192 * 8193 / 2
    work, traffic = flops_qwen3next.flash_gated_call(
        ctx["config"], ctx["mix"], "flash_gated_fwd")
    assert work == pytest.approx(2 * 2 * 256 * triangle * 2 * 16)
    assert traffic == 4 * 2 * 16 * 8192 * 256 * 2
    dq, dq_bytes = flops_qwen3next.flash_gated_call(
        ctx["config"], ctx["mix"], "flash_gated_bwd_dq")
    dkv, dkv_bytes = flops_qwen3next.flash_gated_call(
        ctx["config"], ctx["mix"], "flash_gated_bwd_dkv")
    assert (dq, dkv) == (pytest.approx(1.5 * work), pytest.approx(2 * work))
    assert (dq_bytes, dkv_bytes) == (traffic * 5 // 4, traffic * 6 // 4)
    assert set(flops_qwen3next.GATED_KERNELS) == set(
        scopes_qwen3next.rules()["kernels"])
    # 67.1 M a token forward over 16,384 tokens is one forward call
    assert work == pytest.approx(67.1e6 * 16384, rel=2e-3)


def test_a_pass_of_the_rule_counts_its_operands_once():
    ctx = _cell()
    work, traffic = flops_qwen3next.delta_rule_call(
        ctx["config"], ctx["mix"], "forward")
    tokens = 2 * 8192
    # q, k (16 heads), v (32) bf16 and g, beta float32 in; o bf16 out
    assert traffic == tokens * (2 * (2 * 2048 + 4096) + 2 * 4 * 32 + 2 * 4096)
    per_token = (4 * 64 * 2048 + 32 * (2 * 64 * 384 + 6 * 128 * 128))
    assert flops_qwen3next.rule_flops_per_token(ctx["config"]) == per_token
    assert work == tokens * per_token
    assert flops_qwen3next.delta_rule_call(
        ctx["config"], ctx["mix"], "recompute") == (work, traffic)
    back_work, back_traffic = flops_qwen3next.delta_rule_call(
        ctx["config"], ctx["mix"], "backward")
    assert back_work == 2 * work
    assert back_traffic == 2 * (traffic - tokens * 2 * 4096) + tokens * 2 * 4096
    # at the two peaks the two bounds lie within a fifth of each other: the
    # bytes bound the forward (0.50 against 0.44 ms), the products the backward
    assert 1.0 < (traffic / 819e9) / (work / 197e12) < 1.2
    assert back_work / 197e12 > back_traffic / 819e9
    with pytest.raises(ValueError, match="pass"):
        flops_qwen3next.delta_rule_call(ctx["config"], ctx["mix"], "sideways")


# -- the rules -------------------------------------------------------------------

SCAN = "jit(step_fn)/micro_batches/while/body/closed_call/"
FWD = SCAN + "jvp(Qwen3NextForCausalLM)/"
BWD = SCAN + "transpose(jvp(Qwen3NextForCausalLM))/"
L1 = "layers_1/checkpoint/layers_1/"
L3 = "layers_3/checkpoint/layers_3/"


@pytest.mark.parametrize("op_name,instruction,expected", [
    (FWD + L3 + "mixer/attention_core/flash_gated_fwd/pallas_call",
     "%flash_gated_fwd.3", ("forward", "flash_gated")),
    (BWD + L3 + "mixer/attention_core/flash_gated_bwd_dkv/pallas_call",
     "%flash_gated_bwd_dkv.1", ("backward", "flash_gated")),
    (BWD + "layers_3/checkpoint/rematted_computation/layers_3/mixer/"
     "attention_core/flash_gated_fwd/pallas_call", "%flash_gated_fwd.4",
     ("recompute", "flash_gated")),
    (FWD + L3 + "mixer/attention_core/broadcast_in_dim", "%fusion.1",
     ("forward", "attention_core")),
    (FWD + L3 + "mixer/attn_qkv/q_proj/dot_general", "%fusion.2",
     ("forward", "attn_qkv")),
    (FWD + L3 + "mixer/attn_qk_norm/q_norm/rsqrt", "%fusion.3",
     ("forward", "attn_qk_norm")),
    (FWD + L3 + "mixer/attn_rope/rotary_turn/pallas_call", "%rotary_turn.3",
     ("forward", "attn_rope")),
    (FWD + "attn_rope/cos", "%fusion.8", ("forward", "attn_rope")),
    (BWD + L3 + "mixer/attn_gate/logistic", "%fusion.4",
     ("backward", "attn_gate")),
    (FWD + L3 + "mixer/attn_out/o_proj/dot_general", "%fusion.9",
     ("forward", "attn_out")),
    (FWD + L1 + "mixer/gdn/gdn_in_proj/in_proj_qkvz/dot_general", "%fusion.10",
     ("forward", "gdn_in_proj")),
    (FWD + L1 + "mixer/gdn/gdn_conv/ssm_conv/mul", "%fusion.11",
     ("forward", "gdn_conv")),
    (BWD + L1 + "mixer/gdn/gdn_gates/softplus", "%fusion.12",
     ("backward", "gdn_gates")),
    (FWD + L1 + "mixer/gdn/delta_rule/bhncd,bhnmd->bhncm/dot_general",
     "%fusion.13", ("forward", "delta_rule")),
    (BWD + L1 + "mixer/gdn/delta_rule/while/body/bhrcd,bhrde->bhrce/dot_general",
     "%fusion.14", ("backward", "delta_rule")),
    (BWD + "layers_1/checkpoint/rematted_computation/layers_1/mixer/gdn/"
     "delta_rule/while/body/mul", "%fusion.15", ("recompute", "delta_rule")),
    (FWD + L1 + "mixer/gdn/gdn_gate_norm/rsqrt", "%fusion.16",
     ("forward", "gdn_gate_norm")),
    (FWD + L1 + "mixer/gdn/gdn_out_proj/out_proj/dot_general", "%fusion.17",
     ("forward", "gdn_out_proj")),
    (FWD + L1 + "mixer/gdn/slice", "%fusion.18", ("forward", "gdn_other")),
    (FWD + L1 + "mlp/moe/moe_route/top_k", "%fusion.19", ("forward", "moe_route")),
    (FWD + L1 + "mlp/moe/moe_dispatch/sort", "%fusion.20",
     ("forward", "moe_dispatch")),
    (FWD + L1 + "mlp/moe/while/body/moe_experts/gmm/pallas_call", "%gmm.3",
     ("forward", "moe_experts")),
    (BWD + L1 + "mlp/moe/while/body/moe_combine/scatter-add", "%fusion.21",
     ("backward", "moe_combine")),
    (FWD + L1 + "mlp/moe/moe_shared/moe_shared_gate/bsh,h->bs/dot_general",
     "%fusion.22", ("forward", "moe_shared_gate")),
    (FWD + L1 + "mlp/moe/moe_shared/shared_up/dot_general", "%fusion.23",
     ("forward", "moe_shared")),
    (FWD + L1 + "mlp/moe/while", "%while.9", ("forward", "moe_other")),
    (FWD + L1 + "mixer_norm/mul", "%fusion.24", ("forward", "norm")),
    (FWD + L1 + "mlp_norm/mul", "%fusion.25", ("forward", "norm")),
    (BWD + "while/body/checkpoint/lm_head/dot_general", "%fusion.26",
     ("backward", "lm_head")),
    ("jit(step_fn)/optimizer/clip/mul", "%fusion.40", ("optimizer", "optimizer")),
    (SCAN + "grad_accumulate/add", "%fusion.41", ("other", "accumulate")),
    (None, "%copy.3", ("other", "unnamed_copies")),
    (None, "%while.3", ("other", None)),
])
def test_pass_and_part_rules_of_the_family(op_name, instruction, expected):
    assert scopes.classify(op_name, instruction,
                           scopes_qwen3next.rules()) == expected


def test_the_rules_name_only_scopes_the_program_writes():
    from bert_pytorch_tpu import pretrain

    table = scopes_qwen3next.rules()
    named = {f.strip("/") for rule in table["part"] for f in rule["fragments"]}
    scope_like = {f for f in named if f.replace("_", "").isalnum()
                  and not f.startswith(("flash_", "mixer_norm", "mlp_norm",
                                        "final_norm"))}
    written = set(pretrain.SCOPES) | set(pretrain.QWEN3_NEXT_SCOPES)
    assert scope_like <= written | {"optimizer", "step_metrics", "layers_",
                                    "gmm", "tgmm"}, scope_like - written
    parts = {rule["name"] for rule in table["part"]}
    assert (set(scopes_qwen3next.GDN_PARTS) | set(scopes_qwen3next.FAMILY_PARTS)
            | set(scopes_qwen3next.ATTENTION_PROJ_PARTS)) <= parts
    # the readers shared with the other decoders place this family's ops too:
    # the shared expert's gate under moe_shared, the head's pieces
    from benchmarks.trace import scopes_lm
    shared = scopes_lm.rules()
    assert scopes.classify(
        FWD + L1 + "mlp/moe/moe_shared/moe_shared_gate/bsh,h->bs/dot_general",
        "%fusion.1", shared) == ("forward", "moe_shared")
    assert scopes.classify(BWD + "while/body/checkpoint/lm_head/dot_general",
                           "%fusion.1", shared) == ("backward", "lm_head")
    assert scopes.classify(FWD + L1 + "mlp/moe/while", "%while.9",
                           shared) == ("forward", "moe_other")


# -- the readers -------------------------------------------------------------------

def _family_planes():
    ms = 1e6
    gdn = FWD + "layers_0/mixer/gdn/"
    attn = FWD + "layers_3/mixer/"
    moe = FWD + "layers_0/mlp/moe/"
    ops = [
        ["%fusion.1", 0.0, 4 * ms, gdn + "gdn_in_proj/in_proj_qkvz/dot_general"],
        ["%fusion.2", 4 * ms, 2 * ms, gdn + "gdn_conv/ssm_conv/mul"],
        ["%fusion.3", 6 * ms, 1 * ms, gdn + "gdn_gates/softplus"],
        ["%fusion.4", 7 * ms, 8 * ms, gdn + "delta_rule/while/body/dot_general"],
        ["%fusion.5", 15 * ms, 1 * ms, gdn + "gdn_gate_norm/rsqrt"],
        ["%fusion.6", 16 * ms, 2 * ms, gdn + "gdn_out_proj/out_proj/dot_general"],
        ["%fusion.7", 18 * ms, 2 * ms, attn + "attn_qkv/q_proj/dot_general"],
        ["%fusion.8", 20 * ms, 1 * ms, attn + "attn_qk_norm/q_norm/rsqrt"],
        ["%rotary_turn.1", 21 * ms, 1 * ms, attn + "attn_rope/rotary_turn/pallas_call"],
        ["%flash_gated_fwd.1", 22 * ms, 6 * ms,
         attn + "attention_core/flash_gated_fwd/pallas_call"],
        ["%fusion.9", 28 * ms, 2 * ms, attn + "attention_core/broadcast_in_dim"],
        ["%fusion.10", 30 * ms, 1 * ms, attn + "attn_gate/logistic"],
        ["%fusion.11", 31 * ms, 1 * ms, attn + "attn_out/o_proj/dot_general"],
        ["%fusion.12", 32 * ms, 1 * ms, moe + "moe_route/top_k"],
        ["%fusion.13", 33 * ms, 1 * ms, moe + "moe_dispatch/sort"],
        ["%gmm.1", 34 * ms, 4 * ms, moe + "while/body/moe_experts/gmm/pallas_call"],
        ["%fusion.14", 38 * ms, 1 * ms,
         moe + "moe_shared/moe_shared_gate/dot_general"],
        ["%fusion.15", 39 * ms, 1 * ms, moe + "moe_shared/shared_up/dot_general"],
        ["%fusion.16", 40 * ms, 2 * ms, FWD + "while/body/lm_head/dot_general"],
        ["%while.1", 42 * ms, 1 * ms, None],
    ]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": reduce.OPS_LINE, "events": ops}]}]


@pytest.fixture
def traced(monkeypatch):
    def with_planes(planes):
        from benchmarks.trace import scopes_lm

        monkeypatch.setattr(scopes_qwen3next, "_reductions", {})
        monkeypatch.setattr(scopes_lm, "_reductions", {})
        monkeypatch.setattr(scopes, "newest_trace", lambda under=None: "a.xplane.pb")
        monkeypatch.setattr(scopes, "read_xspace", lambda path: planes)
        ctx = _cell()
        return {"summary": {"busy_s": 43e-3}, "updates": 2, "chips": 1,
                "peak_flops": 197e12, "device_kind": "TPU v5 lite",
                "config": ctx["config"], "mix": ctx["mix"],
                "counters": {"moe_local_slots": 160000.0}, "trace_dir": "x"}
    return with_planes


def test_readers_on_a_small_trace_of_the_family(traced):
    ctx = traced(_family_planes())
    read = lambda name: _reader(name)(ctx)
    assert read("gdn_device_ms.train") == pytest.approx(9.0)
    assert read("delta_rule_device_ms.train") == pytest.approx(4.0)
    assert read("gated_attention_device_ms.train") == pytest.approx(4.0)
    assert read("qwen_attention_proj_device_ms.train") == pytest.approx(3.0)
    assert read("qwen_unattributed_device_pct.train") == pytest.approx(100 / 43)
    assert read("qwen_expert_mfu_pct.train") == pytest.approx(
        100 * 3 * 6 * 2048 * 512 * 160000 / (2e-3 * 197e12))
    # one forward call of the kernel in 6 ms of its own
    work, traffic = flops_qwen3next.flash_gated_call(
        ctx["config"], ctx["mix"], "flash_gated_fwd")
    assert read("flash_gated_roofline_pct.train") == pytest.approx(
        100 * max(work / 197e12, traffic / 819e9) / 6e-3)
    # 2 updates x 3 layers x 4 micro-batches of three passes, by their
    # products, over the 8 ms the scope took
    least = sum(max(w / 197e12, b / 819e9) for w, b in (
        flops_qwen3next.delta_rule_call(ctx["config"], ctx["mix"], which)
        for which in flops_qwen3next.RULE_PASSES))
    assert read("delta_rule_roofline_pct.train") == pytest.approx(
        100 * 2 * 3 * 4 * least / 8e-3)
    # the readers shared with the other decoders read the same trace rightly
    assert read("lm_head_device_ms.train") == pytest.approx(1.0)
    assert read("moe_device_ms.train") == pytest.approx(4.0)
    assert read("moe_dispatch_device_ms.train") == pytest.approx(1.0)


def test_a_trace_without_the_family_gives_nothing_and_does_not_raise(traced):
    other = [{"name": "/device:TPU:0", "lines": [{"name": reduce.OPS_LINE, "events": [
        ["%fusion.1", 0.0, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(NemotronHForCausalLM)/layers_0/mixer/ssm_mixer/ssd_scan/dot"],
        ["%flash_cca_fwd.1", 5e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(ZayaForCausalLM)/layers_1/attn/cca/attention_core/flash_cca_fwd"],
        ["%fusion.2", 10e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/attn/attn_qkv/q_proj/dot_general"],
        ["%gmm.1", 15e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/mlp/moe/while/body/moe_experts/gmm"],
        ["%fusion.3", 20e6, 2e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/mlp/moe/moe_shared/shared_up/dot"],
        ["%fusion.4", 22e6, 2e6, "jit(step_fn)/optimizer/clip/mul"]]}]}]
    ctx = traced(other)
    assert [_reader(name)(ctx) for name in NEW_METRICS] == [None] * len(NEW_METRICS)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_reader_returns_none_on_an_empty_context(name):
    assert _reader(name)({}) is None


# -- correct -----------------------------------------------------------------------

FLOAT32_LIMITS = {"loss_gap_first": 1e-4, "loss_gap_later": 1e-4,
                  "grad_global_norm_gap": 1e-3, "grad_norm_gap_worst_leaf": 5e-3,
                  "head_grad_rel_diff": 1e-3, "all_grad_rel_diff": 1e-3,
                  "delta_norm_gap_worst_leaf": 2e-2, "feed_faults": 0}


def _plant(monkeypatch, fault):
    import jax.numpy as jnp

    from benchmarks.reference import qwen3next_f32 as ref
    from bert_pytorch_tpu.models import decoder, qwen3_next

    def literal(**wrong):
        """The rule token by token (the reference's scan) with a fault in it,
        in the chunked rule's place."""
        def rule(q, k, v, g, beta, chunk):
            ratio = v.shape[2] // k.shape[2]
            return ref.recurrence(jnp.repeat(q, ratio, axis=2),
                                  jnp.repeat(k, ratio, axis=2), v, g, beta,
                                  **wrong).astype(q.dtype)
        return rule

    if fault == "correction_from_the_undecayed_state":
        monkeypatch.setattr(qwen3_next.delta_rule, "gated_delta_rule",
                            literal(faults=("undecayed_read",)))
    elif fault == "beta_left_out":
        monkeypatch.setattr(qwen3_next.delta_rule, "gated_delta_rule",
                            literal(faults=("no_beta",)))
    elif fault == "state_in_bfloat16":
        monkeypatch.setattr(qwen3_next.delta_rule, "gated_delta_rule",
                            literal(precision="fp8"))
    elif fault == "top_k_not_renormalised":
        real = decoder.moe.route
        monkeypatch.setattr(
            decoder.moe, "route",
            lambda x, w, bias, k, scale, norm_topk, score:
            real(x, w, bias, k, scale, False, score))
    elif fault == "shared_gate_dropped":
        monkeypatch.setattr(decoder, "gate_by_token", lambda y, x, vector: y)


def _tiny_run(monkeypatch=None, fault=None, controls=()):
    tmp = tempfile.mkdtemp()
    ctx = cpu_cell_qwen3next.tiny_context(CELL, 2 ** 31 + 77, 0.3, tmp)
    ctx["mix"]["trainer_args"] = ["--dtype", "float32", "--remat", "full"]
    ctx["mix"]["check"] = dict(ctx["mix"]["check"], limits=FLOAT32_LIMITS)
    ctx["controls"] = list(controls)
    kind = bench_run.load_module(ctx["kind_file"], "kind_under_test")
    if not fault:
        return kind.measure(ctx)
    # the fault lives in the PROGRAM alone: the reference runs after the
    # trainer has returned, with the program's modules as they were
    real_drive = kind.base.drive

    def drive_with_the_fault(*a, **k):
        with monkeypatch.context() as planted:
            _plant(planted, fault)
            return real_drive(*a, **k)

    monkeypatch.setattr(kind.base, "drive", drive_with_the_fault)
    return kind.measure(ctx)


def test_sound_in_float32_and_the_control_fails():
    result = _tiny_run(controls=["fp8"])
    assert result["correct"] is True and result["failed"] == 0
    assert result["compiles_in_window"] == 0
    counters = result["counters"]
    assert counters["moe_local_slots"] > 0
    assert counters["moe_dropped_slots"] == 0.0
    # 3 delta-rule layers x 2 rows x 8 chunks of 8, over 4 micro-batches
    assert counters["delta_chunks_run"] == 3 * 2 * 8 * 4
    # 4 micro-batches of 2 rows of 64 tokens through 4 layers, 3 slots a token
    assert counters["moe_local_slots"] < 4 * 512 * 3
    readings = result["readings"]
    assert readings["routing_flip_share"] < 0.01
    control = result["controls"]["fp8"]
    assert any(control[k] > FLOAT32_LIMITS[k] for k in control)
    assert control["all_grad_rel_diff"] > 10 * readings["all_grad_rel_diff"]


@pytest.mark.parametrize("fault", [
    "correction_from_the_undecayed_state", "beta_left_out",
    "top_k_not_renormalised", "shared_gate_dropped", "state_in_bfloat16"])
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    assert _tiny_run(monkeypatch, fault)["correct"] is False


def test_the_kind_is_the_laguna_kind_over_another_family():
    """Nothing of ``train_laguna.py`` is written again: this kind's functions
    are that file's, loaded a second time, and only ``family`` differs; the
    laguna cell's own copy still names its own."""
    from benchmarks.kinds import train_laguna
    from benchmarks.reference import laguna_f32, qwen3next_f32

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    assert kind.base is not train_laguna
    assert kind.base.__file__ == train_laguna.__file__
    assert kind.measure is kind.base.measure and kind.run is kind.base.run
    assert kind.compare_with_reference is kind.base.compare_with_reference
    assert kind.base.family()[0] is qwen3next_f32
    assert kind.base.COUNTERS == ("moe_", "delta_")
    assert train_laguna.family()[0] is laguna_f32
    assert train_laguna.COUNTERS == ("moe_", "attn_")


def test_a_program_without_the_family_is_told_so_at_once(monkeypatch):
    from bert_pytorch_tpu import config as program_config

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    monkeypatch.setattr(program_config, "MODEL_FAMILIES", {
        k: v for k, v in program_config.MODEL_FAMILIES.items()
        if k != "qwen3_next"})
    with pytest.raises(SystemExit, match="unknown model_type 'qwen3_next'"):
        kind.run(dict(_cell(), seed=1, seconds=1.0, trace=False, started=0.0))
