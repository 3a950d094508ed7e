"""The ``train_zaya`` kind, its FLOP counts, its kernels' and passes' operation
and byte counts, its rules and its readers, on the CPU: the counts against the
issue's arithmetic, the scope rules of ``scopes_zaya.json`` on op names as the
program writes them, the readers on a small synthetic trace (and on none, and
on another decoder's trace: nothing to read, no raise), and how ``correct`` is
decided at a size a test can hold: sound in float32, the lower-precision
control failing, and four faults planted under the harness (a convolution that
reads t + 1, the router's state of the layer before dropped, the top-1 weight
renormalised, bfloat16 in the norm of q and k) each coming out not correct."""

import json
import os
import tempfile

import pytest

import benchmarks.run as bench_run
from benchmarks.rehearse import cpu_cell_zaya
from benchmarks.trace import flops_zaya, reduce, scopes, scopes_zaya

ROOT = bench_run.ROOT
CELL = "train-zaya1-8b-seq8192"
NEW_METRICS = (
    "cca_device_ms.train", "cca_mix_device_ms.train",
    "cca_mix_roofline_pct.train", "cca_core_device_ms.train",
    "flash_cca_roofline_pct.train", "zaya_router_device_ms.train",
    "zaya_expert_mfu_pct.train", "residual_merge_device_ms.train",
    "zaya_unattributed_device_pct.train")
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
                 "moe_expert_mfu_pct.train", "phi_mlp_device_ms.train",
                 "laguna_unattributed_device_pct.train"):  # not this family's
        assert CELL not in entries[name]["workloads"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "lm-seq8192-zaya")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["name"] == "zaya1-8b"
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "layer_types"]
    ctx = _cell()
    assert ctx["mix"]["kind"] == "train_zaya"
    assert ctx["config"]["model_type"] == "zaya"
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
                         if '"name": "ZAYA1-8B"' in line)
        assert config["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            if key not in config["reduced"]:
                assert ctx["config"][key] == value, key
    # the decoder cells' traffic but for the kind, the limits and the rows a
    # micro-batch holds: twice the tokens an update, in as many micro-batches
    other = bench_run.context(ROOT, "train-laguna-s-seq8192")["mix"]
    for key in set(other) - {"kind", "check", "local_batch_size",
                             "global_batch_size_per_chip", "recipe"}:
        assert ctx["mix"][key] == other[key], key
    # the recipe but for the schedule's length: a 1% warm-up of 1000 updates
    assert ctx["mix"]["recipe"] == dict(other["recipe"], max_steps=100000)
    assert ctx["mix"]["check"]["updates"] == other["check"]["updates"]
    assert (ctx["mix"]["global_batch_size_per_chip"] * ctx["mix"]["seq_len"]
            == 65536)
    assert flops_zaya.micro_batches(ctx["mix"]) == flops_zaya.micro_batches(other)


# -- FLOPs, operations, bytes --------------------------------------------------------

def test_model_flops_are_the_issues_arithmetic():
    ctx = _cell()
    parts = {k: v / 1e6 for k, v in flops_zaya.forward_flops_per_token(
        ctx["config"], 8192).items()}
    assert parts["cca_core"] / 5 == pytest.approx(16.8, abs=0.05)
    assert parts["cca_proj"] / 5 == pytest.approx(11.1, abs=0.05)
    assert parts["router"] / 5 == pytest.approx(1.3, abs=0.05)
    assert parts["experts"] / 5 == pytest.approx(11.8, abs=0.05)
    assert parts["head"] == pytest.approx(134.7, abs=0.1)
    total = sum(parts.values())
    assert total == pytest.approx(340.2, abs=0.2)
    assert flops_zaya.train_flops_per_update(
        ctx["config"], ctx["mix"], 1) == pytest.approx(3 * 65536 * total * 1e6)
    assert 3 * 65536 * total * 1e6 == pytest.approx(66.9e12, rel=2e-3)
    # the head is 40% here; of the whole model (40 layers of 52.9 M against
    # 1,074 M) a third
    assert parts["head"] / total == pytest.approx(0.40, abs=0.005)
    whole = dict(ctx["config"], num_hidden_layers=40, num_experts=16, ep_size=1,
                 vocab_size=262272)
    all_of_it = flops_zaya.forward_flops_per_token(whole, 8192)
    assert all_of_it["head"] / sum(all_of_it.values()) == pytest.approx(
        0.335, abs=0.01)
    # the program's own copy agrees (it may drift later; the yardstick may not)
    from bert_pytorch_tpu.config import load_model_config
    from bert_pytorch_tpu.utils import flops as program_flops
    assert program_flops.causal_lm_train_flops_per_seq(
        load_model_config(ctx["config_file"]), 8192) == pytest.approx(
            3 * 8192 * total * 1e6)


def test_a_flash_call_counts_the_causal_half_of_eight_heads_of_128():
    ctx = _cell()
    triangle = 8192 * 8193 / 2
    work, traffic = flops_zaya.flash_cca_call(ctx["config"], ctx["mix"],
                                              "flash_cca_fwd")
    assert work == pytest.approx(2 * 2 * 128 * triangle * 2 * 8)
    assert traffic == 4 * 2 * 8 * 8192 * 128 * 2
    dq, dq_bytes = flops_zaya.flash_cca_call(ctx["config"], ctx["mix"],
                                             "flash_cca_bwd_dq")
    dkv, dkv_bytes = flops_zaya.flash_cca_call(ctx["config"], ctx["mix"],
                                               "flash_cca_bwd_dkv")
    assert (dq, dkv) == (pytest.approx(1.5 * work), pytest.approx(2 * work))
    assert (dq_bytes, dkv_bytes) == (traffic * 5 // 4, traffic * 6 // 4)
    assert set(flops_zaya.CCA_KERNELS) == set(scopes_zaya.rules()["kernels"])
    # 16.8 M a token forward over 16,384 tokens is one forward call
    assert work == pytest.approx(16.8e6 * 16384, rel=2e-3)


def test_a_mixing_pass_counts_its_operands_once_and_the_bytes_bound_it():
    ctx = _cell()
    work, traffic = flops_zaya.cca_mix_call(ctx["config"], ctx["mix"], "forward")
    tokens = 2 * 8192
    assert traffic == 2 * tokens * (2 * 1280 + 2 * 256)   # z, v in; q, k, v out
    assert work == 2 * 2 * 1280 * 128 * tokens            # the per-head products
    assert flops_zaya.cca_mix_call(ctx["config"], ctx["mix"], "recompute") == (
        work, traffic)
    back_work, back_traffic = flops_zaya.cca_mix_call(
        ctx["config"], ctx["mix"], "backward")
    assert back_work == 2 * work
    assert back_traffic == 2 * tokens * (3 * 1280 + 2 * 256)
    assert traffic / 819e9 > 2 * work / 197e12
    with pytest.raises(ValueError, match="pass"):
        flops_zaya.cca_mix_call(ctx["config"], ctx["mix"], "sideways")


# -- the rules -------------------------------------------------------------------

SCAN = "jit(step_fn)/micro_batches/while/body/closed_call/"
FWD = SCAN + "jvp(ZayaForCausalLM)/"
BWD = SCAN + "transpose(jvp(ZayaForCausalLM))/"
L1 = "layers_1/checkpoint/layers_1/"


@pytest.mark.parametrize("op_name,instruction,expected", [
    (FWD + L1 + "attn/cca/attention_core/flash_cca_fwd/pallas_call",
     "%flash_cca_fwd.3", ("forward", "flash_cca")),
    (BWD + L1 + "attn/cca/attention_core/flash_cca_bwd_dkv/pallas_call",
     "%flash_cca_bwd_dkv.1", ("backward", "flash_cca")),
    (BWD + "layers_1/checkpoint/rematted_computation/layers_1/attn/cca/"
     "attention_core/flash_cca_fwd/pallas_call", "%flash_cca_fwd.4",
     ("recompute", "flash_cca")),
    (FWD + L1 + "attn/cca/attention_core/broadcast_in_dim", "%fusion.1",
     ("forward", "attention_core")),
    (FWD + L1 + "attn/cca/attn_qkv/q_proj/dot_general", "%fusion.2",
     ("forward", "attn_qkv")),
    (FWD + L1 + "attn/cca/cca_conv/mul", "%fusion.3", ("forward", "cca_conv")),
    (BWD + L1 + "attn/cca/cca_conv/bsgi,kgio->kbsgo/dot_general", "%fusion.4",
     ("backward", "cca_conv")),
    (FWD + L1 + "attn/cca/cca_qk_mean/add", "%fusion.5",
     ("forward", "cca_qk_mean")),
    (FWD + L1 + "attn/cca/cca_value_shift/pad", "%fusion.6",
     ("forward", "cca_value_shift")),
    (FWD + L1 + "attn/cca/cca_norm/rsqrt", "%fusion.7", ("forward", "cca_norm")),
    (FWD + L1 + "attn/cca/attn_rope/rotary_turn/pallas_call", "%rotary_turn.3",
     ("forward", "attn_rope")),
    (FWD + "attn_rope/cos", "%fusion.8", ("forward", "attn_rope")),
    (FWD + L1 + "attn/cca/attn_out/o_proj/dot_general", "%fusion.9",
     ("forward", "attn_out")),
    (FWD + L1 + "attn/cca/reshape", "%fusion.10", ("forward", "cca_other")),
    (FWD + L1 + "moe/moe_route/router/router_down/down_proj/dot_general",
     "%fusion.11", ("forward", "router_down")),
    (FWD + L1 + "moe/moe_route/router/router_eda/mul", "%fusion.12",
     ("forward", "router_eda")),
    (BWD + L1 + "moe/moe_route/router/router_mlp/norm/mul", "%fusion.13",
     ("backward", "router_mlp")),
    (FWD + L1 + "moe/moe_route/router/reduce_max", "%fusion.14",
     ("forward", "moe_route")),
    (FWD + L1 + "mlp/moe/moe_dispatch/sort", "%fusion.15",
     ("forward", "moe_dispatch")),
    (FWD + L1 + "mlp/moe/while/body/moe_experts/gmm/pallas_call", "%gmm.3",
     ("forward", "moe_experts")),
    (BWD + L1 + "mlp/moe/while/body/moe_combine/scatter-add", "%fusion.16",
     ("backward", "moe_combine")),
    (FWD + L1 + "mlp/moe/while", "%while.9", ("forward", "moe_other")),
    (FWD + L1 + "attn_merge/residual_merge/mul", "%fusion.17",
     ("forward", "residual_merge")),
    (BWD + L1 + "mlp_merge/residual_merge/reduce_sum", "%fusion.18",
     ("backward", "residual_merge")),
    (FWD + L1 + "attn_norm/mul", "%fusion.19", ("forward", "norm")),
    (FWD + L1 + "mlp_norm/mul", "%fusion.19", ("forward", "norm")),
    (BWD + "while/body/checkpoint/lm_head/dot_general", "%fusion.20",
     ("backward", "lm_head")),
    ("jit(step_fn)/optimizer/clip/mul", "%fusion.40", ("optimizer", "optimizer")),
    (SCAN + "grad_accumulate/add", "%fusion.41", ("other", "accumulate")),
    (None, "%copy.3", ("other", "unnamed_copies")),
    (None, "%while.3", ("other", None)),
])
def test_pass_and_part_rules_of_the_family(op_name, instruction, expected):
    assert scopes.classify(op_name, instruction, scopes_zaya.rules()) == expected


def test_the_rules_name_only_scopes_the_program_writes():
    from bert_pytorch_tpu import pretrain

    table = scopes_zaya.rules()
    named = {f.strip("/") for rule in table["part"] for f in rule["fragments"]}
    scope_like = {f for f in named if f.replace("_", "").isalnum()
                  and not f.startswith(("flash_", "attn_norm", "mlp_norm",
                                        "final_norm"))}
    written = set(pretrain.SCOPES) | set(pretrain.ZAYA_SCOPES)
    assert scope_like <= written | {"optimizer", "step_metrics", "layers_",
                                    "gmm", "tgmm"}, scope_like - written
    parts = {rule["name"] for rule in table["part"]}
    assert set(scopes_zaya.CCA_PARTS) | set(scopes_zaya.FAMILY_PARTS) <= parts
    # the readers shared with the other decoders place this family's ops too:
    # the router's parts under moe_route, the tied head's pieces
    from benchmarks.trace import scopes_lm
    shared = scopes_lm.rules()
    assert scopes.classify(
        FWD + L1 + "moe/moe_route/router/router_mlp/fc1/dot_general",
        "%fusion.1", shared) == ("forward", "moe_route")
    assert scopes.classify(BWD + "while/body/checkpoint/lm_head/dot_general",
                           "%fusion.1", shared) == ("backward", "lm_head")
    assert scopes.classify(FWD + L1 + "mlp/moe/while", "%while.9",
                           shared) == ("forward", "moe_other")


# -- the readers -------------------------------------------------------------------

def _family_planes():
    ms = 1e6
    cca = FWD + "layers_0/attn/cca/"
    ops = [
        ["%fusion.1", 0.0, 4 * ms, cca + "attn_qkv/q_proj/dot_general"],
        ["%fusion.2", 4 * ms, 2 * ms, cca + "cca_conv/mul"],
        ["%fusion.3", 6 * ms, 1 * ms, cca + "cca_qk_mean/add"],
        ["%fusion.4", 7 * ms, 1 * ms, cca + "cca_norm/rsqrt"],
        ["%fusion.5", 8 * ms, 1 * ms, cca + "cca_value_shift/pad"],
        ["%rotary_turn.1", 9 * ms, 1 * ms, cca + "attn_rope/rotary_turn/pallas_call"],
        ["%flash_cca_fwd.1", 10 * ms, 6 * ms,
         cca + "attention_core/flash_cca_fwd/pallas_call"],
        ["%fusion.6", 16 * ms, 2 * ms, cca + "attention_core/broadcast_in_dim"],
        ["%fusion.7", 18 * ms, 2 * ms, cca + "attn_out/o_proj/dot_general"],
        ["%fusion.8", 20 * ms, 3 * ms,
         FWD + "layers_0/moe/moe_route/router/router_down/down_proj/dot_general"],
        ["%fusion.9", 23 * ms, 1 * ms,
         FWD + "layers_0/moe/moe_route/router/reduce_max"],
        ["%fusion.10", 24 * ms, 2 * ms, FWD + "layers_0/mlp/moe/moe_dispatch/sort"],
        ["%gmm.1", 26 * ms, 8 * ms,
         FWD + "layers_0/mlp/moe/while/body/moe_experts/gmm/pallas_call"],
        ["%fusion.11", 34 * ms, 2 * ms,
         FWD + "layers_0/attn_merge/residual_merge/mul"],
        ["%fusion.12", 36 * ms, 2 * ms, FWD + "while/body/lm_head/dot_general"],
        ["%while.1", 38 * ms, 1 * ms, None],
    ]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": reduce.OPS_LINE, "events": ops}]}]


@pytest.fixture
def traced(monkeypatch):
    def with_planes(planes):
        from benchmarks.trace import scopes_lm

        monkeypatch.setattr(scopes_zaya, "_reductions", {})
        monkeypatch.setattr(scopes_lm, "_reductions", {})
        monkeypatch.setattr(scopes, "newest_trace", lambda under=None: "a.xplane.pb")
        monkeypatch.setattr(scopes, "read_xspace", lambda path: planes)
        ctx = _cell()
        return {"summary": {"busy_s": 39e-3}, "updates": 2, "chips": 1,
                "peak_flops": 197e12, "device_kind": "TPU v5 lite",
                "config": ctx["config"], "mix": ctx["mix"],
                "counters": {"moe_local_slots": 30000.0}, "trace_dir": "x"}
    return with_planes


def test_readers_on_a_small_trace_of_the_family(traced):
    ctx = traced(_family_planes())
    read = lambda name: _reader(name)(ctx)
    assert read("cca_device_ms.train") == pytest.approx(10.0)
    assert read("cca_mix_device_ms.train") == pytest.approx(3.0)
    assert read("cca_core_device_ms.train") == pytest.approx(4.0)
    assert read("zaya_router_device_ms.train") == pytest.approx(2.0)
    assert read("residual_merge_device_ms.train") == pytest.approx(1.0)
    assert read("zaya_unattributed_device_pct.train") == pytest.approx(100 / 39)
    assert read("zaya_expert_mfu_pct.train") == pytest.approx(
        100 * 3 * 6 * 2048 * 2048 * 30000 / (4e-3 * 197e12))
    # one forward call of the kernel in 6 ms of its own
    work, traffic = flops_zaya.flash_cca_call(ctx["config"], ctx["mix"],
                                              "flash_cca_fwd")
    assert read("flash_cca_roofline_pct.train") == pytest.approx(
        100 * max(work / 197e12, traffic / 819e9) / 6e-3)
    # 2 updates x 5 layers x 4 micro-batches of three passes, by their bytes,
    # over the 6 ms the five scopes took
    least = sum(flops_zaya.cca_mix_call(ctx["config"], ctx["mix"], which)[1]
                for which in flops_zaya.MIX_PASSES) / 819e9
    assert read("cca_mix_roofline_pct.train") == pytest.approx(
        100 * 2 * 5 * 4 * least / 6e-3)
    # the readers shared with the other decoders read the same trace rightly
    assert read("lm_head_device_ms.train") == pytest.approx(1.0)
    assert read("moe_device_ms.train") == pytest.approx(7.0)
    assert read("moe_dispatch_device_ms.train") == pytest.approx(3.0)


def test_a_trace_without_the_family_gives_nothing_and_does_not_raise(traced):
    other = [{"name": "/device:TPU:0", "lines": [{"name": reduce.OPS_LINE, "events": [
        ["%fusion.1", 0.0, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(NemotronHForCausalLM)/layers_0/mixer/ssm_mixer/ssd_scan/dot"],
        ["%flash_fwd.1", 5e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/attn/attention_core/flash_fwd"],
        ["%fusion.2", 10e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/attn/attn_qkv/q_proj/dot_general"],
        ["%gmm.1", 15e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/mlp/moe/while/body/moe_experts/gmm"],
        ["%fusion.3", 20e6, 2e6, "jit(step_fn)/optimizer/clip/mul"]]}]}]
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
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models import zaya

    if fault == "convolution_reads_ahead":
        def following(x, steps=1):  # t + steps in t - steps' place
            pad = [(0, 0), (0, steps)] + [(0, 0)] * (x.ndim - 2)
            return jnp.pad(x, pad)[:, steps:]

        monkeypatch.setattr(zaya, "previous", following)
    elif fault == "router_state_dropped":
        real = zaya.ZayaRouter.__call__
        monkeypatch.setattr(
            zaya.ZayaRouter, "__call__",
            lambda self, h, before: real(
                self, h, None if before is None else 0 * before))
    elif fault == "top1_renormalised":
        real = zaya.moe.choose
        monkeypatch.setattr(
            zaya.moe, "choose",
            lambda logits, bias, k, scale, norm_topk=True, score="sigmoid":
            real(logits, bias, k, scale, True, score))
    elif fault == "norm_in_bfloat16":
        def rounded(t, length):
            t = t.astype(jnp.bfloat16)
            return (t * (jnp.bfloat16(length) * jax.lax.rsqrt(jnp.sum(
                jnp.square(t), axis=-1, keepdims=True)))).astype(jnp.float32)

        monkeypatch.setattr(zaya, "to_length", rounded)


def _tiny_run(monkeypatch=None, fault=None, controls=()):
    tmp = tempfile.mkdtemp()
    ctx = cpu_cell_zaya.tiny_context(CELL, 2 ** 31 + 77, 0.3, tmp)
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
    assert counters["moe_local_slots"] > 0 and counters["moe_skip_slots"] > 0
    assert counters["moe_dropped_slots"] == 0.0
    assert counters["router_carried_layers"] == 4.0
    # 4 micro-batches of 2 rows of 64 tokens through 5 layers
    assert counters["moe_local_slots"] + counters["moe_skip_slots"] < 5 * 512
    readings = result["readings"]
    assert 0 < readings["skip_share"] < 0.5 and 0 < readings["local_share"] < 1
    assert readings["routing_flip_share"] < 0.01
    control = result["controls"]["fp8"]
    assert any(control[k] > FLOAT32_LIMITS[k] for k in control)
    assert control["head_grad_rel_diff"] > 10 * readings["head_grad_rel_diff"]


@pytest.mark.parametrize("fault", ["convolution_reads_ahead",
                                   "router_state_dropped", "top1_renormalised",
                                   "norm_in_bfloat16"])
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    assert _tiny_run(monkeypatch, fault)["correct"] is False


def test_the_kind_is_the_laguna_kind_over_another_family():
    """Nothing of ``train_laguna.py`` is written again: this kind's functions
    are that file's, loaded a second time, and only ``family`` differs; the
    laguna cell's own copy still names its own."""
    from benchmarks.kinds import train_laguna
    from benchmarks.reference import laguna_f32, zaya_f32

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    assert kind.base is not train_laguna
    assert kind.base.__file__ == train_laguna.__file__
    assert kind.measure is kind.base.measure and kind.run is kind.base.run
    assert kind.base.family()[0] is zaya_f32
    assert train_laguna.family()[0] is laguna_f32
    assert train_laguna.COUNTERS == ("moe_", "attn_")


def test_a_program_without_the_family_is_told_so_at_once(monkeypatch):
    from bert_pytorch_tpu import config as program_config

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    monkeypatch.setattr(program_config, "MODEL_FAMILIES", {
        k: v for k, v in program_config.MODEL_FAMILIES.items() if k != "zaya"})
    with pytest.raises(SystemExit, match="unknown model_type 'zaya'"):
        kind.run(dict(_cell(), seed=1, seconds=1.0, trace=False, started=0.0))
