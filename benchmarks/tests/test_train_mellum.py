"""The ``train_mellum`` kind, its FLOP and byte counts, its rules and its
readers, on the CPU: the counts against the issue's arithmetic, the scope
rules of ``scopes_mellum.json`` on op names as the program writes them, the
readers on a small synthetic trace of four devices (and on none, and on
another decoder's trace: nothing to read, no raise), the exchange's own times
with a collective in flight, and how ``correct`` is decided at a size a test
can hold: sound in float32 over four virtual devices, the lower-precision
control failing, and the two rehearsed faults (the whole tensors' gradients
not summed over the axis; the exchange's buffers of one chip turned by one
place) each coming out not correct. The runs over four devices are processes
of their own (``rehearse/cpu_cell_mellum.py``: a session of one-device kinds
cannot ask for four). The cell and its configuration are found BY NAME."""

import json
import os
import subprocess
import sys

import pytest

import benchmarks.run as bench_run
from benchmarks.trace import flops_mellum, reduce, scopes, scopes_mellum

ROOT = bench_run.ROOT
CELL = "train-mellum2-ep4-seq8192"
CONFIG = "mellum2-12b-a2.5b"
NEW_METRICS = (
    "moe_exchange_device_ms.train", "moe_exchange_exposed_ms.train",
    "moe_exchange_ici_pct.train", "mellum_expert_mfu_pct.train",
    "mellum_attention_device_ms.train", "mellum_unattributed_device_pct.train",
    "mellum_window_attention_device_ms.train",
    "mellum_full_attention_device_ms.train",
    "mellum_flash_window_roofline_pct.train",
    "mellum_flash_causal_roofline_pct.train")
SHARED_METRICS = (
    "fwd_device_ms.train", "bwd_device_ms.train", "recompute_device_ms.train",
    "optimizer_device_ms.train", "sync_idle_ms.train",
    "loop_work_idle_ms.train", "moe_device_ms.train", "moe_dispatch_device_ms.train",
    "lm_head_device_ms.train",
    "setup_before_main_s.train", "setup_prepare_s.train",
    "setup_state_init_s.train", "setup_step_lower_s.train",
    "setup_step_executable_s.train", "setup_first_update_s.train",
    "setup_unattributed_pct.train", "setup_trace_model_s.train",
    "setup_trace_kernels_s.train", "setup_kernel_builds.train",
    "setup_trace_other_s.train", "setup_init_program_s.train")
LISTLESS = ("data_wait_ms.train", "host_dispatch_ms.train",
            "device_step_ms.train", "step_mfu_pct.train",
            "device_idle_pct.train")


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
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", name + ".py"))
    for name in SHARED_METRICS:
        assert CELL in entries[name]["workloads"]
    for name in LISTLESS:
        assert "workloads" not in entries[name]
    for name in ("glu_expert_mfu_pct.train", "window_attention_device_ms.train",
                 "flash_window_roofline_pct.train",
                 "attention_proj_device_ms.train", "dense_mlp_device_ms.train",
                 "laguna_unattributed_device_pct.train"):
        assert CELL not in entries[name]["workloads"]  # laguna's rules, not ours
    # reduce.COLLECTIVES does not see the chips' all-to-alls (PERF.md 7.18)
    assert CELL not in entries["collective_exposed_ms.train"]["workloads"]
    cells = {w["name"]: w for w in bench["workloads"]}
    cell = cells[CELL]
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        4, "lm-seq8192-mellum-ep4", CONFIG)
    # a quarter of the cells may ask for four chips
    assert sum(w["chips"] == 4 for w in cells.values()) <= len(cells) // 4
    config = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "mlp_layer_types"]
    assert config["file"] == "benchmarks/configs/mellum2-12b-a2.5b.json"
    ctx = _cell()
    assert ctx["mix"]["kind"] == "train_mellum"
    assert ctx["config"]["model_type"] == "mellum"
    assert set(ctx["readers"]) == set(NEW_METRICS + SHARED_METRICS + LISTLESS)
    args = ctx["mix"]["trainer_args"]
    assert args[args.index("--mesh") + 1] == "ep=4"
    for key in ("published", "assumed", "precision", "deployment"):
        assert ctx["config"][key]
    assert set(ctx["config"]["reduced"]) == set(config["reduced"])


def test_every_limit_is_set_and_the_exact_ones_are_zero():
    limits = _cell()["mix"]["check"]["limits"]
    assert set(limits) == {
        "loss_gap_first", "loss_gap_later", "grad_global_norm_gap",
        "grad_norm_gap_worst_leaf", "delta_norm_gap_worst_leaf",
        "head_grad_rel_diff", "all_grad_rel_diff", "feed_faults",
        "exchange_slots_gap"}
    assert limits["feed_faults"] == limits["exchange_slots_gap"] == 0
    assert all(0 < v < 1 for k, v in limits.items()
               if k not in ("feed_faults", "exchange_slots_gap"))


@pytest.mark.parametrize("name", ["all_grad_rel_diff", "head_grad_rel_diff",
                                  "grad_global_norm_gap",
                                  "grad_norm_gap_worst_leaf"])
def test_a_limit_lies_between_the_cells_own_two_readings(name):
    """The largest sound reading on the chips and the fp8 control's
    (``check.readings``), with half as much again of room on both sides."""
    check = _cell()["mix"]["check"]
    sound, control = check["readings"][name]
    assert 1.5 * sound < check["limits"][name] < control / 1.5


# -- the counts --------------------------------------------------------------------

def test_model_flops_are_the_issues_arithmetic():
    ctx = _cell()
    parts = flops_mellum.forward_flops_per_token(ctx["config"], 8192)
    assert parts["attention_proj"] == 4 * 2 * 21.233664e6
    assert parts["experts"] == 4 * (2 * 2304 * 64 + 8 * 6 * 2304 * 896)
    assert parts["head"] == 2 * 2304 * 98304
    whole = sum(parts.values())
    # ISSUE 53: per token experts 396 M, projections 170 M, cores 117 M,
    # head 453 M; the head 40% of the forward FLOPs
    assert parts["experts"] == pytest.approx(397.5e6, rel=2e-3)
    assert parts["attention_proj"] == pytest.approx(169.9e6, rel=2e-3)
    # (cores: 114 M by the pairs a row sees; the issue's 117 M counts the
    # full layer's triangle as S / 2 + 1 / 2 keys a row)
    assert parts["attention_full"] + parts["attention_window"] == pytest.approx(
        114.3e6, rel=2e-3)
    assert parts["head"] / whole == pytest.approx(0.40, abs=0.01)
    update = flops_mellum.train_flops_per_update(ctx["config"], ctx["mix"], 4)
    assert update == 3.0 * 131072 * whole
    # a chip an update: 111.5 TFLOP of model work (the issue's 134 TFLOP
    # holds a fourth forward pass, which --remat full runs and MFU leaves out)
    assert update / 4 == pytest.approx(111.5e12, rel=2e-3)


def test_exchange_bytes_are_the_slots_really_sent():
    config = _cell()["config"]
    # 226 MB out of a chip a call: 3/4 of 65,536 slots of 2304 bfloat16
    one_call = 0.75 * 65536 * 2304 * 2
    assert one_call == pytest.approx(226.5e6, rel=1e-3)
    # an update's forward passes: 4 chips x 4 layers x 4 micro-batches
    slots = 4 * 4 * 4 * 0.75 * 65536
    sent = flops_mellum.exchange_bytes_per_update(config, slots)
    assert sent == slots * (7 * 2304 * 2 + 8)
    assert sent / 4 == pytest.approx(7 * 16 * one_call, rel=1e-3)  # a chip
    assert flops_mellum.routed_expert_train_flops(config, 10.0) == (
        3 * 6 * 2304 * 896 * 10)


# -- the rules ---------------------------------------------------------------------

STEP = "jit(step_fn)/shard_map/micro_batches/while/body/"
BLOCK = STEP + "jvp(MellumForCausalLM)/layers_2/"


@pytest.mark.parametrize("op_name, instruction, expected", [
    (BLOCK + "mlp/moe/while/body/moe_exchange_out/all_to_all",
     "%all-to-all.12", ("forward", "moe_exchange_out")),
    (STEP + "transpose(jvp(MellumForCausalLM))/layers_2/mlp/moe/while/body/"
     "moe_exchange_back/all_to_all", "%all-to-all-start.3",
     ("backward", "moe_exchange_back")),
    (BLOCK + "mlp/moe/moe_exchange_out/pmax", "%all-reduce.4",
     ("forward", "moe_exchange_out")),
    (BLOCK + "mlp/moe/while/body/moe_experts/gmm", "%gmm.7",
     ("forward", "moe_experts")),
    (BLOCK + "mlp/moe/while/body/moe_combine/scatter-add", "%fusion.9",
     ("forward", "moe_combine")),
    (BLOCK + "attn/attention_core/flash_window_fwd", "%flash_window_fwd.1",
     ("forward", "window_attention")),
    (BLOCK + "attn/attn_rope/rotary_turn", "%rotary_turn.2",
     ("forward", "attn_rope")),
    (STEP + "jvp(MellumForCausalLM)/embed_exchange/reduce_scatter",
     "%reduce-scatter.1", ("forward", "embed_exchange")),
    (STEP + "checkpoint/rematted_computation/lm_head_gather/all_gather",
     "%all-gather.5", ("recompute", "lm_head_gather")),
    (STEP + "checkpoint/lm_head/dot_general", "%fusion.11",
     ("other", "lm_head")),
    ("jit(step_fn)/shard_map/grad_sync/psum", "%all-reduce.9",
     ("other", "grad_sync")),
    ("jit(step_fn)/optimizer/clip/mul", "%fusion.20",
     ("optimizer", "optimizer")),
])
def test_pass_and_part_rules_of_the_family(op_name, instruction, expected):
    assert scopes.classify(op_name, instruction,
                           scopes_mellum.rules()) == expected


def test_the_rules_name_only_scopes_the_program_writes():
    from bert_pytorch_tpu import pretrain

    written = set(pretrain.SCOPES) | set(pretrain.MELLUM_SCOPES)
    table = scopes_mellum.rules()
    for rule in table["part"]:
        for fragment in rule["fragments"]:
            bare = fragment.strip("/%")
            if bare in ("moe", "layers_", "optimizer", "step_metrics"):
                continue
            assert (bare in written or bare.startswith(("flash_", "micro_batches"))
                    or bare in ("ragged-dot", "gmm", "tgmm", "copy", "convert",
                                "slice-start", "slice-done", "attn_norm",
                                "mlp_norm", "final_norm", "grad_accumulate")), fragment
    assert set(table["exchange"]) <= written


# -- the readers -------------------------------------------------------------------

def _family_planes():
    """Two devices, one update of 40 ms each, times in ns: attention 10 ms
    (a windowed kernel 4, a causal one 2, the projections 4), experts 8 ms, an all-to-all of 4 ms on the ops line of which 1 ms runs
    beside a fusion, one of 3 ms IN FLIGHT on the async line of which 2 ms lie
    under the experts' product, the head's gather, head and the optimizer."""
    def device(n):
        ops = [
            ["%flash_window_fwd.1", 0.0, 4e6, BLOCK + "attn/attention_core/flash_window_fwd"],
            ["%flash_fwd.1", 4e6, 2e6, BLOCK + "attn/attention_core/flash_fwd"],
            ["%fusion.1", 6e6, 4e6, BLOCK + "attn/attn_qkv/dot_general"],
            ["%all-to-all.1", 10e6, 4e6, BLOCK + "mlp/moe/while/body/moe_exchange_out/all_to_all"],
            ["%gmm.1", 14e6, 8e6, BLOCK + "mlp/moe/while/body/moe_experts/gmm"],
            ["%all-gather.1", 24e6, 1e6, STEP + "checkpoint/lm_head_gather/all_gather"],
            ["%fusion.3", 25e6, 5e6, STEP + "checkpoint/lm_head/dot_general"],
            ["%fusion.4", 30e6, 9e6, "jit(step_fn)/optimizer/clip/mul"],
            ["%fusion.5", 39e6, 1e6, None],
        ]
        in_flight = [["%all-to-all-start.2", 20e6, 3e6,
                      BLOCK + "mlp/moe/while/body/moe_exchange_back/all_to_all"]]
        return {"name": f"/device:TPU:{n}", "lines": [
            {"name": reduce.OPS_LINE, "events": ops},
            {"name": reduce.ASYNC_LINE, "events": in_flight}]}
    return [device(0), device(1)]


@pytest.fixture
def traced(monkeypatch):
    def with_planes(planes):
        from benchmarks.trace import scopes_laguna, scopes_lm

        for module in (scopes_mellum, scopes_lm, scopes_laguna):
            monkeypatch.setattr(module, "_reductions", {})
        monkeypatch.setattr(scopes, "newest_trace", lambda under=None: "a.xplane.pb")
        monkeypatch.setattr(scopes, "read_xspace", lambda path: planes)
        ctx = _cell()
        return {"summary": {"busy_s": 40e-3}, "updates": 1, "chips": 4,
                "peak_flops": 197e12, "device_kind": "TPU v5 lite",
                "config": ctx["config"], "mix": ctx["mix"],
                "counters": {"moe_local_slots": 4.0e6,
                             "moe_exchange_slots_out": 3.0e6},
                "trace_dir": "x"}
    return with_planes


def test_readers_on_a_small_trace_of_the_family(traced):
    ctx = traced(_family_planes())
    read = lambda name: _reader(name)(ctx)
    # 4 ms on the ops line + 3 ms in flight (20-23 ms)
    assert read("moe_exchange_device_ms.train") == pytest.approx(7.0)
    # the 4 ms stand alone; of the 3 ms in flight 2 lie under the experts
    assert read("moe_exchange_exposed_ms.train") == pytest.approx(5.0)
    sent = 3.0e6 * (7 * 2304 * 2 + 8) / 4
    assert read("moe_exchange_ici_pct.train") == pytest.approx(
        100 * sent / (7e-3 * 200e9))
    assert read("mellum_expert_mfu_pct.train") == pytest.approx(
        100 * 3 * 6 * 2304 * 896 * 4.0e6 / (8e-3 * 4 * 197e12))
    assert read("mellum_attention_device_ms.train") == pytest.approx(10.0)
    assert read("mellum_window_attention_device_ms.train") == pytest.approx(4.0)
    assert read("mellum_full_attention_device_ms.train") == pytest.approx(2.0)
    # one forward call of each kernel a chip: 32 heads x 1 row of 8192, the
    # band's pairs against the causal half's, two products of 2 x 128 a pair
    band = 1024 * 8192 - 1024 * 1023 / 2
    assert read("mellum_flash_window_roofline_pct.train") == pytest.approx(
        100 * (2 * 2 * 128 * band * 32 / 197e12) / 4e-3)
    assert read("mellum_flash_causal_roofline_pct.train") == pytest.approx(
        100 * (2 * 2 * 128 * (8192 * 8193 / 2) * 32 / 197e12) / 2e-3)
    assert read("mellum_unattributed_device_pct.train") == pytest.approx(
        100 * 1 / 39)  # (busy: the ops' union with what is in flight)
    # the readers shared with the other decoders read the same trace rightly:
    # the exchange lies under /moe/, the gather's name holds lm_head
    assert read("moe_device_ms.train") == pytest.approx(12.0)
    assert read("lm_head_device_ms.train") == pytest.approx(6.0)


def test_a_trace_without_the_family_gives_nothing_and_does_not_raise(traced):
    other = [{"name": "/device:TPU:0", "lines": [{"name": reduce.OPS_LINE, "events": [
        ["%flash_fwd.1", 5e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_3/attn/attention_core/flash_fwd"],
        ["%gmm.1", 15e6, 5e6, "jit(step_fn)/micro_batches/while/body/"
         "jvp(LagunaForCausalLM)/layers_1/mlp/moe/while/body/moe_experts/gmm"],
        ["%fusion.4", 22e6, 2e6, "jit(step_fn)/optimizer/clip/mul"]]}]}]
    ctx = traced(other)
    assert [_reader(name)(ctx) for name in NEW_METRICS] == [None] * len(NEW_METRICS)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_reader_returns_none_on_an_empty_context(name):
    assert _reader(name)({}) is None


# -- correct -----------------------------------------------------------------------

def _tiny_run(*options):
    """``rehearse/cpu_cell_mellum.py --float32`` in a process of its own (it
    asks for four virtual devices before JAX starts); its result line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "rehearse",
                                      "cpu_cell_mellum.py"),
         "--float32", "--seconds", "0.3", "--seed", str(2 ** 31 + 77), *options],
        env=dict(env, JAX_PLATFORMS="cpu"), cwd=ROOT, capture_output=True,
        text=True, timeout=900)
    assert done.returncode in (0, 1), done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_sound_in_float32_and_the_control_fails():
    from benchmarks.rehearse.cpu_cell_mellum import FLOAT32_LIMITS

    result = _tiny_run("--controls", "fp8")
    assert result["correct"] is True and result["failed"] == 0
    assert result["compiles_in_window"] == 0
    assert result["device"]["count"] == 4
    counters = result["counters"]
    assert counters["moe_dropped_slots"] == 0.0
    # every slot arrives somewhere: 4 layers x 4 micro-batches x 4 rows x 64 x 2
    assert counters["moe_local_slots"] == 4 * 4 * 4 * 64 * 2
    assert counters["moe_exchange_slots_out"] == counters["moe_exchange_slots_in"]
    assert 0.6 < counters["moe_exchange_slots_out"] / counters["moe_local_slots"] < 0.9
    readings = result["readings"]
    assert readings["exchange_slots_gap"] == 0.0
    for name, limit in FLOAT32_LIMITS.items():
        assert readings[name] <= limit, name
    control = result["controls"]["fp8"]
    assert any(control[k] > FLOAT32_LIMITS[k] for k in control)
    for name in ("all_grad_rel_diff", "head_grad_rel_diff"):
        assert control[name] > 100 * readings[name], name


@pytest.mark.parametrize("fault, seen_by, times", [
    ("whole_tensors_not_summed", "grad_norm_gap_worst_leaf", 50),
    ("terms_to_wrong_tokens", "all_grad_rel_diff", 10)])
def test_a_rehearsed_fault_is_not_correct(fault, seen_by, times):
    """... and the number named sees it at this float32 run's limit by that
    many times. The loss at the seeded weights is ln V under both faults:
    neither loss gap sees either."""
    from benchmarks.rehearse.cpu_cell_mellum import FLOAT32_LIMITS

    result = _tiny_run("--fault", fault)
    assert result["correct"] is False
    assert result["readings"][seen_by] > times * FLOAT32_LIMITS[seen_by]
    assert result["readings"]["exchange_slots_gap"] == 0.0
    assert result["dropped_slots"] == 0.0


def test_the_kind_is_the_laguna_kind_over_another_family():
    """Nothing of ``train_laguna.py`` is written again but the routing's read
    and the exchange's exact number: this kind's functions are that file's,
    loaded a second time; the laguna cell's own copy still names its own."""
    from benchmarks.kinds import train_laguna
    from benchmarks.reference import laguna_f32, mellum_f32

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    assert kind.base is not train_laguna
    assert kind.base.__file__ == train_laguna.__file__
    assert kind.measure is kind.base.measure and kind.run is kind.base.run
    assert kind.base.compare_with_reference is kind.compare_with_reference
    assert kind.base.Probes is kind.Probes
    assert kind.base.family()[0] is mellum_f32
    assert kind.base.COUNTERS == train_laguna.COUNTERS == ("moe_", "attn_")
    assert train_laguna.family()[0] is laguna_f32
    assert train_laguna.Probes is not kind.Probes
    assert kind.exchange_slots_gap([]) == float("inf")
    assert kind.exchange_slots_gap([
        {"moe_exchange_slots_out": 5.0, "moe_exchange_slots_in": 5.0},
        {"moe_exchange_slots_out": 7.0, "moe_exchange_slots_in": 4.0}]) == 3.0


def test_a_program_without_the_family_is_told_so_at_once(monkeypatch):
    from bert_pytorch_tpu import config as program_config

    kind = bench_run.load_module(_cell()["kind_file"], "kind_under_test")
    known = {k: v for k, v in program_config.MODEL_FAMILIES.items()
             if k != "mellum"}
    monkeypatch.setattr(program_config, "MODEL_FAMILIES", known)
    with pytest.raises(SystemExit, match="unknown model_type 'mellum'"):
        kind.run(_cell())
