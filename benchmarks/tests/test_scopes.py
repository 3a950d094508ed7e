import json
import os
import re
import struct
import tempfile

import pytest

import benchmarks.run as bench_run
from benchmarks.trace import reduce, scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW_METRICS = (
    "fwd_device_ms.train", "bwd_device_ms.train", "recompute_device_ms.train",
    "optimizer_device_ms.train", "attention_device_ms.train",
    "dropout_device_ms.train", "mlm_head_device_ms.train",
    "unattributed_device_pct.train", "sync_idle_ms.train",
    "loop_work_idle_ms.train")
SCAN = "jit(step_fn)/micro_batches/while/body/closed_call/"
LAYER = "/bert/encoder/while/body/closed_call/"


def _reader(name):
    return bench_run.load_module(
        os.path.join(ROOT, "benchmarks", "metrics", name + ".py"),
        "metric_" + name.replace(".", "_")).read


# -- the rules -------------------------------------------------------------

@pytest.mark.parametrize("op_name,instruction,expected", [
    (SCAN + "jvp(BertForPreTraining)" + LAYER + "layers/output/dot_general",
     "%fusion.931", ("forward", "ffn")),
    (SCAN + "transpose(jvp(BertForPreTraining))" + LAYER
     + "checkpoint/layers/intermediate/dense/dot_general",
     "%fusion.12", ("backward", "ffn")),
    (SCAN + "transpose(jvp(BertForPreTraining))" + LAYER
     + "checkpoint/rematted_computation/layers/attention/attention_core/"
       "flash_fwd/pallas_call", "%flash_fwd.17", ("recompute", "attention_core")),
    (SCAN + "transpose(jvp(BertForPreTraining))" + LAYER
     + "checkpoint/layers/attention/attention_core/flash_bwd_dkv/pallas_call",
     "%flash_bwd_dkv.9", ("backward", "attention_core")),
    (SCAN + "jvp(BertForPreTraining)" + LAYER
     + "layers/attention/attention_core/attention_dropout/jit(_bernoulli)/lt",
     "%fusion.4", ("forward", "attention_dropout")),
    (SCAN + "jvp(BertForPreTraining)" + LAYER
     + "layers/Dropout_0/jit(_bernoulli)/jit(_uniform)/or",
     "%rng-bit-generator.38", ("forward", "dropout")),
    (None, "%rng-bit-generator.2", ("other", "dropout")),
    (SCAN + "jvp(BertForPreTraining)" + LAYER + "layers/attention/query/add",
     "%fusion.7", ("forward", "attention_proj")),
    (SCAN + "jvp(BertForPreTraining)" + LAYER
     + "layers/attention/output_layer_norm/rsqrt", "%fusion.8",
     ("forward", "layer_norm")),
    (SCAN + "transpose(jvp(BertForPreTraining))/predictions/bsh,vh->bsv/"
     "dot_general", "%fusion.9", ("backward", "mlm_head")),
    (SCAN + "jvp(mlm_loss)/reduce_max", "%fusion.10", ("forward", "mlm_head")),
    (SCAN + "transpose(jvp(nsp_loss))/neg", "%fusion.11", ("backward", "mlm_head")),
    (SCAN + "jvp(BertForPreTraining)/bert/embeddings/word_embeddings/"
     "jit(_take)/gather", "%fusion.13", ("forward", "embeddings")),
    (SCAN + "grad_accumulate/add", "%fusion.14", ("other", "accumulate")),
    ("jit(step_fn)/optimizer/lamb/mul", "%fusion.15", ("optimizer", "optimizer")),
    ("jit(step_fn)/optimizer/clip/reduce_sum", "%fusion.16",
     ("optimizer", "optimizer")),
    ("jit(step_fn)/step_metrics/cond/branch_1_fun/sqrt", "%fusion.17",
     ("other", "step_metrics")),
    (SCAN + "transpose(jvp(BertForPreTraining))/bert/encoder/while/body/"
     "dynamic_update_slice", "%bitcast_dynamic-update-slice_fusion.47",
     ("backward", "layer_scan")),
    (SCAN + "dynamic_slice", "%fusion.18", ("other", "micro_batch_scan")),
    (None, "%copy.324", ("other", None)),
    ("jit(step_fn)/mul", "%fusion.19", ("other", None)),  # names of PR 24
])
def test_pass_and_part_rules(op_name, instruction, expected):
    assert scopes.classify(op_name, instruction, scopes.rules()) == expected


def test_rules_and_readers_name_no_op_by_its_number():
    numbered = re.compile(r"\.\d+\b")
    table = scopes.rules()
    for group in ("pass", "part"):
        for rule in table[group]:
            assert not any(numbered.search(f) for f in rule["fragments"]), rule
    assert not any(numbered.search(k) for k in table["kernels"])
    for name in NEW_METRICS:
        path = os.path.join(ROOT, "benchmarks", "metrics", name + ".py")
        with open(path, encoding="utf-8") as f:
            assert not re.search(r"%[a-z_-]+\.\d+|fusion\.\d+", f.read()), name
    assert scopes.kind_of("%rng-bit-generator.38") == "%rng-bit-generator"
    assert scopes.kind_of("%flash_fwd.17") == "%flash_fwd"
    assert scopes.kind_of("%convert") == "%convert"


# -- gaps among nested spans and other threads -------------------------------

def _plane(name, *lines):
    return {"name": name, "lines": [{"name": n, "events": e} for n, e in lines]}


def _synthetic():
    ms = 1e6
    device = _plane("/device:TPU:0", (reduce.OPS_LINE, [
        ["%while.2", 0.0, 10 * ms, SCAN.rstrip("/")],     # self time 2 ms
        ["%fusion.1", 0.0, 5 * ms,
         SCAN + "jvp(M)" + LAYER + "layers/output/dot_general"],
        ["%fusion.2", 5 * ms, 3 * ms,
         SCAN + "transpose(jvp(M))" + LAYER + "checkpoint/layers/output/mul"],
        ["%fusion.3", 10 * ms, 2 * ms, "jit(step_fn)/optimizer/lamb/mul"],
        # idle 12..32 ms
        ["%copy.5", 32 * ms, 1 * ms, None],
        ["%flash_fwd.4", 33 * ms, 2 * ms,
         SCAN + "jvp(M)" + LAYER + "layers/attention/attention_core/flash_fwd/"
         "pallas_call"]]))
    loop = ("python3", [
        ["train", 1 * ms, 29 * ms, {"step_num": 7}],
        ["train:dispatch", 2 * ms, 2 * ms, None],
        ["bench:dispatch", 2.5 * ms, 1 * ms, None],          # not the program's
        ["train:telemetry", 11 * ms, 9 * ms, None],           # 12..20 idle
        ["train:sync", 12 * ms, 4 * ms, None],                # inside telemetry
        ["train:log", 22 * ms, 2 * ms, None],                 # 20..22 under none
        ["train", 30 * ms, 10 * ms, {"step_num": 8}],
        ["train:feed", 30 * ms, 1 * ms, None],
        ["train:dispatch", 31.5 * ms, 3 * ms, None]])         # 31..31.5 none
    feeder = ("python3", [["prefetch:h2d", 19 * ms, 4 * ms, None],
                          ["data:shard_load", 0.0, 1 * ms, None]])
    return [device, _plane("/host:CPU", loop, feeder)]


def test_device_time_by_pass_and_part_and_the_gap_among_spans():
    found = scopes.reduce_scopes(_synthetic())
    assert found["has_spans"] and found["has_scopes"]
    assert found["busy_s"] == pytest.approx(15e-3)
    assert found["by_pass"] == {
        "forward": pytest.approx(7e-3), "backward": pytest.approx(3e-3),
        "other": pytest.approx(3e-3), "optimizer": pytest.approx(2e-3)}
    assert sum(found["by_pass"].values()) == pytest.approx(found["busy_s"])
    assert found["by_part"] == {
        "ffn": pytest.approx(8e-3), "attention_core": pytest.approx(2e-3),
        "micro_batch_scan": pytest.approx(2e-3),
        "optimizer": pytest.approx(2e-3), "unattributed": pytest.approx(1e-3)}
    assert found["unattributed_s"] == pytest.approx(1e-3)
    assert found["kernels"] == {"flash_fwd": pytest.approx(2e-3)}
    # the 20 ms gap: sync is innermost for 12..16, telemetry holds 16..20,
    # none 20..22 and 24..30 and 31..31.5, log 22..24, feed 30..31,
    # dispatch 31.5..32
    assert found["idle_s"] == pytest.approx(20e-3)
    assert found["idle_by_span"] == {
        "none": pytest.approx(8.5e-3), "train:sync": pytest.approx(4e-3),
        "train:telemetry": pytest.approx(4e-3),
        "train:log": pytest.approx(2e-3), "train:feed": pytest.approx(1e-3),
        "train:dispatch": pytest.approx(0.5e-3)}
    assert sum(found["idle_by_span"].values()) == pytest.approx(found["idle_s"])
    # another thread's span is listed beside the gap, and takes nothing from it
    assert found["idle_beside"] == {"prefetch:h2d": pytest.approx(4e-3)}
    threads = list(found["spans"].values())
    assert [s[3] for s in threads[0] if s[0] == "train"] == [7, 8]
    assert {s[0] for s in threads[1]} == {"prefetch:h2d", "data:shard_load"}


def test_a_trace_of_the_parent_has_neither_spans_nor_scopes():
    planes = [_plane("/device:TPU:0", (reduce.OPS_LINE, [
        ["%fusion.1", 0.0, 5e6, "jit(step_fn)/while/body/closed_call/"
         "jvp(M)/bert/encoder/while/body/closed_call/layers/output/dot_general"],
        ["%fusion.2", 9e6, 1e6, "jit(step_fn)/mul"]])),
        _plane("/host:CPU", ("python3", [["bench:dispatch", 5e6, 1e6, None]]))]
    found = scopes.reduce_scopes(planes)
    assert not found["has_spans"] and not found["has_scopes"]
    assert found["idle_by_span"] == {"none": pytest.approx(4e-3)}


# -- the recorded chip trace ---------------------------------------------------

def test_the_recorded_chip_trace_reduces_to_what_summarize_reads():
    with open(os.path.join(HERE, "recorded_scopes.json")) as f:
        planes = json.load(f)
    found = scopes.reduce_scopes(planes)
    plain = [{"name": p["name"], "lines": [
        {"name": line["name"], "events": [e[:3] for e in line["events"]]}
        for line in p["lines"]]} for p in planes]
    summary = reduce.summarize(plain)
    assert found["busy_s"] == pytest.approx(summary["busy_s"], rel=1e-9)
    assert found["idle_s"] == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=1e-9)
    for group in ("by_pass", "by_part"):
        assert sum(found[group].values()) == pytest.approx(
            summary["busy_s"], rel=1e-6)
    assert sum(found["idle_by_span"].values()) == pytest.approx(
        found["idle_s"], rel=1e-9)
    assert found["has_spans"] and found["has_scopes"]
    # round an update's end the unnamed copies and converts weigh most; the
    # rules still place most of the time, in several parts
    assert found["unattributed_s"] < 0.5 * found["busy_s"]
    assert len(found["by_part"]) >= 5
    assert set(found["by_pass"]) <= set(scopes.PASSES)
    # the cut is taken round the longest idle gap: the loop's spans are there
    assert any(name.startswith("train:") for name in found["idle_by_span"])


# -- the file's decoder, and how a reader finds the run's trace ----------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(number << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _xplane(name, stat_names, metadata, lines):
    """metadata: {id: (name, {stat id: str})}; lines: [(name, t0_ns,
    [(metadata id, offset_ps, duration_ps, {stat id: int})])]."""
    body = _field(2, name)
    for line_name, t0, events in lines:
        line = _field(2, line_name) + _field(3, t0)
        for key, offset, duration, stats in events:
            event = _field(1, key) + _field(2, offset) + _field(3, duration)
            for stat, value in stats.items():
                event += _field(4, _field(1, stat) + _field(4, value))
            line += _field(4, event)
        body += _field(3, line)
    for key, (text, stats) in metadata.items():
        meta = _field(1, key) + _field(2, text)
        for stat, value in stats.items():
            meta += _field(5, _field(1, stat) + _field(5, value))
        body += _field(4, _field(1, key) + _field(2, meta))
    for key, text in stat_names.items():
        body += _field(5, _field(1, key) + _field(2, _field(1, key) + _field(2, text)))
    return _field(1, body)


def _xspace():
    device = _xplane(
        "/device:TPU:0", {1: "tf_op", 2: "hlo_category"},
        {7: ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
             {2: "loop fusion", 1: "jit(step_fn)/optimizer/lamb/mul:"}),
         8: ("%copy.4 = f32[8]{0} copy(f32[8]{0} %fusion.3)", {})},
        [("Steps", 0, []),
         (reduce.OPS_LINE, 1000, [(7, 5_000_000, 2_000_000, {}),
                                  (8, 9_000_000, 1_000_000, {})])])
    host = _xplane(
        "/host:CPU", {1: "step_num"},
        {1: ("train", {}), 2: ("train:log", {})},
        [("python3", 2000, [(1, 0, 9_000_000, {1: 7}), (2, 6_000_000, 2_000_000, {})])])
    other = _xplane("/host:metadata", {}, {}, [])
    return device + other + host


def test_the_decoder_reads_names_times_op_names_and_host_stats(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    planes = scopes.read_xspace(str(path))
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/host:CPU"]
    ops = planes[0]["lines"][1]
    assert ops["name"] == reduce.OPS_LINE
    assert ops["events"] == [
        ["%fusion.3", 6000.0, 2000.0, "jit(step_fn)/optimizer/lamb/mul"],
        ["%copy.4", 10000.0, 1000.0, None]]
    assert planes[1]["lines"][0]["events"] == [
        ["train", 2000.0, 9000.0, {"step_num": 7}],
        ["train:log", 8000.0, 2000.0, None]]


def test_readers_find_the_runs_trace_by_the_kinds_prefix(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    monkeypatch.setattr(scopes, "_reductions", {})
    ctx = {"summary": {"busy_s": 3e-6}, "updates": 2}
    assert scopes.for_run(ctx) is None          # no trace anywhere
    trace = tmp_path / "bench_train_abc" / "trace" / "plugins" / "profile" / "x"
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(_xspace())
    assert _reader("optimizer_device_ms.train")(ctx) == pytest.approx(1e-3)
    assert _reader("fwd_device_ms.train")(ctx) == 0.0
    assert _reader("unattributed_device_pct.train")(ctx) == pytest.approx(100 / 3)
    # the 2 us gap between the two ops lies under train:log for 1 us
    assert _reader("loop_work_idle_ms.train")(ctx) == pytest.approx(1e-3)
    assert _reader("sync_idle_ms.train")(ctx) == 0.0
    assert len(scopes._reductions) == 1         # parsed once


@pytest.mark.parametrize("name", NEW_METRICS)
def test_every_new_reader_returns_none_on_an_empty_context(name):
    assert _reader(name)({}) is None


def test_every_new_metric_has_its_entry_and_its_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in NEW_METRICS:
        assert entries[name]["moves"] == "train_tokens_per_s"
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["better"] == "lower"
        assert entries[name]["workloads"] == cells
    assert [m["name"] for m in bench["per_layer"]][-len(NEW_METRICS):] == list(
        NEW_METRICS)
