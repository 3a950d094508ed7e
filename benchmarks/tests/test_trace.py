import json
import os

import pytest

from benchmarks.trace import flops, reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def _plane(name, line, events):
    return {"name": name, "lines": [{"name": line, "events": events}]}


def test_interval_arithmetic():
    assert reduce.union([[5, 7], [0, 2], [1, 3]]) == [[0, 3], [5, 7]]
    assert reduce._minus([[0, 10]], [[2, 3], [5, 20]]) == [[0, 2], [3, 5]]
    assert reduce._minus([[0, 4], [6, 8]], []) == [[0, 4], [6, 8]]


def test_busy_idle_ops_collectives_and_gap_attribution():
    device = _plane("/device:TPU:0", reduce.OPS_LINE, [
        ["%while.9", 0.0, 6.5e6],        # spans its body: self time 0.5 ms
        ["%fusion.1", 0.0, 4e6], ["%fusion.2", 5e6, 1e6],
        ["%all-reduce-done.7", 6.5e6, 0.5e6],
        ["%fusion.1", 17e6, 3e6]])       # after a 10 ms gap
    device["lines"].append({"name": reduce.ASYNC_LINE, "events": [
        ["%all-reduce-start.7", 4e6, 3e6],      # in flight 4..7 ms
        ["%copy-start.3", 8e6, 5e6]]})          # a copy is not a collective
    device["lines"].append({"name": "Steps", "events": [["1", 0.0, 20e6]]})
    host = _plane("/host:CPU", "python", [
        ["bench:data_wait", 7.5e6, 8e6], ["bench:dispatch", 15.5e6, 1e6],
        ["something else", 0.0, 20e6]])
    s = reduce.summarize([device, host])
    assert s["n_devices"] == 1
    assert s["busy_s"] == pytest.approx(10e-3)
    assert s["window_s"] == pytest.approx(20e-3)
    assert s["collective_s"] == pytest.approx(3e-3)
    assert s["collective_exposed_s"] == pytest.approx(2e-3)
    ops = dict(s["device_ops"])
    assert s["device_ops"][0] == ["%fusion.1", pytest.approx(7e-3)]
    assert ops["%while.9"] == pytest.approx(1.5e-3)  # 6.5 less its 5 ms body
    assert dict(s["idle_gaps"]) == {
        "data_wait": pytest.approx(8e-3), "dispatch": pytest.approx(1e-3),
        "loop": pytest.approx(1e-3)}


def test_a_trace_without_device_ops_is_an_error():
    with pytest.raises(ValueError):
        reduce.summarize([_plane("/host:CPU", "python", [["x", 0.0, 1.0]])])


def test_the_recorded_chip_trace_reduces():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        planes = json.load(f)
    s = reduce.summarize(planes)
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["device_ops"] and s["device_ops"][0][1] >= s["device_ops"][-1][1]
    assert s["idle_gaps"], "the cut is taken round the longest idle gap"
    assert abs(sum(g[1] for g in s["idle_gaps"])
               - (s["window_s"] - s["busy_s"])) < 1e-9


def test_flops_and_peaks():
    config = {"hidden_size": 1024, "intermediate_size": 4096,
              "num_hidden_layers": 24, "vocab_size": 30522}
    per_seq = flops.train_flops_per_seq(config, 128, 20)
    encoder = 24 * (8 * 128 * 1024 ** 2 + 4 * 128 ** 2 * 1024 + 4 * 128 * 1024 * 4096)
    heads = 20 * (2 * 1024 ** 2 + 2 * 1024 * 30528) + 2 * 1024 ** 2 + 4 * 1024
    assert per_seq == 3.0 * (encoder + heads)
    assert flops.peak_flops("TPU v5 lite") == 197e12
    for unknown in ("TPU v9", "source", "cpu"):
        with pytest.raises(KeyError):
            flops.peaks(unknown)
