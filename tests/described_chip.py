"""A TPU v5e that is described and not attached, for the tests that compile
for it (``tests/test_chip_compile.py``: the kernels;
``tests/test_chip_compile_steps.py``: whole train steps). The fixtures are
imported by name into those files."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp
# two processes may each hold libtpu to compile (the two files run on two
# xdist workers); without it the second fails on the lock file and SKIPS
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax  # noqa: E402
import pytest  # noqa: E402

# (seq, batch): single-chip microbatches near the largest that fit at
# the phase-1 and phase-2 shapes.
TRAIN_SHAPES = {128: 56, 512: 28}
HBM_BYTES = 16 * 1024 ** 3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    """A described host of four v5e chips (2x2)."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu / no such topology here
        pytest.skip(f"cannot describe a TPU topology: {exc}")


@pytest.fixture(scope="module")
def chip(topo):
    """One described v5e chip."""
    return topo.devices[0]


@pytest.fixture(autouse=True)
def _compile_for_the_chip(monkeypatch):
    """The kernels ask ``interpret_mode()`` — which sees the CPU backend
    here — so the tests steer it themselves: compiled, as on the chip. And
    conftest's fp32 matmul precision is for CPU numerics; the runners leave
    the default, and Mosaic refuses an fp32-precision matmul of bf16 tiles.
    (The persistent compile cache is off for the whole suite, conftest.py:
    an executable compiled for a described chip could be written to it but
    not read back.)"""
    from bert_pytorch_tpu.ops.pallas import (attention, common, layernorm,
                                             selective_scan)

    for module in (common, attention, layernorm, selective_scan):
        monkeypatch.setattr(module, "interpret_mode", lambda: False)
    with jax.default_matmul_precision("default"), \
            jax.default_prng_impl("rbg"):  # the runners' --rng_impl default
        yield


def _assert_kernel(compiled, *names):
    """The program holds a Mosaic kernel, and each ``name=`` its
    ``pl.pallas_call`` gave reached the custom call's ``op_name`` (what a
    profiler trace of the chip shows in place of a number XLA chose)."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for name in names:
        assert re.search(
            r'custom_call_target="tpu_custom_call"[^\n]*'
            rf'op_name="[^"]*[/(]{name}[/)]', text), name  # jvp(name) too
