"""``tools/lowered_steps.py``, the guard a refactor leans on when it says the
cells' programs did not move: the tool runs, what it writes can be compared
across two checkouts (no path of the checkout, no source line, no serialized
kernel), each file holds the kernels that tell which path its cell takes, two
runs give the same bytes, and every family of ``config.MODEL_FAMILIES`` has a
step in it, a FLOP count and its scopes. Nothing here reads a clock."""

import importlib.util
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bert_pytorch_tpu import pretrain
from bert_pytorch_tpu.config import MODEL_FAMILIES
from bert_pytorch_tpu.models import build_pretraining_model
from bert_pytorch_tpu.utils import flops

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO_ROOT, "tools", "lowered_steps.py")

spec = importlib.util.spec_from_file_location("_lowered_steps", TOOL)
tool = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tool)  # imports nothing of the package: main() does

FILES = tuple(name for name, _ in tool.steps())
# file -> (names it holds, names it must not hold): the kernels of PERF.md 3
# that say which path the cell's program takes
PATHS = {
    "nemotron_h": (('"flash_fwd"', '"flash_bwd_dkv"', "@tgmm"),
                   ("flash_window",)),
    "laguna": (('"flash_fwd"', '"flash_window_fwd"', '"flash_window_bwd_dq"',
                '"rotary_turn"', "@tgmm"), ("flash_gated",)),
    "zaya": (('"flash_cca_fwd"', '"flash_cca_bwd_dkv"', '"rotary_turn"',
              "@tgmm"), ('"flash_fwd"',)),
    "phi4flash": (('"selective_scan_fwd"', '"selective_scan_bwd"'),
                  ("flash_",)),
    "bert_phase2": (('"flash_fwd"', '"flash_bwd_dq"', '"flash_bwd_dkv"',
                     "rng_bit_generator"), ("flash_window",)),
    "qwen3_next": (('"delta_rule_fwd"', '"delta_rule_bwd"', '"gdn_mix_fwd"',
                    '"gdn_mix_bwd"', '"gated_norm_fwd"', '"gated_norm_bwd"',
                    '"flash_gated_fwd"', '"flash_gated_bwd_dq"', "@tgmm"),
                   ('"flash_fwd"',)),
    "bert_phase1": (("rng_bit_generator",), ("flash_fwd", "tpu_custom_call")),
    "KeyeVL2": (('"dsa_select"', '"dsa_core_fwd"', '"dsa_core_bwd_dq"',
                 '"dsa_core_bwd_dkv"', '"dsa_index_loss"', "@tgmm"),
                ('"flash_fwd"', "flash_gated")),
    "joyai_llm_flash": (('"flash_mla_fwd"', '"flash_mla_bwd_dq"',
                         '"flash_mla_bwd_dkv"', "@tgmm"),
                        ('"flash_fwd"', '"rotary_turn"')),
    "mellum": (('"flash_fwd"', '"flash_window_fwd"', '"flash_window_bwd_dq"',
                '"rotary_turn"', "@tgmm"),
               ("flash_gated", "all_to_all", "sdy.manual_computation")),
    "mellum_ep4": (('"flash_fwd"', '"flash_window_fwd"', '"rotary_turn"',
                    "@tgmm", "sdy.manual_computation", "stablehlo.all_to_all",
                    "stablehlo.all_gather", "stablehlo.reduce_scatter",
                    '"expert"'), ("flash_gated",)),
    "kernel_bidirectional_dropout": (
        ("name=flash_fwd", "name=flash_bwd_dq", "name=flash_bwd_dkv",
         "prng_seed"), ("flash_window",)),
    "kernel_packed": (("name=flash_fwd", "name=flash_bwd_dkv"),
                      ("prng_seed", "flash_window")),
    "kernel_causal": (("name=flash_fwd", "name=flash_bwd_dq"),
                      ("prng_seed", "flash_window")),
    "kernel_window": (("name=flash_window_fwd", "name=flash_window_bwd_dq",
                       "name=flash_window_bwd_dkv"), ("name=flash_fwd",)),
    "kernel_gdn_mix": (("name=gdn_mix_fwd", "name=gdn_mix_bwd"),
                       ("gated_norm",)),
    "kernel_gated_norm": (("name=gated_norm_fwd", "name=gated_norm_bwd"),
                          ("gdn_mix",)),
}
# family -> the tuple of pretrain that names its step's scopes
SCOPES = {"nemotron_h": "CAUSAL_LM_SCOPES", "laguna": "LAGUNA_SCOPES",
          "phi4flash": "PHI_FLASH_SCOPES", "zaya": "ZAYA_SCOPES",
          "qwen3_next": "QWEN3_NEXT_SCOPES", "KeyeVL2": "KEYE_SCOPES",
          "joyai_llm_flash": "JOYAI_SCOPES", "mellum": "MELLUM_SCOPES"}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The tool's command line, twice at once, each into its own directory."""
    outs = [str(tmp_path_factory.mktemp(f"lowered{i}")) for i in range(2)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, TOOL, REPO_ROOT, out], env=env, cwd=REPO_ROOT,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for out in outs]
    for proc in procs:
        said, _ = proc.communicate(timeout=600)
        assert proc.returncode == 0, said[-3000:]
        assert "not in this checkout" not in said, said
    return outs


def test_the_table_of_paths_covers_every_file_the_tool_writes():
    assert set(PATHS) == set(FILES) and len(FILES) == len(set(FILES))


@pytest.mark.parametrize("name", FILES)
def test_a_written_file_compares_across_checkouts_and_names_its_path(
        runs, name):
    with open(os.path.join(runs[0], name + ".txt")) as f:
        text = f.read()
    assert len(text.splitlines()) > 100
    # what the tool cuts so that two checkouts give equal files
    assert REPO_ROOT not in text and "site-packages" not in text
    assert not re.search(r"/[\w.-]+/[\w./-]+\.py", text)  # an absolute path
    assert not re.search(r"\.py\W{0,2}:?\d", text)  # a source line
    assert "loc(" not in text
    assert set(re.findall(r'backend_config\s*=\s*"((?:[^"\\]|\\.)*)"', text)
               ) <= {"<mosaic>"}
    holds, lacks = PATHS[name]
    assert [n for n in holds if n not in text] == []
    assert [n for n in lacks if n in text] == []


def _kernel_call_sites(text, kernel):
    """How often the step's text runs ``kernel``: a jitted entry point is ONE
    private function that holds the custom call, called from each place the
    trace reached it."""
    holders, inside = set(), None
    for line in text.splitlines():
        header = re.match(r"\s*func\.func (?:private |public )?@([\w.]+)\(",
                          line)
        if header:
            inside = header.group(1)
        elif f'kernel_name = "{kernel}"' in line:
            holders.add(inside)
    return sum(len(re.findall(rf"call @{re.escape(name)}\(", text))
               for name in holders)


def test_the_objectives_kernel_is_called_once_a_layer_and_micro_batch_trip(
        runs):
    """``KeyeVL2.txt`` is two layers under ``--remat full`` with the
    micro-batches as ONE loop: the choice's, the objective's and the core's
    forward kernels each run once a layer there (what they make is kept
    across remat by name, ``ops/remat.py``), as the two backward kernels."""
    with open(os.path.join(runs[0], "KeyeVL2.txt")) as f:
        text = f.read()
    layers = tool.SIZES["KeyeVL2"]["num_hidden_layers"]
    assert _kernel_call_sites(text, "dsa_index_loss") == layers
    assert _kernel_call_sites(text, "dsa_select") == layers
    assert _kernel_call_sites(text, "dsa_core_fwd") == layers
    assert _kernel_call_sites(text, "dsa_core_bwd_dq") == layers


@pytest.mark.parametrize("name,kernel,blocks", [
    # every layer and the multi-token-prediction module
    ("joyai_llm_flash", "flash_mla",
     tool.SIZES["joyai_llm_flash"]["num_hidden_layers"] + 1),
    ("zaya", "flash_cca", tool.SIZES["zaya"]["num_hidden_layers"]),
    # one gated softmax attention in a period of four
    ("qwen3_next", "flash_gated", 1),
])
def test_a_family_that_keeps_the_flash_residuals_runs_its_core_once(
        runs, name, kernel, blocks):
    """Under ``--remat full`` the families that ask for ``FLASH_OUT`` /
    ``FLASH_LSE`` (``KEPT_ACROSS_REMAT`` in ``models/joyai.py``, ``zaya.py``,
    ``qwen3_next.py``, through ``remat_policy(keeping=)``) hold the core's
    forward kernel ONCE a block of attention (twice before PR 49, the second
    in the block's recompute) and the two backward kernels once."""
    assert _held(runs, name, kernel + "_fwd") == blocks
    assert (_held(runs, name, kernel + "_bwd_dq")
            == _held(runs, name, kernel + "_bwd_dkv") == blocks)


@pytest.mark.parametrize("name", ["nemotron_h", "laguna"])
def test_a_family_that_asks_for_nothing_still_runs_its_core_twice(runs, name):
    """The hybrid's and laguna's cells stand at the chip's limit and ask
    ``full`` for nothing: their causal forward runs in the forward pass and
    again in each block's recompute, as before PR 49."""
    assert (_held(runs, name, "flash_fwd")
            == 2 * _held(runs, name, "flash_bwd_dq") > 0)


def test_the_step_under_an_expert_axis_is_one_manual_region(runs):
    """``mellum_ep4.txt``: the update's micro-batches are ONE ``shard_map``
    over the mesh (the optimizer stays outside, on the divided arrays); the
    kernels inside are the one-device step's, call for call; the exchange's
    all-to-alls are there and the one-device step has none."""
    read = lambda name: open(os.path.join(runs[0], name + ".txt")).read()
    one, four = read("mellum"), read("mellum_ep4")
    assert four.count("sdy.manual_computation(") == 1
    for kernel in ("flash_fwd", "flash_window_fwd", "flash_window_bwd_dq",
                   "flash_window_bwd_dkv", "rotary_turn"):
        held = lambda text: text.count(f'kernel_name = "{kernel}"')
        assert held(four) == held(one) > 0, kernel
    assert "stablehlo.all_to_all" not in one
    # the slots' rows and terms, the counts, the backward's cotangents and
    # weights: more than one exchange a layer and pass
    layers = tool.SIZES["mellum"]["num_hidden_layers"]
    assert four.count("stablehlo.all_to_all") >= 6 * layers


def _held(runs, name, kernel):
    with open(os.path.join(runs[0], name + ".txt")) as f:
        return f.read().count(f'kernel_name = "{kernel}"')


def test_a_second_run_writes_the_same_bytes(runs):
    assert sorted(os.listdir(runs[0])) == sorted(os.listdir(runs[1])) == sorted(
        name + ".txt" for name in FILES)
    for name in FILES:
        with open(os.path.join(runs[0], name + ".txt"), "rb") as a, \
                open(os.path.join(runs[1], name + ".txt"), "rb") as b:
            assert a.read() == b.read(), name


@pytest.mark.parametrize("family", sorted(MODEL_FAMILIES))
def test_a_family_of_the_registry_has_all_a_run_asks_of_it(family):
    """What ``run_pretraining.main`` and the guard look up by the family's
    name, each a KeyError at a run's first log line (or a hole in the guard)
    where a ``model_config`` PR forgot it."""
    sizes = dict(tool.SIZES[family], model_type=family)
    config = MODEL_FAMILIES[family].from_dict(sizes)
    assert config.model_type == family
    assert config.to_dict()["model_type"] == family
    model = build_pretraining_model(config, jnp.float32)
    if family == "bert":
        assert {"bert_phase1", "bert_phase2"} <= set(FILES)
        assert getattr(model, "objective", "mlm") == "mlm"
        return
    assert family in FILES
    assert model.objective == "causal_lm"
    assert flops.causal_lm_train_flops_per_seq(config, 64) > 0
    scopes = getattr(pretrain, SCOPES[family])
    assert len(scopes) == len(set(scopes)) and "lm_head" in scopes
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 64), jnp.int32))
    assert jax.tree_util.tree_leaves(shapes)
