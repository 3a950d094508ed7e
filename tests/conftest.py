"""Test harness configuration.

Multi-device logic is tested on a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``) — the TPU-world analog of the
reference's Gloo-backend CPU test harness (reference src/dataset.py:455).
These env vars must be set before jax initializes its backends, hence the
module-level assignment in conftest.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# The tests run on the virtual CPU mesh, whatever the environment offers.
jax.config.update("jax_platforms", "cpu")

# Every entry point now turns JAX's persistent compile cache on, by default
# at <checkout>/.jax_cache (utils/compile_cache.py). The suite must neither
# read a previous run's entries nor pay to write thousands of its own, so
# in THIS process the cache stays off; the tests that are about the cache
# switch it on for themselves (the ``persistent_cache`` fixture below).
jax.config.update("jax_enable_compilation_cache", False)

# The CPU backend's default matmul precision truncates inputs to bf16 (TPU
# MXU emulation), which would drown kernel-vs-reference comparisons in 1e-2
# noise. Tests compare numerics, so force true fp32 matmuls.
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


# What a runner leaves set in the process beside the PRNG impl: its compile
# cache puts the ops' names and source lines into the cache key
# (utils/compile_cache.py enable_compile_cache).
_RUNNER_FLAGS = {flag: getattr(jax.config, flag) for flag in (
    "jax_compilation_cache_include_metadata_in_key",
    "jax_traceback_in_locations_limit")}


@pytest.fixture(autouse=True)
def _reset_prng_impl():
    """run_pretraining sets the process-global PRNG impl (--rng_impl, default
    'rbg') and its compile cache's key flags; reset them so tests that ran
    after a runner test see the same threefry streams and the same cache keys
    as tests that ran first (which worker runs which file after which is
    xdist's choice: tests/test_telemetry.py's cache-hit test failed after a
    runner test of another file had run in its worker)."""
    yield
    jax.config.update("jax_default_prng_impl", "threefry2x32")
    for flag, value in _RUNNER_FLAGS.items():
        jax.config.update(flag, value)


@pytest.fixture
def persistent_cache(tmp_path):
    """JAX's persistent compile cache, on for one test (it is off for the
    suite — see the note at the top): an empty directory of the test's
    own, every compile persisted. Yields the directory; the process is
    left as it was."""
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    cache_dir = str(tmp_path / "jax_cache")
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # jax latches cache-enablement at the first compile of the process;
    # the reset makes it read the config again.
    compilation_cache.reset_cache()
    # What this process traced before is forgotten too: a library function
    # an earlier test of this worker already traced (``lax.top_k``,
    # ``jnp.cumsum`` ...) is otherwise lowered from that trace, and the
    # module a fresh process lowers for the same program then has another
    # key (tests/test_inference_fastpath.py's second process missed the cache
    # after tests/test_keye_vl.py's tests of the exact choice: which worker
    # runs which file after which is xdist's choice).
    jax.clear_caches()
    yield cache_dir
    jax.config.update("jax_enable_compilation_cache", False)
    for key, value in before.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def tiny_config():
    from bert_pytorch_tpu.config import BertConfig

    return BertConfig(
        vocab_size=128,
        hidden_size=32,
        num_hidden_layers=2,
        num_attention_heads=4,
        intermediate_size=64,
        max_position_embeddings=64,
        type_vocab_size=2,
        next_sentence=True,
    )
