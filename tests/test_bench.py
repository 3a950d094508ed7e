"""bench.py: what is left of the parent (one child, passed through), the
MFU accounting, and the compile-cache resolver every entry point shares.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _import_bench(monkeypatch, **env):
    """bench.py imported fresh under ``env`` (its module constants are
    env-derived); the parent half is stdlib-only, so this imports no jax."""
    import importlib.util

    for key, value in env.items():
        monkeypatch.setenv(key, value)
    spec = importlib.util.spec_from_file_location("_bench_t", BENCH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_metric_name_tracks_phase_env(monkeypatch):
    bench = _import_bench(monkeypatch, BENCH_PHASE="2", BENCH_KFAC="1")
    assert bench._metric_name_and_anchor()[0] == \
        "bert_large_phase2_kfac_seq_per_sec"


def _run_bench(extra_env, timeout=240):
    env = dict(os.environ, **extra_env)
    env.pop("BENCH_LEDGER", None)
    return subprocess.run(
        [sys.executable, BENCH], env=env, timeout=timeout,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def test_parent_never_imports_jax():
    """One process for each chip: a parent that had touched JAX would hold
    the chip its child needs."""
    code = ("import sys; sys.argv = ['bench.py']; import bench; "
            "assert 'jax' not in sys.modules, 'bench parent imported jax'")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-1000:]


def test_no_tpu_fails_without_a_result_line():
    """No retry, no probe, no substitute model, no 0.0 result: on a machine
    without a TPU the training bench exits non-zero and prints no metric."""
    proc = _run_bench({"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"metric"' not in proc.stdout
    assert "measures training on a TPU" in proc.stderr


def test_child_exit_code_and_output_pass_through(tmp_path):
    """The parent starts the child once and passes its output and exit code
    through — a child that exits non-zero is not booked as a capture, even
    when it printed a metric line first."""
    fake = tmp_path / "bench.py"
    src = open(BENCH).read()
    marker = 'if __name__ == "__main__":'
    body = (marker + """
    if os.environ.get("BENCH_CHILD") == "1":
        print("child says hello")
        print(json.dumps({"metric": "m", "value": 1.0}))
        sys.exit(int(os.environ["FAKE_RC"]))
    else:
        main()
""")
    fake.write_text(src[:src.index(marker)].replace(
        "REPO_ROOT = os.path.dirname(os.path.abspath(__file__))",
        f"REPO_ROOT = {REPO!r}") + body)
    for rc in (0, 7):
        proc = subprocess.run(
            [sys.executable, str(fake)], timeout=60,
            env=dict(os.environ, FAKE_RC=str(rc), BENCH_LEDGER=""),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        assert proc.returncode == rc, proc.stderr[-500:]
        lines = proc.stdout.splitlines()
        assert lines[0] == "child says hello"
        assert json.loads(lines[-1])["metric"] == "m"


def test_ledger_is_off_unless_named(tmp_path, monkeypatch):
    """<repo>/PERF_LEDGER.jsonl is the driver's record, not this program's
    to write: no BENCH_LEDGER, no ledger."""
    monkeypatch.delenv("BENCH_LEDGER", raising=False)
    assert _import_bench(monkeypatch).LEDGER_PATH == ""
    named = str(tmp_path / "mine.jsonl")
    assert _import_bench(monkeypatch, BENCH_LEDGER=named).LEDGER_PATH == named


class TestFlops:
    def _config(self):
        from bert_pytorch_tpu.config import BertConfig
        return BertConfig(
            vocab_size=30528, hidden_size=1024, num_hidden_layers=24,
            num_attention_heads=16, intermediate_size=4096)

    def test_bert_large_phase1_flops(self):
        from bert_pytorch_tpu.utils import flops
        got = flops.bert_train_flops_per_seq(
            self._config(), seq_len=128, max_pred_per_seq=20)
        # Hand-derived: encoder 24*(8*128*1024^2 + 4*128^2*1024 +
        # 4*128*1024*4096) + heads 20*(2*1024^2 + 2*1024*30528) + pooler
        # + NSP, all x3 for fwd+bwd.
        enc = 24 * (8 * 128 * 1024**2 + 4 * 128**2 * 1024
                    + 4 * 128 * 1024 * 4096)
        heads = 20 * (2 * 1024**2 + 2 * 1024 * 30528)
        heads += 2 * 1024**2 + 2 * 1024 * 2
        assert got == pytest.approx(3.0 * (enc + heads), rel=1e-12)
        # Sanity: BERT-large phase-1 is ~0.24 TFLOPs/seq.
        assert 0.2e12 < got < 0.3e12

    def test_phase2_flops_larger_than_phase1(self):
        from bert_pytorch_tpu.utils import flops
        p1 = flops.bert_train_flops_per_seq(self._config(), 128, 20)
        p2 = flops.bert_train_flops_per_seq(self._config(), 512, 80)
        # Phase 2 is ~4-5x the FLOPs (seq 4x + quadratic attention term).
        assert 4.0 < p2 / p1 < 5.5

    def test_peak_lookup_and_mfu(self):
        from bert_pytorch_tpu.utils import flops
        assert flops.peak_tflops("TPU v5e") == 197.0
        assert flops.peak_tflops("TPU v5 lite") == 197.0  # libtpu's v5e
        assert flops.peak_tflops("TPU v5") == 459.0       # libtpu's v5p
        assert flops.peak_tflops("TPU v4") == 275.0
        c = self._config()
        per_seq = flops.bert_train_flops_per_seq(c, 128, 20)
        # The round-1 claimed 396 seq/s/chip on v5e must land near 0.5 MFU.
        assert 0.4 < flops.mfu(396.0, per_seq, "TPU v5e") < 0.55

    def test_off_tpu_mfu_is_absent_not_zero(self):
        from bert_pytorch_tpu.utils import flops
        assert flops.peak_tflops("cpu") is None
        assert flops.mfu(396.0, 1e12, "cpu") is None

    @pytest.mark.parametrize("kind", ["TPU v5 litepod-next", "TPU v9",
                                      "TPU v5x"])
    def test_unknown_tpu_kind_is_an_error(self, kind):
        """An assumed peak would make every MFU computed from it wrong
        without saying so — and a kind that merely contains "v5" must not
        be handed the v5p peak."""
        from bert_pytorch_tpu.utils import flops
        with pytest.raises(ValueError, match="no peak FLOP/s known"):
            flops.peak_tflops(kind)
        with pytest.raises(ValueError, match="no peak FLOP/s known"):
            flops.mfu(396.0, 1e12, kind)


class TestCompileCache:
    """utils/compile_cache.py: one resolver for every entry point, and a
    directory validated up front (a failure at compile time would only
    surface as a buried JAX warning)."""

    ENV = "JAX_COMPILATION_CACHE_DIR"

    def test_enables_and_creates_dir(self, tmp_path, monkeypatch,
                                     persistent_cache):
        import jax

        from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache

        monkeypatch.delenv(self.ENV, raising=False)
        target = tmp_path / "nested" / "cache"
        assert enable_compile_cache(str(target)) == str(target)
        assert target.is_dir()
        assert jax.config.jax_compilation_cache_dir == str(target)

    def test_unwritable_dir_is_an_error(self, monkeypatch, persistent_cache):
        from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache

        monkeypatch.delenv(self.ENV, raising=False)
        with pytest.raises(OSError):
            enable_compile_cache("/proc/1/nonexistent/cache")

    def test_env_set_repository_code_sets_no_directory(
            self, tmp_path, monkeypatch, persistent_cache):
        """JAX_COMPILATION_CACHE_DIR set: JAX's own handling stands — the
        resolver answers None and nothing writes the config, explicit
        --compile_cache_dir or not."""
        import jax

        from bert_pytorch_tpu.utils import compile_cache

        placed = str(tmp_path / "placed_from_outside")
        monkeypatch.setenv(self.ENV, placed)
        assert compile_cache.resolve_cache_dir() is None
        assert compile_cache.resolve_cache_dir("/elsewhere") is None
        writes = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda key, value: (writes.append(key), real_update(key, value)))
        assert compile_cache.enable_compile_cache(
            str(tmp_path / "explicit")) == placed
        assert "jax_compilation_cache_dir" not in writes
        assert not (tmp_path / "explicit").exists()

    @pytest.mark.parametrize("explicit", ["", "given"])
    def test_env_unset_fixed_checkout_path_or_explicit(
            self, explicit, tmp_path, monkeypatch):
        """Unset: <checkout>/.jax_cache — a fixed path, the same in every
        process, never a temporary or time-stamped name — unless an
        explicit directory was given."""
        from bert_pytorch_tpu.utils import compile_cache

        monkeypatch.delenv(self.ENV, raising=False)
        explicit = str(tmp_path / explicit) if explicit else ""
        got = compile_cache.resolve_cache_dir(explicit)
        if explicit:
            assert got == explicit
            return
        assert got == os.path.join(REPO, ".jax_cache")
        again = subprocess.run(
            [sys.executable, "-c",
             "from bert_pytorch_tpu.utils.compile_cache import "
             "resolve_cache_dir; print(resolve_cache_dir())"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
            env={k: v for k, v in os.environ.items() if k != self.ENV})
        assert again.stdout.strip() == got, again.stderr[-500:]

    def test_harnesses_build_no_cache_under_a_temporary_dir(self):
        """bench.py and tools/chaos_serve.py used to put the replicas'
        compile cache under mkdtemp(): a path that never hits twice."""
        for rel in ("bench.py", os.path.join("tools", "chaos_serve.py")):
            src = open(os.path.join(REPO, rel)).read()
            # no replica is handed a directory: each resolves the same one
            assert '"--compile_cache_dir"' not in src, rel
            assert 'workdir, "compile_cache"' not in src, rel

    def test_no_cache_files_tracked_in_git(self):
        out = subprocess.run(["git", "ls-files", ".jax_cache"], cwd=REPO,
                             capture_output=True, text=True)
        if out.returncode != 0:
            pytest.skip("not a git checkout")
        assert out.stdout.strip() == ""


class TestPallasBhBlock:
    def test_cap_and_divisibility_walk(self):
        from bert_pytorch_tpu.ops.pallas.attention import _pick_bh_block

        # the heuristic caps at 16 (the 4096 VMEM budget)
        assert _pick_bh_block(128, 896) == 16
        # and the divisibility walk rules: bh % g == 0
        assert _pick_bh_block(128, 48) == 16
