"""``utils/compile_cache.py``: the compile-cache resolver every entry point
shares."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
        """tools/chaos_serve.py used to put the replicas' compile cache
        under mkdtemp(): a path that never hits twice."""
        src = open(os.path.join(REPO, "tools", "chaos_serve.py")).read()
        # no replica is handed a directory: each resolves the same one
        assert '"--compile_cache_dir"' not in src
        assert 'workdir, "compile_cache"' not in src

    def test_no_cache_files_tracked_in_git(self):
        out = subprocess.run(["git", "ls-files", ".jax_cache"], cwd=REPO,
                             capture_output=True, text=True)
        if out.returncode != 0:
            pytest.skip("not a git checkout")
        assert out.stdout.strip() == ""
