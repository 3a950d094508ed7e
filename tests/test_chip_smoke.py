"""chip_smoke.py rehearsed on the CPU.

The script proves the system on a TPU (its docstring has the contract). Here
its control flow runs without one: a tiny model, the CPU as the expected
device, four virtual CPU devices for the mesh phase. How the model shrinks
and the TPU requirement lifts is this file's business — it patches the
script's constants in a driver process; no runner grows a switch for it.
A number from these runs is a count or a loss, never a device metric.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

TINY_MODEL = {
    "vocab_size": 128, "hidden_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "intermediate_size": 64, "hidden_act": "gelu",
    "hidden_dropout_prob": 0.1, "attention_probs_dropout_prob": 0.1,
    "max_position_embeddings": 64, "type_vocab_size": 2,
    "initializer_range": 0.02, "next_sentence": True,
    "tokenizer": "wordpiece", "lowercase": True,
}

# The rehearsal: chip_smoke's constants at a size the CPU finishes in
# seconds, and the CPU as the device every child is expected to report.
DRIVER = """
import sys
sys.path.insert(0, {repo!r})
import chip_smoke as cs

cs.MODEL_CONFIG = {model!r}
cs.EXPECT = {{"platform": "cpu", "kernels": "interpreted"}}
cs.KERNELS.update(heads=2, depth=16, hidden=128, batch=2, seqs=[32])
cs.PHASE1.update(seq_len=32, local_batch=4)
cs.PHASE2.update(seq_len=64, local_batch=2)
# a tiny step compiles in under the trainer's 10 s persistence bar: the
# resumed run recompiles it, and its compile event says so
cs.RESUMED_STEP_COMPILE = ["uncached"]
cs.SERVE.update(buckets="16,64")
cs.MESH.update(local_batch=2)
cs.ONE_DEVICE_ENV = {{"XLA_FLAGS": "--xla_force_host_platform_device_count=1"}}
cs.ALL_DEVICES_ENV = {{"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}}
rc = cs.main(sys.argv[1:])
print("PARENT_IMPORTED_JAX", "jax" in sys.modules, file=sys.stderr)
sys.exit(rc)
"""


def _env(cache_dir=None):
    # one CPU device unless a phase says otherwise (conftest asks for eight)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    if cache_dir:
        # a compile cache of the test's own, placed from outside: the first
        # server start is cold whatever an earlier run left in the checkout
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    return env


class _Run:
    """One chip_smoke.py process, its output going to files."""

    def __init__(self, workdir, argv, env):
        workdir.mkdir(parents=True, exist_ok=True)
        self.workdir = workdir
        self._out = open(workdir / "stdout.txt", "w+")
        self._err = open(workdir / "stderr.txt", "w+")
        self._proc = subprocess.Popen(argv, env=env, cwd=str(workdir),
                                      stdout=self._out, stderr=self._err)

    def finish(self, timeout=600):
        try:
            self.returncode = self._proc.wait(timeout=timeout)
        finally:
            if self._proc.poll() is None:
                self._proc.kill()
                self._proc.wait()
            for stream in (self._out, self._err):
                stream.seek(0)
            self.stdout, self.stderr = self._out.read(), self._err.read()
            self._out.close()
            self._err.close()
        self.lines = [json.loads(line) for line in self.stdout.splitlines()]
        return self


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """The three runs the tests below look at, started together and waited
    for together: they are separate processes on separate work directories,
    and one after the other they would cost this file half as much again
    of the suite's time limit."""
    root = tmp_path_factory.mktemp("chip_smoke")
    model = root / "tiny_model.json"
    model.write_text(json.dumps(TINY_MODEL))
    driver = root / "rehearse.py"
    driver.write_text(DRIVER.format(repo=REPO, model=str(model)))

    def rehearse(name, *args):
        return _Run(root / name, [sys.executable, str(driver), *args],
                    _env(str(root / name / "placed_cache")))

    started = {
        "no_tpu": _Run(root / "no_tpu", [sys.executable, SMOKE], _env()),
        "one_chip": rehearse("one_chip"),
        "four_chips": rehearse("four_chips", "--chips", "4"),
    }
    return {name: run.finish() for name, run in started.items()}


def test_fails_and_prints_no_result_without_a_tpu(runs):
    """Asked for the chip where there is none, the script fails with the
    reason: it does not carry on through BERT-large on a CPU, and it prints
    no ``"ok": true``."""
    run = runs["no_tpu"]
    assert run.returncode != 0
    assert '"ok": true' not in run.stdout
    assert [line.get("phase") for line in run.lines[:-1]] == ["kernels"]
    assert run.lines[0]["ok"] is False
    assert "'platform': 'cpu'" in run.lines[0]["error"]
    assert run.lines[-1]["failed"] == ["kernels"]


def test_importing_the_parent_imports_no_jax():
    """One process for each chip: the parent is stdlib only."""
    proc = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; assert 'jax' not in sys.modules"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr[-1000:]


def test_one_chip_rehearsal_runs_every_phase(runs):
    run = runs["one_chip"]
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-2000:]
    assert "PARENT_IMPORTED_JAX False" in run.stderr
    assert run.lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    phases = {line["phase"]: line for line in run.lines[:-1]}
    assert list(phases) == ["kernels", "train", "serve"]
    assert all(line["ok"] for line in phases.values())
    assert phases["kernels"]["interpret_mode"] is True
    assert phases["kernels"]["checks"] >= 10
    train = phases["train"]
    assert train["phase1"]["steps"] == [1, 2, 3, 4]
    assert train["phase1_resume"]["steps"] == [5, 6]  # the count continues
    assert len(train["phase2"]["losses"]) == 3
    serve = phases["serve"]
    assert serve["requests"] == 6
    assert serve["warmup_compiles_cold"] == serve["warmup_compiles"] > 0
    assert serve["second_start_compiles_cold"] == 0
    # JAX_COMPILATION_CACHE_DIR was set: the cache is there and only there
    assert os.listdir(run.workdir / "placed_cache")


def test_four_chip_option_runs_the_mesh_phase_and_no_other(runs):
    run = runs["four_chips"]
    assert run.returncode == 0, run.stdout[-3000:] + run.stderr[-2000:]
    assert "PARENT_IMPORTED_JAX False" in run.stderr
    assert run.lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    assert [line["phase"] for line in run.lines[:-1]] == ["mesh"]
    mesh = run.lines[0]
    assert set(mesh) >= {"dp=1", "dp=4", "dp=2,fsdp=2"}
    for spec, share in (("dp=4", 1.0), ("dp=2,fsdp=2", 0.5)):
        placed = mesh[spec]["placed"]
        assert [placed[k] for k in ("params_devices", "opt_state_devices",
                                    "batch_devices")] == [4, 4, 4]
        assert placed["params_share_on_first_device"] == \
            pytest.approx(share, abs=0.1)
        assert mesh[spec]["max_loss_diff"] <= 0.05


@pytest.mark.parametrize("args", [["--phases", "nope"],
                                  ["--chips", "4", "--phases", "train"]])
def test_a_phase_that_is_not_there_is_refused(args):
    """``--phases`` picks among the phases of the chosen ``--chips`` (to
    find a fault without paying for the rest); with ``--chips 4`` there is
    the mesh phase and no other."""
    proc = subprocess.run([sys.executable, SMOKE, *args], env=_env(),
                          cwd=REPO, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 2 and proc.stdout == ""
    assert "no phase" in proc.stderr
