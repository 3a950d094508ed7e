"""Inference fast path tests (ISSUE 8; docs/serving.md "Inference fast
path"): weight quantization, the forward-only Pallas attention kernel,
and warm-in-seconds cold starts.

Covers, on CPU:

* the quantization rules (ops/quant.py): per-tensor symmetric int8 with
  per-layer scales for the encoder's scan stacks, bf16 storage, the
  EXCLUDE_MODULES downgrade, embeddings/LayerNorm untouched;
* the STREAMING quantized checkpoint load (utils/checkpoint.py): the
  per-leaf decode produces bit-identical trees to the host-side
  transform, casts to the target dtype with no quantization, and fails
  loudly on shape mismatches;
* per-task parity bounds quantized-vs-fp32 on all four served heads —
  the documented levels: |Δlogit| <= 2e-2 for bf16, <= 1e-1 for int8
  (tiny seeded config; real BERT-base measurements in docs/serving.md);
* packed == unpacked parity of ``flash_attention_infer`` in interpret
  mode, and model-level pallas_infer == xla parity;
* the warm cold-start acceptance: a SECOND engine start in a fresh
  process against the persisted AOT compile cache performs ZERO cold
  compiles, with the persistent-cache counter events (not wall clock)
  as the authority;
* the serve_cold_start schema kind and the telemetry-report gates on
  "serve p50 latency" / "serve cold start" / "serve cold compiles".
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bert_pytorch_tpu.config import BertConfig

BF16_LOGIT_ATOL = 2e-2
INT8_LOGIT_ATOL = 1e-1

NER_LABELS = ["O", "B-LOC", "B-PER"]
CLS_LABELS = ["neg", "pos"]
TASKS = {"fill_mask": {}, "classify": {"labels": CLS_LABELS},
         "squad": {}, "ner": {"labels": NER_LABELS}}
BUCKET = 16
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# fixtures


@pytest.fixture(scope="module")
def vocab_file(tmp_path_factory):
    from bert_pytorch_tpu.tools.make_synthetic_data import write_trace_vocab

    d = tmp_path_factory.mktemp("fastpath_vocab")
    return write_trace_vocab(str(d / "vocab.txt"))


@pytest.fixture(scope="module")
def tokenizer(vocab_file):
    from bert_pytorch_tpu.data.tokenization import BertTokenizer

    return BertTokenizer(vocab_file, do_lower_case=True)


@pytest.fixture(scope="module")
def config():
    from bert_pytorch_tpu.tools.make_synthetic_data import TRACE_WORDS

    vocab = 5 + len(TRACE_WORDS)
    vocab += (8 - vocab % 8) % 8
    return BertConfig(
        vocab_size=vocab, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, type_vocab_size=2, next_sentence=True,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)


def _engine(config, tokenizer, quantize=None, **kw):
    import jax.numpy as jnp

    from bert_pytorch_tpu.serve import InferenceEngine

    eng = InferenceEngine(
        config, tokenizer, TASKS, buckets=(BUCKET,), max_batch_size=2,
        dtype=jnp.float32, seed=7, quantize=quantize, **kw)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def engine_fp32(config, tokenizer):
    return _engine(config, tokenizer)


@pytest.fixture(scope="module")
def engine_int8(config, tokenizer):
    return _engine(config, tokenizer, quantize="int8")


@pytest.fixture(scope="module")
def engine_bf16(config, tokenizer):
    return _engine(config, tokenizer, quantize="bf16")


@pytest.fixture(scope="module")
def tiny_params(config):
    """A seeded fp32 params tree (the MLM head's — it exercises the
    encoder, pooler path, and tied decoder)."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu import models

    model = models.BertForMaskedLM(config, dtype=jnp.float32)
    ids = jnp.zeros((1, BUCKET), jnp.int32)
    return model, nn.unbox(
        model.init(jax.random.PRNGKey(0), ids, ids, ids))["params"]


# ---------------------------------------------------------------------------
# ops/quant.py units


def test_quantize_array_roundtrip():
    from bert_pytorch_tpu.ops import quant

    rng = np.random.default_rng(0)
    w = rng.normal(size=(24, 48)).astype(np.float32)
    q, scale = quant.quantize_array(w)
    assert q.dtype == np.int8 and scale.shape == ()
    err = np.max(np.abs(quant.dequantize_array(q, scale) - w))
    # Round-to-nearest on a symmetric grid: error <= scale / 2.
    assert err <= float(scale) / 2 + 1e-9

    # Stacked (scan) mode: one scale per leading slice, so a quiet layer
    # is not forced onto a loud layer's grid.
    w2 = np.stack([w, 100.0 * w])
    q2, scale2 = quant.quantize_array(w2, per_axis0=True)
    assert scale2.shape == (2,)
    assert np.isclose(scale2[1], 100.0 * scale2[0], rtol=1e-5)
    np.testing.assert_array_equal(q2[0], q2[1])


def test_int8_matmul_error_bound():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.ops import quant

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 10, 32)).astype(np.float32))
    w = rng.normal(size=(32, 64)).astype(np.float32)
    q, scale = quant.quantize_array(w)
    ref = x @ jnp.asarray(w)
    out = quant.int8_matmul(x, jnp.asarray(q), jnp.asarray(scale))
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.05, rel
    assert jax.jit(quant.int8_matmul)(x, jnp.asarray(q),
                                      jnp.asarray(scale)).shape == ref.shape


def test_quantize_params_rules(tiny_params):
    import jax

    from bert_pytorch_tpu.ops import quant

    _, p32 = tiny_params
    qp = quant.quantize_params(p32, "int8")
    flat = {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(qp)}

    enc_q = [k for k in flat if "encoder" in k and k.endswith("'kernel_q']")]
    assert enc_q, sorted(flat)[:5]
    for k in enc_q:
        assert flat[k].dtype == np.int8
        scale = flat[k.replace("kernel_q", "kernel_scale")]
        # scan-stacked kernels carry one scale per layer
        assert scale.shape == (flat[k].shape[0],)
    # embeddings and LayerNorm stay fp32
    emb = [k for k in flat if "word_embeddings" in k]
    assert emb and all(flat[k].dtype == np.float32 for k in emb)
    ln = [k for k in flat if "layer_norm" in k and "'scale']" in k]
    assert ln and all(flat[k].dtype == np.float32 for k in ln)
    # dense biases ride bf16
    import jax.numpy as jnp

    bias = [k for k in flat if "intermediate" in k and k.endswith("'bias']")]
    assert bias and all(flat[k].dtype == jnp.bfloat16 for k in bias)


def test_exclude_modules_downgrade(config, tokenizer, engine_int8):
    """The task-head output layers skip int8: their kernels store bf16."""
    import jax
    import jax.numpy as jnp

    flat = {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(
                engine_int8.tasks["classify"].params)}
    cls_kernel = [k for k in flat if "classifier" in k and "kernel" in k]
    assert cls_kernel
    for k in cls_kernel:
        assert "kernel_q" not in k
        assert flat[k].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# streaming checkpoint load


def test_streaming_quantized_load_matches_host_transform(
        tmp_path, tiny_params):
    import jax

    from bert_pytorch_tpu.ops import quant
    from bert_pytorch_tpu.utils import checkpoint as ckpt

    _, p32 = tiny_params
    # A realistic checkpoint: optimizer subtree present and byte-skipped.
    ckpt.save_checkpoint(str(tmp_path), 5, {
        "model": p32,
        "optimizer": {"m": np.ones((64,), np.float32)},
        "epoch": 0})
    path = ckpt.checkpoint_path(str(tmp_path), 5)

    for mode in ("bf16", "int8"):
        streamed = ckpt.load_params_only(path, p32, quantize=mode)
        host = quant.quantize_params(p32, mode)
        s = jax.tree_util.tree_leaves_with_path(streamed)
        h = jax.tree_util.tree_leaves_with_path(host)
        assert len(s) == len(h)
        for (pk, a), (hk, b) in zip(s, h):
            assert str(pk) == str(hk)
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streaming_cast_happens_inside_decode(tmp_path, tiny_params):
    """quantize=None: leaves cast to the TARGET's dtype during the
    streaming decode (the no-quantization host-memory fix)."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.utils import checkpoint as ckpt

    _, p32 = tiny_params
    ckpt.save_checkpoint(str(tmp_path), 1, {"model": p32})
    path = ckpt.checkpoint_path(str(tmp_path), 1)
    target = jax.tree_util.tree_map(
        lambda x: np.asarray(x).astype(jnp.bfloat16)
        if x.dtype == np.float32 else x, p32)
    restored = ckpt.load_params_only(path, target)
    for leaf in jax.tree_util.tree_leaves(restored):
        assert leaf.dtype == jnp.bfloat16


def test_streaming_load_shape_mismatch_raises(tmp_path, config, tiny_params):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu import models
    from bert_pytorch_tpu.utils import checkpoint as ckpt

    _, p32 = tiny_params
    ckpt.save_checkpoint(str(tmp_path), 1, {"model": p32})
    path = ckpt.checkpoint_path(str(tmp_path), 1)
    wrong_cfg = BertConfig(
        vocab_size=config.vocab_size, hidden_size=16, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=32,
        max_position_embeddings=64, type_vocab_size=2, next_sentence=True)
    wrong = models.BertForMaskedLM(wrong_cfg, dtype=jnp.float32)
    ids = jnp.zeros((1, BUCKET), jnp.int32)
    pw = nn.unbox(wrong.init(jax.random.PRNGKey(0), ids, ids, ids))["params"]
    with pytest.raises(ckpt.CheckpointShapeError):
        ckpt.load_params_only(path, pw, quantize="int8")


# ---------------------------------------------------------------------------
# per-head parity bounds (the documented quant levels)


_PARITY_PAYLOADS = {
    "fill_mask": {"text": "the capital of [MASK] is paris"},
    "classify": {"text": "the river runs through london",
                 "text_pair": "england is old"},
    "squad": {"question": "what is the capital of france",
              "context": "the capital of france is paris"},
    "ner": {"text": "william shakespeare wrote hamlet"},
}


def _head_outputs(engine, task):
    """Raw per-request logit slices through the real batched path."""
    from bert_pytorch_tpu.serve.batcher import Request

    spec = engine.tasks[task]
    payload = _PARITY_PAYLOADS[task]
    features = spec.handler.prepare(payload, engine.max_len())
    plan = engine.plan_batch([Request(task, features, payload)],
                            packed=False)
    outputs, info = engine.execute(task, plan)
    assert info["compiles"] == 0  # warmup covered this shape
    out = outputs[0]
    return out if isinstance(out, tuple) else (out,)


@pytest.mark.parametrize("task", sorted(TASKS))
def test_quantized_parity_bounds(task, engine_fp32, engine_bf16,
                                 engine_int8):
    """Served bf16/int8 logits match fp32 within the documented per-level
    bounds, per task head (docs/serving.md "Inference fast path")."""
    ref = _head_outputs(engine_fp32, task)
    for engine, atol in ((engine_bf16, BF16_LOGIT_ATOL),
                        (engine_int8, INT8_LOGIT_ATOL)):
        got = _head_outputs(engine, task)
        assert len(got) == len(ref)
        for a, b in zip(ref, got):
            diff = float(np.max(np.abs(
                np.asarray(a, np.float32) - np.asarray(b, np.float32))))
            assert diff <= atol, (task, engine.quantize, diff)


def test_run_direct_quantized_end_to_end(engine_int8):
    """Postprocessing works over quantized outputs (argmax-stable on the
    seeded tiny config)."""
    result = engine_int8.run_direct(
        "classify", {"text": "paris is big"})
    assert set(result) >= {"label", "scores"}


# ---------------------------------------------------------------------------
# forward-only Pallas kernel (interpret mode on CPU)


def test_infer_kernel_packed_equals_unpacked():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.ops.pallas.attention import flash_attention_infer

    B, S, H, D = 1, 32, 4, 8
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in keys)
    # Two sequences packed into one row: 12 + 8 tokens, rest pad (id 0).
    sids = np.zeros((B, S), np.int32)
    sids[0, :12], sids[0, 12:20] = 1, 2
    packed = flash_attention_infer(q, k, v,
                                   sequence_ids=jnp.asarray(sids))

    def solo(lo, hi):
        pad = S - (hi - lo)
        sl = lambda t: jnp.pad(t[:, lo:hi], ((0, 0), (0, pad),
                                             (0, 0), (0, 0)))
        mask = np.zeros((B, S), np.int32)
        mask[0, :hi - lo] = 1
        from bert_pytorch_tpu.ops.attention import make_attention_bias

        out = flash_attention_infer(
            sl(q), sl(k), sl(v),
            bias=make_attention_bias(jnp.asarray(mask)))
        return out[0, :hi - lo]

    np.testing.assert_allclose(np.asarray(packed[0, :12]),
                               np.asarray(solo(0, 12)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(packed[0, 12:20]),
                               np.asarray(solo(12, 20)), atol=1e-5)


def test_infer_kernel_matches_xla_reference():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.ops import attention as att

    B, S, H, D = 2, 32, 4, 8
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in keys)
    mask = np.ones((B, S), np.int32)
    mask[0, 20:] = 0
    bias = att.make_attention_bias(jnp.asarray(mask))
    ref = att.dot_product_attention(q, k, v, bias=bias, backend="xla")
    out = att.dot_product_attention(q, k, v, bias=bias,
                                    backend="pallas_infer")
    np.testing.assert_allclose(np.asarray(out[:, :20]),
                               np.asarray(ref[:, :20]), atol=1e-5)


def test_infer_backend_rejects_training_dropout():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.ops import attention as att

    x = jnp.zeros((1, 16, 2, 4), jnp.float32)
    with pytest.raises(ValueError, match="forward-only"):
        att.dot_product_attention(
            x, x, x, backend="pallas_infer", deterministic=False,
            dropout_rate=0.1, dropout_rng=jax.random.PRNGKey(0))


def test_model_level_pallas_infer_parity(config, tiny_params):
    """The serve heads produce identical logits under the inference
    kernel (interpret mode) and the XLA path — the parity pattern the
    packed training kernel established (tests/test_packing.py)."""
    import jax.numpy as jnp

    from bert_pytorch_tpu import models

    model_xla, p32 = tiny_params
    model_inf = models.BertForMaskedLM(config, dtype=jnp.float32,
                                       attention_backend="pallas_infer")
    ids = jnp.arange(BUCKET, dtype=jnp.int32)[None, :] % 7 + 1
    mask = jnp.ones_like(ids)
    ref = model_xla.apply({"params": p32}, ids, ids * 0, mask)
    out = model_inf.apply({"params": p32}, ids, ids * 0, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# stable forward names + cold-start stats


def test_forward_names_per_spec(engine_int8):
    """Every (task, bucket, quant) compiles under its own stable fn name
    — compile-cache keys derive from the fn-name-derived HLO module
    name, so this is what makes warm restarts deterministic and the
    CompileMonitor attribution unambiguous."""
    names = {e["fn"] for e in engine_int8.monitor.events
             if e.get("kind") == "compile"}
    expected = {f"serve_{task}_b{BUCKET}_int8" for task in TASKS}
    assert expected <= names, names


def test_cold_start_stats_shape(engine_fp32):
    s = engine_fp32.startup
    assert s["compiles"] == s["compiles_cold"] + s["compiles_warm"] \
        + sum(1 for e in engine_fp32.monitor.events
              if e.get("kind") == "compile" and e.get("cache") == "jit")
    assert s["cold_start_s"] > 0
    assert s["quantize"] == "none"
    assert s["weight_bytes"] > 0


def test_statsz_carries_cold_start_and_quant_mode(engine_int8):
    from bert_pytorch_tpu.serve.stats import ServeTelemetry
    from bert_pytorch_tpu.telemetry.schema import validate_record

    records = []
    tele = ServeTelemetry(emit=records.append, window=4)
    rec = tele.observe_cold_start(engine_int8.startup)
    assert rec["kind"] == "serve_cold_start"
    assert validate_record({"schema": 1, "ts": 0.0, **rec}) == []
    # A stop()/start() cycle re-observes the same engine start: no
    # second record (the report SUMS cold compiles across records — a
    # duplicate would double-count the warm-restart gate).
    assert tele.observe_cold_start(engine_int8.startup) is None
    assert len(records) == 1
    snap = tele.snapshot()
    assert snap["quantize"] == "int8"
    assert snap["cold_start_s"] == engine_int8.startup["cold_start_s"]
    assert snap["warmup_compiles"] == engine_int8.startup["compiles"]
    # steady-state compiles stays the serve acceptance counter (zero).
    assert snap["compiles"] == 0


# ---------------------------------------------------------------------------
# schema + report gating by name


def test_serve_cold_start_schema_lint():
    from bert_pytorch_tpu.telemetry.schema import validate_record

    good = {"schema": 1, "ts": 0.0, "kind": "serve_cold_start",
            "cold_start_s": 1.5, "compiles": 4, "compiles_cold": 4,
            "compiles_warm": 0}
    assert validate_record(good) == []
    bad = dict(good, compiles_cold=3, compiles_warm=2)
    assert any("exceeds compiles" in e for e in validate_record(bad))
    bad2 = dict(good, cold_start_s=-1)
    assert any("non-negative" in e for e in validate_record(bad2))


def test_report_gates_serve_p50_and_cold_start_by_name():
    from bert_pytorch_tpu.telemetry.report import compare, summarize_records

    def summary(p50, cold_s, cold_compiles):
        return summarize_records([
            {"kind": "serve_summary", "requests": 64, "batches": 8,
             "requests_per_sec": 10.0, "latency_p50_ms": p50,
             "latency_p95_ms": p50 * 2, "latency_p99_ms": p50 * 3},
            {"kind": "serve_cold_start", "cold_start_s": cold_s,
             "compiles": 4, "compiles_cold": cold_compiles,
             "compiles_warm": 4 - cold_compiles, "quantize": "int8"},
        ])

    base = summary(10.0, 2.0, 0)
    assert base["serve_cold_start_s"] == 2.0
    assert base["serve_quantize"] == "int8"

    regs, _ = compare(base, summary(10.0, 2.0, 0))
    assert not regs
    # p50 regression is caught BY NAME
    regs, _ = compare(base, summary(20.0, 2.0, 0))
    assert any(r["label"] == "serve p50 latency" for r in regs)
    # cold-start regression by name
    regs, _ = compare(base, summary(10.0, 8.0, 0))
    assert any(r["label"] == "serve cold start" for r in regs)
    # NEW cold compiles against a warm baseline regress regardless of tol
    regs, _ = compare(base, summary(10.0, 2.0, 3))
    assert any(r["label"] == "serve cold compiles" for r in regs)


# ---------------------------------------------------------------------------
# the two-process warm-cache acceptance


_CHILD_SCRIPT = """
import json, sys
import jax
# Match the parent's conftest config: both feed the compile-cache key
# (matmul precision changes the HLO; the XLA_FLAGS device count rides the
# inherited environment).
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")
import jax.numpy as jnp
from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache
assert enable_compile_cache(sys.argv[1], min_compile_secs=0.0)
from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu.serve import InferenceEngine
from bert_pytorch_tpu.data.tokenization import BertTokenizer
from bert_pytorch_tpu.tools.make_synthetic_data import TRACE_WORDS

vocab = 5 + len(TRACE_WORDS); vocab += (8 - vocab %% 8) %% 8
cfg = BertConfig(vocab_size=vocab, hidden_size=32, num_hidden_layers=2,
                 num_attention_heads=4, intermediate_size=64,
                 max_position_embeddings=64, type_vocab_size=2,
                 next_sentence=True, hidden_dropout_prob=0.0,
                 attention_probs_dropout_prob=0.0)
tok = BertTokenizer(sys.argv[2], do_lower_case=True)
eng = InferenceEngine(cfg, tok, {"classify": {"labels": ["a", "b"]}},
                      buckets=(%(bucket)d,), max_batch_size=2,
                      dtype=jnp.float32, seed=11, quantize="int8")
eng.warmup()
print("STARTUP " + json.dumps(eng.startup))
"""


def test_second_process_start_zero_cold_compiles(
        tmp_path, vocab_file, persistent_cache, monkeypatch):
    """THE cold-start acceptance (docs/serving.md): engine start in this
    process populates the persistent AOT cache; a SECOND, fresh process
    warms entirely from it — zero cold compiles, proven by the
    persistent-cache counter events the startup stats split on."""
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig as BC
    from bert_pytorch_tpu.data.tokenization import BertTokenizer
    from bert_pytorch_tpu.serve import InferenceEngine
    from bert_pytorch_tpu.tools.make_synthetic_data import TRACE_WORDS
    from bert_pytorch_tpu.utils.compile_cache import enable_compile_cache

    # (the persistent_cache fixture restores the process-global jax config:
    # later tests must not silently run against this tmp cache)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_dir = str(tmp_path / "aot_cache")
    assert enable_compile_cache(cache_dir, min_compile_secs=0.0) == cache_dir
    vocab = 5 + len(TRACE_WORDS)
    vocab += (8 - vocab % 8) % 8
    cfg = BC(vocab_size=vocab, hidden_size=32, num_hidden_layers=2,
             num_attention_heads=4, intermediate_size=64,
             max_position_embeddings=64, type_vocab_size=2,
             next_sentence=True, hidden_dropout_prob=0.0,
             attention_probs_dropout_prob=0.0)
    tok = BertTokenizer(vocab_file, do_lower_case=True)
    eng = InferenceEngine(
        cfg, tok, {"classify": {"labels": ["a", "b"]}},
        buckets=(BUCKET,), max_batch_size=2, dtype=jnp.float32,
        seed=11, quantize="int8")
    eng.warmup()
    first = eng.startup
    assert first["compiles_cold"] >= 1  # this process paid the compile

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO_ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD_SCRIPT % {"bucket": BUCKET},
         cache_dir, vocab_file],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("STARTUP ")][-1]
    second = json.loads(line[len("STARTUP "):])
    # Cache counter events are the authority: every forward the fresh
    # process compiled was served from the persisted AOT cache.
    assert second["compiles_cold"] == 0, second
    assert second["compiles_warm"] >= 1, second
