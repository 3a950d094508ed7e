"""Sequence packing (ISSUE 3): packer, packed data formats, block-diagonal
attention parity (XLA and Pallas), packed-vs-unpacked model/loss parity,
and padding-aware telemetry — including the CPU smoke acceptance run
(packed padding_efficiency >= 1.5x unpacked, lower wall per real token).
"""

import json
import os

import numpy as np
import pytest

from bert_pytorch_tpu.data import (
    DataLoader,
    DistributedSampler,
    PackedPretrainingDataset,
    ShardedPretrainingDataset,
    first_fit_decreasing,
    pack_features,
    write_packed_shard,
)
from bert_pytorch_tpu.telemetry import schema as tschema
from bert_pytorch_tpu.telemetry.step_timer import StepTimer
from bert_pytorch_tpu.tools.make_synthetic_data import make_shard


# -- packer ---------------------------------------------------------------


def test_ffd_respects_capacity_and_pack_limit():
    lengths = [100, 60, 50, 40, 30, 20, 10, 5]
    packs = first_fit_decreasing(lengths, 128, 3)
    seen = sorted(i for p in packs for i in p)
    assert seen == list(range(len(lengths)))  # every sample placed once
    for p in packs:
        assert sum(lengths[i] for i in p) <= 128
        assert 1 <= len(p) <= 3


def test_ffd_overlong_sample_gets_singleton():
    packs = first_fit_decreasing([300, 10], 128, 8)
    assert [sorted(p) for p in sorted(packs, key=min)] == [[0], [1]]


def test_ffd_is_deterministic_and_orders_by_first_member():
    lengths = list(np.random.default_rng(0).integers(5, 120, 50))
    a = first_fit_decreasing(lengths, 128, 8)
    b = first_fit_decreasing(lengths, 128, 8)
    assert a == b
    firsts = [min(p) for p in a]
    assert firsts == sorted(firsts)


def test_pack_features_layout():
    def sample(n, nsp, base):
        ids = np.arange(base, base + n, dtype=np.int32)
        seg = np.zeros(16, np.int32)
        mask = np.zeros(16, np.int32)
        mask[:n] = 1
        labs = np.full(16, -1, np.int32)
        labs[1] = 7
        row = np.zeros(16, np.int32)
        row[:n] = ids
        return [row, seg, mask, labs, np.int32(nsp)]

    row = pack_features([sample(5, 1, 10), sample(7, 0, 50)], 16, 4)
    ids, seg, mask, labs, nsp, seq_ids, cls = row
    assert list(seq_ids) == [1] * 5 + [2] * 7 + [0] * 4
    assert list(mask) == [1] * 12 + [0] * 4
    assert list(nsp) == [1, 0, -1, -1]
    assert list(cls) == [0, 5, 0, 0]
    assert ids[5] == 50 and labs[1] == 7 and labs[6] == 7


# -- datasets -------------------------------------------------------------


@pytest.fixture()
def mixed_shard_dir(tmp_path):
    d = tmp_path / "data"
    d.mkdir()
    for i in range(2):
        make_shard(str(d / f"s{i}.hdf5"), 48, 64, 500, seed=i,
                   mixed_lengths=True)
    return str(d)


def test_on_the_fly_packing_dataset(mixed_shard_dir):
    import glob

    files = sorted(glob.glob(os.path.join(mixed_shard_dir, "*.hdf5")))
    base = ShardedPretrainingDataset(files, 4, 10, 0.15, vocab_size=500,
                                     seed=1)
    packed = PackedPretrainingDataset(base, max_sequences_per_pack=4)
    assert len(packed) < len(base)  # something actually packed
    assert packed.occupancy > 0.75
    for i in (0, len(packed) // 2, len(packed) - 1):
        ids, seg, mask, labs, nsp, seq_ids, cls = packed[i]
        assert (mask == (seq_ids > 0).astype(np.int32)).all()
        valid = nsp != -1
        assert valid.any()
        # every packed sequence starts with [CLS] (id 2 in synthetic data)
        assert (ids[cls[valid]] == 2).all()
        # MLM labels only on real tokens
        assert (labs[seq_ids == 0] == -1).all()
        # ids within a pack ascend contiguously 1..n
        present = sorted(set(seq_ids[seq_ids > 0]))
        assert present == list(range(1, len(present) + 1))

    # loader collation: packed keys appear, NSP becomes [B, K]
    loader = DataLoader(
        packed, DistributedSampler(packed, num_replicas=1, rank=0),
        batch_size=4)
    batch = next(iter(loader))
    assert batch["next_sentence_labels"].shape == (4, 4)
    assert batch["sequence_ids"].shape == (4, 64)
    assert batch["cls_positions"].shape == (4, 4)


def test_offline_packed_shard_roundtrip(tmp_path):
    path = str(tmp_path / "packed.hdf5")
    make_shard(path, 48, 64, 500, seed=0, mixed_lengths=True, packed=True,
               max_sequences_per_pack=4)
    ds = ShardedPretrainingDataset(path, 4, 10, 0.15, vocab_size=500, seed=1)
    assert ds.packed and ds.max_sequences_per_pack == 4
    assert len(ds) < 48
    ids, seg, mask, labs, nsp, seq_ids, cls = ds[0]
    assert (mask == (seq_ids > 0).astype(np.int32)).all()
    valid = nsp != -1
    assert (ids[cls[valid]] == 2).all()
    assert (labs != -1).sum() > 0  # dynamic masking ran per member
    # masked positions never hit specials or pads
    masked = np.nonzero(labs != -1)[0]
    assert (seq_ids[masked] > 0).all()


def test_encode_data_packed_writer(tmp_path):
    """tools/encode_data.py --pack_sequences path: TrainingSample ->
    FFD-packed shard in the data/packing.py layout, loadable by the
    runtime dataset."""
    from bert_pytorch_tpu.tools.encode_data import (
        TrainingSample, write_packed_samples_to_hdf5)

    class FakeTok:
        def token_to_id(self, t):
            return {"[CLS]": 2, "[SEP]": 3}.get(t, 5 + hash(t) % 100)

    rng = np.random.default_rng(0)
    samples = [
        TrainingSample([f"w{rng.integers(1000)}"
                        for _ in range(int(rng.integers(4, 24)))],
                       next_seq_tokens=[f"w{rng.integers(1000)}"
                                        for _ in range(5)],
                       is_random_next=bool(i % 2))
        for i in range(12)
    ]
    path = str(tmp_path / "enc_packed.hdf5")
    n = write_packed_samples_to_hdf5(path, samples, FakeTok(), 64, 4)
    assert 0 < n < len(samples)  # packing actually combined rows
    ds = ShardedPretrainingDataset(path, 4, 10, 0.15, vocab_size=500, seed=0)
    assert ds.packed and len(ds) == n
    ids, _seg, mask, _labs, nsp, seq_ids, cls = ds[0]
    assert (ids[cls[nsp != -1]] == 2).all()  # members start with [CLS]
    assert (mask == (seq_ids > 0).astype(np.int32)).all()


def test_mixed_packed_and_unpacked_shards_rejected(tmp_path):
    a = str(tmp_path / "a.hdf5")
    b = str(tmp_path / "b.hdf5")
    make_shard(a, 8, 64, 500, seed=0)
    make_shard(b, 8, 64, 500, seed=1, mixed_lengths=True, packed=True)
    with pytest.raises(ValueError, match="mix packed and unpacked"):
        ShardedPretrainingDataset([a, b], 4, 10, 0.15, vocab_size=500)


# -- attention: block-diagonal XLA vs Pallas(interpret) -------------------


def _packed_qkv(seed=0, batch=2, seq=64, heads=4, depth=16):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((batch, seq, heads, depth)),
                           jnp.float32) for _ in range(3))
    seq_ids = np.zeros((batch, seq), np.int32)
    seq_ids[0, :20] = 1
    seq_ids[0, 20:45] = 2
    seq_ids[0, 45:60] = 3
    seq_ids[1, :30] = 1
    seq_ids[1, 30:50] = 2
    return q, k, v, jnp.asarray(seq_ids)


def test_block_diagonal_bias_masks_cross_sequence():
    import jax.numpy as jnp

    from bert_pytorch_tpu.ops.attention import make_attention_bias

    seq_ids = jnp.asarray([[1, 1, 2, 0]], jnp.int32)
    bias = np.asarray(make_attention_bias(None, sequence_ids=seq_ids))[0, 0]
    assert bias.shape == (4, 4)
    allowed = bias == 0.0
    expected = np.array([
        [1, 1, 0, 0],
        [1, 1, 0, 0],
        [0, 0, 1, 0],
        [0, 0, 0, 0],  # pad row: everything masked
    ], bool)
    assert (allowed == expected).all()


def test_flash_attention_packed_matches_xla_forward():
    from bert_pytorch_tpu.ops.attention import (dot_product_attention,
                                                make_attention_bias)
    from bert_pytorch_tpu.ops.pallas.attention import flash_attention

    q, k, v, seq_ids = _packed_qkv()
    bias = make_attention_bias(None, sequence_ids=seq_ids)
    ref = dot_product_attention(q, k, v, bias=bias, backend="xla")
    out = flash_attention(q, k, v, sequence_ids=seq_ids)
    real = np.asarray(seq_ids) > 0
    np.testing.assert_allclose(
        np.asarray(out)[real], np.asarray(ref)[real], atol=2e-5)


def test_flash_attention_packed_grads_match_xla():
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.ops.attention import (dot_product_attention,
                                                make_attention_bias)
    from bert_pytorch_tpu.ops.pallas.attention import flash_attention

    q, k, v, seq_ids = _packed_qkv()
    bias = make_attention_bias(None, sequence_ids=seq_ids)
    real = jnp.asarray(np.asarray(seq_ids) > 0)[:, :, None, None]

    def loss(fn):
        def f(q, k, v):
            return jnp.sum(jnp.where(real, fn(q, k, v), 0.0) ** 2)
        return f

    g_ref = jax.grad(
        loss(lambda q, k, v: dot_product_attention(
            q, k, v, bias=bias, backend="xla")), argnums=(0, 1, 2))(q, k, v)
    g_pal = jax.grad(
        loss(lambda q, k, v: flash_attention(
            q, k, v, sequence_ids=seq_ids)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


def test_packing_rejected_on_ring_backend():
    from bert_pytorch_tpu.ops.attention import dot_product_attention

    q, k, v, seq_ids = _packed_qkv()
    with pytest.raises(ValueError, match="ring"):
        dot_product_attention(q, k, v, backend="ring",
                              sequence_ids=seq_ids)


# -- model parity: packed row == separate rows ----------------------------


def _tiny_model(next_sentence=True):
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining

    cfg = BertConfig(
        vocab_size=200, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, type_vocab_size=2,
        next_sentence=next_sentence)
    return BertForPreTraining(cfg, dtype=jnp.float32), cfg


def test_packed_forward_and_loss_match_unpacked():
    """ISSUE 3 acceptance: the same documents packed into one row vs run
    as separate rows produce identical per-token encoder outputs and
    identical total MLM+NSP loss (fp32, XLA path)."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.models.losses import pretraining_loss

    model, _ = _tiny_model()
    rng = np.random.default_rng(0)
    S, l1, l2 = 64, 22, 31
    ids1 = rng.integers(5, 200, l1).astype(np.int32)
    ids2 = rng.integers(5, 200, l2).astype(np.int32)

    up = {
        "ids": np.zeros((2, S), np.int32),
        "seg": np.zeros((2, S), np.int32),
        "mask": np.zeros((2, S), np.int32),
        "labs": np.full((2, S), -1, np.int32),
        "nsp": np.array([1, 0], np.int32),
    }
    up["ids"][0, :l1] = ids1
    up["ids"][1, :l2] = ids2
    up["seg"][0, l1 // 2:l1] = 1
    up["seg"][1, l2 // 2:l2] = 1
    up["mask"][0, :l1] = 1
    up["mask"][1, :l2] = 1
    up["labs"][0, 3] = ids1[3]
    up["labs"][1, 5] = ids2[5]
    up["labs"][1, 9] = ids2[9]

    pk_ids = np.zeros((1, S), np.int32)
    pk_ids[0, :l1] = ids1
    pk_ids[0, l1:l1 + l2] = ids2
    pk_seg = np.concatenate([up["seg"][0, :l1], up["seg"][1, :l2],
                             np.zeros(S - l1 - l2, np.int32)])[None]
    pk_mask = np.zeros((1, S), np.int32)
    pk_mask[0, :l1 + l2] = 1
    pk_labs = np.full((1, S), -1, np.int32)
    pk_labs[0, 3] = ids1[3]
    pk_labs[0, l1 + 5] = ids2[5]
    pk_labs[0, l1 + 9] = ids2[9]
    seq_ids = np.zeros((1, S), np.int32)
    seq_ids[0, :l1] = 1
    seq_ids[0, l1:l1 + l2] = 2
    cls = np.array([[0, l1, 0]], np.int32)
    pk_nsp = np.array([[1, 0, -1]], np.int32)

    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, S), jnp.int32),
        jnp.zeros((1, S), jnp.int32), jnp.zeros((1, S), jnp.int32))

    bert = lambda m, *a: m.bert(*a)
    seq_u, pooled_u = model.apply(
        params, up["ids"], up["seg"], up["mask"], True, method=bert)
    mlm_u, nsp_u = model.apply(params, up["ids"], up["seg"], up["mask"], True)
    loss_u = pretraining_loss(mlm_u, nsp_u, up["labs"], up["nsp"])

    seq_p, pooled_p = model.apply(
        params, pk_ids, pk_seg, pk_mask, True,
        jnp.asarray(seq_ids), jnp.asarray(cls), method=bert)
    mlm_p, nsp_p = model.apply(
        params, pk_ids, pk_seg, pk_mask, True, None,
        jnp.asarray(seq_ids), jnp.asarray(cls))
    loss_p = pretraining_loss(mlm_p, nsp_p, pk_labs, pk_nsp)

    # identical per-token encoder outputs at each member's positions
    np.testing.assert_allclose(
        np.asarray(seq_p)[0, :l1], np.asarray(seq_u)[0, :l1], atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(seq_p)[0, l1:l1 + l2], np.asarray(seq_u)[1, :l2],
        atol=1e-5)
    # identical pooled vectors per packed sequence
    np.testing.assert_allclose(
        np.asarray(pooled_p)[0, :2], np.asarray(pooled_u), atol=1e-5)
    # identical TOTAL MLM+NSP loss
    assert float(loss_p) == pytest.approx(float(loss_u), abs=1e-5)


def test_packed_parity_holds_on_pallas_interpret_path():
    """The Pallas interpret-mode kernel gives the same packed encoder
    outputs as the XLA block-diagonal path, through the full model."""
    import jax
    import jax.numpy as jnp

    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining

    cfg = BertConfig(
        vocab_size=200, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=64, type_vocab_size=2, next_sentence=True)
    rng = np.random.default_rng(1)
    S = 64
    ids = rng.integers(5, 200, (2, S)).astype(np.int32)
    seq_ids = np.zeros((2, S), np.int32)
    seq_ids[0, :40] = 1
    seq_ids[0, 40:56] = 2
    seq_ids[1, :64] = 1
    mask = (seq_ids > 0).astype(np.int32)
    cls = np.array([[0, 40], [0, 0]], np.int32)

    outs = {}
    for backend in ("xla", "pallas"):
        model = BertForPreTraining(
            cfg, dtype=jnp.float32, attention_backend=backend)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, S), jnp.int32),
            jnp.zeros((1, S), jnp.int32), jnp.zeros((1, S), jnp.int32))
        outs[backend], _ = model.apply(
            params, ids, np.zeros_like(ids), mask, True,
            jnp.asarray(seq_ids), jnp.asarray(cls),
            method=lambda m, *a: m.bert(*a))
    real = seq_ids > 0
    np.testing.assert_allclose(
        np.asarray(outs["pallas"])[real], np.asarray(outs["xla"])[real],
        atol=2e-5)


# -- padding-aware telemetry ---------------------------------------------


def test_step_timer_padding_fields():
    t = [0.0]

    def clock():
        t[0] += 0.05
        return t[0]

    timer = StepTimer(window=2, sync_every=1, clock=clock, seq_per_step=4,
                      tokens_per_step=400)
    for step in (1, 2):
        timer.data_start()
        timer.data_end()
        timer.dispatch_end()
        timer._t_device1 = clock()
        timer.note_tokens(200.0)
        rec = timer.step_done(step)
    assert rec is not None
    assert rec["padding_efficiency"] == pytest.approx(0.5)
    assert rec["tokens_per_s_basis"] == "real"
    assert rec["tokens_per_s"] > 0
    assert timer.run_padding_efficiency() == pytest.approx(0.5)
    assert tschema.validate_record(
        {**rec, "schema": tschema.SCHEMA_VERSION, "ts": 0}) == []


def test_step_timer_tokens_all_basis_when_unsynced():
    timer = StepTimer(window=1, sync_every=0, tokens_per_step=400)
    timer.data_start()
    timer.data_end()
    timer.dispatch_end()
    rec = timer.step_done(1)
    assert rec["tokens_per_s_basis"] == "all"
    assert "padding_efficiency" not in rec


def test_schema_rejects_inconsistent_token_fields():
    base = {"schema": tschema.SCHEMA_VERSION, "ts": 0.0,
            "kind": "step_window", "step": 1, "window_steps": 1,
            "data_wait_p50_s": 0, "data_wait_p95_s": 0, "data_wait_max_s": 0,
            "host_p50_s": 0, "host_p95_s": 0, "host_max_s": 0,
            "device_p50_s": 0, "device_p95_s": 0, "device_max_s": 0,
            "step_p50_s": 0, "steps_per_sec": 1.0, "mfu": 0.0}
    assert tschema.validate_record(base) == []
    assert tschema.validate_record({**base, "tokens_per_s": 5.0})
    assert tschema.validate_record(
        {**base, "tokens_per_s": 5.0, "tokens_per_s_basis": "bogus"})
    assert tschema.validate_record(
        {**base, "tokens_per_s": 5.0, "tokens_per_s_basis": "real"})
    assert tschema.validate_record(
        {**base, "tokens_per_s": 5.0, "tokens_per_s_basis": "real",
         "padding_efficiency": 0.8}) == []
    assert tschema.validate_record({**base, "padding_efficiency": 1.7})
    assert tschema.validate_record({**base, "mfu_real_tokens": 0.1})


# -- acceptance: packed vs unpacked CPU smoke ----------------------------


def _smoke_run(tmp_path, tag, pack):
    import run_pretraining

    data_dir = tmp_path / f"data_{tag}"
    data_dir.mkdir()
    for i in range(2):
        make_shard(str(data_dir / f"s{i}.hdf5"), 96, 128, 1000, seed=i,
                   mixed_lengths=True)
    model_config = {
        "vocab_size": 1000, "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 64,
        "max_position_embeddings": 128, "type_vocab_size": 2,
        "next_sentence": True, "mask_token_id": 4,
    }
    config_path = tmp_path / f"model_{tag}.json"
    config_path.write_text(json.dumps(model_config))
    out = str(tmp_path / f"out_{tag}")
    argv = [
        "--input_dir", str(data_dir), "--output_dir", out,
        "--model_config_file", str(config_path),
        "--global_batch_size", "8", "--local_batch_size", "1",
        "--max_steps", "6", "--steps", "6", "--dtype", "float32",
        "--learning_rate", "1e-3", "--num_steps_per_checkpoint", "100",
        "--skip_final_checkpoint",
        "--telemetry_window", "3", "--telemetry_sync_every", "1",
        "--seed", "11",
    ]
    if pack:
        argv += ["--pack_sequences", "--max_sequences_per_pack", "8"]
    args = run_pretraining.parse_arguments(argv)
    result = run_pretraining.main(args)
    assert result["global_step"] == 6
    jsonl = os.path.join(out, "pretraining_telemetry.jsonl")
    assert tschema.validate_file(jsonl) == []
    summary = None
    windows = []
    for line in open(jsonl):
        rec = json.loads(line)
        if rec.get("kind") == "run_summary":
            summary = rec
        elif rec.get("kind") == "step_window":
            windows.append(rec)
    return jsonl, summary, windows


def test_packed_smoke_padding_efficiency_acceptance(tmp_path):
    """ISSUE 3 acceptance: on a mixed-length synthetic shard (seq 128) a
    packed CPU run reports padding_efficiency >= 1.5x the unpacked run's,
    in the telemetry JSONL and the telemetry-report summary. (What that
    does to tokens per second is a chip cell's to say: a CPU run yields
    counts, not rates.)"""
    from bert_pytorch_tpu.telemetry.report import summarize_file

    _, sum_u, win_u = _smoke_run(tmp_path, "unpacked", pack=False)
    jsonl_p, sum_p, win_p = _smoke_run(tmp_path, "packed", pack=True)

    eff_u = sum_u["padding_efficiency"]
    eff_p = sum_p["padding_efficiency"]
    assert 0 < eff_u < 0.75  # mixed lengths leave real padding
    assert eff_p >= 1.5 * eff_u, (eff_p, eff_u)
    # windows carry the padding-aware fields with the real basis
    assert all(w["tokens_per_s_basis"] == "real" for w in win_p)
    assert all(0 < w["padding_efficiency"] <= 1 for w in win_p)
    # telemetry-report summarizes them
    report = summarize_file(jsonl_p)
    assert report["padding_efficiency"] == pytest.approx(eff_p, abs=0.1)
    assert report["tokens_per_s"] > 0
    assert report["real_tokens_per_sec"] == sum_p["real_tokens_per_sec"]
