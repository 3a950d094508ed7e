"""``utils/flops.py``: the model-FLOP counts and the chips' peaks that the
trainer's telemetry turns into an MFU (absent, not 0.0, off a TPU)."""

import pytest


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
        # 256 sequences in 575.26 ms of device time an update is what the
        # phase-1 cell reads at `step_mfu_pct.train` 54.36 (ledger, PR 39);
        # this count has no padding in it and the cell's has, so near it.
        assert 0.5 < flops.mfu(256 / 0.57526, per_seq, "TPU v5e") < 0.58

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
