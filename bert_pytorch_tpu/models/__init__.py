"""Model library — the TPU-native twin of reference src/modeling.py.

Every public class of the reference model library (modeling.py:188-1327) has a
counterpart here. Differences are deliberate TPU-first design, not omissions:

  - Modules are pure flax.linen; loss computation lives in
    :mod:`bert_pytorch_tpu.models.losses` (functional JAX style) rather than
    inside ``forward`` branches keyed on whether labels were passed.
  - The encoder is a single ``nn.scan`` over layers (one trace, one compile,
    stacked [L, ...] params) with optional rematerialization — replacing the
    reference's Python layer loop + √N-chunked ``checkpointed_forward``
    (modeling.py:495-536).
  - Parameters carry logical axis names consumed by
    :mod:`bert_pytorch_tpu.parallel` for pjit sharding.
"""

from bert_pytorch_tpu.models.bert import (
    BertEmbeddings,
    BertEncoder,
    BertForMaskedLM,
    BertForMultipleChoice,
    BertForNextSentencePrediction,
    BertForPreTraining,
    BertForQuestionAnswering,
    BertForSequenceClassification,
    BertForTokenClassification,
    BertLayer,
    BertModel,
    BertPooler,
    LayerNorm,
    LinearActivation,
)
from bert_pytorch_tpu.models.convert import (
    convert_torch_state_dict,
    export_torch_state_dict,
    from_pretrained,
    is_foreign_checkpoint,
    load_encoder_params,
    load_pretrained_encoder,
    load_tf_checkpoint,
    merge_params,
)
from bert_pytorch_tpu.models.losses import (
    masked_lm_loss,
    next_sentence_loss,
    next_token_loss,
    pretraining_loss,
    span_loss,
    token_classification_loss,
)

from bert_pytorch_tpu.models.joyai import JoyAIForCausalLM
from bert_pytorch_tpu.models.keye_vl import KeyeVLForCausalLM
from bert_pytorch_tpu.models.laguna import LagunaForCausalLM
from bert_pytorch_tpu.models.mellum import MellumForCausalLM
from bert_pytorch_tpu.models.nemotron_h import NemotronHForCausalLM
from bert_pytorch_tpu.models.phi4flash import PhiFlashForCausalLM
from bert_pytorch_tpu.models.qwen3_next import Qwen3NextForCausalLM
from bert_pytorch_tpu.models.zaya import ZayaForCausalLM


def build_pretraining_model(config, dtype, remat: str = "none",
                            attention_backend: str = "xla"):
    """The pretraining model of the family ``config`` belongs to
    (``config.load_model_config`` chose the class from the file's
    ``model_type``). The model's ``objective`` attribute names what
    ``pretrain.make_train_step`` trains it on."""
    from bert_pytorch_tpu.config import (BertConfig, JoyAIConfig,
                                         KeyeVLConfig, LagunaConfig,
                                         MellumConfig, NemotronHConfig,
                                         PhiFlashConfig, Qwen3NextConfig,
                                         ZayaConfig)

    # (MellumConfig is a LagunaConfig: the narrower class first)
    for family, model in ((NemotronHConfig, NemotronHForCausalLM),
                          (MellumConfig, MellumForCausalLM),
                          (LagunaConfig, LagunaForCausalLM),
                          (PhiFlashConfig, PhiFlashForCausalLM),
                          (ZayaConfig, ZayaForCausalLM),
                          (Qwen3NextConfig, Qwen3NextForCausalLM),
                          (KeyeVLConfig, KeyeVLForCausalLM),
                          (JoyAIConfig, JoyAIForCausalLM),
                          (BertConfig, BertForPreTraining)):
        if isinstance(config, family):
            return model(config, dtype=dtype, remat=remat,
                         attention_backend=attention_backend)
    raise TypeError(f"no pretraining model for {type(config).__name__}")


__all__ = [
    "JoyAIForCausalLM",
    "KeyeVLForCausalLM",
    "LagunaForCausalLM",
    "MellumForCausalLM",
    "NemotronHForCausalLM",
    "PhiFlashForCausalLM",
    "Qwen3NextForCausalLM",
    "ZayaForCausalLM",
    "build_pretraining_model",
    "next_token_loss",
    "BertEmbeddings",
    "BertEncoder",
    "BertForMaskedLM",
    "BertForMultipleChoice",
    "BertForNextSentencePrediction",
    "BertForPreTraining",
    "BertForQuestionAnswering",
    "BertForSequenceClassification",
    "BertForTokenClassification",
    "BertLayer",
    "BertModel",
    "BertPooler",
    "LayerNorm",
    "LinearActivation",
    "convert_torch_state_dict",
    "export_torch_state_dict",
    "from_pretrained",
    "is_foreign_checkpoint",
    "load_encoder_params",
    "load_pretrained_encoder",
    "load_tf_checkpoint",
    "merge_params",
    "masked_lm_loss",
    "next_sentence_loss",
    "pretraining_loss",
    "span_loss",
    "token_classification_loss",
]
