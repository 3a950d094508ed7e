"""BERT model family in flax.linen, designed TPU-first.

Component parity with reference src/modeling.py (cited per class). Key design
choices (vs the reference's torch modules):

  - **bf16 compute / fp32 params**: every module takes ``dtype`` (activation
    dtype, default bf16 on TPU) and keeps parameters in fp32; LayerNorm and
    softmax statistics run in fp32. This replaces torch.cuda.amp autocast
    (reference run_pretraining.py:424-434).
  - **Logical axis names** on every parameter via
    ``nn.with_logical_partitioning`` — the parallel layer maps them to mesh
    axes (data/fsdp/tensor) without touching model code.
  - **nn.scan over layers** with optional remat: one compiled layer body for
    all ``num_hidden_layers`` layers (stacked params, leading 'layers' axis),
    replacing the Python loop at modeling.py:522-536 and the √N-chunked
    ``checkpointed_forward`` at modeling.py:503-520.
  - Attention/LayerNorm route through :mod:`bert_pytorch_tpu.ops` so Pallas
    kernels can be swapped in without touching model code (the Apex
    fused-or-fallback pattern of modeling.py:299-336).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from bert_pytorch_tpu.config import BertConfig
from bert_pytorch_tpu import ops
from bert_pytorch_tpu.ops import quant as quant_ops
from bert_pytorch_tpu.ops.activations import ACT2FN
from bert_pytorch_tpu.ops.dropout import Dropout
from bert_pytorch_tpu.ops.remat import remat_policy

Array = jnp.ndarray
Dtype = Any


def bert_normal_init(stddev: float):
    """weight ~ Normal(0, initializer_range) — reference modeling.py:635-640."""
    return nn.initializers.normal(stddev=stddev)


def _kfac_input_stat(x: Array, feature_ndim: int = 1) -> Array:
    """Sum over tokens of x̃x̃ᵀ with the homogeneous bias coordinate appended
    — the K-FAC 'A' factor statistic for a dense layer consuming ``x``.

    The JAX-native analog of kfac_pytorch's forward-hook input capture
    (driven at reference run_pretraining.py:320-355): instead of a module
    hook saving activations, the model sows the already-reduced (d+1, d+1)
    second-moment — under ``nn.scan`` these stack into an (L, d+1, d+1)
    batch that a single batched eigendecomposition inverts on the MXU.
    """
    d = 1
    for s in x.shape[-feature_ndim:]:
        d *= s
    a = x.astype(jnp.float32).reshape(-1, d)
    a = jnp.concatenate([a, jnp.ones_like(a[:, :1])], axis=-1)
    return a.T @ a


# Collections used by the K-FAC taps (see optim/kfac.py).
KFAC_A_COLLECTION = "kfac_a"
KFAC_TAPS_COLLECTION = "kfac_taps"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _g_factor_probe(y: Array, probe: Array, feature_ndim: int) -> Array:
    """Identity on ``y`` whose gradient w.r.t. ``probe`` is the G-factor
    statistic Σᵣ ĝᵣĝᵣᵀ of ``y``'s cotangent.

    The JAX-native analog of kfac_pytorch's *backward* hooks (driven at
    reference run_pretraining.py:320-355): a torch hook computes the
    (d, d) outer product layer-by-layer as autograd walks the graph, so
    the full cotangent is never kept. Differentiating a plain additive
    tap would instead materialize every layer's stacked cotangent under
    ``nn.scan`` — for BERT-large ~2 GB per tap group. This custom_vjp
    moves the outer product INTO the backward pass: the cotangent for
    ``probe`` (shape (d, d)) is the already-reduced factor, so the scan
    accumulates (L, d, d) statistics instead of (L, B, S, d) gradients,
    and a training step can harvest factors from its own backward at the
    cost of the outer-product FLOPs alone (optim/kfac.py, pretrain.py
    ``make_train_step(kfac_capture_model=...)``).
    """
    del probe
    return y


def _g_factor_probe_fwd(y, probe, feature_ndim):
    del probe
    return y, None


def _g_factor_probe_bwd(feature_ndim, _, ct):
    d = 1
    for s in ct.shape[-feature_ndim:]:
        d *= s
    g = ct.reshape(-1, d).astype(jnp.float32)
    return ct, jnp.einsum("ri,rj->ij", g, g)


_g_factor_probe.defvjp(_g_factor_probe_fwd, _g_factor_probe_bwd)


def _kfac_g_tap(mdl: nn.Module, name: str, y: Array,
                feature_ndim: int = 1) -> Array:
    """Register a (d, d) zero probe variable in ``kfac_taps`` and thread
    ``y`` through :func:`_g_factor_probe` so grad-w.r.t.-taps yields the
    per-layer G factors directly. Tap names encode
    '<dense submodule>__<A-factor name>' (see optim/kfac.py
    ``build_layer_specs``)."""
    d = 1
    for s in y.shape[-feature_ndim:]:
        d *= s
    probe = mdl.variable(
        KFAC_TAPS_COLLECTION, name, lambda: jnp.zeros((d, d), jnp.float32))
    return _g_factor_probe(y, probe.value, feature_ndim)


class LayerNorm(nn.Module):
    """Affine LayerNorm; parity with ``BertLayerNorm`` (modeling.py:311-336).

    Calls :func:`bert_pytorch_tpu.ops.layer_norm`, the TPU-native analog of
    Apex ``FusedLayerNormAffineFunction``.
    """

    epsilon: float = 1e-12
    dtype: Dtype = jnp.float32
    backend: str = "xla"

    @nn.compact
    def __call__(self, x: Array) -> Array:
        dim = x.shape[-1]
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones, ("embed",)),
            (dim,),
            jnp.float32,
        )
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros, ("embed",)),
            (dim,),
            jnp.float32,
        )
        return ops.layer_norm(x, scale, bias, self.epsilon, backend=self.backend)


class LinearActivation(nn.Module):
    """Fused linear + activation; parity with modeling.py:141-180.

    On TPU the bias-add and activation fuse into the matmul's epilogue under
    XLA, so this is a Dense followed by ``ACT2FN[act]`` — fusion is the
    compiler's job, matching the intent of the reference's jit-scripted
    ``bias_gelu`` path.
    """

    features: int
    act: str = "gelu"
    dtype: Dtype = jnp.float32
    kernel_init_stddev: float = 0.02
    kernel_axes: tuple = ("embed", "mlp")
    # Inference weight quantization (ops/quant.py): None keeps the exact
    # fp32-param training module; "bf16"/"int8" are serve-only storage
    # modes selected by serve/engine.py.
    quant: Optional[str] = None

    @nn.compact
    def __call__(self, x: Array) -> Array:
        y = quant_ops.make_dense(
            self.quant,
            self.features,
            dtype=self.dtype,
            init_stddev=self.kernel_init_stddev,
            kernel_axes=self.kernel_axes,
            name="dense",
        )(x)
        # 'bias_gelu'/'bias_tanh' name the reference's fused bias+act CUDA
        # path (modeling.py:161-171); the Dense above already added the bias,
        # so the plain activation is the mathematically identical form.
        act = self.act[5:] if self.act.startswith("bias_") else self.act
        return ACT2FN[act](y)


class BertEmbeddings(nn.Module):
    """word + position (+ token-type iff next_sentence) embeddings → LN → dropout.

    Parity with modeling.py:338-373; token-type embeddings are only
    materialized when ``config.next_sentence`` (the RoBERTa config path drops
    them, config/roberta_large_cased_config.json).
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        cfg = self.config
        init = bert_normal_init(cfg.initializer_range)
        self.word_embeddings = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_size,
            embedding_init=nn.with_logical_partitioning(init, ("vocab", "embed")),
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name="word_embeddings",
        )
        self.position_embeddings = nn.Embed(
            cfg.max_position_embeddings,
            cfg.hidden_size,
            embedding_init=nn.with_logical_partitioning(init, ("pos", "embed")),
            dtype=self.dtype,
            param_dtype=jnp.float32,
            name="position_embeddings",
        )
        if cfg.next_sentence:
            self.token_type_embeddings = nn.Embed(
                cfg.type_vocab_size,
                cfg.hidden_size,
                embedding_init=nn.with_logical_partitioning(init, ("types", "embed")),
                dtype=self.dtype,
                param_dtype=jnp.float32,
                name="token_type_embeddings",
            )
        self.layer_norm = LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=self.dtype, name="layer_norm"
        )
        self.dropout = Dropout(rate=cfg.hidden_dropout_prob)

    def __call__(
        self,
        input_ids: Array,
        token_type_ids: Optional[Array] = None,
        deterministic: bool = True,
        sequence_ids: Optional[Array] = None,
    ) -> Array:
        seq_len = input_ids.shape[-1]
        if sequence_ids is not None:
            # Packed rows (data/packing.py): position embeddings restart at
            # 0 for every packed sequence, so a sequence embeds identically
            # whether it rides alone or packed at some row offset (the
            # positional half of Krell 2021's no-cross-contamination
            # requirement; the attention half is the block-diagonal mask).
            idx = jnp.arange(seq_len, dtype=jnp.int32)[None, :]
            is_start = jnp.concatenate(
                [jnp.ones_like(sequence_ids[:, :1], dtype=bool),
                 sequence_ids[:, 1:] != sequence_ids[:, :-1]], axis=-1)
            starts = jnp.where(is_start, idx, 0)
            seg_start = jax.lax.cummax(starts, axis=starts.ndim - 1)
            position_ids = idx - seg_start
        else:
            position_ids = jnp.arange(seq_len, dtype=jnp.int32)[None, :]
        x = self.word_embeddings(input_ids) + self.position_embeddings(position_ids)
        if self.config.next_sentence:
            if token_type_ids is None:
                token_type_ids = jnp.zeros_like(input_ids)
            x = x + self.token_type_embeddings(token_type_ids)
        x = self.layer_norm(x)
        return self.dropout(x, deterministic=deterministic)


class BertSelfAttention(nn.Module):
    """Multi-head self-attention; parity with modeling.py:376-443
    (``BertSelfAttention`` + ``BertSelfOutput`` fused into one module).

    QKV are DenseGeneral projections to [heads, head_dim] (the tensor-parallel
    sharding unit); the attention core routes through
    :func:`bert_pytorch_tpu.ops.dot_product_attention`.
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    attention_backend: str = "xla"
    kfac_tap: bool = False
    quant: Optional[str] = None

    @nn.compact
    def __call__(
        self, hidden: Array, bias: Array, deterministic: bool = True,
        sequence_ids: Optional[Array] = None,
    ) -> Array:
        cfg = self.config
        heads, head_dim = cfg.num_attention_heads, cfg.head_dim

        def qkv_proj(name):
            return quant_ops.make_dense(
                self.quant,
                (heads, head_dim),
                dtype=self.dtype,
                init_stddev=cfg.initializer_range,
                kernel_axes=("embed", "heads", "kv"),
                bias_axes=("heads", "kv"),
                name=name,
            )

        if self.kfac_tap:
            # q/k/v share the input, hence one A factor for all three — the
            # values kfac_pytorch computes three identical copies of.
            self.sow(KFAC_A_COLLECTION, "attn_in_a", _kfac_input_stat(hidden))
        q = qkv_proj("query")(hidden)
        k = qkv_proj("key")(hidden)
        v = qkv_proj("value")(hidden)
        if self.kfac_tap:
            # tap name encodes '<dense submodule>__<A-factor name>'.
            q = _kfac_g_tap(self, "query__attn_in", q, feature_ndim=2)
            k = _kfac_g_tap(self, "key__attn_in", k, feature_ndim=2)
            v = _kfac_g_tap(self, "value__attn_in", v, feature_ndim=2)

        dropout_rng = None
        if not deterministic and cfg.attention_probs_dropout_prob > 0.0:
            dropout_rng = self.make_rng("dropout")
        context = ops.dot_product_attention(
            q,
            k,
            v,
            bias=bias,
            dropout_rng=dropout_rng,
            dropout_rate=cfg.attention_probs_dropout_prob,
            deterministic=deterministic,
            backend=self.attention_backend,
            sequence_ids=sequence_ids,
        )
        if self.kfac_tap:
            self.sow(
                KFAC_A_COLLECTION, "attn_ctx_a",
                _kfac_input_stat(context, feature_ndim=2),
            )
        # Output projection [B,S,H,D] -> [B,S,hidden] (BertSelfOutput dense).
        out = quant_ops.make_dense(
            self.quant,
            cfg.hidden_size,
            axis=(-2, -1),
            dtype=self.dtype,
            init_stddev=cfg.initializer_range,
            kernel_axes=("heads", "kv", "embed"),
            bias_axes=("embed",),
            name="output",
        )(context)
        if self.kfac_tap:
            out = _kfac_g_tap(self, "output__attn_ctx", out)
        out = Dropout(rate=cfg.hidden_dropout_prob)(
            out, deterministic=deterministic
        )
        return LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=self.dtype, name="output_layer_norm"
        )(out + hidden)


class BertLayer(nn.Module):
    """One transformer block: attention → intermediate (bias-GELU) → output.

    Parity with modeling.py:482-493 (``BertLayer`` = ``BertAttention`` +
    ``BertIntermediate`` + ``BertOutput``). Written scan-compatible: called as
    ``carry, _ = layer(carry, bias, deterministic)``.
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    attention_backend: str = "xla"
    kfac_tap: bool = False
    quant: Optional[str] = None

    @nn.compact
    def __call__(self, hidden: Array, bias: Array, deterministic: bool = True,
                 sequence_ids: Optional[Array] = None):
        cfg = self.config
        attn_out = BertSelfAttention(
            cfg,
            dtype=self.dtype,
            attention_backend=self.attention_backend,
            kfac_tap=self.kfac_tap,
            quant=self.quant,
            name="attention",
        )(hidden, bias, deterministic, sequence_ids)
        intermediate = LinearActivation(
            cfg.intermediate_size,
            act=cfg.hidden_act,
            dtype=self.dtype,
            kernel_init_stddev=cfg.initializer_range,
            kernel_axes=("embed", "mlp"),
            quant=self.quant,
            name="intermediate",
        )(attn_out)
        if self.kfac_tap:
            self.sow(KFAC_A_COLLECTION, "mlp_in_a", _kfac_input_stat(intermediate))
        out = quant_ops.make_dense(
            self.quant,
            cfg.hidden_size,
            dtype=self.dtype,
            init_stddev=cfg.initializer_range,
            kernel_axes=("mlp", "embed"),
            name="output",
        )(intermediate)
        if self.kfac_tap:
            out = _kfac_g_tap(self, "output__mlp_in", out)
        out = Dropout(rate=cfg.hidden_dropout_prob)(
            out, deterministic=deterministic
        )
        out = LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=self.dtype, name="output_layer_norm"
        )(out + attn_out)
        return out, None


class BertEncoder(nn.Module):
    """num_hidden_layers × BertLayer under one ``nn.scan``.

    Replaces the Python loop of modeling.py:522-536 and, when
    ``remat != 'none'``, the √N-chunked ``checkpointed_forward``
    (modeling.py:503-520) — on TPU, per-layer remat under scan is the
    memory/compute trade XLA handles natively.
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    remat: str = "none"  # 'none' | 'full' | 'dots'
    attention_backend: str = "xla"
    kfac_tap: bool = False
    quant: Optional[str] = None

    @nn.compact
    def __call__(self, hidden: Array, bias: Array, deterministic: bool = True,
                 sequence_ids: Optional[Array] = None):
        cfg = self.config
        layer_cls = BertLayer
        policy = remat_policy(self.remat)
        if policy is not None:
            layer_cls = nn.remat(
                BertLayer,
                policy=policy,
                prevent_cse=False,
                static_argnums=(3,),  # deterministic
            )
        scanned = nn.scan(
            layer_cls,
            # kfac collections scan to (L, ...) stacks; empty when taps are
            # off, so the extra axes are free.
            variable_axes={"params": 0, KFAC_A_COLLECTION: 0,
                           KFAC_TAPS_COLLECTION: 0},
            split_rngs={"params": True, "dropout": True},
            in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
            length=cfg.num_hidden_layers,
            metadata_params={nn.PARTITION_NAME: "layers"},
        )(
            cfg,
            dtype=self.dtype,
            attention_backend=self.attention_backend,
            kfac_tap=self.kfac_tap,
            quant=self.quant,
            name="layers",
        )
        hidden, _ = scanned(hidden, bias, deterministic, sequence_ids)
        return hidden


class BertPooler(nn.Module):
    """tanh dense over the [CLS] token; parity with modeling.py:538-549.

    For PACKED rows (data/packing.py), ``positions`` [B, K] gathers the
    pooled vector at each packed sequence's own [CLS] offset instead of
    position 0, returning [B, K, hidden]; empty pack slots point at
    offset 0 and are neutralized downstream by their -1 NSP label.
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    quant: Optional[str] = None

    @nn.compact
    def __call__(self, sequence_output: Array,
                 positions: Optional[Array] = None) -> Array:
        if positions is None:
            cls = sequence_output[:, 0]
        else:
            # One-hot matmul instead of gather — the same MXU-friendly
            # trick as the masked-positions MLM gather (BertForPreTraining).
            onehot = jax.nn.one_hot(
                positions, sequence_output.shape[1], dtype=self.dtype)
            cls = jnp.einsum("bks,bsh->bkh", onehot, sequence_output)
        return LinearActivation(
            self.config.hidden_size,
            act="tanh",
            dtype=self.dtype,
            kernel_init_stddev=self.config.initializer_range,
            kernel_axes=("embed", "embed_out"),
            quant=self.quant,
            name="dense_act",
        )(cls)


class BertModel(nn.Module):
    """Encoder backbone: embeddings → encoder → (pooler iff next_sentence).

    Parity with modeling.py:802-883. Returns ``(sequence_output, pooled)``;
    ``pooled`` is None when ``config.next_sentence`` is False
    (modeling.py:875-879).
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    remat: str = "none"
    attention_backend: str = "xla"
    kfac_tap: bool = False
    # Inference weight quantization (ops/quant.py; serve/engine.py sets
    # it). None = the fp32-param training layout, untouched.
    quant: Optional[str] = None

    def setup(self):
        cfg = self.config
        self.embeddings = BertEmbeddings(cfg, dtype=self.dtype)
        self.encoder = BertEncoder(
            cfg,
            dtype=self.dtype,
            remat=self.remat,
            attention_backend=self.attention_backend,
            kfac_tap=self.kfac_tap,
            quant=self.quant,
        )
        if cfg.next_sentence:
            self.pooler = BertPooler(cfg, dtype=self.dtype,
                                     quant=self.quant)

    def __call__(
        self,
        input_ids: Array,
        token_type_ids: Optional[Array] = None,
        attention_mask: Optional[Array] = None,
        deterministic: bool = True,
        sequence_ids: Optional[Array] = None,
        cls_positions: Optional[Array] = None,
    ):
        """``sequence_ids``/``cls_positions`` mark a PACKED batch
        (data/packing.py): block-diagonal attention, per-sequence position
        restart, and — when ``cls_positions`` [B, K] is given — a pooled
        output per packed sequence ([B, K, hidden]) instead of one per row.
        """
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        bias = ops.attention.make_attention_bias(
            attention_mask, dtype=jnp.float32, sequence_ids=sequence_ids)
        hidden = self.embeddings(
            input_ids, token_type_ids, deterministic, sequence_ids)
        sequence_output = self.encoder(
            hidden, bias, deterministic, sequence_ids)
        pooled = (
            self.pooler(sequence_output, cls_positions)
            if self.config.next_sentence else None
        )
        return sequence_output, pooled


class BertPredictionHeadTransform(nn.Module):
    """dense → act → LayerNorm; parity with modeling.py:551-561."""

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    quant: Optional[str] = None

    @nn.compact
    def __call__(self, hidden: Array) -> Array:
        cfg = self.config
        x = LinearActivation(
            cfg.hidden_size,
            act=cfg.hidden_act,
            dtype=self.dtype,
            kernel_init_stddev=cfg.initializer_range,
            kernel_axes=("embed", "embed_out"),
            quant=self.quant,
            name="dense_act",
        )(hidden)
        return LayerNorm(
            epsilon=cfg.layer_norm_eps, dtype=self.dtype, name="layer_norm"
        )(x)


class BertLMPredictionHead(nn.Module):
    """MLM head with the decoder weight-tied to the word embeddings.

    Parity with modeling.py:563-599: ``transform`` then a decoder whose weight
    IS the embedding matrix (570-574) plus a free bias. The tied matrix is
    passed in by the caller (functional tying — no parameter copy exists).
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    quant: Optional[str] = None

    @nn.compact
    def __call__(self, hidden: Array, word_embedding: Array) -> Array:
        cfg = self.config
        x = BertPredictionHeadTransform(cfg, dtype=self.dtype,
                                        quant=self.quant, name="transform")(
            hidden
        )
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros, ("vocab",)),
            (cfg.vocab_size,),
            jnp.float32,
        )
        logits = jnp.einsum(
            "bsh,vh->bsv", x, word_embedding.astype(self.dtype)
        ) + bias.astype(self.dtype)
        return logits


class BertForPreTraining(nn.Module):
    """MLM + NSP pretraining model; parity with modeling.py:886-947.

    Returns ``(prediction_logits, seq_relationship_logits)``;
    ``seq_relationship_logits`` is None when ``config.next_sentence`` is False
    (the RoBERTa path).
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    remat: str = "none"
    attention_backend: str = "xla"
    # K-FAC factor-capture taps (optim/kfac.py). Covers the encoder's dense
    # layers — the same set kfac_pytorch hooks in the reference (q/k/v,
    # attention output, MLP output are nn.Linear; LinearActivation modules
    # and the skipped predictions head / embeddings are not registered there
    # either, reference run_pretraining.py:343-346, modeling.py:141-180).
    kfac_tap: bool = False

    def setup(self):
        cfg = self.config
        self.bert = BertModel(
            cfg,
            dtype=self.dtype,
            remat=self.remat,
            attention_backend=self.attention_backend,
            kfac_tap=self.kfac_tap,
        )
        self.predictions = BertLMPredictionHead(cfg, dtype=self.dtype)
        if cfg.next_sentence:
            self.seq_relationship = nn.Dense(
                2,
                dtype=self.dtype,
                param_dtype=jnp.float32,
                kernel_init=nn.with_logical_partitioning(
                    bert_normal_init(cfg.initializer_range), ("embed", "classes")
                ),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros, ("classes",)
                ),
            )

    def __call__(
        self,
        input_ids: Array,
        token_type_ids: Optional[Array] = None,
        attention_mask: Optional[Array] = None,
        deterministic: bool = True,
        masked_positions: Optional[Array] = None,
        sequence_ids: Optional[Array] = None,
        cls_positions: Optional[Array] = None,
    ):
        """When ``masked_positions`` [B, P] is given, MLM logits are computed
        only at those positions ([B, P, V] instead of [B, S, V]) — the
        TPU-native optimization the reference lacks (its head projects every
        position into the 30k vocab, modeling.py:611-617, though only
        max_pred<=80 of 512 carry loss). ~6x less decoder matmul FLOPs at
        phase-2 shapes.

        ``sequence_ids``/``cls_positions`` select the PACKED-batch path
        (data/packing.py): block-diagonal attention, restarted positions,
        and [B, K, 2] NSP logits — one per packed sequence — whose -1
        labels on empty slots the loss already ignores."""
        sequence_output, pooled = self.bert(
            input_ids, token_type_ids, attention_mask, deterministic,
            sequence_ids, cls_positions,
        )
        if masked_positions is not None:
            # One-hot matmul instead of gather: TPU lowers gather/scatter
            # poorly (scatter-add backward), while [B,P,S]x[B,S,H] batched
            # matmuls ride the MXU in both directions.
            onehot = jax.nn.one_hot(
                masked_positions, sequence_output.shape[1], dtype=self.dtype
            )
            sequence_output = jnp.einsum("bps,bsh->bph", onehot, sequence_output)
        word_embedding = self.bert.embeddings.word_embeddings.embedding
        prediction_logits = self.predictions(sequence_output, word_embedding)
        seq_logits = (
            self.seq_relationship(pooled) if self.config.next_sentence else None
        )
        return prediction_logits, seq_logits


class BertForMaskedLM(nn.Module):
    """MLM only; parity with modeling.py:950-1008.

    ``sequence_ids`` selects the PACKED-row path (data/packing.py):
    block-diagonal attention + per-sequence position restart, so several
    short requests can share one row at serve time (serve/engine.py) with
    per-token logits demultiplexed by segment. No extra parameters — the
    unpacked call compiles the identical program.
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    remat: str = "none"
    attention_backend: str = "xla"
    quant: Optional[str] = None

    def setup(self):
        self.bert = BertModel(
            self.config,
            dtype=self.dtype,
            remat=self.remat,
            attention_backend=self.attention_backend,
            quant=self.quant,
        )
        self.predictions = BertLMPredictionHead(self.config, dtype=self.dtype,
                                                quant=self.quant)

    def __call__(
        self,
        input_ids: Array,
        token_type_ids: Optional[Array] = None,
        attention_mask: Optional[Array] = None,
        deterministic: bool = True,
        sequence_ids: Optional[Array] = None,
        output_positions: Optional[Array] = None,
    ):
        """``output_positions`` [B, P] selects the FUSED-EPILOGUE path
        (docs/serving.md "Raw-speed kernels"): the hidden states are
        gathered at those positions BEFORE the vocab projection, so the
        head emits [B, P, V] instead of [B, S, V] — serve fill_mask only
        ever reads its [MASK] slots, and projecting the other S-P
        positions into the 30k vocab is pure HBM traffic (the serving
        twin of BertForPreTraining's ``masked_positions``). The gather
        is a one-hot matmul: rows multiply by exactly 1.0 and sum with
        exact zeros, so gather-then-project is bit-equal to
        project-then-gather for every param dtype (the matmul is linear
        and row-independent; tests/test_kernels_fastpath.py asserts
        fp32 bit-equality)."""
        sequence_output, _ = self.bert(
            input_ids, token_type_ids, attention_mask, deterministic,
            sequence_ids,
        )
        if output_positions is not None:
            onehot = jax.nn.one_hot(
                output_positions, sequence_output.shape[1],
                dtype=self.dtype)
            sequence_output = jnp.einsum(
                "bps,bsh->bph", onehot, sequence_output)
        word_embedding = self.bert.embeddings.word_embeddings.embedding
        return self.predictions(sequence_output, word_embedding)


class BertForNextSentencePrediction(nn.Module):
    """NSP only; parity with modeling.py:1011-1069."""

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    remat: str = "none"
    attention_backend: str = "xla"

    def setup(self):
        self.bert = BertModel(
            self.config,
            dtype=self.dtype,
            remat=self.remat,
            attention_backend=self.attention_backend,
        )
        self.seq_relationship = nn.Dense(
            2,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(
                bert_normal_init(self.config.initializer_range), ("embed", "classes")
            ),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros, ("classes",)
            ),
        )

    def __call__(
        self,
        input_ids: Array,
        token_type_ids: Optional[Array] = None,
        attention_mask: Optional[Array] = None,
        deterministic: bool = True,
    ):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask, deterministic)
        return self.seq_relationship(pooled)


class _ClassifierHead(nn.Module):
    """Dropout + Dense classifier shared by the task heads."""

    num_labels: int
    dropout_rate: float
    initializer_range: float
    dtype: Dtype = jnp.bfloat16
    quant: Optional[str] = None

    @nn.compact
    def __call__(self, x: Array, deterministic: bool = True) -> Array:
        x = Dropout(rate=self.dropout_rate)(x, deterministic=deterministic)
        # Output layers skip int8 (ops/quant.py EXCLUDE_MODULES): a
        # [hidden, num_labels] kernel saves no bytes worth pre-softmax
        # quantization noise; int8 engines store it bf16 instead.
        return quant_ops.make_dense(
            quant_ops.exclude(self.quant),
            self.num_labels,
            dtype=self.dtype,
            init_stddev=self.initializer_range,
            kernel_axes=("embed", "classes"),
            name="classifier",
        )(x)


class BertForSequenceClassification(nn.Module):
    """Pooled-output classifier; parity with modeling.py:1072-1128.

    ``sequence_ids`` + ``cls_positions`` select the PACKED-row path
    (data/packing.py): K requests share one row, the pooler gathers each
    request's own [CLS] vector, and the head returns [B, K, num_labels]
    (serve/engine.py demultiplexes by pack slot). No extra parameters.
    """

    config: BertConfig
    num_labels: int
    dtype: Dtype = jnp.bfloat16
    remat: str = "none"
    attention_backend: str = "xla"
    quant: Optional[str] = None

    def setup(self):
        self.bert = BertModel(
            self.config,
            dtype=self.dtype,
            remat=self.remat,
            attention_backend=self.attention_backend,
            quant=self.quant,
        )
        self.head = _ClassifierHead(
            self.num_labels,
            self.config.hidden_dropout_prob,
            self.config.initializer_range,
            dtype=self.dtype,
            quant=self.quant,
        )

    def __call__(
        self,
        input_ids: Array,
        token_type_ids: Optional[Array] = None,
        attention_mask: Optional[Array] = None,
        deterministic: bool = True,
        sequence_ids: Optional[Array] = None,
        cls_positions: Optional[Array] = None,
    ):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask,
                              deterministic, sequence_ids, cls_positions)
        return self.head(pooled, deterministic)


class BertForMultipleChoice(nn.Module):
    """[B, C, S] choices → flattened batch → per-choice score;
    parity with modeling.py:1131-1197."""

    config: BertConfig
    num_choices: int
    dtype: Dtype = jnp.bfloat16
    remat: str = "none"
    attention_backend: str = "xla"

    def setup(self):
        self.bert = BertModel(
            self.config,
            dtype=self.dtype,
            remat=self.remat,
            attention_backend=self.attention_backend,
        )
        self.head = _ClassifierHead(
            1,
            self.config.hidden_dropout_prob,
            self.config.initializer_range,
            dtype=self.dtype,
        )

    def __call__(
        self,
        input_ids: Array,
        token_type_ids: Optional[Array] = None,
        attention_mask: Optional[Array] = None,
        deterministic: bool = True,
    ):
        batch, choices, seq = input_ids.shape
        flat = lambda t: None if t is None else t.reshape(batch * choices, seq)
        _, pooled = self.bert(
            flat(input_ids), flat(token_type_ids), flat(attention_mask), deterministic
        )
        scores = self.head(pooled, deterministic)
        return scores.reshape(batch, choices)


class BertForTokenClassification(nn.Module):
    """Per-token classifier; parity with modeling.py:1200-1271.

    ``sequence_ids`` selects the PACKED-row path (data/packing.py): the
    per-token logits of several packed requests ride one row, demultiplexed
    by segment (serve/engine.py). No extra parameters.
    """

    config: BertConfig
    num_labels: int
    dtype: Dtype = jnp.bfloat16
    remat: str = "none"
    attention_backend: str = "xla"
    quant: Optional[str] = None

    def setup(self):
        self.bert = BertModel(
            self.config,
            dtype=self.dtype,
            remat=self.remat,
            attention_backend=self.attention_backend,
            quant=self.quant,
        )
        self.head = _ClassifierHead(
            self.num_labels,
            self.config.hidden_dropout_prob,
            self.config.initializer_range,
            dtype=self.dtype,
            quant=self.quant,
        )

    def __call__(
        self,
        input_ids: Array,
        token_type_ids: Optional[Array] = None,
        attention_mask: Optional[Array] = None,
        deterministic: bool = True,
        sequence_ids: Optional[Array] = None,
    ):
        sequence_output, _ = self.bert(
            input_ids, token_type_ids, attention_mask, deterministic,
            sequence_ids,
        )
        return self.head(sequence_output, deterministic)


class BertForQuestionAnswering(nn.Module):
    """Start/end span logits; parity with modeling.py:1274-1327.

    Returns ``(start_logits, end_logits)`` each [B, S].

    ``sequence_ids`` selects the PACKED-row path (data/packing.py): each
    packed request's start/end logits occupy its own row segment
    (serve/engine.py demultiplexes and decodes spans per request). No
    extra parameters.
    """

    config: BertConfig
    dtype: Dtype = jnp.bfloat16
    remat: str = "none"
    attention_backend: str = "xla"
    quant: Optional[str] = None

    def setup(self):
        self.bert = BertModel(
            self.config,
            dtype=self.dtype,
            remat=self.remat,
            attention_backend=self.attention_backend,
            quant=self.quant,
        )
        self.qa_outputs = quant_ops.make_dense(
            quant_ops.exclude(self.quant),
            2,
            dtype=jnp.float32,
            init_stddev=self.config.initializer_range,
            kernel_axes=("embed", "classes"),
            name="qa_outputs",
        )

    def __call__(
        self,
        input_ids: Array,
        token_type_ids: Optional[Array] = None,
        attention_mask: Optional[Array] = None,
        deterministic: bool = True,
        sequence_ids: Optional[Array] = None,
    ):
        sequence_output, _ = self.bert(
            input_ids, token_type_ids, attention_mask, deterministic,
            sequence_ids,
        )
        logits = self.qa_outputs(sequence_output)
        start_logits, end_logits = jnp.split(logits, 2, axis=-1)
        return start_logits.squeeze(-1), end_logits.squeeze(-1)
