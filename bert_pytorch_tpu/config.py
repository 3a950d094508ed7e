"""Model configuration and the three-level CLI > JSON > defaults config system.

Behavioral parity targets (see SURVEY.md §5.6):
  - ``BertConfig`` semantics of reference src/modeling.py:188-295 —
    ``from_dict`` merges arbitrary keys onto defaults, ``from_json_file`` reads
    a JSON file; data-pipeline keys (vocab_file / tokenizer / lowercase) ride
    along inside the model config.
  - The runner config system of reference run_pretraining.py:75-177: argparse
    defaults are overridden by ``--config_file`` JSON values, which are in turn
    overridden by flags explicitly present on the command line.
"""

from __future__ import annotations

import argparse
import copy
import json
import math
import sys
from typing import Any


class BertConfig:
    """Architecture configuration for the BERT model family.

    Mirrors reference src/modeling.py:188-295 (``BertConfig``): the same
    default values, dict/JSON constructors with merge semantics, and tolerance
    for extra keys (the reference stores tokenizer/data keys in the same file,
    run_pretraining.py:369-374).
    """

    def __init__(
        self,
        vocab_size: int = 30522,
        hidden_size: int = 768,
        num_hidden_layers: int = 12,
        num_attention_heads: int = 12,
        intermediate_size: int = 3072,
        hidden_act: str = "gelu",
        hidden_dropout_prob: float = 0.1,
        attention_probs_dropout_prob: float = 0.1,
        max_position_embeddings: int = 512,
        type_vocab_size: int = 2,
        initializer_range: float = 0.02,
        layer_norm_eps: float = 1e-12,
        next_sentence: bool = True,
        output_all_encoded_layers: bool = False,
        pad_token_id: int = 0,
        **extra: Any,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size
        self.hidden_act = hidden_act
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.initializer_range = initializer_range
        self.layer_norm_eps = layer_norm_eps
        self.next_sentence = next_sentence
        self.output_all_encoded_layers = output_all_encoded_layers
        self.pad_token_id = pad_token_id
        # Extra keys (vocab_file, tokenizer, lowercase, ...) ride along so the
        # data path can read them from the same file.
        for key, value in extra.items():
            setattr(self, key, value)

    # -- constructors --------------------------------------------------------

    @classmethod
    def from_dict(cls, json_object: dict) -> "BertConfig":
        """Construct from a dict, merging onto defaults (modeling.py:255-261)."""
        config = cls()
        for key, value in json_object.items():
            setattr(config, key, value)
        return config

    @classmethod
    def from_json_file(cls, json_file: str) -> "BertConfig":
        with open(json_file, "r", encoding="utf-8") as reader:
            return cls.from_dict(json.loads(reader.read()))

    # -- serialization -------------------------------------------------------

    def to_dict(self) -> dict:
        return copy.deepcopy(self.__dict__)

    def to_json_string(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True) + "\n"

    def to_json_file(self, json_file: str) -> None:
        with open(json_file, "w", encoding="utf-8") as writer:
            writer.write(self.to_json_string())

    def __repr__(self) -> str:
        return f"BertConfig {self.to_json_string()}"

    # -- derived properties --------------------------------------------------

    @property
    def head_dim(self) -> int:
        if self.hidden_size % self.num_attention_heads != 0:
            raise ValueError(
                f"hidden_size {self.hidden_size} is not a multiple of "
                f"num_attention_heads {self.num_attention_heads}"
            )
        return self.hidden_size // self.num_attention_heads

    def padded_vocab_size(self, multiple: int = 8) -> int:
        """Vocab padded up for MXU-friendly tiling (run_pretraining.py:237-238
        pads to a multiple of 8; on TPU 128-lane alignment is natural but 8
        keeps checkpoint-shape parity)."""
        return ((self.vocab_size + multiple - 1) // multiple) * multiple


class NemotronHConfig:
    """Configuration of the ``nemotron_h`` family: a pre-norm residual decoder
    whose layers are a Mamba-2 mixer (``M``), a routed expert layer with a
    shared expert (``E``) or grouped-query causal attention (``*``) ALONE, in
    the order ``hybrid_override_pattern`` gives. Keys and defaults are the
    published ``config.json``'s (NVIDIA-Nemotron-3-Nano-30B-A3B); extra keys
    ride along as on :class:`BertConfig`.

    The expert share is stated here, not in the mesh: ``n_routed_experts`` is
    how many experts THIS chip holds, ``ep_size`` how many chips share each
    expert layer and ``ep_rank`` which of them this is. The router keeps
    ``n_routed_experts * ep_size`` outputs (the published width) and the chip
    holds the experts ``[ep_rank * n_routed_experts, (ep_rank + 1) * ...)``.
    """

    model_type = "nemotron_h"

    def __init__(self, **values: Any):
        defaults = dict(
            vocab_size=131072, hidden_size=2688, num_hidden_layers=52,
            hybrid_override_pattern=(
                "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"),
            num_attention_heads=32, num_key_value_heads=2, head_dim=128,
            mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
            ssm_state_size=128, conv_kernel=4, chunk_size=128,
            use_conv_bias=True, time_step_min=0.001, time_step_max=0.1,
            time_step_floor=0.0001,
            n_routed_experts=128, ep_size=1, ep_rank=0,
            num_experts_per_tok=6, moe_intermediate_size=1856,
            moe_shared_expert_intermediate_size=3712, n_shared_experts=1,
            routed_scaling_factor=2.5, norm_topk_prob=True,
            mlp_hidden_act="relu2", layer_norm_epsilon=1e-5,
            initializer_range=0.02, rescale_prenorm_residual=True,
            tie_word_embeddings=False)
        for key, value in {**defaults, **values}.items():
            setattr(self, key, value)
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or set(pattern) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} must be "
                f"{self.num_hidden_layers} characters of 'M', 'E', '*'")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} of ep_size {self.ep_size}")
        if self.mlp_hidden_act != "relu2" or self.tie_word_embeddings:
            raise ValueError(
                "nemotron_h is built with relu2 experts and an untied head")

    @classmethod
    def from_dict(cls, json_object: dict) -> "NemotronHConfig":
        values = {k: v for k, v in json_object.items() if k != "model_type"}
        return cls(**values)

    def to_dict(self) -> dict:
        return dict(copy.deepcopy(self.__dict__), model_type=self.model_type)

    @property
    def router_experts(self) -> int:
        """The router's width: every expert of the layer, held or not."""
        return self.n_routed_experts * self.ep_size

    @property
    def first_expert(self) -> int:
        return self.ep_rank * self.n_routed_experts

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def init_sample_length(self) -> int:
        """Positions of the sample that initializes the parameters (none
        depends on the length): one chunk of the scan."""
        return self.chunk_size


class LagunaConfig:
    """Configuration of the ``laguna`` family: a pre-norm residual decoder
    whose every layer is attention, then an MLP. The layers differ:
    ``layer_types`` says which attend to the whole prefix
    (``full_attention``) and which to the last ``sliding_window`` positions
    (``sliding_attention``), ``num_attention_heads_per_layer`` how many query
    heads each has (over ``num_key_value_heads`` key-value heads of
    ``head_dim``), ``rope_parameters`` the rotary table of each of the two
    kinds, and ``mlp_layer_types`` which MLP is ``dense``
    (``intermediate_size``) and which ``sparse`` (routed experts of
    ``moe_intermediate_size`` and a shared one). Keys and defaults are the
    published ``config.json``'s (poolside/Laguna-S-2.1); extra keys ride
    along as on :class:`BertConfig`.

    The chip's share is stated here, not in the mesh, as
    :class:`NemotronHConfig` states its experts': ``num_experts`` experts are
    HELD of ``num_experts * ep_size`` (the router's width), ``ep_rank`` says
    which; ``num_key_value_heads`` and ``num_attention_heads_per_layer``
    count the heads held of ``tp_size`` times as many (the output projection
    then adds only their terms).
    """

    model_type = "laguna"

    def __init__(self, **values: Any):
        defaults = dict(
            vocab_size=100352, hidden_size=3072, intermediate_size=12288,
            num_hidden_layers=48, num_attention_heads=48,
            num_key_value_heads=8, head_dim=128, tp_size=1, tp_rank=0,
            num_attention_heads_per_layer=None, layer_types=None,
            sliding_window=512, gating="per-head", attention_bias=False,
            rope_parameters={
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 500000, "factor": 128,
                    "original_max_position_embeddings": 8192, "beta_slow": 1,
                    "beta_fast": 32, "attention_factor": 1.4852030263919618,
                    "partial_rotary_factor": 0.5},
                "sliding_attention": {
                    "rope_type": "default", "rope_theta": 10000,
                    "partial_rotary_factor": 1}},
            mlp_only_layers=[0], mlp_layer_types=None,
            num_experts=256, ep_size=1, ep_rank=0, num_experts_per_tok=10,
            moe_intermediate_size=1024, shared_expert_intermediate_size=1024,
            norm_topk_prob=True, moe_routed_scaling_factor=2.5,
            moe_router_logit_softcapping=0,
            moe_apply_router_weight_on_input=False,
            rms_norm_eps=1e-6, initializer_range=0.02,
            tie_word_embeddings=False)
        for key, value in {**defaults, **values}.items():
            setattr(self, key, value)
        layers = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = [
                "sliding_attention" if i % 4 else "full_attention"
                for i in range(layers)]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = [
                "dense" if i in self.mlp_only_layers else "sparse"
                for i in range(layers)]
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = (
                [self.num_attention_heads] * layers)
        for name, allowed in (
                ("layer_types", {"full_attention", "sliding_attention"}),
                ("mlp_layer_types", {"dense", "sparse"})):
            got = getattr(self, name)
            if len(got) != layers or set(got) - allowed:
                raise ValueError(
                    f"{name} must be {layers} of {sorted(allowed)}: {got}")
        heads = self.num_attention_heads_per_layer
        if len(heads) != layers or any(
                h % self.num_key_value_heads for h in heads):
            raise ValueError(
                f"num_attention_heads_per_layer {heads}: {layers} multiples "
                f"of num_key_value_heads {self.num_key_value_heads}")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} of ep_size {self.ep_size}")
        if not 0 <= self.tp_rank < self.tp_size:
            raise ValueError(f"tp_rank {self.tp_rank} of tp_size {self.tp_size}")
        if (self.gating != "per-head" or self.attention_bias
                or self.tie_word_embeddings
                or self.moe_router_logit_softcapping
                or self.moe_apply_router_weight_on_input):
            raise ValueError(
                "laguna is built with a per-head gate, no attention bias, an "
                "untied head, no soft cap on the router's logits and the "
                "router's weight on the experts' output")

    @classmethod
    def from_dict(cls, json_object: dict) -> "LagunaConfig":
        values = {k: v for k, v in json_object.items() if k != "model_type"}
        return cls(**values)

    def to_dict(self) -> dict:
        return dict(copy.deepcopy(self.__dict__), model_type=self.model_type)

    @property
    def router_experts(self) -> int:
        """The router's width: every expert of the layer, held or not."""
        return self.num_experts * self.ep_size

    @property
    def first_expert(self) -> int:
        return self.ep_rank * self.num_experts

    def rope_of(self, layer: int) -> tuple:
        """(rotary dimensions of a head, the layer kind's ``rope_parameters``
        entry) for layer ``layer``."""
        rope = self.rope_parameters[self.layer_types[layer]]
        return (int(self.head_dim * rope.get("partial_rotary_factor", 1)),
                rope)

    def window_of(self, layer: int):
        """The attention window of layer ``layer``: None on a full layer."""
        return (self.sliding_window
                if self.layer_types[layer] == "sliding_attention" else None)

    @property
    def init_sample_length(self) -> int:
        """Positions of the sample that initializes the parameters (none
        depends on the length)."""
        return 16


def phi_flash_layer_types(n: int) -> list:
    """The published rule of the ``phi4flash`` family for a model of ``n``
    layers (``n % 4 == 0``), layer ``l`` of it: the first half is the
    self-decoder (even ``l`` a Mamba-1 mixer, odd ``l`` differential attention
    under the sliding window), layer ``n / 2`` a Mamba-1 mixer whose scan
    output is kept as the memory, layer ``n / 2 + 1`` full causal differential
    attention whose keys and values are kept, and from there on even ``l`` a
    gated memory unit on that memory and odd ``l`` differential
    cross-attention on those keys and values."""
    if n < 4 or n % 4:
        raise ValueError(f"the phi4flash layer rule needs n % 4 == 0, got {n}")
    half = n // 2
    return [("mamba" if l % 2 == 0 else "sliding_attention") if l < half else
            "mamba_memory" if l == half else
            "full_attention" if l == half + 1 else
            "gmu" if l % 2 == 0 else "cross_attention" for l in range(n)]


class PhiFlashConfig:
    """Configuration of the ``phi4flash`` family: a pre-norm residual decoder
    whose every layer is a mixer, then a gated MLP, under LayerNorm with
    bias, no positions anywhere and a head tied to the embedding. The mixer
    is one of six kinds (``layer_types``; :func:`phi_flash_layer_types` gives
    the published rule): ``mamba`` (a Mamba-1 selective scan), ``mamba_memory``
    (the same, its scan output handed on as the memory), ``sliding_attention``
    / ``full_attention`` (differential attention under the window / the whole
    prefix; the full layer hands on its keys and values), ``gmu`` (a gated
    memory unit on the memory) and ``cross_attention`` (differential
    attention with its own queries on the kept keys and values). Keys and
    defaults are the published ``config.json``'s
    (microsoft/Phi-4-mini-flash-reasoning); extra keys ride along as on
    :class:`BertConfig`.

    ``layer_indices`` holds each layer's index in the PUBLISHED model of
    ``published_num_hidden_layers`` layers (the differential attention's
    ``lambda_init`` depends on it); a model cut to some layers of the
    published one states both lists. The chip's share of the heads is stated
    here, as :class:`LagunaConfig` states it: ``num_attention_heads`` and
    ``num_key_value_heads`` count the heads held of ``tp_size`` times as many
    (the output projection then adds only their terms, and its bias on rank 0
    alone).
    """

    model_type = "phi4flash"

    def __init__(self, **values: Any):
        defaults = dict(
            vocab_size=200064, hidden_size=2560, intermediate_size=10240,
            num_hidden_layers=32, num_attention_heads=40,
            num_key_value_heads=20, tp_size=1, tp_rank=0,
            sliding_window=512, layer_norm_eps=1e-5, hidden_act="silu",
            mb_per_layer=2, max_position_embeddings=262144,
            embd_pdrop=0, resid_pdrop=0, mlp_bias=False, lm_head_bias=False,
            tie_word_embeddings=True, initializer_range=0.02,
            mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
            mamba_dt_rank=None, mamba_conv_bias=True, mamba_proj_bias=False,
            time_step_min=0.001, time_step_max=0.1, lambda_std=0.1,
            scan_chunk=128, layer_types=None, layer_indices=None,
            published_num_hidden_layers=None)
        for key, value in {**defaults, **values}.items():
            setattr(self, key, value)
        layers = self.num_hidden_layers
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.hidden_size // 16)
        if self.published_num_hidden_layers is None:
            self.published_num_hidden_layers = layers
        if self.layer_indices is None:
            self.layer_indices = list(range(layers))
        if self.layer_types is None:
            rule = phi_flash_layer_types(self.published_num_hidden_layers)
            self.layer_types = [rule[l] for l in self.layer_indices]
        kinds = {"mamba", "mamba_memory", "sliding_attention",
                 "full_attention", "gmu", "cross_attention"}
        if (len(self.layer_types) != layers or set(self.layer_types) - kinds
                or len(self.layer_indices) != layers):
            raise ValueError(
                f"layer_types and layer_indices must be {layers} long, of "
                f"{sorted(kinds)}: {self.layer_types}, {self.layer_indices}")
        for reader, writer in (("gmu", "mamba_memory"),
                               ("cross_attention", "full_attention")):
            if reader in self.layer_types and (
                    writer not in self.layer_types
                    or self.layer_types.index(writer)
                    > self.layer_types.index(reader)):
                raise ValueError(
                    f"a {reader} layer reads what a {writer} layer before it "
                    f"hands on: {self.layer_types}")
        if self.layer_types.count("mamba_memory") > 1 or (
                self.layer_types.count("full_attention") > 1):
            raise ValueError(
                "one layer writes the memory and one the kept keys and "
                f"values: {self.layer_types}")
        heads, kv = self.num_attention_heads, self.num_key_value_heads
        if (heads % 2 or kv % 2 or heads % kv
                or self.hidden_size % (heads * self.tp_size)):
            raise ValueError(
                f"{heads} query heads on {kv} key-value heads (both in "
                f"pairs) of hidden {self.hidden_size} / ({heads} x "
                f"{self.tp_size})")
        if not 0 <= self.tp_rank < self.tp_size:
            raise ValueError(f"tp_rank {self.tp_rank} of tp_size {self.tp_size}")
        if (not self.tie_word_embeddings or self.mlp_bias or self.lm_head_bias
                or self.hidden_act != "silu" or self.mb_per_layer != 2
                or self.embd_pdrop or self.resid_pdrop
                or self.mamba_proj_bias or not self.mamba_conv_bias):
            raise ValueError(
                "phi4flash is built with a tied head without bias, a silu "
                "MLP without bias, one Mamba layer in two, no dropout, a "
                "convolution bias and no projection bias in the mixer")

    @classmethod
    def from_dict(cls, json_object: dict) -> "PhiFlashConfig":
        values = {k: v for k, v in json_object.items() if k != "model_type"}
        return cls(**values)

    def to_dict(self) -> dict:
        return dict(copy.deepcopy(self.__dict__), model_type=self.model_type)

    @property
    def head_dim(self) -> int:
        """Width of one query or key head (the values of a pair are twice
        as wide): over the PUBLISHED head count, ``tp_size`` shares."""
        return self.hidden_size // (self.num_attention_heads * self.tp_size)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    def window_of(self, layer: int):
        """The attention window of layer ``layer``: None where it sees the
        whole prefix."""
        return (self.sliding_window
                if self.layer_types[layer] == "sliding_attention" else None)

    def lambda_init(self, layer: int) -> float:
        """``0.8 - 0.6 exp(-0.3 l)`` with ``l`` the published index."""
        return 0.8 - 0.6 * math.exp(-0.3 * self.layer_indices[layer])

    @property
    def init_sample_length(self) -> int:
        """Positions of the sample that initializes the parameters (none
        depends on the length)."""
        return 16


class ZayaConfig:
    """Configuration of the ``zaya`` family: a residual decoder whose every
    layer (``layer_types``: all ``hybrid``) is compressed convolutional
    attention, then a routed expert layer with NO shared expert, each joined
    to the stream by a learned merge (``scale_residual_merge``). The
    attention lives in a latent: ``num_attention_heads`` query heads on
    ``num_key_value_heads`` key-value heads of ``head_dim``, together
    narrower than ``hidden_size``, behind two causal convolutions over
    positions of ``cca_time0`` and ``cca_time1`` taps (``models/zaya.py``).
    The router is an MLP ``router_hidden_size`` wide whose state each layer
    hands to the next (``zaya_use_eda``); it has one output for every expert
    of the layer and, under ``zaya_use_mod``, one more that skips the layer;
    one expert a token. Keys and defaults are the published ``config.json``'s
    (Zyphra/ZAYA1-8B) and, for the four switches that file leaves implicit,
    its siblings' (ZAYA1-base); extra keys ride along as on
    :class:`BertConfig`.

    The chip's share is stated here, as :class:`LagunaConfig` states it:
    ``num_experts`` experts are HELD of ``num_experts * ep_size``, ``ep_rank``
    says which; the router keeps its published width.
    """

    model_type = "zaya"

    def __init__(self, **values: Any):
        defaults = dict(
            vocab_size=262272, hidden_size=2048, num_hidden_layers=40,
            num_attention_heads=8, num_key_value_heads=2, head_dim=128,
            cca=True, cca_time0=2, cca_time1=2, layer_types=None,
            sliding_window=None, partial_rotary_factor=0.5,
            rope_parameters={"hybrid": {
                "partial_rotary_factor": 0.5, "rope_theta": 5000000,
                "rope_type": "default"}},
            num_experts=16, ep_size=1, ep_rank=0, num_experts_per_tok=1,
            moe_intermediate_size=2048, router_hidden_size=256,
            zaya_use_eda=True, zaya_use_mod=True, scale_residual_merge=True,
            hidden_act="silu", attention_bias=False, lm_head_bias=False,
            rms_norm_eps=1e-5, initializer_range=0.02,
            tie_word_embeddings=True, max_position_embeddings=131072)
        for key, value in {**defaults, **values}.items():
            setattr(self, key, value)
        layers = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = ["hybrid"] * layers
        if len(self.layer_types) != layers or set(self.layer_types) - {"hybrid"}:
            raise ValueError(
                f"layer_types must be {layers} of ['hybrid'] (a layer under a "
                f"sliding window is not built): {self.layer_types}")
        if self.sliding_window is not None:
            raise ValueError(
                "zaya is built without a sliding_window: every layer attends "
                f"to the whole prefix (got {self.sliding_window})")
        if self.num_experts_per_tok != 1:
            raise ValueError(
                "zaya routes one expert a token, weighted by its own "
                "probability and never renormalised (num_experts_per_tok "
                f"{self.num_experts_per_tok})")
        if not self.tie_word_embeddings:
            raise ValueError(
                "zaya is built with its head tied to the embedding")
        if (not self.cca or not self.zaya_use_eda or not self.zaya_use_mod
                or not self.scale_residual_merge or self.attention_bias
                or self.lm_head_bias or self.hidden_act != "silu"):
            raise ValueError(
                "zaya is built with compressed convolutional attention, the "
                "router's state handed from layer to layer, the skip among "
                "the router's outputs, learned residual merges, silu experts "
                "and no bias on attention or head")
        heads, kv = self.num_attention_heads, self.num_key_value_heads
        if heads % kv or kv % 2 or self.head_dim % 4:
            raise ValueError(
                f"{heads} query heads on {kv} key-value heads of "
                f"{self.head_dim}: whole groups, and an even number of "
                "key-value heads (half of them read the previous token)")
        if self.cca_time0 < 1 or self.cca_time1 < 1:
            raise ValueError(
                f"cca_time0 {self.cca_time0}, cca_time1 {self.cca_time1}: "
                "a convolution has at least one tap")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} of ep_size {self.ep_size}")

    @classmethod
    def from_dict(cls, json_object: dict) -> "ZayaConfig":
        values = {k: v for k, v in json_object.items() if k != "model_type"}
        return cls(**values)

    def to_dict(self) -> dict:
        return dict(copy.deepcopy(self.__dict__), model_type=self.model_type)

    @property
    def router_experts(self) -> int:
        """Every expert of the layer, held or not."""
        return self.num_experts * self.ep_size

    @property
    def router_outputs(self) -> int:
        """The router's width: every expert and the skip after them."""
        return self.router_experts + 1

    @property
    def first_expert(self) -> int:
        return self.ep_rank * self.num_experts

    @property
    def rope(self) -> tuple:
        """(rotary dimensions of a head, the ``rope_parameters`` entry)."""
        rope = self.rope_parameters["hybrid"]
        return (int(self.head_dim * rope.get(
            "partial_rotary_factor", self.partial_rotary_factor)), rope)

    @property
    def init_sample_length(self) -> int:
        """Positions of the sample that initializes the parameters (none
        depends on the length)."""
        return 16


class Qwen3NextConfig:
    """Configuration of the ``qwen3_next`` family: a pre-norm residual decoder
    whose every layer is a token mixer, then a routed expert layer with a
    GATED shared expert. The mixer (``layer_types``; by default layer ``l`` is
    ``full_attention`` where ``(l + 1) % full_attention_interval == 0`` and
    ``linear_attention`` elsewhere) is either a gated delta-rule layer
    (``linear_num_key_heads`` key heads and ``linear_num_value_heads`` value
    heads of ``linear_key_head_dim`` / ``linear_value_head_dim`` behind a
    causal convolution of ``linear_conv_kernel_dim`` taps, a gated norm:
    ``ops/delta_rule.py``) or gated softmax attention
    (``num_attention_heads`` on ``num_key_value_heads`` heads of ``head_dim``,
    ``partial_rotary_factor`` of each turned, normed queries and keys, a
    sigmoid gate as wide as the output). Every norm multiplies by ``1 + w``
    but the delta-rule layer's gated one. Keys and defaults are the published
    ``config.json``'s (Qwen/Qwen3-Next-80B-A3B-Instruct); extra keys ride
    along as on :class:`BertConfig`.

    The chip's share is stated here, as :class:`LagunaConfig` states it:
    ``num_experts`` experts are HELD of ``num_experts * ep_size``, ``ep_rank``
    says which; the router keeps its published width. Heads are whole.
    """

    model_type = "qwen3_next"

    def __init__(self, **values: Any):
        defaults = dict(
            vocab_size=151936, hidden_size=2048, intermediate_size=5120,
            num_hidden_layers=48, num_attention_heads=16,
            num_key_value_heads=2, head_dim=256, hidden_act="silu",
            partial_rotary_factor=0.25, rope_theta=10000000,
            rope_scaling=None, rms_norm_eps=1e-6, full_attention_interval=4,
            layer_types=None, linear_conv_kernel_dim=4,
            linear_key_head_dim=128, linear_value_head_dim=128,
            linear_num_key_heads=16, linear_num_value_heads=32,
            delta_chunk=64, decoder_sparse_step=1, mlp_only_layers=[],
            num_experts=512, ep_size=1, ep_rank=0, num_experts_per_tok=10,
            moe_intermediate_size=512, shared_expert_intermediate_size=512,
            norm_topk_prob=True, tie_word_embeddings=False,
            use_sliding_window=False, initializer_range=0.02,
            max_position_embeddings=262144)
        for key, value in {**defaults, **values}.items():
            setattr(self, key, value)
        layers = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = [
                "full_attention" if (l + 1) % self.full_attention_interval == 0
                else "linear_attention" for l in range(layers)]
        kinds = {"linear_attention", "full_attention"}
        if len(self.layer_types) != layers or set(self.layer_types) - kinds:
            raise ValueError(
                f"layer_types must be {layers} of {sorted(kinds)}: "
                f"{self.layer_types}")
        if (self.decoder_sparse_step != 1 or self.mlp_only_layers
                or self.tie_word_embeddings or self.use_sliding_window
                or self.rope_scaling or self.hidden_act != "silu"):
            raise ValueError(
                "qwen3_next is built with an expert layer in every layer, an "
                "untied head, silu experts, the default rotary table and no "
                "sliding window")
        heads, kv = self.num_attention_heads, self.num_key_value_heads
        if heads % kv or int(self.head_dim * self.partial_rotary_factor) % 2:
            raise ValueError(
                f"{heads} query heads on {kv} key-value heads of "
                f"{self.head_dim}, {self.partial_rotary_factor} of it turned")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"{self.linear_num_value_heads} value heads on "
                f"{self.linear_num_key_heads} key heads: whole groups")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} of ep_size {self.ep_size}")

    @classmethod
    def from_dict(cls, json_object: dict) -> "Qwen3NextConfig":
        values = {k: v for k, v in json_object.items() if k != "model_type"}
        return cls(**values)

    def to_dict(self) -> dict:
        return dict(copy.deepcopy(self.__dict__), model_type=self.model_type)

    @property
    def router_experts(self) -> int:
        """Every expert of the layer, held or not."""
        return self.num_experts * self.ep_size

    @property
    def first_expert(self) -> int:
        return self.ep_rank * self.num_experts

    @property
    def rope(self) -> tuple:
        """(rotary dimensions of a head, a ``rope_parameters``-style entry)."""
        return (int(self.head_dim * self.partial_rotary_factor),
                {"rope_theta": self.rope_theta, "rope_type": "default"})

    @property
    def init_sample_length(self) -> int:
        """Positions of the sample that initializes the parameters (none
        depends on the length)."""
        return 16


class KeyeVLConfig:
    """Configuration of the ``KeyeVL2`` family's language model: a pre-norm
    residual decoder whose every layer is SPARSE softmax attention, then a
    routed expert layer without a shared expert. Attention
    (``num_attention_heads`` query heads on ``num_key_value_heads`` heads of
    ``head_dim``, q and k normed a head at a time, rotary on the whole head)
    runs, for each query, over the ``sa_config["topk"]`` keys that a learned
    indexer (``indexer_num_heads`` heads of ``indexer_head_dim`` against
    ``indexer_num_kv_heads`` = 1 key head, a weight a head and token) scores
    highest among the causal ones; the indexer learns from a KL term of its
    own that the model returns beside its counters
    (``models/keye_vl.py``, ``ops/sparse_attention.py``). Keys and defaults
    are those of the published ``config.json``'s language model
    (Kwai-Keye/Keye-VL-2.0-30B-A3B; ``text_config`` and ``sa_config`` may be
    given nested as there, or their keys at the top level); the vision tower
    is not built; extra keys ride along as on :class:`BertConfig`.

    The chip's share is stated here, as :class:`LagunaConfig` states it:
    ``num_experts`` experts are HELD of ``num_experts * ep_size``, ``ep_rank``
    says which; the router keeps its published width. Heads are whole.
    """

    model_type = "KeyeVL2"

    def __init__(self, **values: Any):
        defaults = dict(
            vocab_size=151936, hidden_size=2048, intermediate_size=6144,
            num_hidden_layers=48, num_attention_heads=32,
            num_key_value_heads=4, head_dim=128, hidden_act="silu",
            rope_theta=10000000, rope_scaling=None, rms_norm_eps=1e-6,
            decoder_sparse_step=1, mlp_only_layers=[], num_experts=128,
            ep_size=1, ep_rank=0, num_experts_per_tok=8,
            moe_intermediate_size=768, norm_topk_prob=True,
            tie_word_embeddings=False, use_sliding_window=False,
            attention_bias=False, initializer_range=0.02,
            max_position_embeddings=262144,
            sa_config=dict(indexer_num_heads=16, indexer_head_dim=64,
                           indexer_num_kv_heads=1, topk=2048,
                           q_chunk_size=512, kv_chunk_size=512),
            index_loss_coef=1.0)
        nested = dict(values.pop("text_config", None) or {})
        for key, value in {**defaults, **nested, **values}.items():
            setattr(self, key, value)
        self.sa_config = {**defaults["sa_config"], **(self.sa_config or {})}
        if (self.decoder_sparse_step != 1 or self.mlp_only_layers
                or self.tie_word_embeddings or self.use_sliding_window
                or self.attention_bias or self.hidden_act != "silu"):
            raise ValueError(
                "KeyeVL2 is built with an expert layer in every layer, an "
                "untied head, silu experts, no bias and no sliding window")
        scaling = self.rope_scaling or {}
        if scaling.get("rope_type", scaling.get("type", "default")) not in (
                "default", "mrope"):
            raise ValueError(
                "KeyeVL2 is built with the default rotary table (on text "
                "rows the three position streams of a multi-axis rotary "
                f"are equal and it IS that table): {scaling}")
        heads, kv = self.num_attention_heads, self.num_key_value_heads
        if heads % kv or self.head_dim % 2 or self.indexer_head_dim % 2:
            raise ValueError(
                f"{heads} query heads on {kv} key-value heads of "
                f"{self.head_dim}; indexer heads of {self.indexer_head_dim}")
        if self.sa_config["indexer_num_kv_heads"] != 1 or self.topk < 1:
            raise ValueError(
                "the indexer is built with one key head and a positive "
                f"topk: {self.sa_config}")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} of ep_size {self.ep_size}")

    @classmethod
    def from_dict(cls, json_object: dict) -> "KeyeVLConfig":
        values = {k: v for k, v in json_object.items() if k != "model_type"}
        return cls(**values)

    def to_dict(self) -> dict:
        return dict(copy.deepcopy(self.__dict__), model_type=self.model_type)

    @property
    def indexer_num_heads(self) -> int:
        return int(self.sa_config["indexer_num_heads"])

    @property
    def indexer_head_dim(self) -> int:
        return int(self.sa_config["indexer_head_dim"])

    @property
    def topk(self) -> int:
        """Keys a query attends to (all its causal keys where fewer)."""
        return int(self.sa_config["topk"])

    @property
    def router_experts(self) -> int:
        """Every expert of the layer, held or not."""
        return self.num_experts * self.ep_size

    @property
    def first_expert(self) -> int:
        return self.ep_rank * self.num_experts

    def rope_of(self, width: int) -> tuple:
        """(rotary dimensions, a ``rope_parameters``-style entry) for a head
        of ``width`` turned whole (the core's 128, the indexer's 64)."""
        return (width, {"rope_theta": self.rope_theta, "rope_type": "default"})

    @property
    def init_sample_length(self) -> int:
        """Positions of the sample that initializes the parameters (none
        depends on the length)."""
        return 16


class JoyAIConfig:
    """Configuration of the ``joyai_llm_flash`` family: a pre-norm residual
    decoder whose every layer is LATENT attention, then an MLP: dense SwiGLU
    in the first ``first_k_dense_replace`` layers, a routed expert layer with
    a shared expert after them; and ``num_nextn_predict_layers`` = 1
    multi-token-prediction module in the objective (``models/joyai.py``).
    Attention's queries come through a ``q_lora_rank`` latent and its keys
    and values through a ``kv_lora_rank`` one, each normed; a head's query and
    key are ``qk_nope_head_dim`` dimensions of its own beside
    ``qk_rope_head_dim`` turned ones, the key's turned part ONE vector that
    every head reads; values are ``v_head_dim`` wide. Keys and defaults are
    the published ``config.json``'s (jdopensource/JoyAI-LLM-Flash, whose keys
    are DeepSeek-V3's); extra keys ride along as on :class:`BertConfig`.

    The chip's share is stated here, as :class:`NemotronHConfig` states it:
    ``n_routed_experts`` experts are HELD of ``n_routed_experts * ep_size``,
    ``ep_rank`` says which; the router keeps its published width. (The
    published file's own ``ep_size: 1`` is a key of the released serving code
    and belongs under a configuration file's ``published``.) Heads are whole.
    """

    model_type = "joyai_llm_flash"

    def __init__(self, **values: Any):
        defaults = dict(
            vocab_size=129280, hidden_size=2048, intermediate_size=7168,
            num_hidden_layers=40, first_k_dense_replace=1, moe_layer_freq=1,
            num_attention_heads=32, num_key_value_heads=32,
            q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, qk_head_dim=None, v_head_dim=128,
            rope_theta=32000000,
            rope_interleave=True, rope_scaling=None, attention_bias=False,
            hidden_act="silu", rms_norm_eps=1e-6,
            n_routed_experts=256, ep_size=1, ep_rank=0,
            num_experts_per_tok=8, moe_intermediate_size=768,
            n_shared_experts=1, scoring_func="sigmoid",
            topk_method="noaux_tc", n_group=1, topk_group=1,
            norm_topk_prob=True, routed_scaling_factor=2.5,
            num_nextn_predict_layers=1, mtp_loss_coef=0.3,
            tie_word_embeddings=False, initializer_range=0.02,
            max_position_embeddings=131072)
        for key, value in {**defaults, **values}.items():
            setattr(self, key, value)
        if (self.rope_scaling or self.attention_bias or self.moe_layer_freq != 1
                or self.tie_word_embeddings or self.hidden_act != "silu"
                or self.scoring_func != "sigmoid"
                or self.topk_method != "noaux_tc"):
            raise ValueError(
                "joyai_llm_flash is built with the default rotary table, no "
                "bias, an expert layer in every layer after the dense ones, "
                "silu, sigmoid scores under noaux_tc and an untied head")
        if self.qk_head_dim is None:
            self.qk_head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.qk_head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError(
                f"qk_head_dim {self.qk_head_dim} is not qk_nope_head_dim "
                f"{self.qk_nope_head_dim} + qk_rope_head_dim "
                f"{self.qk_rope_head_dim}")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(
                "the group-limited choice is not built: n_group "
                f"{self.n_group}, topk_group {self.topk_group}")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                "multi-token prediction is built at depth 1 (one module), "
                f"not {self.num_nextn_predict_layers}")
        if self.qk_rope_head_dim % 2 or not (
                0 <= self.first_k_dense_replace <= self.num_hidden_layers):
            raise ValueError(
                f"turned dimensions {self.qk_rope_head_dim}; "
                f"{self.first_k_dense_replace} dense layers of "
                f"{self.num_hidden_layers}")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "latent attention gives every query head a key and a value "
                f"of its own: {self.num_key_value_heads} key-value heads on "
                f"{self.num_attention_heads}")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} of ep_size {self.ep_size}")

    @classmethod
    def from_dict(cls, json_object: dict) -> "JoyAIConfig":
        values = {k: v for k, v in json_object.items() if k != "model_type"}
        return cls(**values)

    def to_dict(self) -> dict:
        return dict(copy.deepcopy(self.__dict__), model_type=self.model_type)

    @property
    def router_experts(self) -> int:
        """Every expert of the layer, held or not."""
        return self.n_routed_experts * self.ep_size

    @property
    def first_expert(self) -> int:
        return self.ep_rank * self.n_routed_experts

    @property
    def shared_width(self) -> int:
        return self.n_shared_experts * self.moe_intermediate_size

    @property
    def rope(self) -> tuple:
        """(rotary dimensions, a ``rope_parameters``-style entry) of the
        turned part of a head."""
        return (self.qk_rope_head_dim,
                {"rope_theta": self.rope_theta, "rope_type": "default"})

    @property
    def init_sample_length(self) -> int:
        """Positions of the sample that initializes the parameters (none
        depends on the length)."""
        return 16


class MellumConfig(LagunaConfig):
    """Configuration of the ``mellum`` family (JetBrains/Mellum2-12B-A2.5B):
    :class:`LagunaConfig`'s decoder of sliding and full attention layers
    before routed experts, less what that family adds: no gate on the
    attention's output, one count of query heads for every layer, every MLP
    sparse, no shared expert and no factor on the routed weights. Keys and
    defaults are the published ``config.json``'s; the per-layer lists the
    shared blocks read (``models/laguna.py``) are derived here.
    ``intermediate_size`` is carried and used by no layer.

    ``ep_size`` / ``ep_rank`` state a chip's share as the other expert
    families do (``num_experts`` HELD of ``num_experts * ep_size``). Under a
    mesh with an ``expert`` axis (``--mesh ep=4``) the share is the mesh's
    instead and the file states the whole layer (``ep_size`` 1): the experts
    and the vocabulary's rows are divided over the axis and the slots cross
    it (``ops/moe.py exchanged_experts``)."""

    model_type = "mellum"

    def __init__(self, **values: Any):
        defaults = dict(
            vocab_size=98304, hidden_size=2304, intermediate_size=7168,
            num_hidden_layers=28, num_attention_heads=32,
            num_key_value_heads=4, head_dim=128, layer_types=None,
            mlp_layer_types=None, sliding_window=1024, use_sliding_window=True,
            max_window_layers=0, attention_bias=False, hidden_act="silu",
            max_position_embeddings=131072,
            rope_parameters={
                "full_attention": {
                    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                    "original_max_position_embeddings": 8192, "beta_fast": 32,
                    "beta_slow": 1, "attention_factor": 1.2772588722239782},
                "sliding_attention": {
                    "rope_type": "default", "rope_theta": 500000}},
            num_experts=64, ep_size=1, ep_rank=0, num_experts_per_tok=8,
            moe_intermediate_size=896, norm_topk_prob=True,
            rms_norm_eps=1e-6, initializer_range=0.02,
            tie_word_embeddings=False)
        for key, value in {**defaults, **values}.items():
            setattr(self, key, value)
        layers = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = [
                "full_attention" if i % 4 == 3 else "sliding_attention"
                for i in range(layers)]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = ["sparse"] * layers
        for name, allowed in (
                ("layer_types", {"full_attention", "sliding_attention"}),
                ("mlp_layer_types", {"sparse"})):
            got = getattr(self, name)
            if len(got) != layers or set(got) - allowed:
                raise ValueError(
                    f"{name} must be {layers} of {sorted(allowed)}: {got}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads on "
                f"{self.num_key_value_heads} key-value heads")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} of ep_size {self.ep_size}")
        if (self.attention_bias or self.tie_word_embeddings
                or self.hidden_act != "silu" or not self.use_sliding_window):
            raise ValueError(
                "mellum is built with no attention bias, an untied head, "
                "silu experts and its sliding layers windowed")
        # what models/laguna.py's blocks read beside the published keys
        self.num_attention_heads_per_layer = [self.num_attention_heads] * layers
        self.shared_expert_intermediate_size = 0
        self.moe_routed_scaling_factor = 1.0
        self.tp_size, self.tp_rank = 1, 0

    def to_dict(self) -> dict:
        derived = ("num_attention_heads_per_layer", "tp_size", "tp_rank",
                   "shared_expert_intermediate_size",
                   "moe_routed_scaling_factor")
        return {k: v for k, v in super().to_dict().items()
                if k not in derived}


MODEL_FAMILIES = {"bert": BertConfig, "nemotron_h": NemotronHConfig,
                  "laguna": LagunaConfig, "phi4flash": PhiFlashConfig,
                  "zaya": ZayaConfig, "qwen3_next": Qwen3NextConfig,
                  "KeyeVL2": KeyeVLConfig,
                  "joyai_llm_flash": JoyAIConfig, "mellum": MellumConfig}


def load_model_config(json_file: str):
    """The configuration a model config file describes, of the family its
    ``model_type`` names (none: ``bert``). The runners build the model and
    choose the objective from the class this returns
    (``models.build_pretraining_model``)."""
    with open(json_file, "r", encoding="utf-8") as reader:
        values = json.load(reader)
    family = values.get("model_type", "bert")
    if family not in MODEL_FAMILIES:
        raise ValueError(
            f"unknown model_type {family!r} in {json_file}; this program "
            f"builds {sorted(MODEL_FAMILIES)}")
    return MODEL_FAMILIES[family].from_dict(values)


def parse_args_with_config_file(
    parser: argparse.ArgumentParser,
    argv: list[str] | None = None,
    config_file_flag: str = "--config_file",
) -> argparse.Namespace:
    """Three-level precedence: CLI flag > JSON config file > argparse default.

    Reimplements the mechanism of reference run_pretraining.py:159-177: a
    default-suppressing clone of the parser detects which flags were explicitly
    passed on the command line; JSON config values override defaults; explicit
    CLI flags override the JSON.
    """
    argv = list(sys.argv[1:] if argv is None else argv)
    args = parser.parse_args(argv)

    config_dest = config_file_flag.lstrip("-")
    config_path = getattr(args, config_dest, None)
    if not config_path:
        return args

    with open(config_path, "r", encoding="utf-8") as f:
        config_values = json.load(f)

    # Detect explicitly-passed flags with a default-suppressing aux parser.
    aux = argparse.ArgumentParser(argument_default=argparse.SUPPRESS, add_help=False)
    for action in parser._actions:
        if action.option_strings and not isinstance(action, argparse._HelpAction):
            kwargs: dict[str, Any] = {"dest": action.dest}
            if isinstance(
                action, (argparse._StoreTrueAction, argparse._StoreFalseAction)
            ):
                kwargs["action"] = "store_true"
            else:
                kwargs["type"] = action.type
                kwargs["nargs"] = action.nargs
            aux.add_argument(*action.option_strings, **kwargs)
    explicit, _ = aux.parse_known_args(argv)
    explicitly_set = set(vars(explicit).keys())

    known = {action.dest for action in parser._actions}
    for key, value in config_values.items():
        if key not in known:
            raise ValueError(f"Unknown key '{key}' in config file {config_path}")
        if key not in explicitly_set:
            setattr(args, key, value)
    return args


def require_args(args: argparse.Namespace, names: list[str]) -> None:
    """Required args may come from CLI or config file (run_pretraining.py:573-581)."""
    missing = [name for name in names if getattr(args, name, None) is None]
    if missing:
        raise ValueError(
            f"Missing required arguments (set via CLI or config file): {missing}"
        )
