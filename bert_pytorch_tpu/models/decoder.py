"""What the decoder families share (``models/nemotron_h.py``,
``models/laguna.py``, ``models/phi4flash.py``, ``models/zaya.py``,
``models/qwen3_next.py``, ``models/keye_vl.py``, ``models/joyai.py``): RMSNorm,
LayerNorm, the bias-free projection, the dense gated MLP, the routed expert
layer with or without a shared expert and with a router of its own or one
handed in, and the wrapper round a stack of unlike layers: embedding,
``layers_0 .. layers_{L-1}``, final norm, output head (untied, or the
embedding's transpose), next-token objective.

Layers of unlike kinds hold unlike parameters, so they cannot be stacked and
scanned the way ``models/bert.py`` scans its encoder; each is rematerialized
on its own (``ops/remat.py``'s policy). A layer returns ``(x, counters)``;
the wrapper adds the layers' counters up (``*_max_over_mean``: their
largest; ``*_fill``: their mean) and they ride out of the train step as step
metrics.

**The carried path.** A family whose later layers read what an earlier layer
computed (``CARRIES``: ``models/phi4flash.py``: one layer's scan output, one
layer's keys and values; ``models/zaya.py``: the router's state, which every
layer reads from the one before and writes for the next) has layers that
take and return ``(x, carried)``: ``carried`` is a dict of arrays that starts
empty, a writing layer returns it with its entry added (or replaced), and
every later layer hands it on. Each block is still
rematerialized on its own, so a carried tensor is kept once, as the output of
the block that wrote it, and its cotangent is the sum of what every reader
and the hand-on return, which autodiff forms. The families that carry nothing
are called as before and lower as before.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from bert_pytorch_tpu.ops import moe
from bert_pytorch_tpu.ops.layernorm import layer_norm
from bert_pytorch_tpu.ops.remat import remat_policy

Dtype = Any

MOE_COUNTERS = ("moe_local_slots", "moe_dropped_slots",
                "moe_load_max_over_mean", "moe_pieces_run", "moe_tile_fill")
# ... and what the exchange between chips adds (ops/moe.py exchanged_experts)
EXCHANGE_COUNTERS = (
    "moe_exchange_slots_out", "moe_exchange_slots_in",
    "moe_exchange_bytes_out", "moe_chip_load_max_over_mean")


def normal(std: float):
    return nn.initializers.normal(stddev=std)


def dense(features: int, std: float, dtype, name, axes=None):
    """The bias-free projection; ``axes`` names the kernel's two axes for the
    mesh's rules (``parallel/mesh.py``), none by default."""
    init = normal(std)
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=jnp.float32, name=name,
                    kernel_init=nn.with_logical_partitioning(init, axes)
                    if axes else init)


class RMSNorm(nn.Module):
    """``x rsqrt(mean(x^2) + epsilon) (offset + scale)`` in float32. With
    ``offset`` 0 the learned scale starts at one; with ``offset`` 1
    (``models/qwen3_next.py``: the norm multiplies by ``1 + w``) at zero, so
    both start as the plain norm."""
    epsilon: float = 1e-5
    dtype: Dtype = jnp.float32
    offset: int = 0

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.zeros if self.offset
            else nn.initializers.ones, (x.shape[-1],), jnp.float32)
        if self.offset:
            scale = self.offset + scale
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon)
        return (normed * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """LayerNorm with weight and bias (``ops/layernorm.py``: float32
    statistics)."""
    epsilon: float = 1e-5
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],),
                          jnp.float32)
        return layer_norm(x.astype(self.dtype), scale, bias, self.epsilon)


def gate_by_token(y, x, vector):
    """``y * sigmoid(x . vector)`` a token (y, x [B, S, H]; the product in
    x's dtype as a projection's, the sigmoid float32), in y's dtype."""
    with jax.named_scope("moe_shared_gate"):
        gate = jnp.einsum("bsh,h->bs", x, vector.astype(x.dtype))
        return (y * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
                ).astype(y.dtype)


class DenseMLP(nn.Module):
    """``W_down (silu(W_gate h) * W_up h)``, ``width`` wide; gate and up are
    one projection, the gate's columns first. ``out_std`` is the down
    projection's (it writes into the residual stream)."""
    width: int
    hidden: int
    std: float
    out_std: float
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("dense_mlp"):
            gate, up = jnp.split(dense(
                2 * self.width, self.std, self.dtype, "gate_up_proj")(x),
                2, axis=-1)
            return dense(self.hidden, self.out_std, self.dtype,
                         "down_proj")(jax.nn.silu(gate) * up)


class ExpertLayer(nn.Module):
    """Router over every expert of the layer (``router_experts`` wide), the
    terms of the ``held`` experts from ``first_expert`` on, and the shared
    expert on every token. ``score`` is the routing rule and ``gated`` the
    experts' form (``ops/moe.py``): ``sigmoid`` with its correction bias and
    plain ``activation`` experts for ``nemotron_h``, ``softmax`` and gated
    experts for ``laguna``; the shared expert has the routed experts' form.
    ``shared_width`` 0 builds no shared expert (``zaya``): the output is the
    held experts' terms alone. A family whose router is not one matrix
    computes its routing itself and hands it in (``routing``: ids and weights
    [T, k] from ``moe.choose``); the layer then holds no router parameter,
    and ``router_experts`` is that router's width, a skip it may have among
    its outputs included (no chip holds it: its slots add nothing).
    ``shared_gate`` (``qwen3_next``) multiplies the shared expert's output by
    ``sigmoid(x w_g)`` a token, ``w_g`` one vector of the stream's width.
    With ``axis_names`` the stacked tensors carry the axis name ``experts`` on
    their first axis, for the mesh's rules (``parallel/mesh.py``: a family
    opts in, ``CausalDecoder.AXIS_NAMES``; ``model.init`` then returns them
    boxed, ``nn.unbox``). Under ``expert_axis`` (a mesh axis the caller runs manually,
    ``expert_shards`` wide: ``pretrain.make_train_step``) the layer holds
    ``held / expert_shards`` experts of a WHOLE layer (``held`` =
    ``router_experts``, no shared expert) and the slots cross the axis
    (``ops/moe.py exchanged_experts``); ``first_expert`` is then the axis
    index's and the field is not read. Returns (output, the layer's
    ``moe_*`` counters)."""
    width: int
    shared_width: int
    held: int
    router_experts: int
    first_expert: int
    top_k: int
    route_scale: float
    norm_topk: bool
    activation: Callable
    std: float
    out_std: float
    score: str = "sigmoid"
    gated: bool = False
    shared_gate: bool = False
    # (tests at a small size set a smaller rounding of the pieces)
    piece_multiple: int = moe.GMM_TILE_ROWS
    dtype: Dtype = jnp.float32
    axis_names: bool = False
    expert_axis: Optional[str] = None
    expert_shards: int = 1

    @nn.compact
    def __call__(self, x, routing=None):
        hidden, fan = x.shape[-1], 2 if self.gated else 1
        held = self.held // self.expert_shards
        if self.expert_axis and (routing is not None or self.shared_width
                                 or self.held != self.router_experts):
            raise ValueError(
                "under an expert axis the layer holds a whole layer's "
                "experts, a router matrix of its own and no shared expert")

        def stacked(std):
            init = normal(std)
            return nn.with_logical_partitioning(
                init, ("experts", None, None)) if self.axis_names else init

        if routing is None:
            router_w = self.param("router_kernel", normal(self.std),
                                  (hidden, self.router_experts), jnp.float32)
            correction = None
            if self.score == "sigmoid":
                # The published rule moves this bias outside the gradient,
                # towards balance; here it is a buffer at zero (route() stops
                # its gradient).
                correction = self.param(
                    "router_correction_bias", nn.initializers.zeros,
                    (self.router_experts,), jnp.float32)
        w_up = self.param("experts_up", stacked(self.std),
                          (held, hidden, fan * self.width), jnp.float32)
        w_down = self.param("experts_down", stacked(self.out_std),
                            (held, self.width, hidden), jnp.float32)
        batch, seq = x.shape[:2]
        flat = x.reshape(batch * seq, hidden)
        with jax.named_scope("moe"):
            chosen, weights = routing or moe.route(
                flat, router_w, correction, self.top_k, self.route_scale,
                self.norm_topk, self.score)
            # for a caller that asks (``mutable=["intermediates"]``): which
            # experts each token chose; otherwise nothing is kept
            self.sow("intermediates", "chosen", chosen)
            if self.expert_axis:
                routed, counters = moe.exchanged_experts(
                    flat, chosen, weights, w_up, w_down, self.router_experts,
                    self.activation, self.expert_axis,
                    multiple=self.piece_multiple, gated=self.gated)
            else:
                routed, counters = moe.held_experts(
                    flat, chosen, weights, w_up, w_down, self.first_expert,
                    self.router_experts, self.activation,
                    multiple=self.piece_multiple, gated=self.gated)
            counters = {"moe_" + name: value
                        for name, value in counters.items()}
            if not self.shared_width:
                return routed.reshape(x.shape), counters
            with jax.named_scope("moe_shared"):
                mid = dense(fan * self.shared_width, self.std, self.dtype,
                            "shared_up")(x)
                if self.gated:
                    gate, up = jnp.split(mid, 2, axis=-1)
                    mid = self.activation(gate) * up
                else:
                    mid = self.activation(mid)
                shared = dense(hidden, self.out_std, self.dtype,
                               "shared_down")(mid)
                if self.shared_gate:
                    shared = gate_by_token(shared, x, self.param(
                        "shared_gate", normal(self.std), (hidden,),
                        jnp.float32))
            return shared + routed.reshape(x.shape), counters


def rematerialized(remat: str, block, without=(), keeping=()):
    """``block`` (a module class) under ``remat``'s policy less the names in
    ``without`` and, under 'full', with those in ``keeping``
    (``ops/remat.py``); the class itself under 'none'."""
    policy = remat_policy(remat, without, keeping)
    return block if policy is None else nn.remat(
        block, policy=policy, prevent_cse=True)


class CausalDecoder(nn.Module):
    """The wrapper: a family gives its ``blocks()`` (modules that take x and
    return ``(x, counters)``; under ``CARRIES`` they take ``(x, carried)``
    and return ``(x, carried, counters)``), the norm's epsilon, any
    counters of its own beside the expert layers' (``COUNTERS``) and what
    its layers share (``shared_inputs``). ``NORM`` is
    the final norm's class; under ``TIED_HEAD`` the output head is the
    embedding's transpose and the model holds no ``lm_head``. The model
    returns ``(logits [B, S, V], counters)``.

    **Under an expert axis.** A family with ``AXIS_NAMES`` names its big
    tensors' axes (embedding and head by ``vocab_rows``, the expert layers'
    stacked tensors by ``experts``), and a mesh with an ``expert`` axis
    divides them over it. ``pretrain.make_train_step`` then runs the model
    inside a ``shard_map`` manual over that axis, built a second time with
    ``expert_axis`` (the axis's name) and ``expert_shards`` (its width): every
    chip sees its own rows of the batch and the SHARDS of those tensors
    (``vocab_size / expert_shards`` rows of both tables); attention, norms and
    routers are whole on every chip; the embedding's look-up, the expert
    layers and the loss cross the axis (``embed``, ``ops/moe.py
    exchanged_experts``, ``models/losses.py``)."""
    config: Any
    dtype: Dtype = jnp.float32
    remat: str = "none"
    attention_backend: str = "xla"
    expert_axis: Optional[str] = None
    expert_shards: int = 1

    # What pretrain.make_train_step trains these families on.
    objective = "causal_lm"
    COUNTERS = MOE_COUNTERS
    NORM = RMSNorm
    TIED_HEAD = False
    CARRIES = False
    AXIS_NAMES = False

    def blocks(self, wrap) -> list:
        """The layers, in order; ``wrap`` rematerializes a block class, under
        the policy less the names in its ``without`` and with those in its
        ``keeping`` (``ops/remat.py``)."""
        raise NotImplementedError

    def norm_epsilon(self) -> float:
        raise NotImplementedError

    def kept_across_remat(self) -> dict:
        """What the family asks ``remat='full'`` to keep beside
        ``ops/remat.py KEPT_UNDER_FULL``, for the trainer's start-up line
        (``ops/remat.py kept_residual_bytes``): ``keeping`` (the names its
        ``blocks`` hand ``wrap``), ``regions`` (how many rematerialized
        regions keep them) and the kept output's ``heads`` and ``head_dim``.
        Empty for a family that asks for nothing, which is most."""
        return {}

    def objective_terms(self) -> dict:
        """{counter: coefficient}: what the model returns beside its counters
        that belongs to the OBJECTIVE (``pretrain._apply_causal_lm_loss``
        adds each, times its coefficient, to the next-token loss:
        ``models/keye_vl.py``, the indexer's KL). None for most families."""
        return {}

    def prediction_streams(self) -> dict:
        """{name: (shift, coefficient)}: further streams of hidden states
        through the SHARED output head that belong to the objective: stream
        ``name`` (what ``further_streams`` returns under that name) predicts
        token t + ``shift`` at position t, and
        ``pretrain._apply_causal_lm_loss`` adds ``coefficient`` times its mean
        loss to the next-token loss (``models/joyai.py``: the
        multi-token-prediction module, shift 2). None for most families."""
        return {}

    def further_streams(self, x, embedded, shared) -> tuple:
        """({name: hidden [B, S, H]}, counters) of ``prediction_streams``'
        names, from the last layer's output ``x`` BEFORE the final norm, the
        embedded input ``embedded`` and ``shared_inputs``' tuple. A model
        that names a stream builds it here, from modules of its own."""
        raise NotImplementedError

    def shared_inputs(self, seq: int) -> tuple:
        """What every layer of one call over ``seq`` positions reads and none
        writes, made once ahead of the layers and handed to each after its
        other inputs (``models/laguna.py``: the rotary tables)."""
        return ()

    def setup(self):
        cfg = self.config
        if self.expert_axis and not (self.AXIS_NAMES and not self.TIED_HEAD
                                     and not self.prediction_streams()):
            raise ValueError(
                f"the {cfg.model_type} family does not run under an expert "
                "axis (models/decoder.py AXIS_NAMES)")
        rows = cfg.vocab_size // self.expert_shards
        init = normal(cfg.initializer_range)
        self.embedding = self.param(
            "embedding", nn.with_logical_partitioning(
                init, ("vocab_rows", None)) if self.AXIS_NAMES else init,
            (rows, cfg.hidden_size), jnp.float32)
        self.layers = self.blocks(functools.partial(rematerialized, self.remat))
        self.final_norm = self.NORM(self.norm_epsilon(), self.dtype)
        if not self.TIED_HEAD:
            self.lm_head = dense(
                rows, cfg.initializer_range, self.dtype, None,
                (None, "vocab_rows") if self.AXIS_NAMES else None)

    def on_expert_axis(self, axis: str, shards: int):
        """This model as one chip of ``shards`` runs it inside a ``shard_map``
        manual over the mesh axis ``axis``."""
        if self.config.vocab_size % shards:
            raise ValueError(f"{self.config.vocab_size} rows of the "
                             f"vocabulary over {shards} chips")
        return self.clone(expert_axis=axis, expert_shards=shards)

    def embed(self, input_ids):
        """[B, S] ids -> their rows of the embedding in the compute dtype.
        Under an expert axis the table's rows lie on ``expert_shards`` chips:
        the ids are gathered over the axis (they are small), each chip looks
        up the rows it holds for every chip's tokens, and the sums go back to
        the tokens' owners (one term of each sum is not zero)."""
        if not self.expert_axis:
            return jnp.take(self.embedding, input_ids, axis=0).astype(
                self.dtype)
        with jax.named_scope("embed_exchange"):
            rows = self.embedding.shape[0]
            ids = jax.lax.all_gather(input_ids, self.expert_axis)
            local = ids - jax.lax.axis_index(self.expert_axis) * rows
            here = (local >= 0) & (local < rows)
            found = jnp.where(
                here[..., None],
                jnp.take(self.embedding, jnp.clip(local, 0, rows - 1), axis=0),
                0).astype(self.dtype)
            return jax.lax.psum_scatter(found, self.expert_axis)

    def head_kernel(self, params):
        """The output head's [H, V] matrix in a parameter tree of this
        model (for ``pretrain``'s head in pieces)."""
        return (params["embedding"].T if self.TIED_HEAD
                else params["lm_head"]["kernel"])

    def hidden_states(self, input_ids):
        """[B, S] ids -> (the final norm's output [B, S, H], counters): all
        but the head, for a caller that takes the head in pieces
        (models/losses.py ``chunked_next_token_loss``)."""
        return _run_layers(self, input_ids)[:2]

    def streams(self, input_ids):
        """``hidden_states`` and, third, ``further_streams``' hidden states
        by name (their layers' counters added to the others)."""
        return _run_layers(self, input_ids, further=True)

    def __call__(self, input_ids):
        x, counters = self.hidden_states(input_ids)
        with jax.named_scope("lm_head"):
            if self.TIED_HEAD:
                return jnp.matmul(
                    x, self.embedding.T.astype(self.dtype)), counters
            if self.expert_axis:
                raise ValueError(
                    "under an expert axis no chip holds a row's logits: take "
                    "hidden_states and the loss over the axis "
                    "(models/losses.py chunked_next_token_loss)")
            return self.lm_head(x), counters


def _run_layers(model: CausalDecoder, input_ids, further: bool = False):
    """(the final norm's output, counters, {stream: hidden}) of ``model``
    bound to its parameters. A plain function, so that the two methods that
    call it write the same scopes as when each held this body."""
    embedded = model.embed(input_ids)
    x = embedded
    seen = {name: [] for name in model.COUNTERS}
    carried = {}
    shared = model.shared_inputs(input_ids.shape[1])
    for layer in model.layers:
        if model.CARRIES:
            x, carried, counters = layer(x, carried, *shared)
        else:
            x, counters = layer(x, *shared)
        for name, value in (counters or {}).items():
            seen[name].append(value)
    streams = {}
    if further:
        streams, counters = model.further_streams(x, embedded, shared)
        for name, value in counters.items():
            seen[name].append(value)
    zero = jnp.zeros((), jnp.float32)
    return model.final_norm(x), {
        name: (zero if not values else
               jnp.max(jnp.stack(values))
               if name.endswith("_max_over_mean") else
               jnp.mean(jnp.stack(values))
               if name.endswith("_fill") else sum(values, zero))
        for name, values in seen.items()}, streams
