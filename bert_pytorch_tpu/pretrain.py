"""Pretraining engine: sharded train state + the single jitted train step.

The XLA analog of the reference's hot loop (SURVEY.md §3.1,
run_pretraining.py:405-460): where the reference does
fwd -> bwd -> DDP bucket allreduce -> FusedLAMB per microbatch sequence,
here ONE jitted function scans over the accumulation microbatches
(``lax.scan``), accumulates gradients locally, and applies the optimizer —
XLA inserts the cross-device gradient reduction implied by the shardings
(params replicated/sharded per strategy, batch sharded over data axes), so
no collective is ever written by hand. ``no_sync()`` (run_pretraining.py:
448-453) has no analog: communication happens once per step by construction.

bf16 activations / fp32 params+moments replace torch.cuda.amp + GradScaler
(run_pretraining.py:314-318,424-434) — bf16 needs no loss scaling.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bert_pytorch_tpu.models.losses import (chunked_next_token_loss,
                                            mlm_accuracy, next_token_loss,
                                            pretraining_loss)
from bert_pytorch_tpu.ops.grad_utils import global_norm
from bert_pytorch_tpu.ops.remat import remat_policy
from bert_pytorch_tpu.optim.transforms import (LossScaleState, OptState,
                                               opt_step_count)
from bert_pytorch_tpu.parallel.mesh import (AXIS_EXPERT, AXIS_PIPE, AXIS_SEQ,
                                            BATCH_AXES)
from bert_pytorch_tpu.parallel.sharding import params_shardings
from bert_pytorch_tpu.utils import trace_parts

# Every ``jax.named_scope`` the train steps write where Flax gives no module
# name (here, models/losses.py, ops/attention.py, optim/transforms.py). They
# reach the HLO's ``op_name`` beside the module names and the pass markers
# JAX adds itself (``jvp(``, ``transpose(``, ``rematted_computation``), so a
# profiler trace can be read by part and by pass (docs/telemetry.md).
SCOPES = ("micro_batches", "grad_accumulate", "optimizer", "clip", "lamb",
          "step_metrics", "mlm_loss", "nsp_loss", "attention_core",
          "attention_dropout")
# The scopes a step of the ``causal_lm`` objective writes beside those
# (the nemotron_h family: models/nemotron_h.py, ops/ssm.py, ops/moe.py,
# models/losses.py); it has no ``mlm_loss``, ``nsp_loss``, ``lamb`` or
# ``attention_dropout``.
CAUSAL_LM_SCOPES = (
    "ssm_mixer", "ssm_in_proj", "ssm_conv", "ssd_scan", "ssm_gate_norm",
    "ssm_out_proj", "moe", "moe_route", "moe_dispatch", "moe_experts",
    "moe_combine", "moe_shared", "lm_head", "lm_loss")
# ... and those of the laguna family's step (models/laguna.py; the expert
# layer, the head and the loss are the ones above).
LAGUNA_SCOPES = (
    "attn_qkv", "attn_rope", "attn_gate", "attn_out", "dense_mlp", "moe",
    "moe_route", "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
    "lm_head", "lm_loss")
# ... and those of the phi4flash family's step (models/phi4flash.py,
# ops/ssm.py selective_scan, ops/attention.py differential_attention).
PHI_FLASH_SCOPES = (
    "s6_mixer", "s6_in_proj", "s6_conv", "s6_dt", "selective_scan", "s6_gate",
    "s6_out_proj", "gmu", "attn_qkv", "attn_diff", "attn_out", "dense_mlp",
    "lm_head", "lm_loss")
# ... and those of the zaya family's step (models/zaya.py: attention inside a
# latent, a router of its own under ``moe_route``, the learned merges).
ZAYA_SCOPES = (
    "cca", "attn_qkv", "cca_conv", "cca_qk_mean", "cca_value_shift",
    "cca_norm", "attn_rope", "attn_out", "moe", "moe_route", "router_down",
    "router_eda", "router_mlp", "moe_dispatch", "moe_experts", "moe_combine",
    "residual_merge", "lm_head", "lm_loss")

# ... and those of the qwen3_next family's step (models/qwen3_next.py: the
# delta-rule mixer under ``gdn``, gated softmax attention, the expert layer
# above with a gate on its shared expert).
QWEN3_NEXT_SCOPES = (
    "gdn", "gdn_in_proj", "gdn_conv", "gdn_gates", "delta_rule",
    "gdn_gate_norm", "gdn_out_proj", "attn_qkv", "attn_qk_norm", "attn_rope",
    "attn_gate", "attn_out", "moe", "moe_route", "moe_dispatch",
    "moe_experts", "moe_combine", "moe_shared", "moe_shared_gate", "lm_head",
    "lm_loss")

# ... and those of the KeyeVL2 family's step (models/keye_vl.py: attention
# over the keys an indexer chooses, under ``dsa``, and the indexer's KL).
KEYE_SCOPES = (
    "attn_qkv", "attn_qk_norm", "attn_rope", "attn_out", "dsa",
    "dsa_index_proj", "dsa_scores", "dsa_select", "dsa_core",
    "dsa_index_loss", "moe", "moe_route", "moe_dispatch", "moe_experts",
    "moe_combine", "lm_head", "lm_loss")

# ... and those of the joyai_llm_flash family's step (models/joyai.py: latent
# attention under ``mla``, the multi-token-prediction module under ``mtp``
# and its pass of the shared head).
JOYAI_SCOPES = (
    "mla", "mla_q_proj", "mla_kv_proj", "attn_rope", "mla_core", "attn_out",
    "dense_mlp", "moe", "moe_route", "moe_dispatch", "moe_experts",
    "moe_combine", "moe_shared", "mtp", "mtp_merge", "mtp_head", "mtp_loss",
    "lm_head", "lm_loss")

# ... and those of the mellum family's step (models/mellum.py: laguna's
# blocks without gate, dense layer or shared expert; under an expert axis the
# slots' exchange, the embedding's and the head's crossing, and the one sum of
# the whole tensors' gradients an update).
MELLUM_SCOPES = (
    "attn_qkv", "attn_rope", "attn_out", "moe", "moe_route", "moe_dispatch",
    "moe_exchange_out", "moe_experts", "moe_exchange_back", "moe_combine",
    "embed_exchange", "lm_head_gather", "lm_head", "lm_loss", "grad_sync")

# Rows longer than this many positions take the output head and its loss in
# pieces of this length (models/losses.py chunked_next_token_loss).
LM_HEAD_PIECE = 2048


@flax.struct.dataclass
class TrainState:
    params: Any
    opt_state: OptState
    rng: jax.Array


def _replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def state_shardings(mesh: Mesh, model, rules, sample_inputs,
                    loss_scaled: bool = False) -> TrainState:
    """Shardings for every leaf of TrainState, derived from the model's
    logical axis annotations (no per-param code — the point of the design).
    ``loss_scaled`` matches an fp16 optimizer wrapped in
    ``optim.dynamic_loss_scale`` (two extra replicated scalars)."""
    abstract = jax.eval_shape(
        lambda r: model.init(r, *sample_inputs), jax.random.PRNGKey(0)
    )
    p_shardings = params_shardings(mesh, abstract, rules)["params"]
    repl = _replicated(mesh)
    opt = OptState(count=repl, mu=p_shardings, nu=p_shardings)
    if loss_scaled:
        opt = LossScaleState(scale=repl, growth_count=repl, inner=opt)
    return TrainState(params=p_shardings, opt_state=opt, rng=repl)


def batch_shardings(mesh: Mesh, batch_spec: dict, seq_sharded: bool = False) -> dict:
    """Shardings for the [A, B, ...] stacked microbatch dict: accumulation
    axis replicated (scanned), batch axis sharded over data(+fsdp, +expert:
    ``parallel/mesh.py BATCH_AXES``), and —
    under context parallelism (``seq_sharded``) — the sequence axis of
    [A, B, S] entries sharded over the mesh 'seq' axis."""
    out = {}
    for key, ndim in batch_spec.items():
        spec = [None, BATCH_AXES] + [None] * (ndim - 2)
        if seq_sharded and ndim == 3:
            spec[2] = AXIS_SEQ
        out[key] = NamedSharding(mesh, P(*spec))
    return out


def make_init_fn(model, tx, sample_inputs, shardings: TrainState):
    """Jitted initializer producing an already-sharded TrainState."""

    def init_fn(rng):
        init_rng, state_rng = jax.random.split(rng)
        with trace_parts.modules():
            variables = nn.unbox(model.init(init_rng, *sample_inputs))
        params = variables["params"]
        return TrainState(
            params=params, opt_state=tx.init(params), rng=state_rng
        )

    return jax.jit(init_fn, out_shardings=shardings)


def _mlm_positions(labels, max_pred_per_seq):
    """Extract [B, P] masked positions + gathered labels when P < S (top_k on
    the label mask — stable, so the first max_pred masked positions win)."""
    if max_pred_per_seq is None or max_pred_per_seq >= labels.shape[-1]:
        return labels, None
    is_masked = (labels != -1).astype(jnp.int32)
    _, masked_positions = jax.lax.top_k(is_masked, max_pred_per_seq)
    labels = jnp.take_along_axis(labels, masked_positions, axis=1)
    return labels, masked_positions


def _apply_pretraining_loss(model, variables, mb, rng, next_sentence,
                            max_pred_per_seq, mutable=False):
    """The one shared apply+loss(+accuracy) sequence behind every
    pretraining loss path — the plain train-step loss, the fused-capture
    tapped loss, and the K-FAC stats pass. One definition, so a loss or
    signature change cannot silently diverge between them.

    Returns (loss, acc, mutated); ``mutated`` is None unless ``mutable``
    names collections. ``acc`` is always computed — XLA dead-code
    eliminates it in consumers that drop it.
    """
    labels, masked_positions = _mlm_positions(
        mb["masked_lm_labels"], max_pred_per_seq
    )
    with trace_parts.modules():  # the modules' Python, by class, at trace time
        out = model.apply(
            variables,
            mb["input_ids"],
            mb["segment_ids"],
            mb["input_mask"],
            False,  # deterministic
            masked_positions,
            # Packed batches (data/packing.py) carry the extra arrays; absent
            # keys select the unpacked model path unchanged.
            mb.get("sequence_ids"),
            mb.get("cls_positions"),
            rngs={"dropout": rng},
            **({"mutable": mutable} if mutable else {}),
        )
    (mlm_logits, nsp_logits), mutated = out if mutable else (out, None)
    loss = pretraining_loss(
        mlm_logits,
        nsp_logits if next_sentence else None,
        labels,
        mb["next_sentence_labels"] if next_sentence else None,
    )
    acc = mlm_accuracy(mlm_logits, labels)
    return loss, acc, mutated


def _apply_causal_lm_loss(model, variables, mb):
    """The ``causal_lm`` objective's counterpart of
    :func:`_apply_pretraining_loss`: rows of token ids in, next-token loss
    out, plus what the model names as terms of its objective: scalars among
    its counters (``CausalDecoder.objective_terms``) and further streams
    through the shared head (``CausalDecoder.prediction_streams``: stream
    ``name`` predicts token t + shift at position t; its loss and accuracy go
    into the counters as ``<name>_loss`` and ``<name>_token_accuracy``, under
    the scopes ``<name>_head`` and ``<name>_loss``). Most families name
    neither. Returns (loss, aux); ``aux`` holds the token accuracy and the
    model's counters, one scalar each per micro-batch."""
    ids = mb["input_ids"]
    pieces, ragged = divmod(ids.shape[-1], LM_HEAD_PIECE)
    whole = bool(ragged) or pieces < 2
    if model.expert_axis:
        # The head's columns lie on the axis's chips: always in pieces (one,
        # for a short row), each as long as a chip's share of LM_HEAD_PIECE
        # rows gathered over the axis.
        with trace_parts.modules():
            hidden, counters = model.apply(variables, ids,
                                           method="hidden_states")
        finer = pieces * model.expert_shards
        loss, accuracy = chunked_next_token_loss(
            hidden, model.head_kernel(variables["params"]), ids,
            1 if whole else pieces if ids.shape[-1] % finer else finer,
            axis=model.expert_axis)
        return loss, {"token_accuracy": accuracy, **counters}
    streams = model.prediction_streams()
    method = "streams" if streams else None if whole else "hidden_states"
    with trace_parts.modules():  # the modules' Python, by class, at trace time
        out = model.apply(variables, ids, method=method)
    if streams:
        hidden, counters, further = out
        loss, accuracy = _shared_head_loss(
            model, variables, hidden, ids, 1 if whole else pieces)
    elif whole:
        logits, counters = out
        loss, accuracy = next_token_loss(logits, ids)
    else:  # long rows: the head and the loss in pieces of the sequence
        hidden, counters = out
        loss, accuracy = chunked_next_token_loss(
            hidden, model.head_kernel(variables["params"]), ids, pieces)
    for name, (shift, coefficient) in streams.items():
        term, right = _shared_head_loss(
            model, variables, further[name], ids, 1 if whole else pieces,
            shift, name)
        loss = loss + coefficient * term
        counters = {**counters, name + "_loss": term,
                    name + "_token_accuracy": right}
    for name, coefficient in model.objective_terms().items():
        loss = loss + coefficient * counters[name]
    return loss, {"token_accuracy": accuracy, **counters}


def _shared_head_loss(model, variables, hidden, ids, pieces: int,
                      shift: int = 1, scope: str = "lm"):
    """(loss, accuracy) of one stream through the model's output head against
    token t + ``shift``: whole, or in ``pieces`` of the sequence."""
    kernel = model.head_kernel(variables["params"])
    if pieces > 1:
        return chunked_next_token_loss(hidden, kernel, ids, pieces, shift,
                                       scope)
    with jax.named_scope(scope + "_head"):
        logits = jnp.matmul(hidden, kernel.astype(hidden.dtype))
    return next_token_loss(logits, ids, shift, scope)


# How a counter's name ends says how it is reduced, over an update's
# micro-batches and over the chips of an expert axis alike.
_SUMMED = ("_slots", "_run", "_slots_out", "_slots_in", "_bytes_out")
_WORST = ("_max_over_mean",)


def _aux_metrics(aux) -> dict:
    """Step metrics from the micro-batches' stacked aux: the MLM objective's
    is its accuracy; the causal objective's is a dict whose counters add up
    over the update (``_SUMMED``: ``*_slots``, ``*_run``, the exchange's
    ``*_slots_out``, ``*_slots_in``, ``*_bytes_out``) or take its worst
    (``*_max_over_mean``)."""
    if not isinstance(aux, dict):
        return {"mlm_accuracy": jnp.mean(aux)}
    reduce = lambda name: (jnp.sum if name.endswith(_SUMMED) else
                           jnp.max if name.endswith(_WORST) else
                           jnp.mean)
    return {name: reduce(name)(value) for name, value in aux.items()}


def expert_axis_shards(mesh) -> int:
    """The width of ``mesh``'s expert axis (1: none, or no mesh)."""
    return 1 if mesh is None else mesh.shape.get(AXIS_EXPERT, 1)


def on_expert_axis(fn, mesh, in_specs, out_specs):
    """``fn`` under a ``shard_map`` manual over every axis of ``mesh``, each
    chip with its shards. The collectives inside are written by hand
    (``ops/moe.py exchanged_experts``, ``models/losses.py``,
    ``CausalDecoder.embed``), so nothing is checked or inserted for them
    (``check_vma=False``: a sum over the axis transposes to a sum)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _sums_over_chips(sums, param_specs, rows_over):
    """The chips' gradient sums made the update's: a tensor every chip holds
    whole gets the sum over every axis the rows are divided over; one that is
    divided over the expert axis holds the other chips' terms already (they
    crossed inside the step) and is summed over the other axes alone. Both
    over the number of chips, since each chip's loss is its own rows' mean."""
    def divided(spec) -> bool:
        return any(AXIS_EXPERT in (part if isinstance(part, tuple)
                                   else (part,)) for part in spec)

    others = tuple(a for a in rows_over if a != AXIS_EXPERT)
    chips = jax.lax.axis_size(rows_over)

    def over_chips(g, spec):
        axes = others if divided(spec) else rows_over
        return (jax.lax.psum(g, axes) if axes else g) / chips

    return jax.tree_util.tree_map(over_chips, sums, param_specs)


def _expert_axis_micro_batches(model, mesh, param_specs):
    """``(params, batch) -> (gradient sums, losses [A], aux {name: [A]})`` of
    an update's micro-batches for a model whose experts and vocabulary are
    divided over ``mesh``'s expert axis: the scan over the micro-batches of
    :func:`make_train_step`, inside ONE ``shard_map``. Every chip runs the
    model (``CausalDecoder.on_expert_axis``) on its own rows and takes the
    gradient of its own rows' loss; what crosses the axis inside (the
    embedding's look-up, the slots, the head) carries the other chips' terms
    to the tensors that are divided, so their gradient sums are whole. The
    tensors every chip holds whole (attention, norms, routers) get each
    chip's rows' part, and those are summed over the axis ONCE an update,
    after the last micro-batch (:func:`_sums_over_chips`). The objective is
    the mean over every chip's rows: sums over the chips, over their number.
    Losses and counters are reduced over the chips as ``_aux_metrics`` reduces
    them over the update.
    """
    shards = mesh.shape[AXIS_EXPERT]
    rows_over = tuple(a for a in BATCH_AXES if mesh.shape[a] > 1)
    local = model.on_expert_axis(AXIS_EXPERT, shards)

    def per_chip(params, batch):
        def body(sums, mb):
            (loss, aux), grads = jax.value_and_grad(
                lambda p: _apply_causal_lm_loss(local, {"params": p}, mb),
                has_aux=True)(params)
            with jax.named_scope("grad_accumulate"):
                sums = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(a.dtype), sums, grads)
            return sums, (loss, aux)

        sums, (losses, aux) = _scan_micro_batches(
            body, jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params), batch)
        with jax.named_scope("grad_sync"):
            sums = _sums_over_chips(sums, param_specs, rows_over)
        with jax.named_scope("step_metrics"):
            over = lambda name: (
                jax.lax.psum if name.endswith(_SUMMED) else
                jax.lax.pmax if name.endswith(_WORST) else jax.lax.pmean)
            aux = {name: over(name)(value, rows_over)
                   for name, value in aux.items()}
            return sums, jax.lax.pmean(losses, rows_over), aux

    return on_expert_axis(
        per_chip, mesh, (param_specs, {"input_ids": P(None, BATCH_AXES)}),
        (param_specs, P(), P()))


def _real_tokens(batch):
    """Non-pad tokens of the update: rows of a causal objective are full."""
    if "input_mask" in batch:
        return jnp.sum(batch["input_mask"]).astype(jnp.float32)
    return jnp.asarray(batch["input_ids"].size, jnp.float32)


def make_kfac_fns(
    model_tapped,
    next_sentence: bool = True,
    max_pred_per_seq: Optional[int] = None,
):
    """(apply_loss, tap_shape_fn) for :class:`bert_pytorch_tpu.optim.KFAC`,
    sharing the pretraining loss with the train step.

    ``model_tapped`` must be the same architecture built with
    ``kfac_tap=True``. Remat guidance depends on where the taps fire:
    the decoupled stats pass runs a small batch where ``remat='none'``
    suffices, while the fused in-train capture
    (``make_train_step(kfac_capture_model=...)``) should keep the main
    model's remat so microbatch 0's tapped backward fits the same memory
    budget (taps compose with ``nn.remat``).
    """

    def apply_loss(params, taps, mb, rng):
        loss, _, mutated = _apply_pretraining_loss(
            model_tapped, {"params": params, "kfac_taps": taps}, mb, rng,
            next_sentence, max_pred_per_seq, mutable=["kfac_a"]
        )
        return loss, mutated["kfac_a"]

    def tap_shape_fn(params, mb, rng):
        def f(p, mb_):
            _, _, mutated = _apply_pretraining_loss(
                model_tapped, {"params": p}, mb_, rng,
                next_sentence, max_pred_per_seq,
                mutable=["kfac_taps", "kfac_a"]
            )
            return mutated["kfac_taps"], mutated["kfac_a"]

        return jax.eval_shape(f, params, mb)

    return apply_loss, tap_shape_fn


def _scan_micro_batches(body, init, batch):
    with jax.named_scope("micro_batches"):
        return jax.lax.scan(body, init, batch)


def _jit_train_step(step_fn, shardings, batch_shardings_, kfac,
                    kfac_shardings, fused_kfac=False):
    """Shared jit dispatch for the train-step builders: donated state,
    declared shardings, and the optional kfac_state third argument.
    ``fused_kfac`` marks the in-train factor-capture step, which returns
    (and therefore donates) the kfac_state as a third output."""
    donate = (0, 2) if fused_kfac else (0,)
    if shardings is None:
        return jax.jit(step_fn, donate_argnums=donate)
    in_shardings = (shardings, batch_shardings_)
    if kfac is not None:
        in_shardings = in_shardings + (kfac_shardings,)
    out_shardings = (
        (shardings, None, kfac_shardings) if fused_kfac
        else (shardings, None))
    return jax.jit(
        step_fn,
        donate_argnums=donate,
        in_shardings=in_shardings,
        out_shardings=out_shardings,
    )


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    schedule: Optional[Callable] = None,
    next_sentence: bool = True,
    shardings: Optional[TrainState] = None,
    batch_shardings_: Optional[dict] = None,
    max_pred_per_seq: Optional[int] = None,
    kfac=None,
    kfac_shardings=None,
    kfac_capture_model=None,
    kfac_factor_interval: int = 1,
    kfac_inv_interval: int = 0,
    kfac_capture_microbatches: str = "first",
    loss_scale: bool = False,
    stats_every: int = 0,
    stats_phase: int = 0,
    mesh=None,
):
    """Build the jitted train step.

    ``batch`` is a dict of arrays with a leading accumulation axis:
    input_ids/segment_ids/input_mask/masked_lm_labels [A, B, S],
    next_sentence_labels [A, B]. Returns (new_state, metrics).

    When ``max_pred_per_seq`` is set, the masked positions are extracted
    inside the jitted step and the 30k-vocab decoder runs only on those
    [B, P] positions instead of all [B, S]: same loss, ~S/P less decoder
    compute.

    When ``kfac`` (a :class:`bert_pytorch_tpu.optim.KFAC`) is given, the
    step takes a third ``kfac_state`` argument and preconditions the
    accumulated gradients before the optimizer update (the
    ``preconditioner.step()`` slot in the reference's
    ``take_optimizer_step``, run_pretraining.py:405-417). Requires
    ``schedule`` for the kl_clip learning-rate term.

    ``kfac_capture_model`` switches K-FAC to FUSED in-train factor
    capture: pass the tapped twin of ``model`` (``kfac_tap=True``, same
    dtype/remat/backend) and the step harvests Kronecker factors from
    microbatch 0's own backward pass — the reference's free hook capture
    (run_pretraining.py:320-355) — instead of the runner paying a
    separate stats forward/backward per factor update. The step then
    RETURNS the updated kfac_state: ``(state, metrics, kfac_state)``.
    Factor EMA fires when ``opt_step_count % kfac_factor_interval == 0``
    (a ``lax.cond`` — skipped steps pay no capture FLOPs). With
    ``kfac_inv_interval > 0`` the inverse recompute ALSO runs in-jit
    under a cond on due steps, ordered factors → inverses →
    precondition exactly like kfac_pytorch's ``optimizer.step()``
    (hooks during backward, due inverses, then the preconditioned
    update); with 0 the caller drives ``kfac.update_inverses`` on the
    host and preconditioning sees inverses one factor-update stale.
    ``kfac_capture_microbatches`` picks the capture source on due
    steps: ``'first'`` (default) taps microbatch 0 only — capture cost
    amortizes over the accumulation; ``'all'`` accumulates statistics
    over EVERY microbatch's backward, kfac_pytorch's exact accumulation
    semantics (its hooks fire on each micro-backward), at capture cost
    proportional to accum_steps.

    ``loss_scale=True`` is the fp16 parity mode (reference GradScaler,
    run_pretraining.py:314-318): ``tx`` must be wrapped in
    ``optim.dynamic_loss_scale``; the step multiplies the loss by the
    state's current scale before differentiating and the wrapper
    unscales, finite-checks, and skips/backs off.

    ``stats_every > 0`` splices the in-jit grad-health block
    (telemetry/model_stats.py: per-layer-group grad/param norms and
    update:weight ratios) into ``metrics["grad_health"]``, lax.cond-gated
    on the optimizer step counter so off-cadence steps pay nothing.
    ``stats_phase`` is the optimizer count at run start (resumed runs),
    aligning the due gate with the host's run-local sync cadence.
    TrainTelemetry.step_done pops and emits it.

    ``mesh``: with an ``expert`` axis wider than 1 (``--mesh ep=4``) the
    ``causal_lm`` step runs its micro-batches under a ``shard_map`` over the
    mesh (:func:`_expert_axis_micro_batches`), each chip with its shards of
    ``shardings``; the optimizer, the clipping and the norms stay outside it,
    on the divided arrays, for the compiler. Any other mesh is not read: the
    step takes its layout from ``shardings``.
    """
    # What the model is trained on: ``mlm`` (BERT: masked tokens and next
    # sentence) unless the model's family says otherwise
    # (models/decoder.py: ``causal_lm``, rows of token ids).
    objective = getattr(model, "objective", "mlm")
    if objective not in ("mlm", "causal_lm"):
        raise ValueError(f"unknown objective {objective!r}")
    if objective == "causal_lm" and kfac is not None:
        raise ValueError(
            "the causal_lm objective runs the plain first-order step (no "
            "K-FAC)")
    if kfac is not None and schedule is None:
        raise ValueError("kfac preconditioning requires a schedule")
    if kfac is not None and loss_scale:
        raise ValueError(
            "loss_scale composes with first-order optimizers only; K-FAC "
            "runs in bf16/f32 where no scaler is needed")
    if kfac_capture_model is not None and kfac is None:
        raise ValueError("kfac_capture_model requires kfac")
    fused_kfac = kfac is not None and kfac_capture_model is not None
    if fused_kfac and kfac_factor_interval < 1:
        raise ValueError(
            f"kfac_factor_interval must be >= 1, got {kfac_factor_interval}")
    if kfac_inv_interval and not fused_kfac:
        raise ValueError(
            "kfac_inv_interval (in-jit inverse updates) requires the fused "
            "capture path (kfac_capture_model); host-driven flows call "
            "kfac.update_inverses themselves")
    if kfac_capture_microbatches not in ("first", "all"):
        raise ValueError(
            f"kfac_capture_microbatches must be first|all, got "
            f"{kfac_capture_microbatches!r}")
    sharded_micro_batches = None
    if expert_axis_shards(mesh) > 1:
        if objective != "causal_lm" or loss_scale or shardings is None:
            raise ValueError(
                "an expert axis runs the causal_lm step, with shardings and "
                "without a loss scale")
        sharded_micro_batches = _expert_axis_micro_batches(
            model, mesh, jax.tree_util.tree_map(
                lambda s: s.spec, shardings.params))

    def loss_fn(params, mb, rng):
        if objective == "causal_lm":  # no dropout: rng unused
            return _apply_causal_lm_loss(model, {"params": params}, mb)
        loss, acc, _ = _apply_pretraining_loss(
            model, {"params": params}, mb, rng,
            next_sentence, max_pred_per_seq)
        return loss, acc

    def tapped_loss_fn(params, taps, mb, rng):
        # Same math as loss_fn, through the tapped twin: identical logits
        # (taps are identity in the forward), plus the mutated kfac_a
        # collection and — under grad w.r.t. taps — the per-layer G
        # factors from the _g_factor_probe backward.
        loss, acc, mutated = _apply_pretraining_loss(
            kfac_capture_model, {"params": params, "kfac_taps": taps},
            mb, rng, next_sentence, max_pred_per_seq, mutable=["kfac_a"])
        return loss, (acc, mutated["kfac_a"])

    def step_fn(state: TrainState, batch: dict, kfac_state=None):
        accum_steps = batch["input_ids"].shape[0]
        step_rng, new_rng = jax.random.split(state.rng)
        scale = state.opt_state.scale if loss_scale else None

        def scaled_loss_fn(params, mb, rng):
            loss, acc = loss_fn(params, mb, rng)
            return loss * scale, (loss, acc)

        def body(carry, mb):
            grads_acc, rng = carry
            rng, sub = jax.random.split(rng)
            if loss_scale:
                (_, (loss, acc)), grads = jax.value_and_grad(
                    scaled_loss_fn, has_aux=True)(state.params, mb, sub)
            else:
                (loss, acc), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params, mb, sub)
            with jax.named_scope("grad_accumulate"):
                grads_acc = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(a.dtype), grads_acc, grads
                )
            return (grads_acc, rng), (loss, acc)

        zero_grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params
        )
        if fused_kfac and kfac_capture_microbatches == "all":
            # kfac_pytorch accumulation semantics: every microbatch's
            # backward contributes statistics (its hooks fire per
            # micro-backward); the scan carries factor-stat accumulators
            # alongside the gradient accumulator.
            rows = (accum_steps * batch["input_ids"].shape[1]
                    * batch["input_ids"].shape[2])
            mb_scale = kfac.grad_scale(
                jax.tree_util.tree_map(lambda v: v[0], batch))

            def tapped_body(carry, mb):
                grads_acc, gtap_acc, astat_acc, rng = carry
                rng, sub = jax.random.split(rng)
                (loss, (acc, astats)), (grads, gtaps) = jax.value_and_grad(
                    tapped_loss_fn, argnums=(0, 1), has_aux=True
                )(state.params, kfac.zero_taps(), mb, sub)
                with jax.named_scope("grad_accumulate"):
                    grads_acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(a.dtype), grads_acc, grads)
                    gtap_acc = jax.tree_util.tree_map(
                        jnp.add, gtap_acc, gtaps)
                    astat_acc = jax.tree_util.tree_map(
                        jnp.add, astat_acc, astats)
                return (grads_acc, gtap_acc, astat_acc, rng), (loss, acc)

            def all_capture(ks):
                (grads, gtap_sum, astat_sum, _), (losses, accs) = (
                    _scan_micro_batches(
                        tapped_body,
                        (zero_grads, kfac.zero_taps(), kfac.zero_astats(),
                         step_rng),
                        batch))
                ks = kfac.ema_factors(ks, astat_sum, gtap_sum, rows, mb_scale)
                return losses, accs, grads, ks

            def all_plain(ks):
                (grads, _), (losses, accs) = _scan_micro_batches(
                    body, (zero_grads, step_rng), batch)
                return losses, accs, grads, ks

            if kfac_factor_interval == 1:
                losses, accs, grads, kfac_state = all_capture(kfac_state)
            else:
                due = (opt_step_count(state.opt_state)
                       % kfac_factor_interval) == 0
                losses, accs, grads, kfac_state = jax.lax.cond(
                    due, all_capture, all_plain, kfac_state)
        elif fused_kfac:
            # 'first': microbatch 0 unrolls out of the scan so its
            # backward can be the tapped one; the rng split chain matches
            # body's exactly, so microbatch i sees the same dropout rng
            # either way.
            mb0 = jax.tree_util.tree_map(lambda v: v[0], batch)
            rng_rest, sub0 = jax.random.split(step_rng)
            rows = mb0["input_ids"].shape[0] * mb0["input_ids"].shape[1]

            def mb0_capture(ks):
                (loss0, (acc0, astats)), (g0, gtaps) = jax.value_and_grad(
                    tapped_loss_fn, argnums=(0, 1), has_aux=True
                )(state.params, kfac.zero_taps(), mb0, sub0)
                ks = kfac.ema_factors(
                    ks, astats, gtaps, rows, kfac.grad_scale(mb0))
                return loss0, acc0, g0, ks

            def mb0_plain(ks):
                (loss0, acc0), g0 = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params, mb0, sub0)
                return loss0, acc0, g0, ks

            if kfac_factor_interval == 1:
                loss0, acc0, grads0, kfac_state = mb0_capture(kfac_state)
            else:
                due = (opt_step_count(state.opt_state)
                       % kfac_factor_interval) == 0
                loss0, acc0, grads0, kfac_state = jax.lax.cond(
                    due, mb0_capture, mb0_plain, kfac_state)
            grads0 = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32), grads0)
            if accum_steps > 1:
                rest = jax.tree_util.tree_map(lambda v: v[1:], batch)
                (grads, _), (losses_r, accs_r) = _scan_micro_batches(
                    body, (grads0, rng_rest), rest
                )
                losses = jnp.concatenate([loss0[None], losses_r])
                accs = jnp.concatenate([acc0[None], accs_r])
            else:
                grads = grads0
                losses = loss0[None]
                accs = acc0[None]
        elif sharded_micro_batches is not None:
            grads, losses, accs = sharded_micro_batches(state.params, batch)
        else:
            (grads, _), (losses, accs) = _scan_micro_batches(
                body, (zero_grads, step_rng), batch
            )
        if fused_kfac and kfac_inv_interval:
            # Reference ordering: inverse-due steps rebuild the inverses
            # from the factors THIS step just captured, before
            # preconditioning.
            inv_due = (opt_step_count(state.opt_state)
                       % kfac_inv_interval) == 0
            kfac_state = jax.lax.cond(
                inv_due, kfac.inverse_factors, lambda s: s, kfac_state)
        with jax.named_scope("optimizer"), trace_parts.optimizer():
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)

            if kfac is not None:
                grads = kfac.precondition(
                    kfac_state, grads, schedule(opt_step_count(state.opt_state))
                )
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        with jax.named_scope("step_metrics"):
            # grads carry the loss scale in fp16 mode; report the true norm
            gnorm = (global_norm(grads) / scale if loss_scale
                     else global_norm(grads))
            metrics = {
                "loss": jnp.mean(losses),
                **_aux_metrics(accs),
                "grad_norm": gnorm,
                # Failure sentinel (telemetry/sentinels.py): one scalar the host
                # can fetch for free alongside the loss. isfinite(sum) catches a
                # non-finite loss in ANY microbatch, not just the mean.
                "finite": (jnp.isfinite(jnp.sum(losses))
                           & jnp.isfinite(gnorm)).astype(jnp.float32),
                # Padding-aware throughput accounting (docs/telemetry.md): the
                # non-pad token count this step actually trained on. Telemetry
                # pops it on the sync cadence (never an extra device fetch) and
                # reports padding_efficiency / real-token throughput; with
                # sequence packing this approaches the full batch token budget.
                "real_tokens": _real_tokens(batch),
            }
            if loss_scale:
                metrics["loss_scale"] = scale
            if schedule is not None:
                metrics["learning_rate"] = schedule(opt_step_count(state.opt_state))
            if stats_every:
                from bert_pytorch_tpu.telemetry import model_stats

                # fp16: skipped overflow steps do NOT advance the inner
                # optimizer count (optim/transforms.py dynamic_loss_scale),
                # so a count-based gate would drift off the host's
                # step-index sync cadence after the first skip and the
                # records would silently stop. Compute every step instead —
                # the O(params) reduction is noise next to the step's
                # O(params x tokens) — and let the sync cadence sample.
                metrics["grad_health"] = model_stats.gated_grad_health(
                    state.params, grads, updates,
                    opt_step_count(state.opt_state),
                    1 if loss_scale else stats_every,
                    grad_scale=scale if loss_scale else None,
                    phase=stats_phase)
        new_state = TrainState(params=params, opt_state=opt_state, rng=new_rng)
        if fused_kfac:
            return new_state, metrics, kfac_state
        return new_state, metrics

    return _jit_train_step(
        step_fn, shardings, batch_shardings_, kfac, kfac_shardings,
        fused_kfac=fused_kfac)


def make_pp_train_step(
    model,
    tx: optax.GradientTransformation,
    mesh,
    schedule: Optional[Callable] = None,
    next_sentence: bool = True,
    shardings: Optional[TrainState] = None,
    batch_shardings_: Optional[dict] = None,
    max_pred_per_seq: Optional[int] = None,
    kfac=None,
    kfac_shardings=None,
    stats_every: int = 0,
    stats_phase: int = 0,
):
    """Train step with the encoder executed as a GPipe pipeline over the
    mesh 'pipe' axis (parallel/pipeline.py).

    When ``kfac`` is given the step takes a third ``kfac_state`` argument
    and preconditions the pipeline-accumulated gradients before the
    optimizer update, exactly as in :func:`make_train_step` — the
    preconditioner is a pure per-layer solve over the stacked factors, so
    it composes with the pipe-sharded gradient layout (XLA reshards). The
    factor/inverse cadence runs OUTSIDE this step on the tapped non-pp
    model (the runner's pattern), which under automatic sharding reads the
    pipe-sharded params directly.

    The accumulation microbatches ([A, B, ...] stacked batch) ARE the
    pipeline microbatches: instead of ``lax.scan``-ing them sequentially
    (make_train_step), all A flow through the P pipeline stages concurrently
    and autodiff reverses the schedule for the backward — gradient
    accumulation falls out of the sum over microbatch losses. Embeddings and
    heads (<5% of BERT-large FLOPs) run replicated across stages on the
    flattened [A*B, ...] batch rather than being placed on the first/last
    stage.

    The forward reassembles ``BertForPreTraining.__call__`` (models/bert.py)
    from its submodules functionally, because the encoder's stacked layer
    params must be driven per stage-block; the module definitions and the
    parameter tree are shared with the non-pp path, so checkpoints are
    interchangeable between strategies.
    """
    from bert_pytorch_tpu.models.bert import (
        BertEmbeddings,
        BertLayer,
        BertLMPredictionHead,
        BertPooler,
        bert_normal_init,
    )
    from bert_pytorch_tpu.ops.attention import make_attention_bias
    from bert_pytorch_tpu.parallel.pipeline import gpipe, stage_layer_count

    cfg = model.config
    n_stages = mesh.shape[AXIS_PIPE]
    stage_layer_count(cfg.num_hidden_layers, n_stages)  # validate divisibility

    # pp x sp: with a 'seq' mesh axis the pipeline's shard_map goes manual
    # over {pipe, seq} and the layers run the manual ring-attention body
    # (ops/attention.py backend='ring_manual') — K/V rotate over 'seq'
    # inside the SAME manual region, sidestepping the nested-manual
    # backward Shardy rejects (parallel/pipeline.py docstring).
    seq_manual = mesh.shape.get(AXIS_SEQ, 1) > 1
    layer_backend = "ring_manual" if seq_manual else model.attention_backend

    emb_mod = BertEmbeddings(cfg, dtype=model.dtype)
    layer_mod = BertLayer(
        cfg, dtype=model.dtype, attention_backend=layer_backend
    )
    head_mod = BertLMPredictionHead(cfg, dtype=model.dtype)
    pooler_mod = BertPooler(cfg, dtype=model.dtype) if next_sentence else None
    nsp_mod = (
        nn.Dense(
            2,
            dtype=model.dtype,
            param_dtype=jnp.float32,
            kernel_init=bert_normal_init(cfg.initializer_range),
        )
        if next_sentence
        else None
    )

    policy = remat_policy(model.remat)

    def loss_fn(params, batch, rng):
        n_mb, b, seq = batch["input_ids"].shape
        # Packed rows (data/packing.py) carry the extra arrays; their
        # block-diagonal attention bias replaces the [.., 1, S] padding
        # bias and already encodes the no-cross-contamination mask, so the
        # stages need no extra plumbing. packed x seq-sharding is rejected
        # at spec validation (parallel/mesh.py MeshSpec.validate).
        packed = "sequence_ids" in batch
        if seq_manual and seq % mesh.shape[AXIS_SEQ] != 0:
            raise ValueError(
                f"pp x sp: sequence length {seq} is not divisible by the "
                f"mesh 'seq' axis ({mesh.shape[AXIS_SEQ]})")
        if seq_manual and packed:
            raise ValueError(
                "packed batches cannot shard the sequence axis "
                "(MeshSpec.validate(packed=True) rejects seq>1)")
        # Two streams: embeddings dropout + the per-(layer, microbatch)
        # folding inside the pipeline. The heads are dropout-free.
        emb_rng, pipe_rng = jax.random.split(rng)

        flat = lambda a: a.reshape((n_mb * b,) + a.shape[2:])
        seq_ids = flat(batch["sequence_ids"]) if packed else None
        hidden = emb_mod.apply(
            {"params": params["bert"]["embeddings"]},
            flat(batch["input_ids"]),
            flat(batch["segment_ids"]),
            False,  # deterministic
            seq_ids,
            rngs={"dropout": emb_rng},
        )
        hidden = hidden.reshape(n_mb, b, seq, -1)
        bias = make_attention_bias(flat(batch["input_mask"]), dtype=jnp.float32,
                                   sequence_ids=seq_ids)
        # Unpacked: [A*B, 1, 1, S] -> [A, B, 1, 1, S]; packed
        # block-diagonal: [A*B, 1, S, S] -> [A, B, 1, S, S].
        bias = bias.reshape((n_mb, b) + bias.shape[1:])

        def apply_one(carry, lp, key, bias_mb):
            out, _ = layer_mod.apply(
                {"params": lp}, carry, bias_mb, False, rngs={"dropout": key}
            )
            return out

        if policy is not None:
            apply_one = jax.checkpoint(
                apply_one, policy=policy, prevent_cse=False
            )

        def stage_fn(local_params, h, bias_mb, rng_rep, stage, mb):
            n_local = jax.tree_util.tree_leaves(local_params)[0].shape[0]
            if seq_manual:
                # Decorrelate the hidden-state dropouts across sequence
                # shards: with a replicated key each shard would draw the
                # IDENTICAL mask for its local block of tokens. (The
                # attention-probability dropout decorrelates itself —
                # _ring_shard folds in the seq index too.)
                rng_rep = jax.random.fold_in(
                    rng_rep, jax.lax.axis_index(AXIS_SEQ))

            def body(carry, xs):
                lp, j = xs
                key = jax.random.fold_in(
                    jax.random.fold_in(rng_rep, stage * n_local + j), mb
                )
                return apply_one(carry, lp, key, bias_mb), None

            h, _ = jax.lax.scan(
                body, h, (local_params, jnp.arange(n_local, dtype=jnp.int32))
            )
            return h

        hidden = gpipe(
            stage_fn,
            params["bert"]["encoder"]["layers"],
            hidden,
            bias,
            mesh,
            replicated=pipe_rng,
            seq_axis=AXIS_SEQ if seq_manual else None,
            x_seq_dim=2,
            consts_seq_dims=4 if seq_manual else None,
        )

        seq_out = hidden.reshape(n_mb * b, seq, -1)
        labels, masked_positions = _mlm_positions(
            flat(batch["masked_lm_labels"]), max_pred_per_seq
        )
        if masked_positions is not None:
            onehot = jax.nn.one_hot(masked_positions, seq, dtype=model.dtype)
            seq_out = jnp.einsum("bps,bsh->bph", onehot, seq_out)
        word_embedding = params["bert"]["embeddings"]["word_embeddings"][
            "embedding"
        ]
        mlm_logits = head_mod.apply(
            {"params": params["predictions"]}, seq_out, word_embedding
        )
        nsp_logits = None
        nsp_labels = None
        if next_sentence:
            # Packed rows pool at each packed sequence's own [CLS] offset
            # ([A*B, K, hidden]); empty pack slots are neutralized by
            # their -1 NSP label (same contract as the non-pp path).
            pooled = pooler_mod.apply(
                {"params": params["bert"]["pooler"]},
                hidden.reshape(n_mb * b, seq, -1),
                flat(batch["cls_positions"]) if packed else None,
            )
            nsp_logits = nsp_mod.apply(
                {"params": params["seq_relationship"]}, pooled
            )
            nsp_labels = batch["next_sentence_labels"]
        # Per-MICROBATCH loss, then mean — the accumulation semantics of
        # make_train_step (and the reference's loss/accumulation_steps,
        # run_pretraining.py:445): each microbatch's masked-token mean gets
        # equal weight regardless of how many positions were masked in it.
        unflat = lambda a: a.reshape((n_mb, b) + a.shape[1:])
        losses = jax.vmap(pretraining_loss)(
            unflat(mlm_logits),
            unflat(nsp_logits) if next_sentence else None,
            unflat(labels),
            nsp_labels if next_sentence else None,
        )
        accs = jax.vmap(mlm_accuracy)(unflat(mlm_logits), unflat(labels))
        return jnp.mean(losses), jnp.mean(accs)

    if kfac is not None and schedule is None:
        raise ValueError("kfac preconditioning requires a schedule")

    def step_fn(state: TrainState, batch: dict, kfac_state=None):
        step_rng, new_rng = jax.random.split(state.rng)
        with trace_parts.modules():  # the stages' *.apply calls
            (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch, step_rng
            )
        with jax.named_scope("optimizer"), trace_parts.optimizer():
            if kfac is not None:
                grads = kfac.precondition(
                    kfac_state, grads, schedule(opt_step_count(state.opt_state))
                )
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
        with jax.named_scope("step_metrics"):
            gnorm = global_norm(grads)
            metrics = {
                "loss": loss,
                "mlm_accuracy": acc,
                "grad_norm": gnorm,
                # Failure sentinel (telemetry/sentinels.py), same contract as
                # make_train_step: a NaN in any microbatch propagates into the
                # mean loss, so isfinite(loss) covers them all.
                "finite": (jnp.isfinite(loss)
                           & jnp.isfinite(gnorm)).astype(jnp.float32),
                # Padding-aware accounting, same contract as make_train_step.
                "real_tokens": jnp.sum(batch["input_mask"]).astype(jnp.float32),
            }
            if schedule is not None:
                metrics["learning_rate"] = schedule(opt_step_count(state.opt_state))
            if stats_every:
                # Same grad-health block as make_train_step; the norms are
                # pure per-leaf reductions, so XLA reshards them over the
                # pipe-sharded gradient layout for free.
                from bert_pytorch_tpu.telemetry import model_stats

                metrics["grad_health"] = model_stats.gated_grad_health(
                    state.params, grads, updates,
                    opt_step_count(state.opt_state), stats_every,
                    phase=stats_phase)
        return TrainState(params=params, opt_state=opt_state, rng=new_rng), metrics

    return _jit_train_step(
        step_fn, shardings, batch_shardings_, kfac, kfac_shardings)


def make_eval_step(model, next_sentence: bool = True):
    """Deterministic forward + loss for held-out evaluation. Handles
    packed validation batches the same way the train step does (the extra
    keys select the block-diagonal path)."""

    def eval_fn(params, batch):
        mlm_logits, nsp_logits = model.apply(
            {"params": params},
            batch["input_ids"],
            batch["segment_ids"],
            batch["input_mask"],
            True,  # deterministic
            None,  # masked_positions
            batch.get("sequence_ids"),
            batch.get("cls_positions"),
        )
        loss = pretraining_loss(
            mlm_logits,
            nsp_logits if next_sentence else None,
            batch["masked_lm_labels"],
            batch["next_sentence_labels"] if next_sentence else None,
        )
        return loss, mlm_accuracy(mlm_logits, batch["masked_lm_labels"])

    return jax.jit(eval_fn)


def check_batch_process_locality(mesh: Mesh) -> None:
    """Raise if any batch shard's replica set spans processes.

    The multi-host input path feeds each process ITS OWN loader slice
    (per-rank DataLoaders + ``make_array_from_process_local_data``). That
    is only correct when every (data, fsdp) batch shard — including its
    replicas over the pipe/seq/model axes — lives within one process;
    otherwise two processes would supply DIFFERENT host data for the same
    global rows and training silently diverges across ranks. The default
    id-ordered mesh satisfies this whenever pipe*seq*model divides the
    per-host device count (model parallelism inside the host, data across
    hosts — the layout you want on ICI anyway); reordered meshes that
    stripe pipe/model across hosts need a replicated input feed instead.
    """
    if jax.process_count() == 1:
        return
    devs = mesh.devices  # [data, fsdp, pipe, seq, model]
    d, f = devs.shape[0], devs.shape[1]
    for di in range(d):
        for fi in range(f):
            procs = {dev.process_index for dev in devs[di, fi].flat}
            if len(procs) > 1:
                raise ValueError(
                    f"batch shard (data={di}, fsdp={fi}) is replicated "
                    f"across processes {sorted(procs)} via the "
                    "pipe/seq/model axes; the per-process input pipeline "
                    "would feed it conflicting data. Keep pipe*seq*model "
                    "within one host (the default device order does this "
                    "when it divides the per-host chip count), or feed "
                    "every replica host identical batches."
                )


def put_batch(batch: dict, shardings: dict) -> dict:
    """Host numpy batch -> global sharded device arrays.

    Single-process: a device_put per array. Multi-host: each process passes
    its local slice of the global batch and
    ``make_array_from_process_local_data`` assembles the global array — the
    analog of per-rank DataLoaders feeding DDP (SURVEY §3.1).
    """
    if jax.process_count() == 1:
        # One device_put for the whole dict: a single dispatch instead of
        # one per array.
        return jax.device_put(batch, {k: shardings[k] for k in batch})
    return {
        k: jax.make_array_from_process_local_data(shardings[k], v)
        for k, v in batch.items()
    }


def device_prefetch(loader, accum_steps: int, shardings: dict,
                    depth: int = 2, start_epoch: int = 0):
    """The run's ONE feed: ``(epoch, device-resident stacked batch)``,
    staged ``depth`` ahead, epoch after epoch without end.

    A :class:`~bert_pytorch_tpu.data.device_prefetch.DevicePrefetcher`
    over :func:`~bert_pytorch_tpu.data.loader.epoch_chain` of the loader:
    a background thread stacks the microbatches and dispatches
    ``device_put`` with the step's input shardings, so the H2D transfer
    (and the per-call dispatch latency) hides behind device compute — the
    role the reference's 4 pinned-memory DataLoader workers +
    non_blocking copies play on GPU (run_pretraining.py:394-395,539).
    When the loader's epoch is exhausted that thread, not the loop, steps
    the sampler (and so the masks) into the next one and goes on: at a
    boundary the loop finds the new epoch's first batches staged. Every
    item carries the epoch its rows and masks were drawn under; the
    sampler's live ``epoch`` and ``index`` are the PRODUCER's, ahead of
    training, so a checkpoint records the epoch of the batch last trained
    and the rows trained in it (run_pretraining.py), and a resume passes
    that epoch as ``start_epoch`` over a sampler restored to that index.
    The loop's ``data_wait`` then measures only true producer stalls, and
    the staging share reports as telemetry's ``h2d_wait`` sub-phase
    (attach the returned prefetcher to TrainTelemetry). ``depth <= 0``
    stages inline on the loop thread.
    """
    from bert_pytorch_tpu.data.device_prefetch import DevicePrefetcher
    from bert_pytorch_tpu.data.loader import epoch_chain

    def stage(item):
        epoch, host = item
        return epoch, put_batch(stack_microbatches(host, accum_steps),
                                shardings)

    return DevicePrefetcher(epoch_chain(loader, start_epoch), stage=stage,
                            depth=depth)


def stack_microbatches(batch: dict, accum_steps: int) -> dict:
    """[A*B, ...] host batch -> [A, B, ...] for the scan."""
    out = {}
    for k, v in batch.items():
        if v.shape[0] % accum_steps != 0:
            raise ValueError(
                f"batch dim {v.shape[0]} not divisible by accumulation steps "
                f"{accum_steps}"
            )
        out[k] = v.reshape((accum_steps, v.shape[0] // accum_steps) + v.shape[1:])
    return out
