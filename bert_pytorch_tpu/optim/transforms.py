"""Optimizers as optax-style gradient transformations.

TPU-native replacements for the reference's native optimizer stack
(SURVEY.md §2.3): Apex ``FusedLAMB``/``FusedAdam`` (run_pretraining.py:295,
src/optimization.py:25) and the pure-torch ``BertAdam``
(src/optimization.py:64-174). On TPU "fused" is what XLA does to any jitted
elementwise update chain — the multi-tensor-apply machinery has no analog to
build; what matters is matching the update *math* and keeping the state
checkpointable (a flat (count, mu, nu) pytree).

All three optimizers share the same state layout so checkpoints can swap
between them across phases (the reference's phase-2 surgery overwrites step
counts in place, run_pretraining.py:298-309 — see ``reset_count``).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import optax

from bert_pytorch_tpu.ops.grad_utils import global_norm

ScalarOrSchedule = Union[float, Callable]


class OptState(NamedTuple):
    count: jnp.ndarray  # int32 optimizer-step counter (drives the schedule)
    mu: optax.Params  # first moment
    nu: optax.Params  # second moment


class LossScaleState(NamedTuple):
    """fp16 dynamic-loss-scaling wrapper state (reference GradScaler
    analog, run_pretraining.py:314-318; checkpointed like its 'scaler'
    entry at :519-523 — the whole tuple rides inside the checkpoint's
    'optimizer' tree)."""

    scale: jnp.ndarray         # f32 current loss scale
    growth_count: jnp.ndarray  # i32 consecutive finite steps since growth
    inner: OptState


def _lr_at(learning_rate: ScalarOrSchedule, count):
    return learning_rate(count) if callable(learning_rate) else learning_rate


def _update_moments(grads, state, b1, b2):
    mu = jax.tree_util.tree_map(
        lambda m, g: b1 * m + (1.0 - b1) * g.astype(m.dtype), state.mu, grads
    )
    nu = jax.tree_util.tree_map(
        lambda v, g: b2 * v + (1.0 - b2) * jnp.square(g.astype(v.dtype)),
        state.nu,
        grads,
    )
    return mu, nu


def _init_moments(params):
    zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
    return (
        jax.tree_util.tree_map(zeros, params),
        jax.tree_util.tree_map(zeros, params),
    )


def _mask_tree(params, mask):
    if mask is None:
        return jax.tree_util.tree_map(lambda _: True, params)
    return mask(params) if callable(mask) else mask


def _clip_by_global_norm(grads, max_grad_norm):
    """Global-norm clipping to ``max_grad_norm`` (None or <= 0: off), under
    the ``clip`` scope: the one clipping LAMB and AdamW share."""
    if max_grad_norm is None or max_grad_norm <= 0:
        return grads
    with jax.named_scope("clip"):
        gnorm = global_norm(grads)
        gscale = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-6))
        return jax.tree_util.tree_map(lambda g: g * gscale, grads)


def lamb(
    learning_rate: ScalarOrSchedule,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.01,
    weight_decay_mask=None,
    max_grad_norm: Optional[float] = 1.0,
    bias_correction: bool = True,
    trust_clip: Optional[float] = None,
) -> optax.GradientTransformation:
    """LAMB — the large-batch optimizer of the BERT recipe.

    Semantics of Apex ``FusedLAMB`` (driven at run_pretraining.py:295 with the
    no-decay grouping of :279-286): global-norm gradient clipping to
    ``max_grad_norm``, bias-corrected Adam moments, update
    ``m̂/(√v̂+eps) + wd·p``, and a per-parameter trust ratio
    ``‖p‖/‖update‖`` scaling the learning rate (1.0 where either norm is 0).
    ``weight_decay_mask`` plays the role of the reference's two param groups.
    """

    def init(params):
        mu, nu = _init_moments(params)
        return OptState(jnp.zeros((), jnp.int32), mu, nu)

    def update(grads, state, params):
        if params is None:
            raise ValueError("lamb requires params")
        grads = _clip_by_global_norm(grads, max_grad_norm)

        with jax.named_scope("lamb"):
            mu, nu = _update_moments(grads, state, b1, b2)
            count = state.count + 1
            if bias_correction:
                c1 = 1.0 - b1 ** count.astype(jnp.float32)
                c2 = 1.0 - b2 ** count.astype(jnp.float32)
            else:
                c1 = c2 = 1.0

            decay_mask = _mask_tree(params, weight_decay_mask)
            lr = _lr_at(learning_rate, state.count)

            def param_update(m, v, p, use_decay):
                m_hat = m / c1
                v_hat = v / c2
                upd = m_hat / (jnp.sqrt(v_hat) + eps)
                if weight_decay > 0:
                    upd = upd + weight_decay * jnp.where(use_decay, 1.0, 0.0) * p.astype(
                        jnp.float32
                    )
                p_norm = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
                u_norm = jnp.sqrt(jnp.sum(jnp.square(upd)))
                ratio = jnp.where(
                    (p_norm > 0) & (u_norm > 0), p_norm / u_norm, 1.0
                )
                if trust_clip is not None:
                    ratio = jnp.minimum(ratio, trust_clip)
                return (-lr * ratio * upd).astype(p.dtype)

            updates = jax.tree_util.tree_map(param_update, mu, nu, params, decay_mask)
        return updates, OptState(count, mu, nu)

    return optax.GradientTransformation(init, update)


def adamw(
    learning_rate: ScalarOrSchedule,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.01,
    weight_decay_mask=None,
    bias_correction: bool = True,
    max_grad_norm: Optional[float] = None,
) -> optax.GradientTransformation:
    """Adam with decoupled weight decay — the Apex ``FusedAdam`` role in
    finetuning (run_squad.py:982-988, run_ner.py:243 use
    bias_correction=False; the default here is True). ``max_grad_norm``
    clips by the global norm first, as :func:`lamb` does (off by default:
    the finetuning runners never clipped)."""

    def init(params):
        mu, nu = _init_moments(params)
        return OptState(jnp.zeros((), jnp.int32), mu, nu)

    def update(grads, state, params):
        grads = _clip_by_global_norm(grads, max_grad_norm)
        mu, nu = _update_moments(grads, state, b1, b2)
        count = state.count + 1
        if bias_correction:
            c1 = 1.0 - b1 ** count.astype(jnp.float32)
            c2 = 1.0 - b2 ** count.astype(jnp.float32)
        else:
            c1 = c2 = 1.0
        decay_mask = _mask_tree(params, weight_decay_mask)
        lr = _lr_at(learning_rate, state.count)

        def param_update(m, v, p, use_decay):
            upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if weight_decay > 0:
                upd = upd + weight_decay * jnp.where(use_decay, 1.0, 0.0) * p.astype(
                    jnp.float32
                )
            return (-lr * upd).astype(p.dtype)

        updates = jax.tree_util.tree_map(param_update, mu, nu, params, decay_mask)
        return updates, OptState(count, mu, nu)

    return optax.GradientTransformation(init, update)


def bert_adam(
    learning_rate: float,
    schedule: str = "warmup_linear",
    warmup: float = -1.0,
    t_total: int = -1,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-6,
    weight_decay: float = 0.01,
    weight_decay_mask=None,
    max_grad_norm: float = 1.0,
) -> optax.GradientTransformation:
    """``BertAdam`` — Adam with the BERT weight-decay fix, schedule computed
    *inside* the optimizer, no bias correction, per-parameter grad clipping.

    Parity with src/optimization.py:64-174: lr at step t is
    ``base * schedule_fct(t/t_total, warmup)`` evaluated with the pre-update
    step count (optimization.py:163-170), clipping is per-parameter
    ``clip_grad_norm_(p, max_grad_norm)`` (optimization.py:144-145), and the
    decayed update is ``m/(√v+eps) + wd·p`` with no bias correction.
    Used by the fp32 SQuAD path (run_squad.py:999-1002).
    """
    from bert_pytorch_tpu.optim.schedules import (
        warmup_constant_schedule,
        warmup_cosine_schedule,
        warmup_linear_schedule,
        warmup_poly_schedule,
    )

    factories = {
        "warmup_linear": warmup_linear_schedule,
        "warmup_cosine": warmup_cosine_schedule,
        "warmup_constant": warmup_constant_schedule,
        "warmup_poly": warmup_poly_schedule,
    }
    if schedule not in factories:
        raise ValueError(f"Invalid schedule parameter: {schedule}")
    if t_total != -1:
        # offset=0: BertAdam reads state['step'] before incrementing it.
        sched = factories[schedule](learning_rate, warmup, t_total, offset=0)
    else:
        sched = lambda count: jnp.asarray(learning_rate, jnp.float32)

    def init(params):
        mu, nu = _init_moments(params)
        return OptState(jnp.zeros((), jnp.int32), mu, nu)

    def update(grads, state, params):
        # Per-parameter clipping (optimization.py:144-145).
        if max_grad_norm > 0:

            def clip(g):
                n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                return g * jnp.minimum(1.0, max_grad_norm / (n + 1e-6)).astype(
                    g.dtype
                )

            grads = jax.tree_util.tree_map(clip, grads)
        mu, nu = _update_moments(grads, state, b1, b2)
        decay_mask = _mask_tree(params, weight_decay_mask)
        lr = sched(state.count)

        def param_update(m, v, p, use_decay):
            upd = m / (jnp.sqrt(v) + eps)
            if weight_decay > 0:
                upd = upd + weight_decay * jnp.where(use_decay, 1.0, 0.0) * p.astype(
                    jnp.float32
                )
            return (-lr * upd).astype(p.dtype)

        updates = jax.tree_util.tree_map(param_update, mu, nu, params, decay_mask)
        return updates, OptState(state.count + 1, mu, nu)

    return optax.GradientTransformation(init, update)


def no_decay_mask(params) -> optax.Params:
    """True where weight decay applies. The analog of the reference's no-decay
    param grouping (run_pretraining.py:279-286: names containing bias/gamma/
    beta/LayerNorm are excluded) — here: any 'bias' leaf and every LayerNorm
    parameter ('scale' lives only in LayerNorm modules). Of the
    ``nemotron_h`` family (models/nemotron_h.py) also every ``*_bias`` and
    ``*_scale`` leaf (the convolution's bias, ``dt_bias``, the gated norm's
    scale, the router's correction buffer) and the state-space mixer's
    ``A_log`` and ``D``, as Mamba-2's own recipe exempts them; of the
    ``phi4flash`` family (models/phi4flash.py) also the differential
    attention's four ``lambda_*`` vectors; of the ``qwen3_next`` family
    (models/qwen3_next.py) also the shared expert's gate vector
    ``shared_gate`` (its ``A_log``, ``dt_bias`` and norm scales go by the
    names above); of the ``joyai_llm_flash`` family (models/joyai.py) the two
    latent norms, the multi-token-prediction module's ``enorm`` and ``hnorm``
    and the router's correction buffer go by the names above too."""
    import flax.traverse_util as traverse_util

    flat = traverse_util.flatten_dict(params)
    mask = {
        path: not (
            path[-1] in ("bias", "scale", "A_log", "D", "shared_gate")
            or path[-1].endswith(("_bias", "_scale"))
            or path[-1].startswith("lambda_")
            or any("layer_norm" in part for part in path)
        )
        for path in flat
    }
    return traverse_util.unflatten_dict(mask)


def reset_count(state, count: int):
    """Phase-switch surgery: overwrite the optimizer step counter, keeping
    moments — the analog of rewriting 'step'/'t_total'/'warmup'/'lr' in the
    loaded checkpoint (run_pretraining.py:298-309). t_total/warmup/lr live in
    the schedule closure here and are rebuilt from the new phase config.
    A loss-scaled (fp16) state keeps its scale across the phase switch,
    exactly like the reference's GradScaler surviving the surgery."""
    if isinstance(state, LossScaleState):
        return state._replace(inner=reset_count(state.inner, count))
    return OptState(jnp.asarray(count, jnp.int32), state.mu, state.nu)


def opt_step_count(state):
    """The optimizer-step counter, whether or not the state is wrapped in
    a :class:`LossScaleState` (fp16 mode)."""
    if isinstance(state, LossScaleState):
        return state.inner.count
    return state.count


def dynamic_loss_scale(
    tx: optax.GradientTransformation,
    init_scale: float = 2.0 ** 16,
    growth_factor: float = 2.0,
    backoff_factor: float = 0.5,
    growth_interval: int = 2000,
) -> optax.GradientTransformation:
    """Wrap ``tx`` with torch.cuda.amp.GradScaler semantics for fp16.

    The caller multiplies the LOSS by the current scale (read it off the
    state with ``state.scale``) before differentiating; this transform
    receives the scaled gradients, unscales them, and:

    - finite grads: applies the inner update; after ``growth_interval``
      consecutive finite steps the scale doubles;
    - any inf/nan: the step is SKIPPED (zero updates, inner state kept,
      its count not incremented) and the scale is halved.

    bf16 needs none of this (same exponent range as f32) — the wrapper
    exists as the reference-parity fp16 mode (SURVEY.md §2.3 "keep
    optional fp16+scaler for parity testing"; reference
    run_pretraining.py:314-318, 424-434). Defaults match
    ``torch.cuda.amp.GradScaler()``: init 2**16, growth 2x / backoff 0.5x,
    growth interval 2000.
    """

    def init(params):
        return LossScaleState(
            scale=jnp.asarray(init_scale, jnp.float32),
            growth_count=jnp.asarray(0, jnp.int32),
            inner=tx.init(params),
        )

    def update(grads, state, params=None):
        inv = (1.0 / state.scale).astype(jnp.float32)
        grads = jax.tree_util.tree_map(
            lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype), grads)
        finite = jnp.asarray(True)
        for g in jax.tree_util.tree_leaves(grads):
            finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
        updates, inner_new = tx.update(grads, state.inner, params)
        updates = jax.tree_util.tree_map(
            lambda u: jnp.where(finite, u, jnp.zeros_like(u)), updates)
        inner = jax.tree_util.tree_map(
            lambda n, o: jnp.where(finite, n, o), inner_new, state.inner)
        growth_count = jnp.where(finite, state.growth_count + 1, 0)
        grew = growth_count >= growth_interval
        scale = jnp.where(
            finite,
            jnp.where(grew, state.scale * growth_factor, state.scale),
            state.scale * backoff_factor,
        )
        growth_count = jnp.where(grew, 0, growth_count)
        return updates, LossScaleState(scale, growth_count, inner)

    return optax.GradientTransformation(init, update)
