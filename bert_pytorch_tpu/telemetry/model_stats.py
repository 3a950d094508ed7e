"""Model-internals health: in-jit grad/param/update statistics + the
host-side divergence early-warning that consumes them.

The systems telemetry (step_timer/compile_events/sentinels) says where the
wallclock goes; this module says whether the MODEL is healthy while it
goes there. Rounds 2-4 lost runs to divergences the flat loss log only
showed after the fact: the K-FAC kl_clip mistunes and fp16 overflows all
announced themselves as a grad-norm spike (or an update:weight ratio
drifting toward 1) many steps before the loss went NaN and the
FailureSentinel's non-finite tripwire could fire.

In-jit half (:func:`gated_grad_health`, called by both pretraining step
builders and every finetune runner's inline step): per-layer-group
gradient norms, parameter norms, and update:weight ratios, reduced to a
handful of scalars INSIDE the jitted step — one elementwise
square+reduce over the trees, fused into the step program. The block is
``lax.cond``-gated on the optimizer-step counter so off-cadence steps pay
a predicate instead of the reduction, and the host only reads it on
synced steps (the ``--telemetry_sync_every`` machinery), so steady-state
steps stay fetch-free.

Layer groups follow the parameter tree: the shared ``bert`` container
splits one level deeper (``bert/embeddings``, ``bert/encoder``,
``bert/pooler``), every other top-level module (``predictions``,
``qa_outputs``, ``classifier``, ...) is one group. The ``nn.scan``-stacked
encoder additionally reports a per-layer gradient-norm vector (leading
``layers`` axis), which localises a divergence to a layer index.

Host half (:class:`DivergenceMonitor`, driven by
``TrainTelemetry.step_done``): an EMA envelope over the global grad norm
plus an absolute bound on the update:weight ratio. Violations emit
``kind="divergence"`` records and follow the existing FailureSentinel
policy: ``continue`` logs, ``abort`` raises :class:`DivergenceError`
(a :class:`~bert_pytorch_tpu.telemetry.sentinels.NonFiniteError`, so
runner-level handling is shared) after ``patience`` consecutive warned
observations.
"""

from __future__ import annotations

from typing import Callable, Optional

from bert_pytorch_tpu.telemetry.sentinels import NonFiniteError

_EPS = 1e-12


class DivergenceError(NonFiniteError):
    """Raised by the abort policy after ``patience`` consecutive
    grad-health warnings (grad-norm spike / update-ratio drift)."""


def _path_names(path):
    names = []
    for p in path:
        name = getattr(p, "key", None)
        if name is None:
            name = getattr(p, "idx", None)
        names.append(str(name))
    return names


def _group_key(path) -> str:
    """Layer-group name for one parameter path: the shared 'bert'
    container splits one level deeper; everything else groups by its
    top-level module."""
    names = _path_names(path)
    if not names:
        return "params"
    if len(names) >= 2 and names[0] == "bert":
        return f"{names[0]}/{names[1]}"
    return names[0]


def grad_health(params, grads, updates, grad_scale=None) -> dict:
    """Tree-reduced grad/param/update statistics (device scalars).

    Returns ``{"grad_norm", "param_norm", "update_ratio", "groups":
    {group: {"grad_norm", "param_norm", "update_ratio"}}}`` plus
    ``"per_layer_grad_norm"`` ([L]) when the tree has an ``nn.scan``-
    stacked ``layers`` axis. ``update_ratio`` is ||update|| / ||param||
    — the step-relative weight change LAMB/AdamW aim to keep small; a
    ratio drifting toward 1 means the optimizer is rewriting the weights
    wholesale. ``grads`` are the gradients the step applied (post-clip
    where the step clips); ``grad_scale`` divides the reported grad norms
    (the fp16 path's gradients carry the dynamic loss scale — reporting
    the scaled norm would make the spike detector see every loss-scale
    doubling as a 2x 'spike').
    """
    import jax
    import jax.numpy as jnp

    def sumsq(x):
        return jnp.sum(jnp.square(x.astype(jnp.float32)))

    g_leaves = jax.tree_util.tree_flatten_with_path(grads)[0]
    p_leaves = jax.tree_util.tree_leaves(params)
    u_leaves = jax.tree_util.tree_leaves(updates)

    groups: dict = {}
    per_layer: dict = {}
    for (path, g), p, u in zip(g_leaves, p_leaves, u_leaves):
        key = _group_key(path)
        acc = groups.setdefault(key, [0.0, 0.0, 0.0])
        acc[0] = acc[0] + sumsq(g)
        acc[1] = acc[1] + sumsq(p)
        acc[2] = acc[2] + sumsq(u)
        if "layers" in _path_names(path) and g.ndim > 0:
            # Stacked encoder: reduce every axis but the leading layer
            # axis, giving a per-layer grad-norm vector.
            vec = jnp.sum(jnp.square(g.astype(jnp.float32)),
                          axis=tuple(range(1, g.ndim)))
            dim = int(g.shape[0])
            per_layer[dim] = per_layer.get(dim, 0.0) + vec

    inv_scale = 1.0 if grad_scale is None else 1.0 / grad_scale
    out_groups = {}
    tot_g = tot_p = tot_u = 0.0
    for key, (gsq, psq, usq) in groups.items():
        tot_g, tot_p, tot_u = tot_g + gsq, tot_p + psq, tot_u + usq
        pn = jnp.sqrt(psq)
        out_groups[key] = {
            "grad_norm": jnp.sqrt(gsq) * inv_scale,
            "param_norm": pn,
            "update_ratio": jnp.sqrt(usq) / (pn + _EPS),
        }
    pn = jnp.sqrt(tot_p)
    out = {
        "grad_norm": jnp.sqrt(tot_g) * inv_scale,
        "param_norm": pn,
        "update_ratio": jnp.sqrt(tot_u) / (pn + _EPS),
        "groups": out_groups,
    }
    if len(per_layer) == 1:  # unambiguous single stacked-layer axis
        (vec,) = per_layer.values()
        out["per_layer_grad_norm"] = jnp.sqrt(vec) * inv_scale
    return out


def gated_grad_health(params, grads, updates, count, every: int,
                      grad_scale=None, phase: int = 0):
    """The in-jit grad-health block, ``lax.cond``-gated on the optimizer
    step counter: due steps (``(count - phase) % every == 0``) pay the
    tree reduction, all others a predicate + zeros. Returns None when
    ``every`` <= 0 (disabled) — callers splice the result into their
    metrics dict as ``metrics["grad_health"]``.

    ``phase`` is the optimizer count at RUN START (known when the step is
    built): the host reads the block on its own run-local 0-based sync
    cadence, so a checkpoint-resumed run whose absolute count is not a
    multiple of ``every`` would otherwise have its due steps land only on
    unsynced steps — zero records for the whole resumed run. It enters
    the program only as ``phase % every``: the value is a compile-time
    constant, so the raw resume step would make every resumed run a new
    program that no persistent-cache entry matches. Reduced, a resume from
    a multiple of the cadence (the checkpoint cadence is one, by default)
    is the very program the first run compiled.

    The ``"due"`` scalar tells the host whether the values are real; the
    host additionally only fetches on synced steps, so the cadence that
    matters end-to-end is ``lcm(every, telemetry_sync_every)`` in the
    aligned (default) configuration where both are the same knob.
    """
    import jax
    import jax.numpy as jnp

    if not every or every <= 0:
        return None

    def compute():
        return grad_health(params, grads, updates, grad_scale=grad_scale)

    due = ((count - phase % every) % every) == 0
    if every == 1:
        stats = compute()
    else:
        shapes = jax.eval_shape(compute)
        zeros = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        stats = jax.lax.cond(due, compute, lambda: zeros)
    stats["due"] = jnp.asarray(due, jnp.float32)
    return stats


def finetune_grad_health(params, grads, updates, opt_state,
                         stats_every: int, fp16_scale=None):
    """The one grad-health splice shared by the finetune runners' inline
    train steps (run_squad/glue/ner/swag) — the cadence invariants live
    HERE, not in four copies:

    * gate on the PRE-update optimizer count (``opt_state`` BEFORE
      ``tx.update``): the host reads the block on its run-local 0-based
      sync cadence, and the post-update count is off by one;
    * fp16 (``fp16_scale`` = the live loss scale): skipped overflow
      steps don't advance the count, so a count gate drifts off the
      cadence after the first skip — compute every step instead and let
      the sync cadence sample; the reported grad norms are divided by
      the scale.

    Returns the block for ``metrics["grad_health"]`` or None (disabled).
    """
    from bert_pytorch_tpu.optim.transforms import opt_step_count

    if not stats_every or stats_every <= 0:
        return None
    return gated_grad_health(
        params, grads, updates, opt_step_count(opt_state),
        1 if fp16_scale is not None else stats_every,
        grad_scale=fp16_scale)


def health_record(step: int, stats) -> dict:
    """Host-side conversion of a fetched grad-health block into one
    ``kind="grad_health"`` JSONL record (floats/lists only). The caller
    has already synced, so the fetch does not block on compute — but it
    is still one host<->device transfer per array, so pull the WHOLE
    tree in a single device_get instead of ~50 scalar round trips."""
    import jax

    stats = jax.device_get(stats)

    def f(x):
        return float(x)

    record = {
        "kind": "grad_health",
        "tag": "telemetry",
        "step": int(step),
        "grad_norm": f(stats["grad_norm"]),
        "param_norm": f(stats["param_norm"]),
        "update_ratio": f(stats["update_ratio"]),
        "groups": {
            name: {k: f(v) for k, v in vals.items()}
            for name, vals in stats["groups"].items()
        },
    }
    if "per_layer_grad_norm" in stats:
        record["per_layer_grad_norm"] = [
            round(float(v), 8) for v in stats["per_layer_grad_norm"]]
    return record


class DivergenceMonitor:
    """Host-side divergence early-warning over the grad-health stream.

    Two checks, both configurable and individually disabled by 0:

    * grad-norm spike — the observed global grad norm exceeds
      ``spike_factor`` x its own EMA (seeded over the first ``warmup``
      observations, during which no spike can fire: step-0 norms are
      legitimately wild);
    * update-ratio drift — the global update:weight ratio exceeds
      ``ratio_max`` (a per-step relative weight change of that size means
      the optimizer is rewriting the model, the signature of a blown
      learning rate or a mistuned K-FAC kl_clip).

    Warnings emit ``kind="divergence"`` records and follow the
    FailureSentinel policy: ``abort`` raises :class:`DivergenceError`
    after ``patience`` CONSECUTIVE warned observations (observations
    happen on the grad-health cadence, so real-step latency scales with
    it, same caveat as the sentinel's).
    """

    POLICIES = ("continue", "abort")

    def __init__(self, emit: Optional[Callable[[dict], None]] = None,
                 policy: str = "continue", patience: int = 3,
                 spike_factor: float = 10.0, ratio_max: float = 1.0,
                 warmup: int = 10, ema_decay: float = 0.9):
        if policy not in self.POLICIES:
            raise ValueError(
                f"divergence policy must be one of {self.POLICIES}, got "
                f"{policy!r}")
        self._emit = emit
        self.policy = policy
        self.patience = max(1, int(patience))
        self.spike_factor = float(spike_factor)
        self.ratio_max = float(ratio_max)
        self.warmup = max(1, int(warmup))
        self.ema_decay = float(ema_decay)
        self.ema = None
        self.observations = 0
        self.consecutive = 0
        self.total_warnings = 0

    def observe(self, step: int, grad_norm: float,
                update_ratio: Optional[float] = None) -> bool:
        """Feed one grad-health observation; True when healthy."""
        import math

        grad_norm = float(grad_norm)
        if not math.isfinite(grad_norm):
            return True  # the non-finite sentinel owns that signal
        warnings = []
        if (self.spike_factor and self.ema is not None
                and self.observations >= self.warmup
                and grad_norm > self.spike_factor * self.ema):
            warnings.append(("grad_norm_spike", grad_norm,
                             self.spike_factor * self.ema))
        if (self.ratio_max and update_ratio is not None
                and math.isfinite(float(update_ratio))
                and float(update_ratio) > self.ratio_max):
            warnings.append(("update_ratio_high", float(update_ratio),
                             self.ratio_max))
        if not warnings:
            # The EMA only absorbs HEALTHY observations: folding a
            # spiked norm in would raise the threshold under a
            # diverged-but-plateaued run, so it warns once and then the
            # abort policy's consecutive count can never accumulate.
            self.ema = (grad_norm if self.ema is None
                        else self.ema_decay * self.ema
                        + (1.0 - self.ema_decay) * grad_norm)
        self.observations += 1
        if not warnings:
            self.consecutive = 0
            return True
        self.consecutive += 1
        self.total_warnings += len(warnings)
        for reason, value, threshold in warnings:
            if self._emit is not None:
                self._emit({
                    "kind": "divergence",
                    "tag": "telemetry",
                    "step": int(step),
                    "reason": reason,
                    "value": round(value, 8),
                    "threshold": round(threshold, 8),
                    "consecutive": self.consecutive,
                    "policy": self.policy,
                })
        if self.policy == "abort" and self.consecutive >= self.patience:
            reason, value, threshold = warnings[0]
            raise DivergenceError(
                f"grad-health divergence warning ({reason}: {value:.4g} vs "
                f"threshold {threshold:.4g}) for {self.consecutive} "
                f"consecutive observations (last step {step}); aborting per "
                f"--sentinel_policy abort")
        return False
