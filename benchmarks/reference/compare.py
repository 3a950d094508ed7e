"""The comparison that decides ``correct`` for a training cell.

Numbers, each with a limit of its own (the cell's mix file holds the limits,
PERF.md the readings they were set from):

* ``loss_gap_first``: |program - reference| loss of the first update, taken
  at the seeded weights. Seeded weights give ln V + ln 2 in any precision, so
  this is held against a part of the batch being left out, not against
  precision.
* ``loss_gap_later``: the largest such gap over the later followed updates.
  After one LAMB update from seeded weights the two runs stand at parameters
  that differ in the signs of their smallest gradients, so this swings by
  some hundredths; it is held against a loss that has left the rails.
* ``grad_global_norm_gap``: |program - reference| / reference of the first
  update's gradient norm before clipping.
* ``grad_norm_gap_worst_leaf``: the first gradient as the optimizer got it
  (worked out from its first moment and the clipping the step's own gradient
  norm implies; before clipping, because the clip couples every tensor to the
  NSP head's badly conditioned share of the norm), per tensor (per layer for a stacked
  tensor): the gap between the two NORMS, not the norm of a difference, over
  the reference's norm of that tensor or of the median tensor, whichever is
  larger; the worst tensor decides.
* ``delta_norm_gap_worst_leaf``: the same for the parameters' change over the
  followed updates, by the tensor as the program stores it (the encoder's
  layers of one kind are one stacked tensor with one trust ratio), held
  against a step that returns its state unchanged. Tensors whose reference
  gradient is all but zero (under 1e-3 of the median: the key bias, which
  softmax cancels exactly) are left out here: LAMB divides their rounding
  noise by its own size, so their change is noise in any precision.
* ``head_grad_rel_diff``: the norm of the DIFFERENCE of the first gradients
  of the MLM head's transform (dense, bias, LayerNorm), over the reference's
  norm. The head sees the last encoder layer's output, where the forward
  error of all the layers has gathered, and not the long backward pass: of
  all tensors it is the steadiest from seed to seed and the one that parts
  the stated precision from the step below (PERF.md has the readings). This
  is the number the control fails.
* ``all_grad_rel_diff``: the same over every tensor together (all the
  parameters' first gradient): the backward pass of every layer is in it.
* ``feed_faults``: rows fed to the step that are not the generated rows under
  a legal mask. Exact: the limit is 0.
"""

from __future__ import annotations

import numpy as np

DEAD_GRADIENT = 1e-3
HEAD = ("mlm_w", "mlm_b", "mlm_ln_g", "mlm_ln_b")


def _flat(norms: dict) -> dict:
    out = {}
    for name, value in norms.items():
        value = np.atleast_1d(np.asarray(value, np.float64))
        for i, v in enumerate(value):
            out[f"{name}[{i}]" if len(value) > 1 else name] = float(v)
    return out


def worst_leaf_gap(program: dict, reference: dict, skip=()):
    """(gap, tensor) over all tensors but ``skip``."""
    prog, ref = _flat(program), _flat(reference)
    median = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for name, r in ref.items():
        if name in skip:
            continue
        gap = abs(prog[name] - r) / max(r, median)
        if not gap <= worst:  # also catches NaN
            worst, where = gap, name
    return worst, where


def _whole(norms: dict) -> dict:
    """Per-layer norms of a stacked tensor folded into the stack's norm."""
    return {name: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64)))))
            for name, v in norms.items()}


def _pooled(diff_norms: dict, ref_norms: dict, names) -> float:
    diff, ref = _whole(diff_norms), _whole(ref_norms)
    return float(np.sqrt(sum(diff[n] ** 2 for n in names))
                 / np.sqrt(sum(ref[n] ** 2 for n in names)))


def numbers(program: dict, reference: dict) -> dict:
    """``program`` and ``reference`` as ``bert_f32.follow`` returns them;
    ``program`` also holds ``grad_diff_norms``, the per-tensor norm of its
    first gradient's difference from the reference's."""
    ref_grads = _whole(reference["grad_norms"])
    floor = DEAD_GRADIENT * float(np.median(list(ref_grads.values())))
    dead = tuple(name for name, v in ref_grads.items() if v < floor)
    grad_gap, grad_where = worst_leaf_gap(
        program["grad_norms"], reference["grad_norms"])
    delta_gap, delta_where = worst_leaf_gap(
        _whole(program["delta_norms"]), _whole(reference["delta_norms"]),
        skip=dead)
    loss_gaps = [abs(a - b) for a, b in zip(program["loss"], reference["loss"])]
    if len(program["loss"]) != len(reference["loss"]) or len(loss_gaps) < 2:
        loss_gaps += [float("inf")] * 2
    print(f"losses: program {program['loss']} reference {reference['loss']}")
    print(f"worst tensors: gradient {grad_where}, change {delta_where}; "
          f"{len(dead)} tensors with a dead gradient left out of the change")
    return {
        "loss_gap_first": loss_gaps[0],
        "loss_gap_later": max(loss_gaps[1:]),
        "head_grad_rel_diff": _pooled(
            program["grad_diff_norms"], reference["grad_norms"], HEAD),
        "all_grad_rel_diff": _pooled(
            program["grad_diff_norms"], reference["grad_norms"],
            list(reference["grad_norms"])),
        "grad_global_norm_gap": abs(
            program["grad_global_norm"] - reference["grad_global_norm"])
        / reference["grad_global_norm"],
        "grad_norm_gap_worst_leaf": grad_gap,
        "delta_norm_gap_worst_leaf": delta_gap,
    }


def judge(values: dict, limits: dict):
    """(correct, one printed line per number beside its limit)."""
    missing = sorted(set(values) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing} in the cell's mix file")
    correct, lines = True, []
    for name in sorted(values):
        ok = bool(values[name] <= limits[name])  # NaN fails
        correct = correct and ok
        lines.append(f"compare {name}: {values[name]:.6g} limit {limits[name]:.6g} "
                     f"{'ok' if ok else 'FAILS'}")
    return correct, lines

