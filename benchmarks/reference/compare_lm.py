"""The comparison that decides ``correct`` for a ``train_lm`` cell: the
numbers of ``compare.py`` (its helpers, unchanged), over the tensors of
``nemotron_h_f32``. There is no dropout, so the program's side is the TIMED
step's own first updates.

* ``loss_gap_first`` / ``loss_gap_later``: |program - reference| loss of the
  first followed update (at the seeded weights: ln V in any precision, so it
  is held against a part of the batch or of the model being left out) and the
  largest over the later ones.
* ``grad_global_norm_gap``: relative gap of the first update's gradient norm
  before clipping.
* ``grad_norm_gap_worst_leaf`` / ``delta_norm_gap_worst_leaf``: the gap
  between the two NORMS of the first gradient (worked out from the optimizer's
  first moment and the clipping the step's own gradient norm implies) and of
  the parameters' change over the followed updates, per tensor (per expert for
  the experts' stacked tensors), over the reference's norm of that tensor or
  of the median tensor, whichever is larger; the worst decides. Tensors whose
  reference gradient is all but zero (the router's correction buffer, which
  has none) are left out of the change.
* ``head_grad_rel_diff``: the norm of the DIFFERENCE of the first gradients of
  the output head and the final norm, over the reference's norm: they see the
  last layer's output, where the forward error of every layer has gathered.
* ``all_grad_rel_diff``: the same over every tensor together.
* ``feed_faults``: rows fed to the step that are not generated rows. Exact.

Printed beside them and not judged: ``routing_flip_share``, the share of
token-slots of the first micro-batch whose chosen expert differs between
program and reference (near-ties flip on the bfloat16 rounding of the
router's input).
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference import compare

HEAD = ("head", "final_norm")


def numbers(program: dict, reference: dict) -> dict:
    """``program`` and ``reference`` as ``nemotron_h_f32.follow`` returns
    them; ``program`` also holds ``grad_diff_norms``."""
    ref_grads = compare._whole(reference["grad_norms"])
    floor = compare.DEAD_GRADIENT * float(np.median(list(ref_grads.values())))
    dead = tuple(name for name, v in ref_grads.items() if v < floor)
    grad_gap, grad_where = compare.worst_leaf_gap(
        program["grad_norms"], reference["grad_norms"])
    delta_gap, delta_where = compare.worst_leaf_gap(
        compare._whole(program["delta_norms"]),
        compare._whole(reference["delta_norms"]), skip=dead)
    loss_gaps = [abs(a - b) for a, b in zip(program["loss"], reference["loss"])]
    if len(program["loss"]) != len(reference["loss"]) or len(loss_gaps) < 2:
        loss_gaps += [float("inf")] * 2
    print(f"losses: program {program['loss']} reference {reference['loss']}")
    print(f"worst tensors: gradient {grad_where}, change {delta_where}; "
          f"{len(dead)} tensors with a dead gradient left out of the change")
    return {
        "loss_gap_first": loss_gaps[0],
        "loss_gap_later": max(loss_gaps[1:]),
        "head_grad_rel_diff": compare._pooled(
            program["grad_diff_norms"], reference["grad_norms"], HEAD),
        "all_grad_rel_diff": compare._pooled(
            program["grad_diff_norms"], reference["grad_norms"],
            list(reference["grad_norms"])),
        "grad_global_norm_gap": abs(
            program["grad_global_norm"] - reference["grad_global_norm"])
        / reference["grad_global_norm"],
        "grad_norm_gap_worst_leaf": grad_gap,
        "delta_norm_gap_worst_leaf": delta_gap,
    }


def routing_flip_share(program_chosen: list, reference_chosen: list) -> float:
    """Share of token-slots whose expert one side chose and the other did
    not, over the E layers ([tokens, k] ids each, in any order within a
    token)."""
    flipped = total = 0
    for mine, theirs in zip(program_chosen, reference_chosen):
        mine = np.sort(np.asarray(mine).reshape(-1, np.shape(mine)[-1]), axis=-1)
        theirs = np.sort(np.asarray(theirs).reshape(mine.shape), axis=-1)
        for a, b in zip(mine, theirs):
            flipped += len(set(a.tolist()) - set(b.tolist()))
        total += mine.size
    return flipped / max(total, 1)


judge = compare.judge
