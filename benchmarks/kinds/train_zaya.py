"""The ``train_zaya`` kind of cell: next-token pretraining of a ``zaya``
configuration through ``run_pretraining.main``.

The ``train_laguna`` kind (``kinds/train_laguna.py``, itself ``train_lm``'s
probes, window, comparison and result over another family) with ONE thing
changed: ``family()``, the place where that file names its reference, its
mapping and its FLOP counts. Nothing of it is written again here: its source
is loaded a second time under this kind's name, so that its functions look
``family`` up in a module of their own, and that module's ``family`` and
``COUNTERS`` are set to this family's (the first copy, which the laguna cell
runs, is untouched). What the family needs of the rest holds as it stands: the
expert layer is ``layers_<i>/mlp`` and sows ``chosen`` there (``Probes.routing``),
the head is tied and the reference keeps the one tensor under the name
``head`` (``compare_lm.HEAD``), and the step counters begin with ``moe_`` or
``router_``.

Beside that kind's lines this one prints, from the reference's routing of the
first micro-batch, the share of tokens that drew the skip and the share that
drew an expert held here (``compare skip_share``, ``compare local_share``:
printed, not judged, as ``routing_flip_share`` is: under top-1 a flipped
token's whole expert term goes elsewhere).

A program without the ``zaya`` family (the parent of the PR that added this
file) is told so plainly and at once: exit code 1, before any set-up.
"""

from __future__ import annotations

import importlib.util

from benchmarks.kinds import train_laguna


def family():
    """(reference, mapping to the program's tree, FLOP counts) of the family
    this kind trains."""
    from benchmarks.reference import zaya_f32, zaya_map
    from benchmarks.trace import flops_zaya

    return zaya_f32, zaya_map, flops_zaya


def _over_this_family():
    """``kinds/train_laguna.py`` loaded again, its ``family`` this file's."""
    spec = importlib.util.spec_from_file_location(
        __name__ + "_base", train_laguna.__file__)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.family = family
    module.COUNTERS = ("moe_", "router_")
    return module


base = _over_this_family()
ChipError = base.ChipError
Probes, drive, run, measure = base.Probes, base.drive, base.run, base.measure
_compare = base.compare_with_reference


def compare_with_reference(ctx: dict, probes, known: set):
    """``train_laguna``'s comparison, and the two shares of the reference's
    routing printed beside it."""
    import numpy as np

    correct, numbers, controls, raw = _compare(ctx, probes, known)
    sizes = probes.sizes
    chosen = np.concatenate([np.asarray(c).reshape(-1) for c in probes.chosen])
    numbers["skip_share"] = float(np.mean(chosen == sizes["experts"]))
    numbers["local_share"] = float(np.mean(
        (chosen >= sizes["first"]) & (chosen < sizes["first"] + sizes["held"])))
    print(f"compare skip_share: {numbers['skip_share']:.6g}, local_share: "
          f"{numbers['local_share']:.6g} (the program's routing of the first "
          "micro-batch; printed, not judged)")
    return correct, numbers, controls, raw


base.compare_with_reference = compare_with_reference
