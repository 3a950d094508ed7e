"""The ``train_qwen3next`` kind of cell: next-token pretraining of a
``qwen3_next`` configuration through ``run_pretraining.main``.

The ``train_laguna`` kind (``kinds/train_laguna.py``, itself ``train_lm``'s
probes, window, comparison and result over another family) with ONE thing
changed: ``family()``, the place where that file names its reference, its
mapping and its FLOP counts. Nothing of it is written again here: as
``kinds/train_zaya.py`` does, its source is loaded a second time under this
kind's name, so that its functions look ``family`` up in a module of their
own, and that module's ``family`` and ``COUNTERS`` are set to this family's
(the first copy, which the laguna cell runs, is untouched). What the family
needs of the rest holds as it stands: every layer's expert layer is
``layers_<i>/mlp`` and sows ``chosen`` there (``Probes.routing``), the head is
untied and the reference keeps it under the name ``head``
(``compare_lm.HEAD``), and the step counters begin with ``moe_`` or
``delta_``.

A program without the ``qwen3_next`` family (the parent of the PR that added
this file) is told so plainly and at once: exit code 1, before any set-up.
"""

from __future__ import annotations

import importlib.util

from benchmarks.kinds import train_laguna


def family():
    """(reference, mapping to the program's tree, FLOP counts) of the family
    this kind trains."""
    from benchmarks.reference import qwen3next_f32, qwen3next_map
    from benchmarks.trace import flops_qwen3next

    return qwen3next_f32, qwen3next_map, flops_qwen3next


def _over_this_family():
    """``kinds/train_laguna.py`` loaded again, its ``family`` this file's."""
    spec = importlib.util.spec_from_file_location(
        __name__ + "_base", train_laguna.__file__)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.family = family
    module.COUNTERS = ("moe_", "delta_")
    return module


base = _over_this_family()
ChipError = base.ChipError
Probes, drive, run, measure = base.Probes, base.drive, base.run, base.measure
compare_with_reference = base.compare_with_reference
