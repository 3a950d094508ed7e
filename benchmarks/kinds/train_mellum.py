"""The ``train_mellum`` kind of cell: pretraining of a ``mellum``
configuration WHOLE on a four-chip host, its experts and its vocabulary
divided over an expert axis (``--mesh ep=4`` among the mix's
``trainer_args``, after the ``--mesh dp=4`` ``trainer_argv`` writes: the last
one counts), through ``run_pretraining.main``.

The ``train_laguna`` kind (``kinds/train_laguna.py``, itself ``train_lm``'s
probes, window, comparison and result over another family), loaded a second
time under this kind's name as ``kinds/train_joyai.py`` does, with its
``family()`` this family's (reference, mapping, FLOP counts). The program's
parameter tree is the whole model's, as global arrays whose shards lie on the
four chips, so the base's seeded init, its norms by tensor (by expert, every
one of the 64) and its comparison read it as they read a one-chip tree; the
reference places its own arrays over the four chips
(``reference/mellum_f32.py``) and knows nothing of the mesh. Two things are
this kind's:

* ``Probes.routing``: the first micro-batch's routing is read under the same
  ``shard_map`` the step runs (``pretrain.on_expert_axis``: each chip its own
  rows through the model as one chip of four runs it), not through the whole
  model on divided arrays.
* ``exchange_slots_gap``, a ninth judged number: the largest difference, over
  every update of the run, between ``moe_exchange_slots_out`` and
  ``moe_exchange_slots_in`` (slots that left a chip and slots that reached
  one, each summed over the chips). Exact: its limit is 0, as
  ``moe_dropped_slots``'s is in the base.

A program without the ``mellum`` family (the parent of the PR that added this
file) is told so plainly and at once: exit code 1, before any set-up.
"""

from __future__ import annotations

import importlib.util

from benchmarks.kinds import train_laguna


def family():
    """(reference, mapping to the program's tree, FLOP counts) of the family
    this kind trains."""
    from benchmarks.reference import mellum_f32, mellum_map
    from benchmarks.trace import flops_mellum

    return mellum_f32, mellum_map, flops_mellum


def _over_this_family():
    """``kinds/train_laguna.py`` loaded again, its ``family`` this file's."""
    spec = importlib.util.spec_from_file_location(
        __name__ + "_base", train_laguna.__file__)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.family = family
    return module


base = _over_this_family()
ChipError = base.ChipError
_base_compare = base.compare_with_reference


class Probes(base.Probes):
    """The base's probes; the routing is read as the step computes it."""

    def routing(self, params, ids):
        import jax
        from jax.sharding import PartitionSpec as P

        from bert_pytorch_tpu import pretrain
        from bert_pytorch_tpu.parallel.mesh import (AXIS_EXPERT, BATCH_AXES,
                                                    current_mesh)

        mesh = current_mesh()
        if pretrain.expert_axis_shards(mesh) < 2:
            return super().routing(params, ids)
        local = self.model.on_expert_axis(AXIS_EXPERT, mesh.shape[AXIS_EXPERT])

        def chosen(p, i):
            _, kept = local.apply({"params": p}, i, method="hidden_states",
                                  mutable=["intermediates"])
            kept = kept["intermediates"]
            layers = sorted(kept, key=lambda name: int(name.split("_")[1]))
            return [kept[name]["mlp"]["chosen"][0] for name in layers]

        specs = jax.tree_util.tree_map(lambda a: a.sharding.spec, params)
        rows = P(BATCH_AXES)
        return jax.device_get(jax.jit(pretrain.on_expert_axis(
            chosen, mesh, (specs, rows), rows))(params, ids))


def exchange_slots_gap(counters: list) -> float:
    """The largest |slots out - slots in| over the updates' step metrics."""
    import jax

    fetched = jax.device_get([
        {k: m[k] for k in ("moe_exchange_slots_out", "moe_exchange_slots_in")}
        for m in counters if "moe_exchange_slots_out" in m])
    if not fetched:
        return float("inf")
    return max(abs(float(m["moe_exchange_slots_out"])
                   - float(m["moe_exchange_slots_in"])) for m in fetched)


def compare_with_reference(ctx: dict, probes, known: set):
    """The base's comparison, then this kind's exact number."""
    from benchmarks.reference import compare_lm

    correct, numbers, controls, raw = _base_compare(ctx, probes, known)
    extra = {"exchange_slots_gap": exchange_slots_gap(probes.counters)}
    limits = ctx["mix"]["check"]["limits"]
    ok, lines = compare_lm.judge(extra, {name: limits[name] for name in extra})
    print("\n".join(lines))
    numbers.update(extra)
    for other in controls.values():  # a control has no exchange of its own
        other.update(exchange_slots_gap=0.0)
    return correct and ok, numbers, controls, raw


base.Probes = Probes
base.compare_with_reference = compare_with_reference
drive, run, measure = base.drive, base.run, base.measure
