"""The ``train_keye`` kind of cell: next-token pretraining of a ``KeyeVL2``
configuration through ``run_pretraining.main``.

The ``train_laguna`` kind (``kinds/train_laguna.py``, itself ``train_lm``'s
probes, window, comparison and result over another family), loaded a second
time under this kind's name as ``kinds/train_zaya.py`` does, with its
``family()`` this family's (reference, mapping, FLOP counts) and its step
counters ``moe_`` and ``dsa_``. Two things are added round what that file
does, because this family's objective has a term of the model's own and its
attention a choice:

* ``index_grad_rel_diff``: the norm of the difference of the first gradients
  of the three indexer matrices of every layer, over the reference's norm of
  them. Their gradient is the KL's alone and is small beside the whole, so
  ``all_grad_rel_diff`` would not see the KL left out or the indexer's input
  not detached. Judged against the mix's limit, like the other seven.
* ``selection_flip_share``: the share of the first micro-batch's chosen
  query-key pairs that one side chose and the other did not, over the layers
  (near-ties at the k-th score flip on the bfloat16 rounding of the indexer's
  operands). Printed and not judged, as ``routing_flip_share`` is. The
  program's choice is what ``SparseAttention`` sows as ``selected`` (packed
  bits), read in the same forward call as the experts' ``chosen``.

* ``objective_leak_rel``: the gradient of the model's own objective term
  ALONE (the indexer's KL, what ``objective_terms`` names) through the
  program's model at the seeded weights on the first micro-batch: the norm of
  what reaches every leaf that is no indexer matrix, over the norm of what
  reaches the indexer's. Exactly 0 when sound, because the indexer reads its
  input detached and the KL reads the core's probabilities detached; with the
  input left attached the KL leaks into the other leaves at a fraction of a
  percent of THEIR gradient, which ``all_grad_rel_diff`` cannot tell from the
  bfloat16 step's own rounding. Read in set-up beside the routing, judged.

* ``chosen_pairs_gap``: the first update's ``dsa_pairs_run``, the pairs the
  program's choice kept, against the count the rule gives (layers x rows x
  the sum over t of min(t + 1, topk), ``flops_keye.chosen_pairs``), as a
  share of it. Exactly 0 when sound; a choice one key short of ``topk``
  moves 0.05% of the cell's pairs, which no gradient's number can tell from
  the flips of rounding. Judged.

A program without the ``KeyeVL2`` family (the parent of the PR that added
this file) is told so plainly and at once: exit code 1, before any set-up.
"""

from __future__ import annotations

import importlib.util

from benchmarks.kinds import train_laguna


def family():
    """(reference, mapping to the program's tree, FLOP counts) of the family
    this kind trains."""
    from benchmarks.reference import keye_f32, keye_map
    from benchmarks.trace import flops_keye

    return keye_f32, keye_map, flops_keye


def _over_this_family():
    """``kinds/train_laguna.py`` loaded again, its ``family`` this file's."""
    spec = importlib.util.spec_from_file_location(
        __name__ + "_base", train_laguna.__file__)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.family = family
    module.COUNTERS = ("moe_", "dsa_")
    return module


base = _over_this_family()
ChipError = base.ChipError
_base_compare = base.compare_with_reference


class Probes(base.Probes):
    """The base's probes; the forward call that reads the experts' routing
    also reads each layer's chosen keys, and one backward pass beside it
    reads ``objective_leak_rel``."""

    selected = objective_leak = None

    def routing(self, params, ids):
        import jax

        def chosen(p, i):
            _, kept = self.model.apply({"params": p}, i, method="hidden_states",
                                       mutable=["intermediates"])
            return kept["intermediates"]

        self.objective_leak = float(jax.jit(self.leak)(params, ids))
        kept = jax.device_get(jax.jit(chosen)(params, ids))
        layers = sorted(kept, key=lambda name: int(name.split("_")[1]))
        self.selected = [kept[name]["attention"]["selected"][0]
                         for name in layers]
        return [kept[name]["mlp"]["chosen"][0] for name in layers]

    def leak(self, params, ids):
        """Of the gradient of the model's objective terms alone: the norm on
        the leaves that are no indexer matrix over the norm on the indexer's."""
        import jax
        import jax.numpy as jnp

        model = self.model
        if not model.objective_terms():
            return jnp.zeros(())

        def terms(p):
            _, counters = model.apply({"params": p}, ids, method="hidden_states")
            return sum(coefficient * counters[name] for name, coefficient
                       in model.objective_terms().items())

        indexer, others = 0.0, 0.0
        for path, g in jax.tree_util.tree_leaves_with_path(
                jax.grad(terms)(params)):
            square = jnp.sum(jnp.square(g.astype(jnp.float32)))
            if "/index_" in jax.tree_util.keystr(path, simple=True,
                                                 separator="/"):
                indexer += square
            else:
                others += square
        return jnp.sqrt(others) / (jnp.sqrt(indexer) + 1e-30)


def selection_flip_share(program: list, reference: list) -> float:
    """Pairs one side chose and the other did not, over the pairs the
    reference chose (packed bits a layer, [rows, S, S / 8])."""
    import numpy as np

    counts = np.array([bin(i).count("1") for i in range(256)], np.int64)
    flipped = total = 0
    for mine, theirs in zip(program, reference):
        mine = np.asarray(mine).reshape(np.shape(theirs))
        flipped += int(counts[np.bitwise_xor(mine, theirs)].sum())
        total += int(counts[theirs].sum())
    return flipped / max(2 * total, 1)


def exact_numbers(ctx: dict, probes) -> dict:
    """The two numbers that read exactly 0 of a sound program."""
    import jax
    import numpy as np

    from benchmarks.trace import flops_keye

    config, ids = ctx["config"], probes.fed[0]
    want = (config["num_hidden_layers"] * int(np.prod(ids.shape[:-1]))
            * flops_keye.chosen_pairs(ids.shape[-1],
                                      int(config["sa_config"]["topk"])))
    kept = float(jax.device_get(probes.counters[0]["dsa_pairs_run"]))
    return {"objective_leak_rel": probes.objective_leak,
            "chosen_pairs_gap": abs(kept - want) / want}


def compare_with_reference(ctx: dict, probes, known: set):
    """The base's comparison, then the numbers of this family."""
    from benchmarks.reference import compare, compare_lm, keye_map

    correct, numbers, controls, raw = _base_compare(ctx, probes, known)
    names = keye_map.indexer_names(probes.sizes)
    reference = raw["reference"]
    # (the choices are large: they leave ``raw`` before it is written out)
    selected = reference.pop("selected")
    for precision in controls:
        raw[precision].pop("selected")
    pooled = lambda diff: compare._pooled(diff, reference["grad_norms"], names)
    extra = dict(exact_numbers(ctx, probes), index_grad_rel_diff=pooled(
        raw["program"]["grad_diff_norms"]))
    limits = ctx["mix"]["check"]["limits"]
    ok, lines = compare_lm.judge(extra, {name: limits[name] for name in extra})
    print("\n".join(lines))
    numbers.update(extra)
    for precision, other in controls.items():
        other["index_grad_rel_diff"] = pooled(raw[precision]["grad_diff_norms"])
    flips = selection_flip_share(probes.selected, selected)
    print(f"compare selection_flip_share: {flips:.6g} (printed, not judged)")
    numbers["selection_flip_share"] = flips
    return correct and ok, numbers, controls, raw


base.Probes = Probes
base.compare_with_reference = compare_with_reference
drive, run, measure = base.drive, base.run, base.measure
