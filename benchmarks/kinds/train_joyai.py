"""The ``train_joyai`` kind of cell: pretraining of a ``joyai_llm_flash``
configuration, with its multi-token-prediction objective, through
``run_pretraining.main``.

The ``train_laguna`` kind (``kinds/train_laguna.py``, itself ``train_lm``'s
probes, window, comparison and result over another family), loaded a second
time under this kind's name as ``kinds/train_zaya.py`` and
``kinds/train_keye.py`` do, with its ``family()`` this family's (reference,
mapping, FLOP counts) and its step counters ``moe_``, ``mla_`` and ``mtp_``.
The base's eight judged numbers stand as they are (the loss gaps on the WHOLE
objective, next-token loss + 0.3 x the module's). Three are added, because
this family's objective has a second term and its attention two latents:

* ``mtp_loss_gap``: the largest gap, over the followed updates, between the
  program's ``mtp_loss`` counter (the second term alone, the mean over the
  update's micro-batches) and the reference's. The whole loss moves by 0.3 of
  it, which would hide a wrong shift under the first term's spread.
* ``mtp_grad_rel_diff``: the norm of the difference of the first gradients
  of the module's own tensors (``W_eh``, ``enorm``, ``hnorm``, its block, its
  final norm: ``joyai_map.mtp_names``), over the reference's norm of them.
  Their gradient is the second term's alone and small beside the whole: it
  reads 1.0 with the term left out.
* ``latent_grad_rel_diff``: the same over ``W_qa``, ``W_kva`` and the two
  latent norms of every block (``joyai_map.latent_names``): a latent norm
  left out, or a shared turned key whose gradient is not summed over the
  heads, shows here first.

``routing_flip_share`` is printed and not judged, as in the base; the routing
read in set-up is every expert layer's and, last, the module's block's (the
forward call goes through ``streams``, which runs the module).

A program without the ``joyai_llm_flash`` family (the parent of the PR that
added this file) is told so plainly and at once: exit code 1, before any
set-up.
"""

from __future__ import annotations

import importlib.util

from benchmarks.kinds import train_laguna

NEW_NUMBERS = ("mtp_loss_gap", "mtp_grad_rel_diff", "latent_grad_rel_diff")


def family():
    """(reference, mapping to the program's tree, FLOP counts) of the family
    this kind trains."""
    from benchmarks.reference import joyai_f32, joyai_map
    from benchmarks.trace import flops_joyai

    return joyai_f32, joyai_map, flops_joyai


def _over_this_family():
    """``kinds/train_laguna.py`` loaded again, its ``family`` this file's."""
    spec = importlib.util.spec_from_file_location(
        __name__ + "_base", train_laguna.__file__)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    module.family = family
    module.COUNTERS = ("moe_", "mla_", "mtp_")
    return module


base = _over_this_family()
ChipError = base.ChipError
_base_compare = base.compare_with_reference


class Probes(base.Probes):
    """The base's probes; the routing is read through ``streams``, so that
    the module's block routes too: the expert layers' in order, the module's
    last (``joyai_f32.follow``'s ``chosen``)."""

    def routing(self, params, ids):
        import jax

        def chosen(p, i):
            _, kept = self.model.apply({"params": p}, i, method="streams",
                                       mutable=["intermediates"])
            return kept["intermediates"]

        kept = jax.device_get(jax.jit(chosen)(params, ids))
        layers = sorted((name for name in kept if name.startswith("layers_")),
                        key=lambda name: int(name.split("_")[1]))
        routed = [kept[name]["mlp"]["chosen"][0] for name in layers
                  if "chosen" in kept[name].get("mlp", {})]
        if "mtp" in kept:
            routed.append(kept["mtp"]["block"]["mlp"]["chosen"][0])
        return routed


def family_numbers(program: dict, reference: dict, mtp_loss: list,
                   sizes: dict) -> dict:
    """This family's three, from a side's first-gradient differences
    (``grad_diff_norms``) and its second term's losses."""
    from benchmarks.reference import compare, joyai_map

    pooled = lambda names: compare._pooled(
        program["grad_diff_norms"], reference["grad_norms"], names)
    gaps = [abs(a - b) for a, b in zip(mtp_loss, reference["mtp_loss"])]
    if len(gaps) != len(reference["mtp_loss"]) or not gaps:
        gaps = [float("inf")]
    return {"mtp_loss_gap": max(gaps),
            "mtp_grad_rel_diff": pooled(joyai_map.mtp_names(sizes)),
            "latent_grad_rel_diff": pooled(joyai_map.latent_names(sizes))}


def compare_with_reference(ctx: dict, probes, known: set):
    """The base's comparison, then the numbers of this family."""
    import jax

    from benchmarks.reference import compare_lm

    correct, numbers, controls, raw = _base_compare(ctx, probes, known)
    reference = raw["reference"]
    mine = [float(jax.device_get(m["mtp_loss"]))
            for m in probes.counters[:probes.check_updates]]
    print(f"second term: program {mine} reference {reference['mtp_loss']}")
    extra = family_numbers(raw["program"], reference, mine, probes.sizes)
    limits = ctx["mix"]["check"]["limits"]
    ok, lines = compare_lm.judge(extra, {name: limits[name] for name in extra})
    print("\n".join(lines))
    numbers.update(extra)
    for precision, other in controls.items():
        other.update(family_numbers(raw[precision], reference,
                                    raw[precision]["mtp_loss"], probes.sizes))
    return correct and ok, numbers, controls, raw


base.Probes = Probes
base.compare_with_reference = compare_with_reference
drive, run, measure = base.drive, base.run, base.measure
