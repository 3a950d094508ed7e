"""The ``train_phi4flash`` kind of cell: next-token pretraining of a
``phi4flash`` configuration through ``run_pretraining.main``.

The ``train_lm`` kind (``kinds/train_lm.py``) over another family, as
``kinds/train_laguna.py`` is: the same three probes round
``pretrain.make_init_fn`` (weights from the seed by the reference's generator,
built in one program with the optimizer's state), ``pretrain.make_train_step``
(no dropout anywhere, so the check's updates go through THE TIMED STEP ITSELF:
the first ``check.updates`` calls are the check's, then one warm-up update,
then the window) and ``pretrain.device_prefetch`` (the feed is timed); the
same window and the same result. ``train_lm.py`` names its reference, its
mapping and its FLOP counts in the bodies of five functions, so those five are
written a third time here over ``phi4flash_f32``, ``phi4flash_map`` and
``flops_phi4flash`` (``family()`` is the one place that names them); the
probes' class, the trainer's argument line and everything of
``kinds/train.py`` are imported.

What differs from the other two decoder kinds: the family routes nothing (no
``chosen`` experts to compare, no dropped slots to count), and its head is
TIED, so the comparison's ``head_grad_rel_diff`` is taken over the embedding
(which is the head) and the final norm's weight and bias: ``numbers`` below is
``compare_lm.numbers`` with those names. The kind hands the readers the
family's step counters (``scan_chunks_run``, ``attn_*_tiles_run``,
``memory_readers``, ``shared_kv_readers``).

A program without the ``phi4flash`` family (the parent of the PR that added
this file) is told so plainly and at once: exit code 1, before any set-up.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import statistics
import tempfile

from benchmarks.kinds import train as base
from benchmarks.kinds import train_lm

ChipError = base.ChipError
now = base.now
require_family = train_lm.require_family
trainer_argv = train_lm.trainer_argv
COUNTERS = ("scan_", "attn_", "memory_", "shared_kv_")
HEAD = ("emb", "final_norm_w", "final_norm_b")


def family():
    """(reference, mapping to the program's tree, FLOP counts) of the family
    this kind trains."""
    from benchmarks.reference import phi4flash_f32, phi4flash_map
    from benchmarks.trace import flops_phi4flash

    return phi4flash_f32, phi4flash_map, flops_phi4flash


class Probes(train_lm.Probes):
    """``train_lm``'s probes with this family's norms and no routing."""

    def __init__(self, args, seed, sizes, *rest, **kwargs):
        import jax

        super().__init__(args, seed, sizes, *rest, **kwargs)
        mapping = family()[1]
        self.helpers["norms"] = jax.jit(
            lambda tree: mapping.leaf_norms(tree, sizes))

    def routing(self, params, ids):
        return []  # the family routes nothing


def numbers(program: dict, reference: dict) -> dict:
    """``compare_lm.numbers`` for a tied head: the head's tensors are the
    embedding and the final norm's weight and bias."""
    from benchmarks.reference import compare

    ref_grads = compare._whole(reference["grad_norms"])
    floor = compare.DEAD_GRADIENT * float(
        statistics.median(ref_grads.values()))
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


@contextlib.contextmanager
def probed(pretrain, probes):
    """The ``train`` kind's context manager, with this family's seeded init
    and a step that is its own check."""
    import jax

    ref, mapping, _ = family()
    real_init, real_step = pretrain.make_init_fn, pretrain.make_train_step

    def make_init_fn(model, tx, sample_inputs, shardings):
        template = real_init(model, tx, sample_inputs, shardings)

        def seeded_init(rng):
            def build(key, rng):
                params = mapping.to_program(
                    ref.seeded_params(key, probes.sizes), probes.sizes)
                return pretrain.TrainState(
                    params=params, opt_state=tx.init(params),
                    rng=jax.random.split(rng)[1])

            want = jax.tree_util.tree_structure(jax.eval_shape(template, rng))
            got = jax.tree_util.tree_structure(jax.eval_shape(
                build, ref.key_from_seed(probes.seed), rng))
            if want != got:
                raise RuntimeError(
                    "the program's state is not the one "
                    f"reference/phi4flash_map.py describes:\n{want}\n{got}")
            with base.persist_small_compiles():
                state = jax.jit(build, out_shardings=shardings)(
                    ref.key_from_seed(probes.seed), rng)
            probes.seeded_host = jax.device_get(state.params)
            return state

        return seeded_init

    def make_train_step(model, *a, **k):
        probes.model = model
        step = real_step(model, *a, **k)
        return base._StepProbe(step, step, probes)

    # base.probed patches all three and restores them; its own init and step
    # wrappers are BERT's, so ours go over them inside it.
    with base.probed(pretrain, probes):
        pretrain.make_init_fn = make_init_fn
        pretrain.make_train_step = make_train_step
        yield


def drive(ctx: dict, work: str):
    """Set-up and window: the trainer's own ``main`` under the probes."""
    from benchmarks.traffic import generate_lm

    import run_pretraining
    from bert_pytorch_tpu import pretrain

    ref = family()[0]
    mix, config = ctx["mix"], ctx["config"]
    chips, seed = int(ctx["cell"]["chips"]), int(ctx["seed"])
    known = generate_lm.write_shards(
        mix, int(config["vocab_size"]), seed, os.path.join(work, "shards"))
    args = run_pretraining.parse_arguments(trainer_argv(
        mix, chips, seed, work, ctx["config_file"]))
    probes = Probes(
        args, seed, ref.sizes(config), int(mix["check"]["updates"]),
        float(ctx["seconds"]), ref.Recipe(**mix["recipe"]),
        trace_dir=os.path.join(work, "trace") if ctx["trace"] else None,
        trace_updates=int(mix.get("trace_updates", 3)))
    with probed(pretrain, probes):
        run_pretraining.main(args)
    if probes.t_close is None:
        raise RuntimeError("the trainer returned before the window closed")
    return probes, known


def compare_with_reference(ctx: dict, probes: Probes, known: set):
    """The comparison, outside the window, the program's state freed."""
    import jax
    import numpy as np

    from benchmarks.reference import compare
    from benchmarks.traffic import generate_lm

    ref, mapping, _ = family()
    mix, config, seed = ctx["mix"], ctx["config"], int(ctx["seed"])
    check, recipe = mix["check"], ref.Recipe(**mix["recipe"])
    feed_faults = [f for u in probes.fed for f in generate_lm.check_fed_rows(
        u, known, int(config["vocab_size"]))]
    first_gradient = mapping.from_program(jax.tree_util.tree_map(
        lambda m: np.asarray(m) * probes.unclip, probes.first_mu), probes.sizes)
    probes.first_mu = None

    def follow(precision, **kwargs):
        with base.persist_small_compiles():
            return ref.follow(seed, config, recipe, probes.fed, precision,
                              **kwargs)

    reference = follow("f32", first_gradient_to_compare=first_gradient,
                       keep_first_gradient="controls" in ctx)
    del first_gradient
    ref_gradient = reference.pop("first_gradient", None)
    program = {"loss": probes.losses, "grad_global_norm": probes.grad_global,
               "grad_norms": probes.grad_norms, "delta_norms": probes.delta_norms,
               "grad_diff_norms": reference.pop("grad_diff_norms")}
    found = numbers(program, reference)
    found["feed_faults"] = float(len(feed_faults))
    correct, lines = compare.judge(found, check["limits"])
    for line in ["feed fault: " + f for f in feed_faults] + lines:
        print(line)
    controls, raw = {}, {"program": program, "reference": reference}
    for precision in ctx.get("controls", ()):  # margins and tests only
        raw[precision] = follow(precision, first_gradient_to_compare=ref_gradient)
        controls[precision] = numbers(raw[precision], reference)
    return correct, found, controls, raw


def traced_metrics(ctx: dict, probes: Probes, updates: int, device: dict,
                   counters: dict) -> dict:
    """Per-layer metrics and the breakdown from the traced window; the
    readers' context is ``train_lm``'s (``config``, ``mix``, ``counters`` per
    update, ``trace_dir`` beside the ``train`` kind's keys)."""
    from benchmarks.trace import flops, reduce

    chips = device["count"]
    summary = reduce.summarize(reduce.read_trace_dir(probes.trace_dir))
    device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
    reader_ctx = {
        "summary": summary, "updates": updates, "chips": chips,
        "dispatch_s": probes.dispatch_s, "data_wait_s": probes.data_wait_s,
        "flops_per_update": family()[2].train_flops_per_update(
            ctx["config"], ctx["mix"], chips),
        "peak_flops": flops.peak_flops(device["kind"]),
        "config": ctx["config"], "mix": ctx["mix"], "counters": counters,
        "device_kind": device["kind"], "trace_dir": probes.trace_dir,
    }
    metrics = {}
    for name, reader in ctx["readers"].items():
        value = reader(reader_ctx)
        if value is not None:
            metrics[name] = {"value": value, "unit": ctx["units"][name]}
    return {"metrics": metrics,
            "breakdown": {"device_ops": summary["device_ops"][:10],
                          "idle_gaps": summary["idle_gaps"][:10]}}


def run(ctx: dict) -> dict:
    """One run of one cell, on the chips it asks for."""
    require_family(ctx["config"])
    base.require_chips(int(ctx["cell"]["chips"]))
    return measure(ctx)


def measure(ctx: dict) -> dict:
    """A run without the look for a chip (tests and rehearsals start here);
    ``ctx`` as for ``kinds/train.py``."""
    import jax

    require_family(ctx["config"])
    chips = int(ctx["cell"]["chips"])
    # (the prefix is how trace/scopes.py finds a train kind's trace)
    work = tempfile.mkdtemp(prefix="bench_train_phi4flash_")
    try:
        probes, known = drive(ctx, work)
        if probes.compiles_in_window:
            raise RuntimeError(
                f"{probes.compiles_in_window} programs compiled inside the "
                "window: set-up has to warm every shape the window uses")
        setup_s = probes.t_open - ctx["started"]
        window_s = probes.t_close - probes.t_open
        tokens = probes.tokens
        finite = [float(f) for f in jax.device_get(probes.finite)]
        fetched = jax.device_get([
            {k: v for k, v in m.items() if k.startswith(COUNTERS)}
            for m in probes.counters])
        in_window = fetched[probes.check_updates:]
        counters = {name: float(statistics.fmean(m[name] for m in in_window))
                    for name in (in_window[0] if in_window else {})}
        peak, allocator_peak, temp = base.memory_peak_bytes(
            os.path.join(work, "out"), probes.in_use_at_open)
        devices = jax.devices()[:chips]
        limit = (devices[0].memory_stats() or {}).get("bytes_limit")
        if limit:
            # (kinds/train_lm.py: the donated state is partly counted twice;
            # the chip cannot hold more than its limit)
            peak = min(peak, int(limit))
        gc.collect()
        t_compare = now()
        correct, found, controls, raw = compare_with_reference(
            ctx, probes, known)
        comparison_s = now() - t_compare
        between = [b - a for a, b in zip(probes.called_at, probes.called_at[1:])]
        print("host in the window, longest / median seconds: step call to step "
              f"call {max(between, default=0):.3f} / "
              f"{statistics.median(between or [0]):.3f}, inside the step call "
              f"{max(probes.dispatch_s):.3f} / "
              f"{statistics.median(probes.dispatch_s):.3f}, waiting for the feed "
              f"{max(probes.data_wait_s, default=0):.3f}; full garbage "
              f"collections {[round(p, 3) for p in probes.gc_pauses]}")
        print(f"window: {len(tokens)} updates, {sum(tokens)} tokens in "
              f"{window_s:.4f} s; set-up {setup_s:.2f} s; comparison "
              f"{comparison_s:.2f} s; live at window open "
              f"{probes.in_use_at_open} + step temporaries {temp} bytes "
              f"(allocator peak {allocator_peak}, limit {limit}); compiles in "
              f"window {probes.compiles_in_window}; step counters per "
              f"update {counters}")
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        result = {"correct": bool(correct), "attempted": len(tokens),
                  "failed": sum(1 for f in finite if f != 1.0),
                  "metrics": {}, "device": device,
                  # not on the result line (run.py prints the contract's keys):
                  "compiles_in_window": probes.compiles_in_window,
                  "counters": counters, "readings": found,
                  "comparison_s": comparison_s}
        if "controls" in ctx:
            result["controls"] = controls
            result["raw"] = json.loads(json.dumps(
                raw, default=lambda a: [float(v) for v in a.reshape(-1)]))
        if ctx["trace"]:
            result.update(traced_metrics(ctx, probes, len(tokens), device,
                                         counters))
            return result
        result["metrics"] = {
            "train_tokens_per_s": {"value": sum(tokens) / window_s,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        if device["platform"] == "tpu":
            from benchmarks.trace import flops
            share = (family()[2].train_flops_per_update(
                ctx["config"], ctx["mix"], chips) * len(tokens) / window_s
                / (chips * flops.peak_flops(device["kind"])))
            print("model FLOP/s utilization over the window (end to end, from "
                  f"wall time): {100 * share:.2f}%")
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)
