"""The ``train_lm`` kind of cell: next-token pretraining of a ``nemotron_h``
configuration through ``run_pretraining.main``.

As in the ``train`` kind (``kinds/train.py``, whose probes this file reuses),
the system under test is the trainer's own entry with its loader, device
prefetch, accumulation and logging, on shards this benchmark wrote from the
seed (``traffic/generate_lm.py``), and the benchmark puts three probes round
calls into the program's layers and edits nothing:

* ``pretrain.make_init_fn``: the state gets its weights from the seed by the
  reference's generator (``reference/nemotron_h_f32.py``), built in one
  program with the optimizer's state so that no throw-away parameters leave
  a hole in the device's memory (the state is two thirds of the chip).
* ``pretrain.make_train_step``: the step the trainer builds is the timed one,
  and there is no dropout, so the check's updates go through THE TIMED STEP
  ITSELF: the first ``check.updates`` calls are the check's (what they were
  fed, each loss, the optimizer's first gradient, the parameters' change, the
  first micro-batch's routing), then one warm-up update, then the window.
* ``pretrain.device_prefetch``: the feed is timed.

The window is the ``train`` kind's: whole optimizer updates, open to close,
every row full, so tokens = rows x sequence length. After the trainer has
returned and its state is freed, the float32 reference follows the same
updates from the same seed and the two are compared
(``reference/compare_lm.py``). ``moe_dropped_slots`` is read from every
update's step metrics: a run in which it is not 0 fails.

A program without the ``nemotron_h`` family (the parent of the PR that added
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

ChipError = base.ChipError
now = base.now


def require_family(config: dict):
    """Leave, plainly and at once, where the program cannot build the
    configuration's family (it would otherwise take the file for BERT's)."""
    from bert_pytorch_tpu import config as program_config

    family = config.get("model_type")
    known = getattr(program_config, "MODEL_FAMILIES", {"bert": None})
    if family not in known:
        raise SystemExit(
            f"benchmark: unknown model_type {family!r}: this program builds "
            f"{sorted(known)}")


class Probes:
    """Everything the probes record in one run."""

    def __init__(self, args, seed, sizes, check_updates, seconds, recipe,
                 trace_dir=None, trace_updates=3):
        import jax

        from benchmarks.reference import nemotron_h_map

        self.args, self.seed, self.sizes = args, seed, sizes
        self.b1, self.max_grad_norm = recipe.b1, recipe.max_grad_norm
        self.check_updates, self.seconds = check_updates, seconds
        self.trace_dir, self.trace_updates = trace_dir, trace_updates
        self.calls = 0
        self.model = None          # the model the trainer built its step from
        self.seeded_host = None    # host copy of the seeded parameters
        self.first_mu = self.unclip = None
        self.fed, self.losses, self.chosen = [], [], None
        self.grad_global = self.grad_norms = self.delta_norms = None
        self.t_open = self.t_close = None
        self.tracing = False
        self.dispatch_s, self.data_wait_s, self.called_at = [], [], []
        self.tokens, self.finite, self.counters = [], [], []
        self.compiles_in_window = 0
        self.gc_pauses, self._gc_started = [], None
        self.in_use_at_open = 0
        self.helpers = {
            "norms": jax.jit(lambda tree: nemotron_h_map.leaf_norms(tree, sizes)),
            "diff": jax.jit(lambda a, b: a - b),
        }

    window_open = base.Probes.window_open
    on_compile = base.Probes.on_compile
    on_gc = base.Probes.on_gc
    on_feed = base.Probes.on_feed

    def routing(self, params, ids):
        """The experts the program's own model sends each token of one
        micro-batch to, per E layer (forward only, at the given weights)."""
        import jax

        def chosen(p, i):
            _, kept = self.model.apply({"params": p}, i, method="hidden_states",
                                       mutable=["intermediates"])
            return kept["intermediates"]

        kept = jax.device_get(jax.jit(chosen)(params, ids))
        layers = sorted(kept, key=lambda name: int(name.split("_")[1]))
        return [kept[name]["mixer"]["chosen"][0] for name in layers]

    def on_step(self, timed, _check, state, batch, rest):
        import jax
        import numpy as np

        index = self.calls
        self.calls += 1
        checking = index < self.check_updates
        if checking:
            self.fed.append(np.asarray(jax.device_get(batch["input_ids"])))
            if index == 0:
                with base.persist_small_compiles():
                    self.chosen = self.routing(state.params,
                                               batch["input_ids"][0])
        elif index == self.check_updates:  # the warm-up update
            out = timed(state, batch, *rest)
            jax.block_until_ready(out[0])
            return out
        elif self.t_open is None:
            jax.block_until_ready(state)
            gc.collect()  # not somewhere in the window (kinds/train.py)
            self.in_use_at_open = max(
                (d.memory_stats() or {}).get("bytes_in_use", 0)
                for d in jax.local_devices())
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
                self.tracing = True
            self.t_open = now()
        with jax.profiler.TraceAnnotation("bench:dispatch"):
            t0 = now()
            out = timed(state, batch, *rest)
            t1 = now()
        new_state, metrics = out[0], out[1]
        if checking:
            with base.persist_small_compiles():
                jax.block_until_ready(new_state)
                self.losses.append(float(metrics["loss"]))
                self.counters.append(metrics)
                if index == 0:
                    self.grad_global = float(metrics["grad_norm"])
                    clip = min(1.0, self.max_grad_norm / (self.grad_global + 1e-6))
                    self.unclip = 1.0 / ((1.0 - self.b1) * clip)
                    norms = jax.device_get(
                        self.helpers["norms"](new_state.opt_state.mu))
                    self.grad_norms = {k: v * self.unclip
                                       for k, v in norms.items()}
                    self.first_mu = jax.device_get(new_state.opt_state.mu)
                if index == self.check_updates - 1:
                    # leaf by leaf: the seeded weights wait on the host
                    change = jax.tree_util.tree_map(
                        lambda new, old: self.helpers["diff"](new, old),
                        new_state.params, self.seeded_host)
                    self.delta_norms = jax.device_get(
                        self.helpers["norms"](change))
                    del change
                    self.seeded_host = None
            return out
        self.dispatch_s.append(t1 - t0)
        self.called_at.append(t0)
        self.tokens.append(int(np.prod(batch["input_ids"].shape)))
        self.finite.append(metrics["finite"])
        self.counters.append(metrics)
        elapsed = now() - self.t_open
        traced_enough = self.tracing and (
            len(self.tokens) >= self.trace_updates or elapsed >= self.seconds)
        if traced_enough or (not self.tracing and elapsed >= self.seconds):
            jax.block_until_ready(new_state)
            self.t_close = now()
            if self.tracing:
                jax.profiler.stop_trace()
                self.tracing = False
            self.args.max_steps = 0  # the trainer's loop ends after this update
        return out


@contextlib.contextmanager
def probed(pretrain, probes):
    """The ``train`` kind's context manager, with this kind's seeded init and
    a step that is its own check."""
    import jax

    from benchmarks.reference import nemotron_h_f32 as ref
    from benchmarks.reference import nemotron_h_map

    real_init, real_step = pretrain.make_init_fn, pretrain.make_train_step

    def make_init_fn(model, tx, sample_inputs, shardings):
        template = real_init(model, tx, sample_inputs, shardings)

        def seeded_init(rng):
            def build(key, rng):
                params = nemotron_h_map.to_program(
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
                    f"reference/nemotron_h_map.py describes:\n{want}\n{got}")
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


def trainer_argv(mix, chips, seed, work, model_config_file):
    recipe = mix["recipe"]
    argv = [
        "--input_dir", os.path.join(work, "shards"),
        "--output_dir", os.path.join(work, "out"),
        "--model_config_file", model_config_file,
        "--local_batch_size", str(mix["local_batch_size"]),
        "--global_batch_size", str(mix["global_batch_size_per_chip"] * chips),
        "--optimizer", "adamw", "--adamw_clip",
        "--adam_beta2", str(recipe["b2"]), "--adam_eps", str(recipe["eps"]),
        "--weight_decay", str(recipe["weight_decay"]),
        "--max_grad_norm", str(recipe["max_grad_norm"]),
        "--learning_rate", str(recipe["learning_rate"]),
        "--warmup_proportion", str(recipe["warmup_proportion"]),
        "--max_steps", str(recipe["max_steps"]),
        "--lr_decay", "constant",
        "--seed", str(seed % (2 ** 31 - 1)),
        "--skip_final_checkpoint", "--disable_tensorboard",
    ]
    if chips > 1:
        argv += ["--mesh", f"dp={chips}"]
    return argv + list(mix.get("trainer_args", []))


def drive(ctx: dict, work: str):
    """Set-up and window: the trainer's own ``main`` under the probes."""
    from benchmarks.reference import nemotron_h_f32 as ref
    from benchmarks.traffic import generate_lm

    import run_pretraining
    from bert_pytorch_tpu import pretrain

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

    from benchmarks.reference import compare_lm, nemotron_h_map
    from benchmarks.reference import nemotron_h_f32 as ref
    from benchmarks.traffic import generate_lm

    mix, config, seed = ctx["mix"], ctx["config"], int(ctx["seed"])
    check, recipe = mix["check"], ref.Recipe(**mix["recipe"])
    feed_faults = [f for u in probes.fed for f in generate_lm.check_fed_rows(
        u, known, int(config["vocab_size"]))]
    first_gradient = nemotron_h_map.from_program(jax.tree_util.tree_map(
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
    chosen = reference.pop("chosen")
    numbers = compare_lm.numbers(program, reference)
    numbers["feed_faults"] = float(len(feed_faults))
    correct, lines = compare_lm.judge(numbers, check["limits"])
    flips = compare_lm.routing_flip_share(probes.chosen, chosen)
    for line in ["feed fault: " + f for f in feed_faults] + lines:
        print(line)
    print(f"compare routing_flip_share: {flips:.6g} (printed, not judged)")
    numbers["routing_flip_share"] = flips
    controls, raw = {}, {"program": program, "reference": reference}
    for precision in ctx.get("controls", ()):  # margins.py only
        raw[precision] = follow(precision, first_gradient_to_compare=ref_gradient)
        raw[precision].pop("chosen")
        controls[precision] = compare_lm.numbers(raw[precision], reference)
    return correct, numbers, controls, raw


def traced_metrics(ctx: dict, probes: Probes, updates: int, device: dict,
                   counters: dict) -> dict:
    """Per-layer metrics and the breakdown from the traced window. The
    readers' context holds the ``train`` kind's keys and, for this kind's
    readers, ``config``, ``mix``, ``counters`` (per update) and ``trace_dir``."""
    from benchmarks.trace import flops, flops_lm, reduce

    chips = device["count"]
    summary = reduce.summarize(reduce.read_trace_dir(probes.trace_dir))
    device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
    reader_ctx = {
        "summary": summary, "updates": updates, "chips": chips,
        "dispatch_s": probes.dispatch_s, "data_wait_s": probes.data_wait_s,
        "flops_per_update": flops_lm.train_flops_per_update(
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
    work = tempfile.mkdtemp(prefix="bench_train_lm_")
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
            {k: v for k, v in m.items() if k.startswith("moe_")}
            for m in probes.counters])
        in_window = fetched[probes.check_updates:]
        counters = {name: float(statistics.fmean(m[name] for m in in_window))
                    for name in (in_window[0] if in_window else {})}
        dropped = sum(float(m.get("moe_dropped_slots", 0.0)) for m in fetched)
        peak, allocator_peak, temp = base.memory_peak_bytes(
            os.path.join(work, "out"), probes.in_use_at_open)
        devices = jax.devices()[:chips]
        limit = (devices[0].memory_stats() or {}).get("bytes_limit")
        if limit:
            # What was live at the window's opening plus the compiler's figure
            # for the step's temporaries can pass what the chip has (the state
            # is donated to the step and partly counted in both); the chip
            # cannot hold more than its limit, so that is the most reported.
            peak = min(peak, int(limit))
        gc.collect()
        t_compare = now()
        correct, numbers, controls, raw = compare_with_reference(
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
              f"window {probes.compiles_in_window}; routing counters per "
              f"update {counters}; dropped slots over the run {dropped:.0f}")
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        result = {"correct": bool(correct) and dropped == 0,
                  "attempted": len(tokens),
                  "failed": sum(1 for f in finite if f != 1.0),
                  "metrics": {}, "device": device,
                  # not on the result line (run.py prints the contract's keys):
                  "compiles_in_window": probes.compiles_in_window,
                  "counters": counters, "dropped_slots": dropped,
                  "readings": numbers, "comparison_s": comparison_s}
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
            from benchmarks.trace import flops, flops_lm
            share = (flops_lm.train_flops_per_update(ctx["config"], ctx["mix"], chips)
                     * len(tokens) / window_s
                     / (chips * flops.peak_flops(device["kind"])))
            print("model FLOP/s utilization over the window (end to end, from "
                  f"wall time): {100 * share:.2f}%")
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)
