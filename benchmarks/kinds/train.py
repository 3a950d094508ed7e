"""The ``train`` kind of cell: pretraining through ``run_pretraining.main``.

The system under test is the trainer's own entry: ``run_pretraining.main``
runs in this process with its loader, device prefetch, accumulation and
logging, on shards this benchmark wrote from the seed. The benchmark puts
three probes round calls into the program's layers and edits nothing:

* ``pretrain.make_init_fn``: the state the trainer builds gets its weights
  from the seed by the benchmark's generator (the reference draws the same).
* ``pretrain.make_train_step``: the step the trainer builds, with the
  configuration's dropout on, is the timed one. A float32 reference cannot
  draw the program's dropout masks, so the check's updates go through a
  second step, built by the same call from the same model with both dropout
  rates at 0: the first ``check.updates`` calls are the check's (what they
  were fed, each loss, the optimizer's first gradient, the parameters'
  change); the timed step takes over that state, runs one update as its
  warm-up, and then the window opens on it.
* ``pretrain.device_prefetch``: the feed is timed.

The window: opens on the timed step when everything before it (the check's
updates, the timed step's warm-up update) has finished on the device,
counts whole optimizer updates, and closes when the first update dispatched
after ``--seconds`` has finished; tokens are the unpadded tokens of those
updates, time is open to close. The run ends by lowering ``args.max_steps``,
so the trainer leaves its loop by its own clean path.

After the trainer has returned and its state is freed, the float32 reference
follows the same updates from the same seed and the two are compared.
"""

from __future__ import annotations

import contextlib
import copy
import gc
import glob
import json
import os
import shutil
import statistics
import tempfile
import time

now = time.perf_counter


class ChipError(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise ChipError(f"no TPU: JAX reports platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise ChipError(f"the cell needs {chips} chips, JAX reports {len(devices)}")
    return devices


@contextlib.contextmanager
def persist_small_compiles():
    """Let the benchmark's own small programs into the persistent cache (the
    trainer only persists compiles of 10 s and more)."""
    import jax

    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    jax.config.update(name, 0)
    try:
        yield
    finally:
        jax.config.update(name, before)


class Probes:
    """Everything the probes record in one run."""

    def __init__(self, args, seed, sizes, check_updates, seconds,
                 trace_dir=None, trace_updates=4, b1=0.9, max_grad_norm=1.0):
        import jax
        import jax.numpy as jnp

        from benchmarks.reference import bert_f32, program_map

        self.args, self.seed, self.sizes = args, seed, sizes
        # After one update the optimizer's first moment holds (1 - b1) of the
        # gradient it got, scaled by its own clipping to max_grad_norm:
        self.b1, self.max_grad_norm = b1, max_grad_norm
        self.first_mu = None  # host copy of that first moment
        self.unclip = None    # first moment -> the gradient the optimizer got
        self.check_updates, self.seconds = check_updates, seconds
        self.trace_dir, self.trace_updates = trace_dir, trace_updates
        self.calls = 0
        self.dropout = {}  # step -> the two rates its model was built with
        self.fed, self.losses = [], []
        self.grad_global = self.grad_norms = self.delta_norms = None
        self.t_open = self.t_close = None
        self.tracing = False
        self.dispatch_s, self.data_wait_s, self.called_at = [], [], []
        self.tokens, self.finite = [], []
        self.compiles_in_window = 0
        self.gc_pauses, self._gc_started = [], None  # full collections in the window
        self.in_use_at_open = 0

        def delta(params, key):
            start = program_map.to_program(
                bert_f32.seeded_params(key, sizes), sizes["A"])
            return program_map.leaf_norms(
                jax.tree_util.tree_map(lambda a, b: a - b, params, start))

        # small programs of the benchmark's own, compiled in set-up
        self.helpers = {"norms": jax.jit(program_map.leaf_norms),
                        "delta": jax.jit(delta),
                        "tokens": jax.jit(lambda m: jnp.sum(m))}

    @property
    def window_open(self):
        return self.t_open is not None and self.t_close is None

    def on_compile(self, event, *_a, **_k):
        if self.window_open and "backend_compile" in event:
            self.compiles_in_window += 1

    def on_gc(self, phase, info):
        if info["generation"] == 2 and self.window_open:
            if phase == "start":
                self._gc_started = now()
            elif self._gc_started is not None:
                self.gc_pauses.append(now() - self._gc_started)
                self._gc_started = None

    def on_feed(self, seconds):
        if self.window_open:
            self.data_wait_s.append(seconds)

    def on_step(self, timed, check, state, batch, rest):
        import jax

        from benchmarks.reference import bert_f32

        index = self.calls
        self.calls += 1
        helpers = self.helpers
        checking = index < self.check_updates
        fn = check if checking else timed
        if checking:
            self.fed.append(jax.device_get(batch))
        elif index == self.check_updates:  # the timed step's warm-up update
            out = fn(state, batch, *rest)
            jax.block_until_ready(out[0])
            return out
        elif self.t_open is None:
            jax.block_until_ready(state)
            # Tracing and compiling leave the collector close to a full pass
            # over some 10^5 objects; taken here, it does not stall the loop
            # for some tenths of a second somewhere in the window.
            gc.collect()
            self.in_use_at_open = max(
                (d.memory_stats() or {}).get("bytes_in_use", 0)
                for d in jax.local_devices())
            if self.trace_dir:
                jax.profiler.start_trace(self.trace_dir)
                self.tracing = True
            self.t_open = now()
        with jax.profiler.TraceAnnotation("bench:dispatch"):
            t0 = now()
            out = fn(state, batch, *rest)
            t1 = now()
        new_state, metrics = out[0], out[1]
        if checking:
            with persist_small_compiles():
                jax.block_until_ready(new_state)
                self.losses.append(float(metrics["loss"]))
                float(helpers["tokens"](batch["input_mask"]))  # warms it
                if index == 0:
                    self.grad_global = float(metrics["grad_norm"])
                    clip = min(1.0, self.max_grad_norm / (self.grad_global + 1e-6))
                    self.unclip = 1.0 / ((1.0 - self.b1) * clip)
                    norms = jax.device_get(helpers["norms"](new_state.opt_state.mu))
                    self.grad_norms = {k: v * self.unclip for k, v in norms.items()}
                    t_copy = now()
                    self.first_mu = jax.device_get(new_state.opt_state.mu)
                    print(f"first moment copied to the host in {now() - t_copy:.2f} s")
                if index == self.check_updates - 1:
                    self.delta_norms = jax.device_get(helpers["delta"](
                        new_state.params, bert_f32.key_from_seed(self.seed)))
            return out
        self.dispatch_s.append(t1 - t0)
        self.called_at.append(t0)
        self.tokens.append(helpers["tokens"](batch["input_mask"]))
        self.finite.append(metrics["finite"])
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


class _StepProbe:
    """Stands for the timed step (the trainer's cost record lowers it)."""

    def __init__(self, timed, check, probes):
        self._timed, self._check, self._probes = timed, check, probes

    def __getattr__(self, name):
        return getattr(self._timed, name)

    def __call__(self, state, batch, *rest):
        return self._probes.on_step(self._timed, self._check, state, batch, rest)


class _FeedProbe:
    def __init__(self, inner, probes):
        self._inner, self._probes = inner, probes

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __iter__(self):
        import jax

        inner = iter(self._inner)
        while True:
            t0 = now()
            with jax.profiler.TraceAnnotation("bench:data_wait"):
                try:
                    item = next(inner)
                except StopIteration:
                    return
            self._probes.on_feed(now() - t0)
            yield item


@contextlib.contextmanager
def probed(pretrain, probes):
    """Put the three probes round the program's calls; take them off after."""
    import jax

    from benchmarks.reference import bert_f32, program_map

    real = (pretrain.make_init_fn, pretrain.make_train_step,
            pretrain.device_prefetch)

    def make_init_fn(model, tx, sample_inputs, shardings):
        init = real[0](model, tx, sample_inputs, shardings)

        def seeded_init(rng):
            state = init(rng)
            with persist_small_compiles():
                params = jax.jit(
                    lambda key: program_map.to_program(
                        bert_f32.seeded_params(key, probes.sizes), probes.sizes["A"]),
                    out_shardings=shardings.params,
                )(bert_f32.key_from_seed(probes.seed))
            want = jax.tree_util.tree_structure(state.params)
            got = jax.tree_util.tree_structure(params)
            if want != got:
                raise RuntimeError(
                    "the program's parameter tree is not the one "
                    f"reference/program_map.py describes:\n{want}\n{got}")
            return state.replace(params=params)

        return seeded_init

    def make_train_step(model, *a, **k):
        rates = ("hidden_dropout_prob", "attention_probs_dropout_prob")
        no_dropout = copy.copy(model.config)
        for rate in rates:
            setattr(no_dropout, rate, 0.0)
        check_model = model.clone(config=no_dropout)
        for name, built in (("timed", model), ("check", check_model)):
            probes.dropout[name] = [float(getattr(built.config, r)) for r in rates]
        return _StepProbe(real[1](model, *a, **k),
                          real[1](check_model, *a, **k), probes)

    def device_prefetch(*a, **k):
        return _FeedProbe(real[2](*a, **k), probes)

    pretrain.make_init_fn = make_init_fn
    pretrain.make_train_step = make_train_step
    pretrain.device_prefetch = device_prefetch
    jax.monitoring.register_event_duration_secs_listener(probes.on_compile)
    gc.callbacks.append(probes.on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(probes.on_gc)
        (pretrain.make_init_fn, pretrain.make_train_step,
         pretrain.device_prefetch) = real
        jax.monitoring.unregister_event_duration_listener(probes.on_compile)


def trainer_argv(root, mix, chips, seed, work, model_config_file):
    recipe = mix["recipe"]
    argv = [
        "--input_dir", os.path.join(work, "shards"),
        "--output_dir", os.path.join(work, "out"),
        "--model_config_file", model_config_file,
        "--config_file", os.path.join(root, mix["recipe_file"]),
        "--max_predictions_per_seq", str(mix["max_predictions_per_seq"]),
        "--masked_token_fraction", str(mix["masked_token_fraction"]),
        "--local_batch_size", str(mix["local_batch_size"]),
        "--global_batch_size", str(mix["global_batch_size_per_chip"] * chips),
        "--learning_rate", str(recipe["learning_rate"]),
        "--warmup_proportion", str(recipe["warmup_proportion"]),
        "--max_steps", str(recipe["max_steps"]),
        "--lr_decay", "poly",
        "--seed", str(seed % (2 ** 31 - 1)),
        "--skip_final_checkpoint", "--disable_tensorboard",
    ]
    if chips > 1:
        argv += ["--mesh", f"dp={chips}"]
    return argv + list(mix.get("trainer_args", []))


def memory_peak_bytes(out_dir, in_use_at_open):
    """Peak bytes on the fullest chip: what was live when the window opened
    plus the compiled step's temporaries, or the allocator's own peak where
    that is higher (the TPU runtime's peak leaves a running program's
    temporaries out, so it is set-up's peak)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    temp = 0
    for path in glob.glob(os.path.join(out_dir, "*.jsonl")):
        with open(path, encoding="utf-8") as f:
            for line in f:
                if '"compile_cost"' in line and "train_step" in line:
                    temp = max(temp, int(json.loads(line).get("temp_bytes", 0)))
    return (int(max(max(peaks), in_use_at_open + temp)), int(max(peaks)),
            int(temp))


def drive(ctx: dict, work: str):
    """Set-up and window: the trainer's own ``main`` under the probes.
    Returns (probes, digests of the generated rows)."""
    from benchmarks.reference import bert_f32
    from benchmarks.traffic import generate

    import run_pretraining
    from bert_pytorch_tpu import pretrain

    mix, config = ctx["mix"], ctx["config"]
    chips, seed = int(ctx["cell"]["chips"]), int(ctx["seed"])
    sizes = bert_f32.sizes(config)
    known = generate.write_shards(
        mix, int(config["vocab_size"]), seed, os.path.join(work, "shards"),
        group=int(mix["global_batch_size_per_chip"]) * chips)
    args = run_pretraining.parse_arguments(trainer_argv(
        ctx["root"], mix, chips, seed, work, ctx["config_file"]))
    recipe = bert_f32.Recipe(**mix["recipe"])
    probes = Probes(
        args, seed, sizes, int(mix["check"]["updates"]), float(ctx["seconds"]),
        trace_dir=os.path.join(work, "trace") if ctx["trace"] else None,
        trace_updates=int(mix.get("trace_updates", 4)),
        b1=recipe.b1, max_grad_norm=recipe.max_grad_norm)
    with probed(pretrain, probes):
        run_pretraining.main(args)
    if probes.t_close is None:
        raise RuntimeError("the trainer returned before the window closed")
    return probes, known


def compare_with_reference(ctx: dict, probes: Probes, known: dict, devices):
    """The comparison, outside the window, the program's state freed: the
    float32 reference follows the updates the step was fed. Returns (correct,
    the numbers, and for margins.py the controls' numbers and raw readings)."""
    import jax
    import numpy as np

    from benchmarks.reference import bert_f32, compare, program_map
    from benchmarks.traffic import generate

    mix, config, seed = ctx["mix"], ctx["config"], int(ctx["seed"])
    check, recipe = mix["check"], bert_f32.Recipe(**mix["recipe"])
    feed_faults = [f for u in probes.fed for f in generate.check_fed_rows(
        u, known, int(mix["max_predictions_per_seq"]))]
    first_gradient = program_map.from_program(jax.tree_util.tree_map(
        lambda m: np.asarray(m) * probes.unclip, probes.first_mu))
    probes.first_mu = None

    def follow(precision, **kwargs):
        with persist_small_compiles():
            return bert_f32.follow(
                seed, config, recipe, probes.fed, precision,
                int(check["block_rows"]),
                devices=devices if len(devices) > 1 else None, **kwargs)

    reference = follow("f32", first_gradient_to_compare=first_gradient,
                       keep_first_gradient="controls" in ctx)
    del first_gradient
    ref_gradient = reference.pop("first_gradient", None)
    program = {"loss": probes.losses, "grad_global_norm": probes.grad_global,
               "grad_norms": probes.grad_norms, "delta_norms": probes.delta_norms,
               "grad_diff_norms": reference.pop("grad_diff_norms")}
    numbers = compare.numbers(program, reference)
    numbers["feed_faults"] = float(len(feed_faults))
    correct, lines = compare.judge(numbers, check["limits"])
    for line in ["feed fault: " + f for f in feed_faults] + lines:
        print(line)
    controls, raw = {}, {"program": program, "reference": reference}
    for precision in ctx.get("controls", ()):  # margins.py only
        raw[precision] = follow(precision, first_gradient_to_compare=ref_gradient)
        controls[precision] = compare.numbers(raw[precision], reference)
    return correct, numbers, controls, raw


def traced_metrics(ctx: dict, probes: Probes, updates: int, device: dict) -> dict:
    """Per-layer metrics and the breakdown from the traced window; fills the
    device's ``busy_s`` and ``window_s``."""
    from benchmarks.trace import flops, reduce

    chips = device["count"]
    summary = reduce.summarize(reduce.read_trace_dir(probes.trace_dir))
    device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
    reader_ctx = {
        "summary": summary, "updates": updates, "chips": chips,
        "dispatch_s": probes.dispatch_s, "data_wait_s": probes.data_wait_s,
        "flops_per_update": flops.train_flops_per_update(
            ctx["config"], ctx["mix"], chips),
        "peak_flops": flops.peak_flops(device["kind"]),
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
    require_chips(int(ctx["cell"]["chips"]))
    return measure(ctx)


def measure(ctx: dict) -> dict:
    """A run without the look for a chip (tests and rehearsals start here).
    ``ctx``: root, cell, config, config_file, mix, seed, seconds, trace,
    started, readers (name -> reader), units; ``controls`` names precisions
    the reference is also followed in, in the program's place (margins.py)."""
    import jax

    chips = int(ctx["cell"]["chips"])
    work = tempfile.mkdtemp(prefix="bench_train_")
    try:
        probes, known = drive(ctx, work)
        if probes.compiles_in_window:
            raise RuntimeError(
                f"{probes.compiles_in_window} programs compiled inside the "
                "window: set-up has to warm every shape the window uses")
        setup_s = probes.t_open - ctx["started"]
        window_s = probes.t_close - probes.t_open
        tokens = [float(t) for t in jax.device_get(probes.tokens)]
        finite = [float(f) for f in jax.device_get(probes.finite)]
        peak, allocator_peak, temp = memory_peak_bytes(
            os.path.join(work, "out"), probes.in_use_at_open)
        devices = jax.devices()[:chips]
        gc.collect()
        t_compare = now()
        correct, numbers, controls, raw = compare_with_reference(
            ctx, probes, known, devices)
        comparison_s = now() - t_compare
        between = [b - a for a, b in zip(probes.called_at, probes.called_at[1:])]
        print("host in the window, longest / median seconds: step call to step "
              f"call {max(between, default=0):.3f} / "
              f"{statistics.median(between or [0]):.3f}, inside the step call "
              f"{max(probes.dispatch_s):.3f} / "
              f"{statistics.median(probes.dispatch_s):.3f}, waiting for the feed "
              f"{max(probes.data_wait_s, default=0):.3f}; full garbage "
              f"collections {[round(p, 3) for p in probes.gc_pauses]}")
        print(f"window: {len(tokens)} updates, {sum(tokens):.0f} real tokens in "
              f"{window_s:.4f} s; set-up {setup_s:.2f} s; comparison "
              f"{comparison_s:.2f} s; live at window open "
              f"{probes.in_use_at_open} + step temporaries {temp} bytes "
              f"(allocator peak {allocator_peak}); compiles in window "
              f"{probes.compiles_in_window}; dropout rates {probes.dropout}")
        device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        result = {"correct": bool(correct), "attempted": len(tokens),
                  "failed": sum(1 for f in finite if f != 1.0),
                  "metrics": {}, "device": device,
                  # not on the result line (run.py prints the contract's keys):
                  "compiles_in_window": probes.compiles_in_window,
                  "dropout": probes.dropout,
                  "readings": numbers, "comparison_s": comparison_s}
        if "controls" in ctx:
            result["controls"] = controls
            result["raw"] = json.loads(json.dumps(
                raw, default=lambda a: [float(v) for v in a.reshape(-1)]))
        if ctx["trace"]:
            result.update(traced_metrics(ctx, probes, len(tokens), device))
            return result
        result["metrics"] = {
            "train_tokens_per_s": {"value": sum(tokens) / window_s,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        if device["platform"] == "tpu":
            from benchmarks.trace import flops
            share = flops.mfu(ctx["config"], ctx["mix"], chips, device["kind"],
                              len(tokens) / window_s)
            print("model FLOP/s utilization over the window (end to end, from "
                  f"wall time): {100 * share:.2f}%")
        return result
    finally:
        shutil.rmtree(work, ignore_errors=True)
