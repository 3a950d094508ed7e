"""The step names itself: spans in the trainer's loop, scopes in the jitted
step (ISSUE 25; docs/telemetry.md "Profiler trace windows").

A three-update tiny ``run_pretraining.main`` runs twice: once under a CPU
``jax.profiler`` session the TEST opens (the program's own window stays off,
so what is checked is that the spans are written whoever opened the session),
once with no session. The compiled text of a tiny step is checked for the
scope names and for the pass markers JAX writes itself.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bert_pytorch_tpu import optim, pretrain
from bert_pytorch_tpu.telemetry.profiler import SPANS

# What the tiny run below does not do: no validation set, no checkpoint to
# resume from.
NOT_EXERCISED = ("train:eval", "startup:restore")
STEPS = 3


def _tiny_run(tmp, out_name):
    import run_pretraining
    from bert_pytorch_tpu.tools.make_synthetic_data import make_shard

    data = tmp / "data"
    if not data.exists():
        data.mkdir()
        # 8 updates an epoch: the feed, some batches ahead of the 3 updates
        # trained, stays inside epoch 0 (a second pass over the shards would
        # load them again, each load on a thread of its own)
        for i in range(2):
            make_shard(str(data / f"shard_{i}.hdf5"), 128, 32, 1000, seed=i)
        (tmp / "model.json").write_text(json.dumps({
            "vocab_size": 1000, "hidden_size": 32, "num_hidden_layers": 2,
            "num_attention_heads": 4, "intermediate_size": 64,
            "max_position_embeddings": 32, "type_vocab_size": 2,
            "next_sentence": True, "mask_token_id": 4}))
    losses = []
    from bert_pytorch_tpu.utils import logging as logger

    real_log = logger.log

    def keep(**record):
        if record.get("tag") == "train":
            losses.append(record["step_loss"])
        return real_log(**record)

    logger.log = keep
    try:
        run_pretraining.main(run_pretraining.parse_arguments([
            "--input_dir", str(data), "--output_dir", str(tmp / out_name),
            "--model_config_file", str(tmp / "model.json"),
            "--global_batch_size", "32", "--local_batch_size", "2",
            "--max_steps", str(STEPS), "--steps", str(STEPS),
            "--learning_rate", "1e-3", "--warmup_proportion", "0.25",
            # a checkpoint at update 2, a device sync at updates 1 and 3
            "--num_steps_per_checkpoint", "2", "--telemetry_sync_every", "2",
            "--dtype", "float32", "--seed", "7", "--skip_final_checkpoint",
            "--disable_tensorboard"]))
    finally:
        logger.log = real_log
    return losses


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """{"lines": [[(name, start, end, stats)]] per host thread of the traced
    run, "traced": its losses, "plain": the untraced run's losses,
    "plain_dir": the untraced run's output directory}."""
    from jax.profiler import ProfileData

    tmp = tmp_path_factory.mktemp("spans")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the annotations are the host tracer's
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=options)
    try:
        traced = _tiny_run(tmp, "out_traced")
    finally:
        jax.profiler.stop_trace()
    plain = _tiny_run(tmp, "out_plain")
    jax.config.update("jax_default_prng_impl", "threefry2x32")
    [path] = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                       recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in line.events
                      if e.name == "train" or e.name in SPANS]
            if events:
                lines.append(events)
    return {"lines": lines, "traced": traced, "plain": plain,
            "plain_dir": str(tmp / "out_plain")}


def _line_of(runs, name):
    return [line for line in runs["lines"]
            if any(e[0] == name for e in line)]


@pytest.mark.parametrize("name", SPANS)
def test_every_span_the_run_exercises_is_in_the_trace(runs, name):
    found = _line_of(runs, name)
    if name in NOT_EXERCISED:
        assert not found
        return
    # (a shard's load is a thread of its own, ``data/dataset.py
    # _async_load_file``: two loads share a line only where the first thread's
    # id is free again before the second starts, which a loaded host does not
    # promise)
    assert found if name == "data:shard_load" else len(found) == 1, (
        f"{name} on {len(found)} threads")
    count = sum(1 for line in found for e in line if e[0] == name)
    if name in ("train:feed", "train:dispatch", "train:telemetry",
                "train:fetch_metrics", "train:log"):
        assert count == STEPS
    elif name == "train:sync":
        assert count == 2       # --telemetry_sync_every 2: updates 1 and 3
    elif name == "train:checkpoint":
        assert count == 1       # update 2
    elif name.startswith("startup:"):
        assert count == 1       # once, before the loop (the session was
        #                         opened by hand before main)
    else:
        assert count >= 1       # per batch / per shard, ahead of the loop


def test_train_spans_nest_in_their_step_annotation(runs):
    [loop] = _line_of(runs, "train")
    steps = [e for e in loop if e[0] == "train"]
    assert [e[3]["step_num"] for e in steps] == [1, 2, 3]
    for (_, _, end, _), (_, start, _, _) in zip(steps, steps[1:]):
        assert end <= start     # one step at a time
    for name, start, end, _ in loop:
        if name == "train":
            continue
        if name.startswith("startup:"):     # main's thread, before the loop
            assert end <= steps[0][1]
            continue
        assert name.startswith("train:")
        inside = [s[3]["step_num"] for s in steps
                  if s[1] <= start and end <= s[2]]
        assert len(inside) == 1, f"{name} lies in steps {inside}"
    # the order of one step, here update 2 (no sync, a checkpoint)
    second = [e[0] for e in loop if steps[1][1] <= e[1] < steps[1][2]]
    assert second == ["train", "train:feed", "train:dispatch",
                      "train:telemetry", "train:fetch_metrics", "train:log",
                      "train:checkpoint"]


def test_feeding_threads_write_their_own_spans(runs):
    [loop] = _line_of(runs, "train")
    [producer] = _line_of(runs, "prefetch:source_wait")
    assert producer is not loop
    assert {e[0] for e in producer} == {"prefetch:source_wait",
                                        "prefetch:h2d",
                                        "prefetch:epoch_start"}
    # the epoch's start lies inside the pull that met it
    [(_, start, end, stats)] = [e for e in producer
                                if e[0] == "prefetch:epoch_start"]
    assert stats["epoch"] == 0
    assert any(s <= start and end <= e for name, s, e, _ in producer
               if name == "prefetch:source_wait")
    [loader] = _line_of(runs, "data:collate")
    shards = _line_of(runs, "data:shard_load")  # a thread a load
    assert len({id(loop), id(producer), id(loader)}) == 3
    assert shards and not {id(loop), id(producer), id(loader)} & {
        id(line) for line in shards}


def test_without_a_session_nothing_is_traced_and_the_losses_are_the_same(runs):
    assert len(runs["traced"]) == STEPS
    assert runs["plain"] == runs["traced"]
    assert not glob.glob(os.path.join(runs["plain_dir"], "**", "*.xplane.pb"),
                         recursive=True)


# -- the jitted step -------------------------------------------------------

@pytest.fixture(scope="module")
def step_text():
    """Compiled text of a tiny ``make_train_step`` (LAMB, two micro-batches,
    dropout on, ``remat dots``, grad-health block on)."""
    from bert_pytorch_tpu.config import BertConfig
    from bert_pytorch_tpu.models import BertForPreTraining

    config = BertConfig(
        vocab_size=512, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=32)
    model = BertForPreTraining(config, dtype=jnp.float32, remat="dots")
    accum, rows, seq = 2, 4, 16
    batch = {key: np.zeros((accum, rows, seq), np.int32)
             for key in ("input_ids", "segment_ids")}
    batch["input_mask"] = np.ones((accum, rows, seq), np.int32)
    batch["masked_lm_labels"] = np.full((accum, rows, seq), -1, np.int32)
    batch["masked_lm_labels"][:, :, 3] = 5
    batch["next_sentence_labels"] = np.zeros((accum, rows), np.int32)
    tx = optim.lamb(1e-3)
    sample = (jnp.zeros((1, seq), jnp.int32),) * 3
    state = pretrain.make_init_fn(model, tx, sample, None)(
        jax.random.PRNGKey(0))
    step = pretrain.make_train_step(model, tx, max_pred_per_seq=4,
                                    stats_every=4)
    # as every entry point runs (utils/compile_cache.py): an op's location
    # holds the line that wrote it, not its callers
    limit = "jax_traceback_in_locations_limit"
    before = getattr(jax.config, limit)
    jax.config.update(limit, 1)
    try:
        return step.lower(state, batch).compile().as_text()
    finally:
        jax.config.update(limit, before)


def _op_names(text):
    import re

    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.mark.parametrize("scope", pretrain.SCOPES)
def test_every_scope_reaches_the_compiled_step(step_text, scope):
    assert any(f"/{scope}/" in name or f"({scope})" in name
               for name in _op_names(step_text)), scope


def test_the_pass_is_in_the_op_name(step_text):
    names = _op_names(step_text)
    layers = [n for n in names if "/layers/" in n]
    assert any("jvp(" in n and "transpose(" not in n for n in layers)
    assert any("transpose(jvp(" in n for n in layers)
    remat = [n for n in layers if "rematted_computation" in n]
    assert any("transpose(" in n for n in remat)  # recomputed in the backward
    # the optimizer and the step's metrics lie outside the micro-batch scan
    assert any(n.startswith("jit(step_fn)/optimizer/lamb/") for n in names)
    assert any(n.startswith("jit(step_fn)/optimizer/clip/") for n in names)
    assert any(n.startswith("jit(step_fn)/step_metrics/") for n in names)
    assert any("/micro_batches/while/body/" in n and "/grad_accumulate/" in n
               for n in names)


def test_an_executable_with_other_names_is_not_served_from_the_cache(
        persistent_cache, monkeypatch):
    """A cached executable keeps the op names it was compiled with, and a
    trace shows them: so the names are in the cache key
    (utils/compile_cache.py), and the same arithmetic under another scope
    is another entry."""
    from bert_pytorch_tpu.utils import compile_cache

    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    flags = ("jax_compilation_cache_include_metadata_in_key",
             "jax_traceback_in_locations_limit")
    before = [getattr(jax.config, flag) for flag in flags]

    def scoped(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2.0
        return jax.jit(f)

    def entries():
        return {name for name in os.listdir(persistent_cache)
                if name.startswith("jit_f") and not name.endswith("-atime")}

    try:
        compile_cache.enable_compile_cache(persistent_cache,
                                           min_compile_secs=0.0)
        assert getattr(jax.config, flags[0])
        x = jnp.arange(8.0)
        scoped("clip")(x)
        first = entries()
        scoped("clip")(x)   # the line it is called from is not in the key
        assert entries() == first and len(first) == 1
        scoped("lamb")(x)
        assert len(entries()) == 2
    finally:
        for flag, value in zip(flags, before):
            jax.config.update(flag, value)
