"""The generator of causal-LM training traffic: rows of token ids from a mix's
parameters (a data file beside this one) and ``--seed``.

Documents have log-normal lengths (``documents.median_tokens``, ``sigma``,
clipped to ``min_tokens`` .. ``max_tokens``); their ids are uniform over the
vocabulary slice but the end-of-document id; they are concatenated, each
followed by ``eod_id``, and the stream is cut into rows of ``seq_len``: every
row is full, and documents run across row ends as they do in a packed
pretraining corpus. Everything comes from the run's seed.

Shard layout: the trainer's token-row format (``data.TokenRowsDataset`` of
the program): ``input_ids`` [N, S] int32 and nothing else.
"""

from __future__ import annotations

import os

import numpy as np

from benchmarks.traffic.generate import row_digest


def make_rows(mix: dict, vocab_size: int, seed: int) -> np.ndarray:
    """input_ids [sequences, seq_len] for one seed."""
    spec = mix["documents"]
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x1A27]))
    seq, n = int(mix["seq_len"]), int(mix["sequences"])
    need, eod = n * seq, int(spec["eod_id"])
    lengths = []
    while sum(lengths) + len(lengths) < need:
        drawn = rng.lognormal(np.log(spec["median_tokens"]), spec["sigma"],
                              max(need // int(spec["median_tokens"]), 16))
        lengths += list(np.clip(drawn, spec["min_tokens"],
                                spec["max_tokens"]).astype(np.int64))
    ids = rng.integers(0, vocab_size - 1, need, dtype=np.int32)
    ids += ids >= eod                       # uniform over all ids but eod
    ends = np.cumsum(np.asarray(lengths) + 1) - 1   # where each eod falls
    ids[ends[ends < need]] = eod
    return ids.reshape(n, seq)


def write_shards(mix: dict, vocab_size: int, seed: int, out_dir: str) -> set:
    """Write the mix's shards for ``seed`` under ``out_dir``. Returns the
    digests of the rows: the check of the feed tells by them that what
    reached the step came from these rows."""
    import h5py

    ids = make_rows(mix, vocab_size, seed)
    os.makedirs(out_dir, exist_ok=True)
    bounds = np.linspace(0, len(ids), int(mix["shards"]) + 1).astype(int)
    for s in range(int(mix["shards"])):
        with h5py.File(os.path.join(out_dir, f"shard_{s:03d}.hdf5"), "w") as f:
            f.create_dataset("input_ids", data=ids[bounds[s]:bounds[s + 1]],
                             dtype="i4")
    return {row_digest(row) for row in ids}


def check_fed_rows(fed: np.ndarray, known: set, vocab_size: int) -> list:
    """What is wrong with the rows one update was fed ([micro, rows, S]), as
    a list of strings (empty = sound): each must be a generated row, inside
    the vocabulary slice, and none may repeat."""
    faults, seen = [], set()
    rows = np.asarray(fed).reshape(-1, fed.shape[-1])
    for i, row in enumerate(rows):
        digest = row_digest(row)
        if digest not in known:
            faults.append(f"row {i}: not one of the generated rows")
        if digest in seen:
            faults.append(f"row {i}: repeats within the update")
        seen.add(digest)
        if row.min() < 0 or row.max() >= vocab_size:
            faults.append(f"row {i}: an id outside the vocabulary slice")
    return faults[:10]
