"""The one general generator of training traffic: pretraining shards from a
mix's parameters (a data file beside this one) and ``--seed``.

A mix fixes the sequence length, how long the sequences are, how many there
are and how they are spread over shards. One multiset of ``group`` lengths
(``group`` = the sequences of one optimizer update) is drawn from the mix's own
``draw_seed`` and every group of ``group`` consecutive rows holds exactly that
multiset, in an order the run's seed decides; the trainer's sampler reads rows
in file order, so every update of every seed trains on the same number of real
tokens. Token ids, the split into two segments, the next-sentence labels and
the order come from the run's seed.

Length draw, after google-research/bert ``create_pretraining_data.py``:
a sequence fills ``seq_len`` except with probability ``short_seq_prob``,
when its length is uniform between ``min_tokens`` and ``seq_len``.

Shard layout: the trainer's own input format (``tools/encode_data.py`` of the
program): ``input_ids`` [N, S] padded with 0, ragged
``special_token_positions`` ([CLS], first [SEP], last [SEP]) and
``next_sentence_labels``; the trainer's loader masks on the fly. Token ids are
uniform over the published vocabulary's ordinary entries, so lookups and the
decoder touch the whole embedding table.
"""

from __future__ import annotations

import hashlib
import os

import numpy as np

CLS, SEP, MASK = 101, 102, 103   # bert-base/large-uncased vocab.txt
FIRST_ORDINARY_ID = 1000         # below: [PAD], [unused*], specials, glyphs


def group_lengths(mix: dict, group: int) -> np.ndarray:
    """The multiset of ``group`` sequence lengths (tokens, specials included)
    that every update of the mix trains on."""
    spec = mix["lengths"]
    rng = np.random.default_rng(int(spec["draw_seed"]))
    seq = int(mix["seq_len"])
    short = rng.random(group) < float(spec["short_seq_prob"])
    drawn = rng.integers(int(spec["min_tokens"]), seq + 1, group)
    return np.where(short, drawn, seq).astype(np.int32)


def make_rows(mix: dict, vocab_size: int, seed: int, group: int):
    """(input_ids [N, S], specials [N, 3], next_sentence [N]) for one seed."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xBE27]))
    seq, n = int(mix["seq_len"]), int(mix["sequences"])
    if n % group:
        raise ValueError(f"{n} sequences are not whole groups of {group}")
    lengths = rng.permuted(
        np.tile(group_lengths(mix, group), (n // group, 1)), axis=1).reshape(-1)
    ids = rng.integers(FIRST_ORDINARY_ID, vocab_size, (n, seq), dtype=np.int32)
    # first [SEP] somewhere strictly inside, leaving a token on each side
    first_sep = 2 + (rng.random(n) * (lengths - 4)).astype(np.int32)
    last = lengths - 1
    cols = np.arange(seq, dtype=np.int32)[None, :]
    ids[cols >= lengths[:, None]] = 0
    ids[:, 0] = CLS
    ids[np.arange(n), first_sep] = SEP
    ids[np.arange(n), last] = SEP
    specials = np.stack([np.zeros(n, np.int32), first_sep, last], axis=1)
    next_sentence = rng.integers(0, 2, n).astype(np.int8)
    return ids, specials, next_sentence


def row_digest(row: np.ndarray) -> bytes:
    return hashlib.blake2b(np.ascontiguousarray(row, np.int32).tobytes(),
                           digest_size=12).digest()


def write_shards(mix: dict, vocab_size: int, seed: int, out_dir: str,
                 group: int) -> dict:
    """Write the mix's shards for ``seed`` under ``out_dir``. Returns
    {digest of an unmasked row: its next-sentence label}, which the check of
    the feed uses to tell that what reached the step came from these rows."""
    import h5py

    ids, specials, next_sentence = make_rows(mix, vocab_size, seed, group)
    os.makedirs(out_dir, exist_ok=True)
    shards = int(mix["shards"])
    bounds = np.linspace(0, len(ids), shards + 1).astype(int)
    ragged = h5py.vlen_dtype(np.dtype("i4"))
    for s in range(shards):
        lo, hi = bounds[s], bounds[s + 1]
        with h5py.File(os.path.join(out_dir, f"shard_{s:03d}.hdf5"), "w") as f:
            f.create_dataset("input_ids", data=ids[lo:hi], dtype="i4")
            ds = f.create_dataset("special_token_positions", (hi - lo,), dtype=ragged)
            for i in range(hi - lo):
                ds[i] = specials[lo + i]
            f.create_dataset("next_sentence_labels", data=next_sentence[lo:hi],
                             dtype="i1")
    return {row_digest(ids[i]): int(next_sentence[i]) for i in range(len(ids))}


def check_fed_rows(batch: dict, known: dict, max_predictions: int) -> list:
    """What is wrong with a batch the step was fed, as a list of strings
    (empty = sound): every row must be one of the generated rows under a
    legal mask (labels name the original token; at most ``max_predictions``
    of them, at least one; padding never masked), and no row may repeat."""
    faults = []
    ids = np.asarray(batch["input_ids"]).reshape(-1, batch["input_ids"].shape[-1])
    labels = np.asarray(batch["masked_lm_labels"]).reshape(ids.shape)
    mask = np.asarray(batch["input_mask"]).reshape(ids.shape)
    nsp = np.asarray(batch["next_sentence_labels"]).reshape(-1)
    original = np.where(labels >= 0, labels, ids)
    seen = set()
    for i in range(len(ids)):
        digest = row_digest(original[i])
        if digest not in known:
            faults.append(f"row {i}: not one of the generated rows")
        elif known[digest] != int(nsp[i]):
            faults.append(f"row {i}: next-sentence label differs")
        if digest in seen:
            faults.append(f"row {i}: repeats within the update")
        seen.add(digest)
        n_masked = int((labels[i] >= 0).sum())
        if not 1 <= n_masked <= max_predictions:
            faults.append(f"row {i}: {n_masked} masked positions")
        if ((labels[i] >= 0) & (mask[i] == 0)).any():
            faults.append(f"row {i}: padding masked")
        if int(mask[i].sum()) != int((original[i] != 0).sum()):
            faults.append(f"row {i}: input_mask does not cover the tokens")
    return faults[:10]
