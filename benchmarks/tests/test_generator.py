import collections
import glob
import hashlib
import os

import numpy as np
import pytest

from benchmarks.traffic import generate

GROUP = 100
MIX = {"seq_len": 64, "sequences": 2000, "shards": 3,
       "lengths": {"draw_seed": 0, "short_seq_prob": 0.1, "min_tokens": 8}}


def _digest(directory):
    h = hashlib.sha256()
    for path in sorted(glob.glob(os.path.join(directory, "*.hdf5"))):
        import h5py
        with h5py.File(path, "r") as f:
            for key in sorted(f.keys()):
                for row in f[key][:]:
                    h.update(np.asarray(row).tobytes())
    return h.hexdigest()


def test_one_seed_twice_is_identical_and_two_seeds_differ(tmp_path):
    big = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    a = generate.write_shards(MIX, 30522, big, str(tmp_path / "a"), GROUP)
    b = generate.write_shards(MIX, 30522, big, str(tmp_path / "b"), GROUP)
    c = generate.write_shards(MIX, 30522, big + 1, str(tmp_path / "c"), GROUP)
    assert _digest(tmp_path / "a") == _digest(tmp_path / "b")
    assert _digest(tmp_path / "a") != _digest(tmp_path / "c")
    assert a == b and a != c and len(a) == MIX["sequences"]


def test_every_update_of_every_seed_gets_the_same_lengths_in_another_order():
    one = generate.make_rows(MIX, 30522, 1, GROUP)[1][:, 2] + 1
    two = generate.make_rows(MIX, 30522, 2, GROUP)[1][:, 2] + 1
    want = collections.Counter(generate.group_lengths(MIX, GROUP).tolist())
    for lengths in (one, two):
        for update in lengths.reshape(-1, GROUP):
            assert collections.Counter(update.tolist()) == want
    assert (one != two).any() and (one[:GROUP] != one[GROUP:2 * GROUP]).any()
    with pytest.raises(ValueError):
        generate.make_rows(MIX, 30522, 1, 300)


def test_length_histogram_matches_the_stated_draw():
    lengths = generate.group_lengths(MIX, 40000)
    full = (lengths == MIX["seq_len"]).mean()
    # P(full) = 1 - p + p / (seq - min + 1)
    assert abs(full - (0.9 + 0.1 / 57)) < 0.01
    short = lengths[lengths < MIX["seq_len"]]
    assert short.min() >= 8 and abs(short.mean() - (8 + 63) / 2) < 1.0


def test_rows_are_well_formed_and_ids_span_the_vocabulary():
    ids, specials, nsp = generate.make_rows(MIX, 30522, 7, GROUP)
    rows = np.arange(len(ids))
    assert (ids[:, 0] == generate.CLS).all()
    assert (ids[rows, specials[:, 1]] == generate.SEP).all()
    assert (ids[rows, specials[:, 2]] == generate.SEP).all()
    assert (0 < specials[:, 1]).all() and (specials[:, 1] < specials[:, 2]).all()
    content = ids[(ids != 0) & (ids != generate.CLS) & (ids != generate.SEP)]
    assert content.min() >= generate.FIRST_ORDINARY_ID and content.max() > 30000
    assert set(nsp.tolist()) == {0, 1}


def test_check_of_the_feed_tells_a_foreign_row_and_an_illegal_mask():
    ids, specials, nsp = generate.make_rows(MIX, 30522, 7, GROUP)
    known = {generate.row_digest(ids[i]): int(nsp[i]) for i in range(len(ids))}
    rows = ids[:4].copy()
    labels = np.full_like(rows, -1)
    labels[:, 5] = rows[:, 5]
    rows[:, 5] = generate.MASK
    batch = {"input_ids": rows[None], "masked_lm_labels": labels[None],
             "input_mask": (ids[:4] != 0).astype(np.int32)[None],
             "next_sentence_labels": nsp[:4].astype(np.int32)[None]}
    assert generate.check_fed_rows(batch, known, 20) == []
    foreign = dict(batch, input_ids=batch["input_ids"].copy())
    foreign["input_ids"][0, 0, 7] += 1
    assert any("not one of" in f for f in generate.check_fed_rows(foreign, known, 20))
    unmasked = dict(batch, masked_lm_labels=np.full_like(labels, -1)[None],
                    input_ids=ids[:4][None])
    assert any("0 masked" in f for f in generate.check_fed_rows(unmasked, known, 20))
    repeated = {k: np.concatenate([v, v], axis=1) for k, v in batch.items()}
    assert any("repeats" in f for f in generate.check_fed_rows(repeated, known, 20))
