"""Generate synthetic pretraining shards for smoke tests and rehearsals.

Writes HDF5 shards in the same formats the real pipeline produces
(reference utils/encode_data.py:183-210 for the new
``special_token_positions`` format; NVIDIA DeepLearningExamples layout for
the legacy pre-masked format, reference dataset.py:184-192) so the data
runtime and runners can be exercised end-to-end without the real corpus.

``--requests N`` switches to REQUEST-TRACE mode (docs/serving.md): a JSONL
trace of N online-inference requests — mixed task heads, short-biased
text lengths (the same u^2 draw as ``--mixed_lengths``, which is what
makes request packing worth testing), Poisson arrival offsets — plus a
``vocab.txt`` covering the trace's word list, consumed by the serving
smoke test (tests/test_serve.py).
"""

from __future__ import annotations

import argparse
import json
import os

import h5py
import numpy as np

# Word list for synthetic request text; ``write_trace_vocab`` derives a
# WordPiece vocab covering exactly these, so any trace line tokenizes
# without [UNK] under either the C++ or the pure-Python tokenizer.
TRACE_WORDS = (
    "the capital of france is paris what who wrote hamlet shakespeare "
    "william city big a in was by play london england river runs through "
    "where mountain tall old new house red blue green").split()
TRACE_TASKS = ("fill_mask", "classify", "squad", "ner")


def write_trace_vocab(path: str) -> str:
    """WordPiece vocab covering :data:`TRACE_WORDS` + the BERT specials."""
    tokens = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + list(TRACE_WORDS)
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(tokens) + "\n")
    return path


def _trace_text(rng, n_words: int) -> str:
    return " ".join(
        TRACE_WORDS[i]
        for i in rng.integers(0, len(TRACE_WORDS), max(1, n_words)))


def make_request_trace(
    path: str,
    num_requests: int,
    seed: int = 0,
    tasks=TRACE_TASKS,
    max_words: int = 48,
    rate_rps: float = 100.0,
) -> str:
    """Write a JSONL request trace for the serving engine.

    Each line: ``{"id", "arrival_s", "task", "payload"}``. Lengths are
    short-biased (``lo + (max-lo) * u^2`` words — the Wikipedia-style
    spread of ``--mixed_lengths``, so packing has headroom); arrivals are
    Poisson (exponential inter-arrival at ``rate_rps``; 0 = all at t=0,
    a closed-loop saturation replay).
    """
    rng = np.random.default_rng(seed)
    lines = []
    t = 0.0
    for i in range(num_requests):
        if rate_rps > 0:
            t += float(rng.exponential(1.0 / rate_rps))
        task = str(tasks[int(rng.integers(0, len(tasks)))])
        n_words = 3 + int((max_words - 3) * float(rng.random()) ** 2)
        if task == "fill_mask":
            words = _trace_text(rng, n_words).split()
            words[int(rng.integers(0, len(words)))] = "[MASK]"
            payload = {"text": " ".join(words)}
        elif task == "classify":
            payload = {"text": _trace_text(rng, n_words)}
            if rng.random() < 0.3:
                payload["text_pair"] = _trace_text(
                    rng, max(1, n_words // 2))
        elif task == "squad":
            payload = {
                "question": _trace_text(rng, min(8, max(3, n_words // 4))),
                "context": _trace_text(rng, n_words),
            }
        else:  # ner
            payload = {"text": _trace_text(rng, n_words)}
        lines.append(json.dumps({
            "id": i, "arrival_s": round(t, 6), "task": task,
            "payload": payload}))
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    return path


def make_shard(
    path: str,
    num_samples: int,
    seq_len: int,
    vocab_size: int,
    seed: int = 0,
    nsp: bool = True,
    legacy: bool = False,
    max_pred_per_seq: int = 20,
    mixed_lengths: bool = False,
    packed: bool = False,
    max_sequences_per_pack: int = 8,
):
    """``mixed_lengths`` draws content lengths uniformly from nearly the
    whole range (instead of the [S/2, S) default) — a stand-in for the
    Wikipedia-style length distribution that makes sequence packing
    (docs/packing.md) worth ~2x, so packing is exercisable in tests.
    ``packed`` additionally packs the generated samples
    first-fit-decreasing and writes an OFFLINE-PACKED shard
    (data/packing.py layout) instead of the unpacked one."""
    if packed and legacy:
        raise ValueError("packed shards use the new format only")
    rng = np.random.default_rng(seed)
    input_ids = np.zeros((num_samples, seq_len), np.int32)
    specials = []
    next_sentence = rng.integers(0, 2 if nsp else 1, num_samples).astype(np.int8)

    cls_id, sep_id = 2, 3  # arbitrary special ids clear of 0 ([PAD])
    for i in range(num_samples):
        # Random content length; two segments when NSP.
        if mixed_lengths:
            # Short-biased draw (u^2 over the full range): mean occupancy
            # ~0.4 like real Wikipedia-style corpora (Krell 2021 fig. 1),
            # with occasional near-full rows so truncation paths are hit.
            lo = min(6, seq_len - 4)
            content = lo + int((seq_len - 2 - lo) * rng.random() ** 2)
        else:
            content = int(rng.integers(seq_len // 2, seq_len - 1))
        ids = rng.integers(5, vocab_size, size=content).astype(np.int32)
        if nsp:
            split = int(rng.integers(1, content - 1)) if content > 2 else 1
            row = np.concatenate(
                [[cls_id], ids[:split], [sep_id], ids[split:], [sep_id]]
            )
            special = [0, split + 1, len(row) - 1]
        else:
            row = np.concatenate([[cls_id], ids, [sep_id]])
            special = [0, len(row) - 1]
        row = row[:seq_len]
        special = [min(p, seq_len - 1) for p in special]
        input_ids[i, : len(row)] = row
        specials.append(special)

    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    if packed:
        from bert_pytorch_tpu.data.packing import (first_fit_decreasing,
                                                   write_packed_shard)

        lengths = [sp[-1] + 1 for sp in specials]
        packs = first_fit_decreasing(lengths, seq_len, max_sequences_per_pack)
        rows = [
            [(input_ids[i, :lengths[i]], specials[i], int(next_sentence[i]))
             for i in pack]
            for pack in packs
        ]
        write_packed_shard(path, rows, seq_len, max_sequences_per_pack)
        return path
    with h5py.File(path, "w") as f:
        f.create_dataset("input_ids", data=input_ids, dtype="i4", compression="gzip")
        if legacy:
            segment_ids = np.zeros_like(input_ids)
            input_mask = np.zeros_like(input_ids)
            positions = np.zeros((num_samples, max_pred_per_seq), np.int32)
            label_ids = np.zeros((num_samples, max_pred_per_seq), np.int32)
            for i, special in enumerate(specials):
                input_mask[i, : special[-1] + 1] = 1
                if len(special) == 3:
                    segment_ids[i, special[1] + 1 : special[2] + 1] = 1
                n_mask = int(rng.integers(1, max_pred_per_seq))
                cand = [
                    p for p in range(1, special[-1]) if p not in special
                ][:n_mask]
                positions[i, : len(cand)] = cand
                label_ids[i, : len(cand)] = input_ids[i, cand]
            f.create_dataset("segment_ids", data=segment_ids, dtype="i4")
            f.create_dataset("input_mask", data=input_mask, dtype="i4")
            f.create_dataset("masked_lm_positions", data=positions, dtype="i4")
            f.create_dataset("masked_lm_ids", data=label_ids, dtype="i4")
        else:
            # Ragged special_token_positions (2 or 3 entries per sample).
            dt = h5py.vlen_dtype(np.dtype("i4"))
            ds = f.create_dataset("special_token_positions", (num_samples,), dtype=dt)
            for i, special in enumerate(specials):
                ds[i] = np.asarray(special, np.int32)
        f.create_dataset(
            "next_sentence_labels", data=next_sentence, dtype="i1", compression="gzip"
        )
    return path


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--output_dir", required=True)
    p.add_argument("--num_shards", type=int, default=2)
    p.add_argument("--samples_per_shard", type=int, default=64)
    p.add_argument("--seq_len", type=int, default=128)
    p.add_argument("--vocab_size", type=int, default=30522)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--no_nsp", action="store_true")
    p.add_argument("--legacy", action="store_true")
    p.add_argument("--mixed_lengths", action="store_true",
                   help="draw content lengths from (6, seq_len) instead of "
                        "[seq_len/2, seq_len) — the length spread that makes "
                        "sequence packing (docs/packing.md) worth testing")
    p.add_argument("--packed", action="store_true",
                   help="write offline-PACKED shards (data/packing.py "
                        "layout); combine with --mixed_lengths")
    p.add_argument("--max_sequences_per_pack", type=int, default=8)
    p.add_argument("--requests", type=int, default=0,
                   help="REQUEST-TRACE mode: write a JSONL trace of N "
                        "online-inference requests (mixed tasks, short-"
                        "biased lengths, Poisson arrivals) plus a "
                        "covering vocab.txt into --output_dir, for "
                        "the serving smoke test (docs/serving.md)")
    p.add_argument("--request_rate", type=float, default=100.0,
                   help="Poisson arrival rate (req/s) for --requests; "
                        "0 = all arrivals at t=0 (saturation replay)")
    p.add_argument("--max_words", type=int, default=48,
                   help="--requests: max words per request text (short-"
                        "biased draw below this)")
    args = p.parse_args(argv)

    if args.requests:
        trace = make_request_trace(
            os.path.join(args.output_dir, "requests.jsonl"),
            args.requests, seed=args.seed, max_words=args.max_words,
            rate_rps=args.request_rate)
        vocab = write_trace_vocab(
            os.path.join(args.output_dir, "vocab.txt"))
        print(f"wrote {trace}")
        print(f"wrote {vocab}")
        return

    for s in range(args.num_shards):
        path = os.path.join(args.output_dir, f"shard_{s:04d}.hdf5")
        make_shard(
            path,
            args.samples_per_shard,
            args.seq_len,
            args.vocab_size,
            seed=args.seed + s,
            nsp=not args.no_nsp,
            legacy=args.legacy,
            mixed_lengths=args.mixed_lengths,
            packed=args.packed,
            max_sequences_per_pack=args.max_sequences_per_pack,
        )
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
