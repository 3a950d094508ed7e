"""Sharded HDF5 pretraining dataset with dynamic masking.

Behavioral parity with reference src/dataset.py:9-338
(``ShardedPretrainingDataset``): at most two shard files in RAM (current +
background-thread prefetch of the next), segment/input-mask derivation from
``special_token_positions``, dynamic masking with the 80/10/10 split, legacy
NVIDIA pre-masked format support, and warn-and-skip shard verification.
Offline-PACKED shards (sequence packing, data/packing.py / docs/packing.md)
are auto-detected: samples then carry sequence_ids/cls_positions and
per-sequence NSP labels, with dynamic masking run per packed member.

Deliberate deviations from the reference (SURVEY.md §7 "known quirks"):
  - mask positions are sampled WITHOUT replacement (the reference's
    ``np.random.choice`` default could duplicate positions, dataset.py:286);
  - masking draws come from a PER-SAMPLE generator seeded on
    ``(seed, epoch, index)`` instead of one sequential stream
    (dataset.py:122-123): draws for sample i no longer depend on how many
    samples were read before it, so a checkpoint-resumed run reproduces
    the exact masking an uninterrupted run would have applied (the
    property the chaos harness asserts, docs/fault_tolerance.md), worker
    processes decorrelate without per-worker reseeding, and epochs still
    re-draw (dynamic masking stays dynamic);
  - the in-file index is computed from the file start (the reference's
    ``idx -= file_sample_end_idx`` negative indexing, dataset.py:171, is
    equivalent but obscure);
  - HDF5 shard opens/reads retry with backoff (``utils/retry.py``) and a
    configurable skip-shard-vs-abort startup policy — transient storage
    errors cost a delay, not the run (docs/fault_tolerance.md).

No torch dependency: samples are numpy int32 arrays ready for
``jax.device_put`` batching.
"""

from __future__ import annotations

import os
import threading
import warnings
from typing import Callable, Optional, Sequence

import h5py
import numpy as np

from bert_pytorch_tpu.telemetry.profiler import span
from bert_pytorch_tpu.utils.retry import RetryPolicy, retry_call


class DataReadError(RuntimeError):
    """A shard read failed past the retry budget (or the startup
    verification failed under ``shard_error_policy='abort'``)."""


NEW_FORMAT_KEYS = ("input_ids", "special_token_positions", "next_sentence_labels")
LEGACY_FORMAT_KEYS = (
    "input_ids",
    "segment_ids",
    "input_mask",
    "masked_lm_positions",
    "masked_lm_ids",
    "next_sentence_labels",
)
# Offline-packed shards (data/packing.py write_packed_shard; docs/packing.md):
# several sequences share one row; samples gain sequence_ids/cls_positions
# and per-sequence NSP labels. Detected per dataset (mixing packed and
# unpacked shards is an error — the sample shapes differ).
PACKED_KEY = "packed_sequence_lengths"


class ShardedPretrainingDataset:
    """Streams sorted HDF5 shards keeping <= 2 files in memory.

    ``__getitem__`` expects forward-moving indices (per reader); use
    :class:`bert_pytorch_tpu.data.sampler.DistributedSampler`, which chunks
    contiguously. Forward skips (strided DataLoader workers) and cyclic
    wrap-around (epoch restarts, including mid-dataset chunk starts for
    ranks > 0) are supported; a genuinely random access pattern (shuffling
    sampler) is not an error but reloads shard files pathologically — the
    contiguity contract lives in the sampler (cf. the invariant check at
    reference dataset.py:161-169, which also rejected the legal multi-rank
    epoch restart).
    """

    # The keys every shard must hold, one entry per sample.
    COUNT_KEYS = ("input_ids", "next_sentence_labels")

    def __init__(
        self,
        files: Sequence[str] | str,
        mask_token_index: Optional[int],
        max_pred_per_seq: int,
        masked_lm_prob: float,
        vocab_size: int,
        original_token_prob: float = 0.1,
        random_token_prob: float = 0.1,
        seed: Optional[int] = None,
        read_retries: int = 2,
        retry_base_delay_s: float = 0.2,
        shard_error_policy: str = "skip",
        on_fault: Optional[Callable[[dict], None]] = None,
    ):
        if mask_token_index is not None and not isinstance(mask_token_index, (int, np.integer)):
            raise ValueError("mask_token_index must be an integer")
        if not isinstance(max_pred_per_seq, (int, np.integer)) or max_pred_per_seq < 0:
            raise ValueError("max_pred_per_seq must be an integer >= 0")
        if not 0 <= masked_lm_prob <= 1:
            raise ValueError("masked_lm_prob must be in [0,1]")
        if not isinstance(vocab_size, (int, np.integer)) or vocab_size < 0:
            raise ValueError("vocab_size must be an integer >= 0")
        if not 0 <= original_token_prob <= 1:
            raise ValueError("original_token_prob must be in [0,1]")
        if not 0 <= random_token_prob <= 1:
            raise ValueError("random_token_prob must be in [0,1]")
        if random_token_prob + original_token_prob > 1:
            raise ValueError("random_token_prob + original_token_prob > 1")

        if shard_error_policy not in ("skip", "abort"):
            raise ValueError(
                f"shard_error_policy must be 'skip' or 'abort', got "
                f"{shard_error_policy!r}")
        # Data-path resilience knobs (docs/fault_tolerance.md): every HDF5
        # open/read goes through utils/retry.py with these bounds, and the
        # STARTUP verification applies the skip-vs-abort policy. A
        # mid-stream read that stays broken past the retries always raises
        # DataReadError — the index space is fixed at startup, so silently
        # dropping a shard then would feed wrong samples for its range.
        self.read_retries = max(0, int(read_retries))
        self.retry_base_delay_s = float(retry_base_delay_s)
        self.shard_error_policy = shard_error_policy
        self.on_fault = on_fault

        if isinstance(files, str):
            files = [files]
        files = sorted(files)  # all processes must agree on the order
        (self.files, self.file_idxs, self.packed,
         self.max_sequences_per_pack) = self._verify_and_count_samples(files)

        self.mask_token_index = mask_token_index
        self.max_pred_per_seq = int(max_pred_per_seq)
        self.masked_lm_prob = float(masked_lm_prob)
        self.vocab_size = int(vocab_size)
        self.original_token_prob = float(original_token_prob)
        self.random_token_prob = float(random_token_prob)
        self.seed = seed
        self.epoch = 0
        self._mask_seed_base = self._seed_base(seed)
        self._rng = np.random.default_rng(seed)

        self.file_idx: Optional[int] = None
        self.next_file_idx: Optional[int] = None
        self.file_sample_start_idx = -1
        self.file_sample_end_idx = -1
        self.data = None
        self._next_file_data = None
        self._next_file_error: Optional[BaseException] = None
        self._next_file_thread: Optional[threading.Thread] = None

    # -- pickling (DataLoader worker processes) ------------------------------

    def __getstate__(self):
        """Drop the streaming runtime (loaded shard data, prefetch thread):
        a worker process re-streams from its own file handles. The fault
        hook is dropped too (a telemetry emit closure doesn't pickle;
        workers fall back to warnings). Masking draws need no per-worker
        reseeding — they derive from (seed, epoch, index)."""
        state = self.__dict__.copy()
        for k in ("data", "_next_file_data", "_next_file_thread", "_rng",
                  "_next_file_error", "on_fault"):
            state[k] = None
        state["file_idx"] = None
        state["next_file_idx"] = None
        state["file_sample_start_idx"] = -1
        state["file_sample_end_idx"] = -1
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._rng = np.random.default_rng(self.seed)

    @staticmethod
    def _seed_base(seed: Optional[int]) -> int:
        """Base entropy for the per-sample masking derivation. ``None``
        keeps its pre-PR-5 meaning — fresh OS entropy per dataset, so
        unseeded runs draw run-unique masks instead of silently colliding
        with seed=0. The base is pickled to worker processes, so every
        reader of one dataset instance still agrees per index."""
        if seed is not None:
            return int(seed) % (2 ** 63)
        return int(np.random.SeedSequence().entropy) % (2 ** 63)

    def reseed(self, seed: Optional[int]) -> None:
        self.seed = seed
        self._mask_seed_base = self._seed_base(seed)
        self._rng = np.random.default_rng(seed)

    # -- epoch / size --------------------------------------------------------

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch

    def __len__(self) -> int:
        return self.file_idxs[-1][1]

    # -- streaming -----------------------------------------------------------

    def _seek(self, idx: int) -> int:
        """Bring the shard that holds sample ``idx`` into ``self.data`` (the
        streaming walk below) and return the sample's index inside it."""
        if self.data is None:
            # First access: infer the starting file from idx and prefetch it.
            self.next_file_idx = self._file_idx_for(idx)
            self._next_file_thread = self._async_load_file(self.next_file_idx)

        if not (self.file_sample_start_idx <= idx < self.file_sample_end_idx):
            # Walk the cyclic file sequence forward to the file holding idx.
            # Multiple swaps: a strided reader (a DataLoader worker taking
            # every Nth batch) may skip past an entire small shard in one
            # step. Cyclic: an epoch restart (rank-chunk end -> chunk start,
            # possibly mid-dataset for ranks > 0) walks through the wrap —
            # the previous one-swap-only logic raised on exactly that legal
            # multi-rank restart. The access contract (contiguous forward
            # chunks) is owned by DistributedSampler; a shuffling sampler
            # here degrades to pathological full-file reloads per access
            # rather than an error.
            target = self._file_idx_for(idx)  # raises if idx >= len(self)
            while self.file_idx != target:
                # Swap in the prefetched file; start loading its successor.
                del self.data  # drop the old shard before holding two new
                self._next_file_thread.join()
                if self._next_file_error is not None:
                    # The prefetch thread exhausted the retry budget; a
                    # swallowed error here would surface later as a
                    # baffling KeyError on stale/None data.
                    error, self._next_file_error = self._next_file_error, None
                    self.data = None
                    raise DataReadError(
                        f"shard load failed past "
                        f"{self.read_retries + 1} attempt(s): "
                        f"{type(error).__name__}: {error}") from error
                self.data = self._next_file_data
                self.file_idx = self.next_file_idx
                self.next_file_idx = (self.next_file_idx + 1) % len(self.files)
                self._next_file_thread = self._async_load_file(self.next_file_idx)
                (self.file_sample_start_idx,
                 self.file_sample_end_idx) = self.file_idxs[self.file_idx]
        return idx - self.file_sample_start_idx

    def __getitem__(self, idx: int):
        local = self._seek(idx)
        # Per-sample masking generator, derived from (seed, epoch, index):
        # sample i's draws are independent of read order and worker
        # topology, so a resumed run masks exactly like an uninterrupted
        # one (module docstring; docs/fault_tolerance.md). seed=None uses
        # a per-instance random base (see _seed_base).
        self._rng = np.random.default_rng(
            (self._mask_seed_base, int(self.epoch), int(idx)))

        input_ids = np.array(self.data["input_ids"][local])
        next_sentence_label = np.asarray(self.data["next_sentence_labels"][local])

        if self.packed:
            return self._packed_item(local, input_ids, next_sentence_label)
        if "special_token_positions" in self.data:
            special = np.asarray(self.data["special_token_positions"][local])
            segment_ids = self._get_segment_ids(input_ids, special)
            input_mask = self._get_input_mask(input_ids, special)
            masked_input_ids, masked_lm_labels = self._mask_input(input_ids, special)
        else:
            # Legacy NVIDIA pre-masked format (reference dataset.py:184-192).
            segment_ids = np.asarray(self.data["segment_ids"][local])
            input_mask = np.asarray(self.data["input_mask"][local])
            positions = np.asarray(self.data["masked_lm_positions"][local])
            ids = np.asarray(self.data["masked_lm_ids"][local])
            masked_input_ids = input_ids
            masked_lm_labels = self._get_masked_labels(input_ids, positions, ids)

        return [
            masked_input_ids.astype(np.int32),
            segment_ids.astype(np.int32),
            input_mask.astype(np.int32),
            masked_lm_labels.astype(np.int32),
            next_sentence_label.astype(np.int32),
        ]

    def _packed_item(self, local: int, input_ids, nsp_labels):
        """One offline-packed row (data/packing.py layout): re-derive
        sequence_ids/segments/cls positions from the per-member lengths and
        run dynamic masking per member — the same draws a member would get
        unpacked, just rebased onto its offset in the row."""
        lengths = np.asarray(self.data[PACKED_KEY][local], np.int64)
        specials_all = np.asarray(
            self.data["packed_special_token_positions"][local], np.int64)
        nsp_labels = np.asarray(nsp_labels, np.int64).reshape(-1)
        k_max = self.max_sequences_per_pack
        seq_len = input_ids.shape[0]

        segment_ids = np.zeros_like(input_ids)
        input_mask = np.zeros_like(input_ids)
        sequence_ids = np.zeros_like(input_ids)
        labels = np.full_like(input_ids, -1)
        nsp = np.full(k_max, -1, np.int32)
        cls_positions = np.zeros(k_max, np.int32)

        offset = 0
        for k, n in enumerate(lengths):
            n = int(n)
            span = slice(offset, offset + n)
            sequence_ids[span] = k + 1
            input_mask[span] = 1
            cls_positions[k] = offset
            nsp[k] = int(nsp_labels[k])
            member_specials = (
                specials_all[(specials_all >= offset)
                             & (specials_all < offset + n)] - offset)
            if len(member_specials) == 3:
                # [CLS] a [SEP] b [SEP]: second segment gets type 1
                # (the unpacked _get_segment_ids rule, rebased).
                segment_ids[offset + member_specials[1] + 1:
                            offset + member_specials[2] + 1] = 1
            ids_view = input_ids[span]
            _, member_labels = self._mask_input(ids_view, member_specials)
            labels[span] = member_labels
            offset += n
        assert offset <= seq_len, (offset, seq_len)

        return [
            input_ids.astype(np.int32),
            segment_ids.astype(np.int32),
            input_mask.astype(np.int32),
            labels.astype(np.int32),
            nsp.astype(np.int32),
            sequence_ids.astype(np.int32),
            cls_positions.astype(np.int32),
        ]

    def _file_idx_for(self, idx: int) -> int:
        for i, (start, end) in enumerate(self.file_idxs):
            if start <= idx < end:
                return i
        raise ValueError(f"idx ({idx}) exceeds dataset size ({len(self)})")

    def _async_load_file(self, file_idx: int) -> threading.Thread:
        self._next_file_error = None
        th = threading.Thread(
            target=self._load_hdf5, args=(self.files[file_idx],), daemon=True
        )
        th.start()
        return th

    # -- resilient shard IO (docs/fault_tolerance.md) ------------------------

    def _emit_fault(self, record: dict) -> None:
        """Best-effort fault telemetry (run_pretraining wires the JSONL
        sink in via ``on_fault``); never let an emit failure mask the IO
        error being reported."""
        if self.on_fault is None:
            return
        try:
            self.on_fault(record)
        except Exception:
            pass

    def _retry_policy(self) -> RetryPolicy:
        return RetryPolicy(attempts=self.read_retries + 1,
                           base_delay_s=self.retry_base_delay_s)

    def _read_shard(self, filepath: str, reader: Callable) -> dict:
        """Run ``reader(h5py.File)`` with retry/backoff; transient storage
        errors (and armed fault injections, testing/faults.py) cost a
        delay, a warning, and a ``fault`` telemetry record — not the run.
        """
        def attempt():
            from bert_pytorch_tpu.testing import faults
            faults.get_plan().shard_read_check(
                filepath, emit=self._emit_fault)
            with h5py.File(filepath, "r") as f:
                return reader(f)

        def on_retry(n, exc, delay):
            warnings.warn(
                f"shard read of {filepath} failed (attempt {n}: "
                f"{type(exc).__name__}: {exc}); retrying in {delay:.2f}s")
            self._emit_fault({
                "kind": "fault", "tag": "telemetry",
                "fault": "shard_read_retry", "injected": False,
                "path": filepath, "attempt": n,
                "error": f"{type(exc).__name__}: {exc}"})

        return retry_call(attempt, policy=self._retry_policy(),
                          on_retry=on_retry,
                          description=f"shard read {filepath}")

    def _load_hdf5(self, filepath: str) -> None:
        try:
            with span("data:shard_load"):
                self._next_file_data = self._read_shard(
                    filepath,
                    lambda f: {key: np.asarray(f[key][:])
                               for key in f.keys()})
        except BaseException as e:
            # Runs on the prefetch thread: park the error for the swap in
            # __getitem__ to re-raise (a daemon thread's traceback would
            # otherwise vanish and the consumer would read stale data).
            self._next_file_error = e

    # -- feature derivation (reference dataset.py:224-296) -------------------

    @staticmethod
    def _get_segment_ids(input_ids, special_token_positions):
        """[CLS] a... [SEP] b... [SEP] pad -> 0 0...0 0 1...1 1 0...0
        (reference dataset.py:224-238)."""
        segment_ids = np.zeros_like(input_ids)
        if len(special_token_positions) == 3:
            segment_ids[
                special_token_positions[1] + 1 : special_token_positions[2] + 1
            ] = 1
        return segment_ids

    @staticmethod
    def _get_input_mask(input_ids, special_token_positions):
        """1 through the final [SEP], 0 on padding (dataset.py:240-252)."""
        input_mask = np.zeros_like(input_ids)
        input_mask[: special_token_positions[-1] + 1] = 1
        return input_mask

    @staticmethod
    def _get_masked_labels(input_ids, masked_lm_positions, masked_lm_ids):
        """Scatter true ids at masked positions, -1 elsewhere
        (legacy format; dataset.py:254-275)."""
        labels = np.full_like(input_ids, -1)
        index = len(input_ids)
        padded = np.nonzero(masked_lm_positions == 0)[0]
        if len(padded) != 0:
            index = padded[0]
        labels[masked_lm_positions[:index]] = masked_lm_ids[:index]
        return labels

    def _mask_input(self, input_ids, special_token_positions):
        """Dynamic masking (dataset.py:277-296): choose up to
        min(max_pred, max(1, round-down of len*prob)) non-special positions;
        each keeps its token w.p. original_token_prob, becomes random w.p.
        random_token_prob, else [MASK].

        Fully vectorized: this runs per sample on the host data path and was
        the pipeline's hot spot as a Python loop (~80% of __getitem__; the
        numpy form is ~10x faster, which is what lets one producer feed
        multiple chips — see tools/bench_loader.py for measured rates).
        """
        masked_lm_labels = np.full_like(input_ids, -1)
        candidates = np.arange(int(special_token_positions[-1]))
        candidates = candidates[
            ~np.isin(candidates, np.asarray(special_token_positions))
        ]
        if candidates.size == 0:
            return input_ids, masked_lm_labels
        mask_count = min(
            self.max_pred_per_seq,
            max(1, int(candidates.size * self.masked_lm_prob)),
        )
        mask_indices = self._rng.choice(
            candidates, size=min(mask_count, candidates.size), replace=False
        )
        masked_lm_labels[mask_indices] = input_ids[mask_indices]
        draws = self._rng.random(mask_indices.size)
        rand_sel = mask_indices[
            (draws >= self.original_token_prob)
            & (draws < self.original_token_prob + self.random_token_prob)
        ]
        mask_sel = mask_indices[
            draws >= self.original_token_prob + self.random_token_prob
        ]
        if rand_sel.size:
            input_ids[rand_sel] = self._rng.integers(
                0, self.vocab_size - 1, size=rand_sel.size
            )
        input_ids[mask_sel] = self.mask_token_index
        return input_ids, masked_lm_labels

    # -- shard verification (dataset.py:298-338) -----------------------------

    def _verify_and_count_samples(self, files):
        """Open every shard (with retry) and count samples. Unreadable
        shards follow ``shard_error_policy``: 'skip' (default) keeps the
        reference's warn-and-skip stance; 'abort' raises — a run that
        would rather fail fast than silently train on a subset."""
        current_idx = 0
        verified_files, verified_idxs = [], []
        packed_flags, pack_limits = [], []
        keys = list(self.COUNT_KEYS)

        def skip_or_abort(fpath, why):
            if self.shard_error_policy == "abort":
                raise DataReadError(
                    f"{why} (shard_error_policy='abort'): {fpath}")
            warnings.warn(f"{why}: {fpath}. Skipping File")
            self._emit_fault({
                "kind": "fault", "tag": "telemetry", "fault": "shard_skipped",
                "injected": False, "path": fpath, "error": why})

        def read_counts(f):
            counts = [len(f[key]) for key in keys]
            is_packed = PACKED_KEY in f
            pack_limit = 0
            if is_packed:
                from bert_pytorch_tpu.data.packing import (
                    PACKED_MAX_SEQUENCES_ATTR)
                pack_limit = int(f.attrs[PACKED_MAX_SEQUENCES_ATTR])
            return counts, is_packed, pack_limit

        for fpath in files:
            if not os.path.isfile(fpath):
                skip_or_abort(fpath, "File not found")
                continue
            try:
                counts, is_packed, pack_limit = self._read_shard(
                    fpath, read_counts)
            except Exception:
                skip_or_abort(fpath, f"Unable to read keys ({keys})")
                continue
            if len(set(counts)) != 1:
                skip_or_abort(
                    fpath, "Number of samples per key do not match")
                continue
            verified_files.append(fpath)
            verified_idxs.append((current_idx, current_idx + counts[0]))
            packed_flags.append(is_packed)
            if is_packed:
                # Only VERIFIED shards may shape the dataset-wide pack
                # limit (a rejected shard contributes zero samples and
                # must not widen every [B, K] batch array).
                pack_limits.append(pack_limit)
            current_idx += counts[0]
        if not verified_files:
            raise RuntimeError("Unable to open any valid data files")
        if len(set(packed_flags)) > 1:
            # Packed and unpacked samples have different shapes; one batch
            # cannot hold both, and silently dropping either set would skew
            # the data distribution.
            raise ValueError(
                "cannot mix packed and unpacked shards in one dataset: "
                f"packed={[f for f, p in zip(verified_files, packed_flags) if p]}")
        packed = packed_flags[0]
        return (verified_files, verified_idxs, packed,
                max(pack_limits) if packed else 0)


class TokenRowsDataset(ShardedPretrainingDataset):
    """Rows of token ids for a causal objective: shards that hold
    ``input_ids`` [N, S] alone, every row full. Same streaming, retries and
    shard verification as the masked-LM dataset; a sample is
    ``{"input_ids": row}`` (nothing is masked, nothing is drawn), and the
    loader collates dict samples by key."""

    COUNT_KEYS = ("input_ids",)

    def __init__(self, files, **resilience):
        super().__init__(files, None, 0, 0.0, 0, **resilience)
        if self.packed:
            raise ValueError("token-row shards are never offline-packed")

    def __getitem__(self, idx: int):
        local = self._seek(idx)
        return {"input_ids": np.asarray(
            self.data["input_ids"][local], np.int32)}
