"""Step-time decomposition with correct device-sync discipline.

Under JAX's async dispatch the wall time of ``train_step(...)`` is only the
HOST cost of building and enqueueing the program; the device executes in the
background and the next blocking operation (a metrics fetch, the next
``device_put``) absorbs the device time. Naive timing therefore conflates
three very different bottlenecks. The :class:`StepTimer` splits each step:

* ``data_wait`` — host blocked on the input pipeline (loader + prefetch);
* ``host`` — dispatch: trace/lower lookup + enqueue (compile lands here on
  step 0, which is why window records also carry ``max``, not just p50);
* ``device`` — dispatch-return until ``jax.block_until_ready`` on the
  step's metrics completes. Correct only when the caller syncs, so the
  timer owns the sync (:meth:`device_sync`) and records device time ONLY
  for synced steps.

Per-step syncing costs one host<->device round trip and stops the host from
running ahead of the device, so the sync cadence is a knob:
``sync_every=1`` gives the full decomposition, ``sync_every=N`` samples every Nth step and the unsynced steps contribute
data/host times only (``synced_steps`` in the record says how many device
samples a window holds). At ``N>1`` each device sample is the residual
BACKLOG at the sync point — the device work of the unsynced steps queued
since the previous sync, minus whatever overlapped host time — so the
``device_*`` percentiles then characterise sync tails, not single steps.

Every ``window`` steps :meth:`step_done` returns one ``kind="step_window"``
record (schema.py) with p50/p95/max per component and MFU. ``mfu_basis``
says how MFU was computed: ``"device"`` (from measured device seconds — the
hardware-normalised number that does not move when the input pipeline
stalls) when every step in the window was synced, ``"wall"`` (window FLOPs
over window wall time, the conventional definition) otherwise — dividing
per-step FLOPs by a multi-step backlog interval would deflate MFU by
roughly the sync cadence.

Padding-aware accounting (sequence packing, data/packing.py): given
``tokens_per_step`` (the step's token budget, pad included) and per-step
real-token counts (``note_tokens``, fed from the train step's
``real_tokens`` metric on the sync cadence), windows additionally report
``padding_efficiency`` (real/budget over the sampled steps),
``tokens_per_s`` with an explicit ``tokens_per_s_basis`` ("real" — pad
divided out; "all" — raw budget rate, the pre-packing convention), and
``mfu_real_tokens`` (MFU scaled to count only real-token FLOPs as useful
work, while ``mfu`` keeps reporting hardware occupancy).

Async-hot-path accounting (docs/telemetry.md): with a device prefetcher
attached, :meth:`note_h2d` records the host->device share of each step's
data wait and windows carry ``h2d_wait_*`` percentiles (clamped so
``h2d_wait <= data_wait`` always holds — it is a sub-phase);
:meth:`note_ckpt_stall` folds a checkpoint save's host stall into the step
it rode on, and windows with such steps carry ``ckpt_steps`` +
``ckpt_step_*`` percentiles — the checkpoint-step vs steady-state
comparison that async checkpointing (utils/checkpoint.py) collapses.

The clock is injectable for tests (``clock=fake``); the timer never calls
into JAX except through the ``sync`` callable handed to it.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from bert_pytorch_tpu.telemetry.profiler import span
from bert_pytorch_tpu.utils import flops as flops_util


def _percentile(sorted_vals: list, frac: float) -> float:
    """Nearest-rank percentile of an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(frac * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[idx]


def _stats(vals: list, prefix: str) -> dict:
    s = sorted(vals)
    return {
        f"{prefix}_p50_s": round(_percentile(s, 0.50), 6),
        f"{prefix}_p95_s": round(_percentile(s, 0.95), 6),
        f"{prefix}_max_s": round(s[-1] if s else 0.0, 6),
    }


class StepTimer:
    def __init__(
        self,
        window: int = 20,
        sync_every: int = 1,
        clock: Callable[[], float] = time.perf_counter,
        seq_per_step: Optional[int] = None,
        flops_per_seq: Optional[float] = None,
        device_kind: str = "",
        n_devices: int = 1,
        tokens_per_step: Optional[int] = None,
    ):
        self.window = max(1, int(window))
        self.sync_every = max(0, int(sync_every))  # 0 = never sync
        self._clock = clock
        self.seq_per_step = seq_per_step
        self.flops_per_seq = flops_per_seq
        self.device_kind = device_kind
        self.n_devices = max(1, int(n_devices))
        # Padding-aware accounting (docs/telemetry.md): tokens_per_step is
        # the step's token BUDGET (rows x seq_len, pad included); the train
        # step reports the real (non-pad) count via note_tokens on the sync
        # cadence. Their ratio is padding_efficiency — what sequence
        # packing (data/packing.py) exists to raise.
        self.tokens_per_step = tokens_per_step
        self.run_real_tokens = 0.0
        self.run_token_steps = 0
        self._step_index = 0
        self._reset_window()
        self._t_data0 = self._t_data1 = self._t_dispatch1 = None
        self._t_device1 = None
        self._pending_h2d = None
        self._h2d_attached = False
        self._last_step_s = 0.0

    def _reset_window(self):
        self._data_waits: list = []
        self._hosts: list = []
        self._devices: list = []
        self._steps: list = []
        self._real_tokens: list = []
        self._h2ds: list = []
        self._ckpt_steps_s: list = []
        self._window_t0 = None

    # -- per-step marks, in order --------------------------------------

    def data_start(self) -> None:
        self._t_data0 = self._clock()
        if self._window_t0 is None:
            self._window_t0 = self._t_data0

    def data_end(self) -> None:
        self._t_data1 = self._clock()

    def data_wait_s(self) -> float:
        """The wait for the batch just delivered (between the two marks
        above)."""
        return max(0.0, self._t_data1 - self._t_data0)

    def dispatch_end(self) -> None:
        self._t_dispatch1 = self._clock()

    def marks(self) -> tuple:
        """The open step's clock reads so far: feed entered, feed left,
        step call returned."""
        return (self._t_data0, self._t_data1, self._t_dispatch1)

    def should_sync(self) -> bool:
        if self.sync_every == 0:
            return False
        return self._step_index % self.sync_every == 0

    def note_h2d(self, h2d_wait_s: float) -> None:
        """Record the host->device share of THIS step's data wait (the
        device-prefetch stage's attribution, data/device_prefetch.py).
        Called by the telemetry facade right after ``data_end``; clamped
        to the step's measured data_wait at :meth:`step_done`, so the
        ``h2d_wait_* <= data_wait_*`` invariant holds by construction."""
        self._pending_h2d = max(0.0, float(h2d_wait_s))
        self._h2d_attached = True

    def note_ckpt_stall(self, stall_s: float) -> None:
        """Record a checkpoint save's host stall, attributed to the step
        it rode on (the one that just finished). Window records then carry
        ``ckpt_steps`` and ``ckpt_step_*`` percentiles over step+stall
        durations — the number async checkpointing exists to collapse
        toward the steady-state step time (docs/telemetry.md)."""
        base = self._steps[-1] if self._steps else self._last_step_s
        self._ckpt_steps_s.append(base + max(0.0, float(stall_s)))

    def note_tokens(self, real_tokens: float) -> None:
        """Record one step's REAL (non-pad) token count. Called by the
        telemetry facade on synced steps only — the count rides in the
        step metrics, so reading it off-cadence would itself be a sync.
        Window records then report padding_efficiency and real-token
        throughput from the sampled steps."""
        self._real_tokens.append(float(real_tokens))
        self.run_real_tokens += float(real_tokens)
        self.run_token_steps += 1

    def run_padding_efficiency(self) -> Optional[float]:
        """Run-level real/budget token ratio over the sampled steps (None
        when no counts were observed or the budget is unknown)."""
        if not self.run_token_steps or not self.tokens_per_step:
            return None
        return self.run_real_tokens / (
            self.run_token_steps * self.tokens_per_step)

    def device_sync(self, sync_target) -> bool:
        """Block until the step's outputs are ready and record the device
        tail. Call after :meth:`dispatch_end`, only when :meth:`should_sync`
        (the caller may also force a sync, e.g. on log steps)."""
        import jax

        with span("train:sync"):
            jax.block_until_ready(sync_target)
        self._t_device1 = self._clock()
        return True

    def step_done(self, step: int) -> Optional[dict]:
        """Finish the step; every ``window`` steps return the window record.

        Monotonic by construction: each component is a difference of
        successive clock reads, so components are non-negative and their
        sum never exceeds the step's total wall time.
        """
        if self._t_data0 is None or self._t_data1 is None:
            return None  # marks were skipped (e.g. epoch boundary)
        self._data_waits.append(self.data_wait_s())
        if self._h2d_attached:
            # Clamp to the step's own data_wait: h2d is a SUB-phase of it
            # (steps with no note contribute 0 — the prefetcher reported
            # nothing to attribute).
            self._h2ds.append(min(self._pending_h2d or 0.0,
                                  self._data_waits[-1]))
            self._pending_h2d = None
        if self._t_dispatch1 is not None:
            self._hosts.append(max(0.0, self._t_dispatch1 - self._t_data1))
            if self._t_device1 is not None and \
                    self._t_device1 >= self._t_dispatch1:
                self._devices.append(self._t_device1 - self._t_dispatch1)
        end = self._t_device1 if self._t_device1 is not None \
            else (self._t_dispatch1 if self._t_dispatch1 is not None
                  else self._t_data1)
        self._steps.append(max(0.0, end - self._t_data0))
        self._last_step_s = self._steps[-1]
        self._t_data0 = self._t_data1 = self._t_dispatch1 = None
        self._t_device1 = None
        self._step_index += 1

        if len(self._steps) < self.window:
            return None
        record = self._window_record(step, end)
        self._reset_window()
        return record

    def flush(self, step: int) -> Optional[dict]:
        """Emit a final partial-window record (end of run)."""
        if not self._steps and not self._ckpt_steps_s:
            # A checkpoint stall noted after the last full window rolled
            # (the end-of-run save) must still land in a record.
            return None
        record = self._window_record(step, None)
        self._reset_window()
        return record

    # -- window rollup --------------------------------------------------

    def _window_record(self, step: int, window_end) -> dict:
        n = len(self._steps)
        wall = ((window_end - self._window_t0)
                if (window_end is not None and self._window_t0 is not None)
                else sum(self._steps)) or 1e-9
        record = {
            "kind": "step_window",
            "tag": "telemetry",
            "step": step,
            "window_steps": n,
            "synced_steps": len(self._devices),
            "steps_per_sec": round(n / wall, 4),
        }
        record.update(_stats(self._data_waits, "data_wait"))
        if self._h2d_attached:
            # H2D sub-phase of data_wait (device prefetch attribution).
            # Per-step samples are clamped to that step's data_wait, and the
            # emitted percentiles are clamped pairwise again so the
            # h2d_wait <= data_wait invariant survives rounding and
            # unequal sample counts (schema.py lints it).
            h2d = _stats(self._h2ds, "h2d_wait")
            for suffix in ("p50_s", "p95_s", "max_s"):
                h2d[f"h2d_wait_{suffix}"] = min(
                    h2d[f"h2d_wait_{suffix}"], record[f"data_wait_{suffix}"])
            record.update(h2d)
        record.update(_stats(self._hosts, "host"))
        record.update(_stats(self._devices, "device"))
        record.update(_stats(self._steps, "step"))
        if self._ckpt_steps_s:
            # Steps a checkpoint save rode on, with the save's host stall
            # folded in: the checkpoint-step vs steady-state comparison
            # telemetry-report aggregates (async saves collapse these
            # toward step_p95_s).
            record["ckpt_steps"] = len(self._ckpt_steps_s)
            record.update(_stats(self._ckpt_steps_s, "ckpt_step"))
        mfu, record["mfu_basis"] = self._window_mfu(wall, n)
        if mfu is not None:
            record["mfu"] = mfu
        if self.seq_per_step:
            record["seq_per_sec"] = round(self.seq_per_step * n / wall, 2)
        if self.tokens_per_step:
            # Padding-aware throughput: tokens_per_s with an explicit basis
            # so pre-packing artifacts stay comparable. "real" divides out
            # the pad tokens (sampled from the steps the sync cadence
            # observed); "all" is the raw token budget rate (the only
            # number available when no step in the window was sampled).
            if self._real_tokens:
                eff = (sum(self._real_tokens)
                       / (len(self._real_tokens) * self.tokens_per_step))
                eff = min(1.0, eff)
                record["padding_efficiency"] = round(eff, 4)
                record["tokens_per_s"] = round(
                    self.tokens_per_step * n / wall * eff, 2)
                record["tokens_per_s_basis"] = "real"
                if record.get("mfu"):
                    # Tokens-basis MFU: counts only real-token FLOPs as
                    # useful work (pad FLOPs ARE executed — "mfu" keeps
                    # reporting hardware occupancy; this reports how much
                    # of it trained the model).
                    record["mfu_real_tokens"] = round(
                        record["mfu"] * eff, 4)
            else:
                record["tokens_per_s"] = round(
                    self.tokens_per_step * n / wall, 2)
                record["tokens_per_s_basis"] = "all"
        return record

    def _window_mfu(self, wall: float, n_steps: int):
        """(mfu, basis). Device basis — window FLOPs over the peak FLOPs
        the chips could have delivered in the measured DEVICE seconds —
        only when EVERY step was synced; with a sampled cadence each device
        interval is a multi-step backlog, which would deflate device-basis
        MFU by ~the cadence, so the window falls back to wall basis (FLOPs
        over window wall time, the conventional definition). ``(None,
        "none")`` — the record then carries no ``mfu`` at all — when there
        is nothing to measure it against: no FLOP model, no elapsed time,
        or a device that is not a TPU (the CPU test mesh has no peak)."""
        if not self.seq_per_step or not self.flops_per_seq:
            return None, "none"
        if self._devices and len(self._devices) == n_steps:
            elapsed, basis = sum(self._devices), "device"
        else:
            elapsed, basis = wall, "wall"
        if elapsed <= 0:
            return None, "none"
        value = flops_util.mfu(
            self.seq_per_step * n_steps / elapsed / self.n_devices,
            self.flops_per_seq, self.device_kind)
        if value is None:
            return None, "none"
        return round(value, 4), basis
