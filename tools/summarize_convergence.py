#!/usr/bin/env python
"""Summarize a CONVERGENCE_r*.csv (scripts/convergence_r0N.sh output).

Prints one JSON object with, per optimizer leg: loss/accuracy at step
milestones and the end of the run, plus K-FAC-vs-LAMB loss deltas at
equal STEPS and — when the CSV carries samples_per_second — at equal
WALLCLOCK. Both comparisons matter: the reference wires K-FAC for
quality-per-step (run_pretraining.py:320-355), but the preconditioner
only pays for itself if the per-step cost doesn't erase the advantage in
wall-clock terms (PARITY.md compares loss @ step).

  python tools/summarize_convergence.py CONVERGENCE_r03.csv
"""

from __future__ import annotations

import csv
import json
import sys


def _elapsed_proxy(row) -> float | None:
    """Per-row cumulative elapsed time, up to the (constant) global-batch
    factor: the runner logs samples_per_second = samples_seen / elapsed
    and samples_seen = step * gbs, so step / sps == elapsed / gbs — a
    time scale that is comparable ACROSS legs of the same capture."""
    sps = row.get("samples_per_second")
    if not sps:
        return None
    try:
        return int(row["step"]) / float(sps)
    except (ValueError, ZeroDivisionError):
        return None


def summarize(path: str) -> dict:
    legs: dict[str, list[dict]] = {}
    with open(path) as f:
        for rec in csv.DictReader(f):
            legs.setdefault(rec["optimizer"], []).append(rec)

    out: dict = {"file": path, "legs": {}}
    for name, rows in legs.items():
        rows.sort(key=lambda r: int(r["step"]))
        by_step = {int(r["step"]): r for r in rows}
        last = rows[-1]
        milestones = {}
        for s in (10, 25, 50, 100, 150, 200, 500, 1000, 2000, 5000):
            if s in by_step:
                milestones[str(s)] = round(float(by_step[s]["loss"]), 4)
        out["legs"][name] = {
            "steps": int(last["step"]),
            "first_loss": round(float(rows[0]["loss"]), 4),
            "final_loss": round(float(last["loss"]), 4),
            "final_mlm_accuracy": round(float(last["mlm_accuracy"]), 4),
            "loss_at_step": milestones,
        }
    kfac_legs = [k for k in legs if k.startswith("kfac")]
    if "lamb" in legs and kfac_legs:
        out["kfac_vs_lamb"] = {}
        lamb = legs["lamb"]
        lamb_t = [_elapsed_proxy(r) for r in lamb]
        for kname in kfac_legs:
            kf = legs[kname]
            n = min(int(lamb[-1]["step"]), int(kf[-1]["step"]))
            l_loss = next(float(r["loss"]) for r in lamb
                          if int(r["step"]) == n)
            k_loss = next(float(r["loss"]) for r in kf
                          if int(r["step"]) == n)
            cmp = {
                "equal_step": n,
                "lamb_loss": round(l_loss, 4),
                "kfac_loss": round(k_loss, 4),
                # positive = K-FAC is ahead (lower loss) at equal steps
                "kfac_advantage": round(l_loss - k_loss, 4),
            }
            kf_t = [_elapsed_proxy(r) for r in kf]
            # Equal wallclock: compare each leg's loss at the largest
            # elapsed time BOTH legs reached. Rows without a usable proxy
            # (no samples_per_second column, or the step-1 row where the
            # runner logs 0 before its timer starts) are ignored; skipped
            # entirely when either leg has no usable row in the horizon.
            lamb_v = [(i, t) for i, t in enumerate(lamb_t) if t is not None]
            kf_v = [(i, t) for i, t in enumerate(kf_t) if t is not None]
            horizon = (min(lamb_v[-1][1], kf_v[-1][1])
                       if lamb_v and kf_v else None)
            l_in = [i for i, t in lamb_v
                    if horizon is not None and t <= horizon]
            k_in = [i for i, t in kf_v
                    if horizon is not None and t <= horizon]
            if l_in and k_in:
                l_i, k_i = max(l_in), max(k_in)
                l_wc = float(lamb[l_i]["loss"])
                k_wc = float(kf[k_i]["loss"])
                cmp["equal_wallclock"] = {
                    "lamb_step": int(lamb[l_i]["step"]),
                    "kfac_step": int(kf[k_i]["step"]),
                    "lamb_loss": round(l_wc, 4),
                    "kfac_loss": round(k_wc, 4),
                    # positive = K-FAC ahead per unit wall-clock
                    "kfac_advantage": round(l_wc - k_wc, 4),
                    # K-FAC per-step cost relative to LAMB
                    "step_cost_ratio": round(
                        (kf_v[-1][1] / int(kf[kf_v[-1][0]]["step"]))
                        / (lamb_v[-1][1] / int(lamb[lamb_v[-1][0]]["step"])),
                        3),
                }
            out["kfac_vs_lamb"][kname] = cmp
    return out


if __name__ == "__main__":
    print(json.dumps(summarize(sys.argv[1])))
