#!/bin/bash
# Long anchored convergence run (VERDICT r2 #6): thousands of steps at a
# budget-feasible geometry — BERT-base on the offline chain's
# document-structured corpus — with loss-at-milestone targets stated IN
# ADVANCE (written to a milestones JSON before the run starts; the final
# artifact records pass/fail against it). This is the single-chip proxy
# for the phase-1+2-to-reference-loss target (PARITY.md); the model's
# numerical agreement with the HF torch forward (tests/test_convert.py)
# anchors the loss scale to an external implementation.
#
#   bash scripts/convergence_long_r03.sh [workdir]
#
# RESUMABLE: unlike the 200-step capture, this leg checkpoints every 250
# steps and auto-resumes from the latest checkpoint, so an interruption
# costs at most 250 steps of progress.
# Artifacts: CONVERGENCE_LONG_r03.csv + LONG_RUN_r03.json (milestones,
# measured losses, verdict per milestone).
set -euo pipefail
cd "$(dirname "$0")/.."
W=${1:-/tmp/bert_conv_long_r03}
# Artifact prefix: empty (default) writes the repo-root chip artifacts;
# CPU sanity runs MUST set LONG_ARTIFACT_PREFIX to a scratch path so a
# sanity pass can never masquerade as (or suppress) the chip capture.
PREFIX=${LONG_ARTIFACT_PREFIX:-}
MODEL=${LONG_MODEL:-bert_base}
STEPS=${LONG_STEPS:-5000}
LOCAL_BATCH=${LONG_LOCAL_BATCH:-64}
GLOBAL_BATCH=${LONG_GLOBAL_BATCH:-256}
# LAMB sqrt LR scaling from the phase-1 recipe: 6e-3 * sqrt(256/65536).
LR=${LONG_LR:-3.75e-4}
CACHE=${BENCH_COMPILE_CACHE_DIR:-${XDG_CACHE_HOME:-$HOME/.cache}/bert_tpu_jax_cache}
mkdir -p "$W"

source scripts/lib_synth_corpus.sh
synth_corpus_build "$W" "$MODEL" 8 3

# Milestones STATED IN ADVANCE (a pre-registration: written before any
# training step runs, never overwritten). Grounded on the r02 on-chip
# BERT-large leg over the same corpus family (7.03 -> 4.65 in 200 steps at
# gbs 512) scaled for the smaller model, smaller batch, and longer
# horizon; "floor" values are must-pass, "target" values are expected.
if [ ! -f "$W/milestones.json" ]; then
  cat > "$W/milestones.json" <<'EOF'
{
  "stated_before_run": true,
  "floor": {"500": 6.2, "1000": 5.8, "2000": 5.3, "5000": 4.7},
  "target": {"500": 5.6, "1000": 5.1, "2000": 4.5, "5000": 3.8},
  "final_mlm_accuracy_floor": 0.18
}
EOF
fi

echo "== $MODEL, $STEPS steps, gbs $GLOBAL_BATCH, LR $LR (auto-resume on)"
python run_pretraining.py --input_dir "$W/encoded" \
    --output_dir "$W/run" \
    --model_config_file "$W/model.json" \
    --global_batch_size "$GLOBAL_BATCH" --local_batch_size "$LOCAL_BATCH" \
    --steps "$STEPS" --max_steps "$STEPS" \
    --learning_rate "$LR" --warmup_proportion 0.1 \
    --max_predictions_per_seq 20 --remat dots \
    --log_prefix log --log_steps 5 --num_steps_per_checkpoint 250 \
    --compile_cache_dir "$CACHE"

echo "== artifact: ${PREFIX}CONVERGENCE_LONG_r03.csv + ${PREFIX}LONG_RUN_r03.json"
python - "$W" "$STEPS" "$GLOBAL_BATCH" "$MODEL" "$LR" "$PREFIX" <<'EOF'
import csv, json, sys
w, steps, gbs, model, lr, prefix = sys.argv[1:7]
rows = [r for r in csv.DictReader(open(f"{w}/run/log_metrics.csv"))
        if r["tag"] == "train"]
with open(f"{prefix}CONVERGENCE_LONG_r03.csv", "w", newline="") as fo:
    wr = csv.writer(fo)
    wr.writerow(["optimizer", "step", "loss", "mlm_accuracy",
                 "learning_rate", "samples_per_second"])
    for r in rows:
        wr.writerow(["lamb", r["step"], r["step_loss"], r["mlm_accuracy"],
                     r["learning_rate"], r.get("samples_per_second", "")])
ms = json.load(open(f"{w}/milestones.json"))
by_step = {int(r["step"]): r for r in rows}
checks = {}
for kind in ("floor", "target"):
    for s, bound in ms[kind].items():
        row = by_step.get(int(s))
        got = float(row["step_loss"]) if row else None
        checks[f"{kind}@{s}"] = {
            "bound": bound, "loss": got,
            "pass": got is not None and got <= bound}
final = rows[-1]
acc = float(final["mlm_accuracy"])
checks["final_mlm_accuracy_floor"] = {
    "bound": ms["final_mlm_accuracy_floor"], "mlm_accuracy": acc,
    "pass": acc >= ms["final_mlm_accuracy_floor"]}
out = {
    "run": {"model": model, "steps": int(final["step"]),
            "global_batch": int(gbs), "learning_rate": lr,
            "final_loss": float(final["step_loss"]),
            "final_mlm_accuracy": acc},
    "milestones": ms, "checks": checks,
    "all_floors_pass": all(v["pass"] for k, v in checks.items()
                           if k.startswith("floor") or k.startswith("final")),
}
json.dump(out, open(f"{prefix}LONG_RUN_r03.json", "w"), indent=1)
print(json.dumps(out["checks"], indent=1))
print("all floors pass:", out["all_floors_pass"])
EOF
if [ -z "$PREFIX" ]; then
  python tools/plot_convergence.py CONVERGENCE_LONG_r03.csv \
      docs/convergence_long_r03.png \
      "BERT-base long run (gbs 256, LAMB, one v5e chip)"
else
  python tools/plot_convergence.py "${PREFIX}CONVERGENCE_LONG_r03.csv" \
      "${PREFIX}convergence_long_sanity.png"
fi
echo "long convergence OK"
