#!/bin/bash
# Full offline capability chain on locally synthesized data (zero egress):
#
#   synthesize text -> format/shard -> train WordPiece vocab (C++ trainer)
#   -> encode to HDF5 -> pretrain -> SQuAD-style finetune from the
#   pretraining checkpoint -> predict on a HELD-OUT dev set -> official
#   EM/F1 eval subprocess -> one JSON artifact.
#
# This is the reference's create_datasets.sh:85-141 + run_squad.py:1197-1224
# loop, proven end to end rather than piecewise (VERDICT r1 next-step #8).
#
#   bash scripts/e2e_offline.sh [workdir] [result_json]
#
# Profile via E2E_PROFILE: "tiny" (default; CPU-runnable in ~5 min, 2-layer
# model) or "chip" (BERT-base, a few hundred pretrain steps — run on TPU).
#
# RESUMABLE (same scheme as convergence_r02.sh): the data build is stamped
# by profile and skipped when already complete; the pretrain leg is skipped
# when its final checkpoint exists (and auto-resumes from any partial
# checkpoint otherwise); the finetune leg is skipped when the dev-set
# predictions exist, restarting from the pretrained checkpoint if
# interrupted. The shared compile cache covers recompiles either way.
set -euo pipefail
# Per-user scratch cache for the runner legs (not the world-shared /tmp,
# where another user could pre-seed entries that JAX deserializes as
# executables). The runners' own default is the checkout's .jax_cache/.
CACHE=${BENCH_COMPILE_CACHE_DIR:-${XDG_CACHE_HOME:-$HOME/.cache}/bert_tpu_jax_cache}
cd "$(dirname "$0")/.."
W=${1:-/tmp/bert_e2e}
RESULT=${2:-$W/e2e_result.json}
PROFILE=${E2E_PROFILE:-tiny}
mkdir -p "$W"

if [ "$PROFILE" = "chip" ]; then
  ART_PER_FILE=2000; VOCAB=8192
  HID=768; LAYERS=12; HEADS=12; FFN=3072
  PRETRAIN_STEPS=300; PRETRAIN_BATCH=64; LR=1e-3; CKPT_EVERY=100
  SQUAD_PARAS=400; SQUAD_STEPS=300; SQUAD_BATCH=32
else
  ART_PER_FILE=150; VOCAB=2048
  HID=128; LAYERS=2; HEADS=4; FFN=512
  PRETRAIN_STEPS=20; PRETRAIN_BATCH=16; LR=1e-3; CKPT_EVERY=10
  SQUAD_PARAS=40; SQUAD_STEPS=20; SQUAD_BATCH=8
fi

STAMP="profile=$PROFILE v2"
if [ ! -f "$W/.data_ok" ] || [ "$(cat "$W/.data_ok")" != "$STAMP" ]; then
  if [ -f "$W/.data_ok" ]; then
    echo "!! profile stamp mismatch (have '$(cat "$W/.data_ok")', want" \
         "'$STAMP') — REBUILDING $W from scratch"
  fi
  rm -rf "$W" && mkdir -p "$W"

  echo "== 1. synthesize corpus (shared fact world, seed 0)"
  python -m bert_pytorch_tpu.tools.make_synthetic_text corpus \
      --output_dir "$W/formatted" --num_files 4 \
      --articles_per_file "$ART_PER_FILE" --seed 0

  echo "== 2. shard on article boundaries"
  python -m bert_pytorch_tpu.tools.shard \
      --input_glob "$W/formatted/*.txt" \
      --output_dir "$W/sharded" --max_bytes_per_shard 200k

  echo "== 3. train WordPiece vocab (C++ trainer)"
  python -m bert_pytorch_tpu.tools.build_vocab \
      --input_glob "$W/sharded/*.txt" \
      --output "$W/vocab.txt" --vocab_size "$VOCAB" --min_frequency 1

  echo "== 4. encode documents -> HDF5 pretraining shards"
  python -m bert_pytorch_tpu.tools.encode_data \
      --input_dir "$W/sharded" --output_dir "$W/encoded" \
      --vocab_file "$W/vocab.txt" --max_seq_len 128 --next_seq_prob 0.5

  echo "== 5. model config sized to the trained vocab"
  python - "$W" "$HID" "$LAYERS" "$HEADS" "$FFN" <<'EOF'
import json, sys
w, hid, layers, heads, ffn = sys.argv[1], *map(int, sys.argv[2:])
n_vocab = sum(1 for l in open(f"{w}/vocab.txt") if l.strip())
json.dump({
    "vocab_size": n_vocab, "hidden_size": hid, "num_hidden_layers": layers,
    "num_attention_heads": heads, "intermediate_size": ffn,
    "max_position_embeddings": 512, "type_vocab_size": 2,
    "next_sentence": True, "vocab_file": f"{w}/vocab.txt",
    "tokenizer": "wordpiece", "lowercase": True,
}, open(f"{w}/model.json", "w"))
print("vocab entries:", n_vocab)
EOF

  echo "== 5b. synthesize SQuAD train + HELD-OUT dev (same fact world)"
  python -m bert_pytorch_tpu.tools.make_synthetic_text squad \
      --output "$W/squad_train.json" --paragraphs "$SQUAD_PARAS" \
      --qas_per_paragraph 3 --seed 11 --fact_seed 0
  python -m bert_pytorch_tpu.tools.make_synthetic_text squad \
      --output "$W/squad_dev.json" --paragraphs $((SQUAD_PARAS / 4)) \
      --qas_per_paragraph 3 --seed 97 --fact_seed 0

  echo "== 5c. synthesize SQuAD v2.0 train + dev (1/3 impossible questions)"
  python -m bert_pytorch_tpu.tools.make_synthetic_text squad \
      --output "$W/squad_v2_train.json" --paragraphs "$SQUAD_PARAS" \
      --qas_per_paragraph 3 --seed 23 --fact_seed 0 --impossible_frac 0.33
  python -m bert_pytorch_tpu.tools.make_synthetic_text squad \
      --output "$W/squad_v2_dev.json" --paragraphs $((SQUAD_PARAS / 4)) \
      --qas_per_paragraph 3 --seed 131 --fact_seed 0 --impossible_frac 0.33

  echo "$STAMP" > "$W/.data_ok"
else
  echo "== corpus/vocab/encode/squad data reused from $W ('$STAMP')"
fi

echo "== 6. pretrain"
if [ -f "$W/pretrain/pretrain_ckpts/ckpt_$PRETRAIN_STEPS.msgpack" ]; then
  echo "   already complete (ckpt_$PRETRAIN_STEPS exists), skipping"
else
  # Partial checkpoints are NOT cleared: run_pretraining auto-resumes from
  # the newest one, and CKPT_EVERY is below the step count so mid-run
  # checkpoints genuinely exist (an interrupted 300-step chip leg redoes
  # at most the last 100 steps, not the whole run).
  # local batch = global / device count (run_pretraining requires the
  # global batch to divide by local_batch x data shards; on an 8-chip host
  # the per-chip batch is PRETRAIN_BATCH/8). Device count is only probed
  # when the leg actually runs — a skipped rerun never touches a device.
  NDEV=$(python -c "import jax; print(len(jax.devices()))")
  LOCAL_BATCH=$((PRETRAIN_BATCH / NDEV))
  if [ "$LOCAL_BATCH" -lt 1 ]; then LOCAL_BATCH=1; fi
  # round the global batch to LOCAL*NDEV so the divisibility check always
  # holds (e.g. 16 samples on 6 devices -> local 2, global 12)
  PRETRAIN_BATCH=$((LOCAL_BATCH * NDEV))
  python run_pretraining.py --input_dir "$W/encoded" \
      --output_dir "$W/pretrain" \
      --model_config_file "$W/model.json" \
      --global_batch_size "$PRETRAIN_BATCH" --local_batch_size "$LOCAL_BATCH" \
      --steps "$PRETRAIN_STEPS" --max_steps "$PRETRAIN_STEPS" \
      --learning_rate "$LR" --warmup_proportion 0.1 \
      --max_predictions_per_seq 20 \
      --log_prefix log --num_steps_per_checkpoint "$CKPT_EVERY" \
      --compile_cache_dir "$CACHE"
fi
CKPT=$(ls -t "$W"/pretrain/pretrain_ckpts/ckpt_*.msgpack | head -1)
echo "pretrained checkpoint: $CKPT"

echo "== 7. finetune from the pretraining checkpoint + official eval"
if [ -f "$W/squad_out/predictions.json" ]; then
  echo "   already complete (predictions.json exists), skipping"
else
  rm -rf "$W/squad_out"
  python run_squad.py \
      --output_dir "$W/squad_out" \
      --config_file "$W/model.json" \
      --init_checkpoint "$CKPT" \
      --train_file "$W/squad_train.json" \
      --predict_file "$W/squad_dev.json" \
      --do_train --do_predict --do_eval --do_lower_case \
      --eval_script scripts/squad_evaluate_v11.py \
      --train_batch_size "$SQUAD_BATCH" --predict_batch_size "$SQUAD_BATCH" \
      --max_steps "$SQUAD_STEPS" --max_seq_length 128 \
      --doc_stride 64 --max_query_length 24 \
      --learning_rate 5e-5 --skip_cache \
      --compile_cache_dir "$CACHE"
fi

echo "== 7b. SQuAD v2.0 finetune (impossible questions) + official v2 eval"
if [ -f "$W/squad_v2_out/null_odds.json" ]; then
  # null_odds.json is written AFTER predictions.json; gating on the
  # last-written artifact keeps an interrupted leg re-runnable
  echo "   already complete (v2 null_odds.json exists), skipping"
else
  rm -rf "$W/squad_v2_out"
  python run_squad.py \
      --output_dir "$W/squad_v2_out" \
      --config_file "$W/model.json" \
      --init_checkpoint "$CKPT" \
      --train_file "$W/squad_v2_train.json" \
      --predict_file "$W/squad_v2_dev.json" \
      --do_train --do_predict --do_eval --do_lower_case \
      --version_2_with_negative \
      --eval_script scripts/squad_evaluate_v20.py \
      --train_batch_size "$SQUAD_BATCH" --predict_batch_size "$SQUAD_BATCH" \
      --max_steps "$SQUAD_STEPS" --max_seq_length 128 \
      --doc_stride 64 --max_query_length 24 \
      --learning_rate 5e-5 --skip_cache \
      --compile_cache_dir "$CACHE"
fi

echo "== 8. EM/F1 artifact (re-run the official metrics on both dev sets)"
SCORES=$(python scripts/squad_evaluate_v11.py \
    "$W/squad_dev.json" "$W/squad_out/predictions.json")
SCORES_V2=$(python scripts/squad_evaluate_v20.py \
    "$W/squad_v2_dev.json" "$W/squad_v2_out/predictions.json" \
    --na-prob-file "$W/squad_v2_out/null_odds.json")
python - "$RESULT" "$PROFILE" "$SCORES" "$SCORES_V2" <<'EOF'
import json, sys
result, profile = sys.argv[1], sys.argv[2]
scores, v2 = json.loads(sys.argv[3]), json.loads(sys.argv[4])
out = {"metric": "e2e_offline_squad", "profile": profile,
       "exact_match": scores["exact_match"], "f1": scores["f1"],
       "v2": {k: v2[k] for k in (
           "exact", "f1", "total", "HasAns_exact", "HasAns_f1",
           "NoAns_exact", "NoAns_f1", "best_exact", "best_exact_thresh",
           "best_f1", "best_f1_thresh") if k in v2}}
json.dump(out, open(result, "w"), indent=2)
print(json.dumps(out))
EOF
echo "e2e_offline OK -> $RESULT"
