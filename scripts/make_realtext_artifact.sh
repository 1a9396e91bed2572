#!/bin/bash
# Package a real-text training run into a committed artifact directory:
# loss curve + metrics CSV (from log.txt via obs/plotting.py), ppl + cloze
# eval scores (tools/evaluate.py), config, and corpus manifest.
#
# Usage: scripts/make_realtext_artifact.sh <run_dir> <out_dir> \
#            [val_jsonl] [corpus_manifest]
set -euo pipefail
RUN=${1:?run dir}
OUT=${2:?out dir}
VAL=${3:-/tmp/realrun/data/val.jsonl}
MANIFEST=${4:-/tmp/realrun/corpus.manifest.json}
REPO="$(cd "$(dirname "$0")/.." && pwd)"
# Default to CPU: the evals are small, and a chip belongs to one process
# at a time. Export ARTIFACT_JAX_PLATFORM=tpu to eval on the chip.
PY=(env PYTHONPATH="$REPO" JAX_PLATFORMS="${ARTIFACT_JAX_PLATFORM:-cpu}" python)

mkdir -p "$OUT"
cp "$RUN/config.yaml" "$RUN/log.txt" "$OUT/"
[ -f "$MANIFEST" ] && cp "$MANIFEST" "$OUT/corpus.manifest.json"

"${PY[@]}" -m mlx_cuda_distributed_pretraining_tpu.obs.plotting "$RUN" \
  --out "$OUT/loss_curve.png"
[ -f "$RUN/metrics.csv" ] && cp "$RUN/metrics.csv" "$OUT/" || true

NAME=$(basename "$RUN")
ROOT=$(dirname "$RUN")
"${PY[@]}" -m mlx_cuda_distributed_pretraining_tpu.tools.evaluate \
  --run "$NAME" --runs-root "$ROOT" --task ppl --data "$VAL" \
  --seq-len 512 --batch-size 4 > "$OUT/eval_ppl.json"
"${PY[@]}" -m mlx_cuda_distributed_pretraining_tpu.tools.make_cloze_eval \
  "$VAL" --out "$OUT/cloze.jsonl" --n 400
"${PY[@]}" -m mlx_cuda_distributed_pretraining_tpu.tools.evaluate \
  --run "$NAME" --runs-root "$ROOT" --task mc --data "$OUT/cloze.jsonl" \
  > "$OUT/eval_cloze.json"
cat "$OUT"/eval_*.json
echo "artifact at $OUT"
