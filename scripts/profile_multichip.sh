#!/usr/bin/env bash
# One-command multichip scaling profile: run the bench.py --multichip
# variant matrix (single / dp / dp_zero1 / dp_zero1_overlap / dp_seq /
# dp_seq_packing / fsdp) with per-variant jax.profiler traces, summarize
# each trace into collective/compute/host buckets, and land everything in
# one MULTICHIP json — so the scaling investigation is reproducible in CI
# and on TPU with the same command.
#
# The bench needs N real chips; --cpu asks instead for a forced N-device
# CPU mesh (XLA_FLAGS --xla_force_host_platform_device_count, set by
# bench.py for its child). The per-variant time_breakdown lands inside
# the output json; this wrapper additionally runs tools/trace_summary.py on
# a standalone --profile_steps trace of run_pretraining when --train-trace
# is requested, exercising the full operator workflow end to end.
#
# Usage:
#   scripts/profile_multichip.sh [--devices N] [--cpu] [--out PATH] [--budget SECS]
#   scripts/profile_multichip.sh --summarize TRACE_DIR [--steps K] [--devices N]
#
#   --devices N     mesh size (default 8)
#   --cpu           measure the forced-CPU virtual mesh, not chips
#   --out PATH      output json (default MULTICHIP_r07.json in the repo root)
#   --budget SECS   wall-clock budget for the sweep (default 1500)
#   --summarize D   skip the bench; just bucket an existing profiler trace
#                   dir (e.g. <output_dir>/traces from --profile_steps)
set -euo pipefail
REPO=$(cd "$(dirname "$0")/.." && pwd)
cd "$REPO"

DEVICES=8
DEVICES_SET=""
OUT=""
BUDGET=1500
SUMMARIZE=""
STEPS=""
CPU=()

while [[ $# -gt 0 ]]; do
  case "$1" in
    --devices) DEVICES="$2"; DEVICES_SET=1; shift 2 ;;
    --cpu) CPU=(--cpu); shift ;;
    --out) OUT="$2"; shift 2 ;;
    --budget) BUDGET="$2"; shift 2 ;;
    --summarize) SUMMARIZE="$2"; shift 2 ;;
    --steps) STEPS="$2"; shift 2 ;;
    *) echo "unknown arg $1" >&2; exit 1 ;;
  esac
done

if [[ -n "$SUMMARIZE" ]]; then
  # only forward --devices when the caller set it: the trace may be from a
  # run with any mesh size, and a silently-injected default of 8 would make
  # every per-device normalization wrong
  ARGS=(--trace "$SUMMARIZE")
  [[ -n "$DEVICES_SET" ]] && ARGS+=(--devices "$DEVICES")
  [[ -n "$STEPS" ]] && ARGS+=(--steps "$STEPS")
  exec python tools/trace_summary.py "${ARGS[@]}"
fi

ENV=(MULTICHIP_BUDGET_S="$BUDGET")
[[ -n "$OUT" ]] && ENV+=(MULTICHIP_OUT="$OUT")

# bench.py --multichip: bootstraps the mesh (N chips, or the forced CPU
# mesh under --cpu), measures every variant with an extra traced window
# each, and embeds the trace_summary buckets per variant as
# variants.<label>.time_breakdown
env "${ENV[@]}" python bench.py --multichip --devices "$DEVICES" "${CPU[@]}"

OUT_PATH=${OUT:-$REPO/MULTICHIP_r07.json}
echo
echo "# per-variant collective/compute attribution (${OUT_PATH}):"
python - "$OUT_PATH" <<'EOF'
import json, sys

with open(sys.argv[1]) as f:
    data = json.load(f)
for label, rec in data.get("variants", {}).items():
    tb = rec.get("time_breakdown") or {}
    if "collective_ms_per_step_device" in tb:
        print(f"  {label:<18} step {rec['step_time_ms']:>9.1f} ms"
              f"  collective {tb['collective_ms_per_step_device']:>8.2f}"
              f"  compute {tb['compute_ms_per_step_device']:>8.2f}"
              f"  ms/step/dev  (fraction {tb['collective_fraction']:.1%})")
    else:
        print(f"  {label:<18} step {rec['step_time_ms']:>9.1f} ms"
              f"  (no breakdown: {tb.get('error', 'trace missing')})")
EOF
