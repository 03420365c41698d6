#!/usr/bin/env bash
# Distillation CI gate (CPU, minutes): the whole student factory proves
# itself end to end on a tiny marker task —
#
# (a) a teacher finetunes on the marker classify task (run_finetune.py),
# (b) run_distill.py trains a narrower/shallower student from it
#     (packed, tap losses + width-bridging projections) and the logged
#     KD-mix train loss DECREASES (first vs last telemetry record),
# (c) the student checkpoint serves through run_server.py with ITS OWN
#     model_config.json; /healthz reports per-task model_params > 0 and
#     the student's param count is strictly below the teacher's
#     (compression, not relabeling), and a loadtest burst answers 2xx
#     with --model_tag stamped into the mode artifact,
# (d) teacher + student legs assemble into a DISTILL artifact
#     (loadtest --assemble --kind distill) carrying accuracy deltas and
#     vs_teacher_per_chip, schema-valid,
# (e) loadtest --check_distill PASSES on the clean student and TRIPS
#     (exit nonzero) on `run_distill.py --inject broken_student` — the
#     negative control that the accuracy floor actually gates.
#
#   scripts/check_distill.sh
#
# Fast by design (tiny model, short bursts): it gates accuracy, not speed.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

WORK="$(mktemp -d)"
SERVER_PID=""
cleanup() {
    [ -n "$SERVER_PID" ] && kill "$SERVER_PID" 2>/dev/null || true
    rm -rf "$WORK"
}
trap cleanup EXIT

echo "check_distill: building marker-task fixture ..." >&2
python - "$WORK" <<'EOF'
import json, sys
import numpy as np
work = sys.argv[1]
VOCAB = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"] + (
    "the cat sat on mat a dog did run in park fast slow red blue "
    "green and is was to of thing . , ?").split()
open(f"{work}/vocab.txt", "w").write("\n".join(VOCAB) + "\n")
cfg = {"vocab_size": len(VOCAB), "hidden_size": 32,
       "num_hidden_layers": 2, "num_attention_heads": 4,
       "intermediate_size": 64, "max_position_embeddings": 64,
       "hidden_dropout_prob": 0.0, "attention_probs_dropout_prob": 0.0,
       "fused_ops": False, "attention_impl": "xla", "lowercase": True,
       "tokenizer": "wordpiece", "vocab_file": f"{work}/vocab.txt"}
json.dump(cfg, open(f"{work}/model_config.json", "w"))
rng = np.random.RandomState(0)
words = [w for w in VOCAB if not w.startswith("[")]
sent = lambda n: " ".join(rng.choice(words, n))
for split, n in (("train", 32), ("test", 12)):
    with open(f"{work}/cls_{split}.tsv", "w") as f:
        for i in range(n):
            lab = i % 2
            marker = "cat cat cat" if lab else "dog dog dog"
            f.write(f"{'positive' if lab else 'negative'}\t"
                    f"{marker} {sent(2 + i % 8)}\n")
EOF

COMMON_ARGS=(--task classify
    --train_file "$WORK/cls_train.tsv" --test_file "$WORK/cls_test.tsv"
    --model_config_file "$WORK/model_config.json"
    --epochs 14 --lr 1e-3 --batch_size 8 --max_seq_len 32
    --dtype float32)

echo "check_distill: (a) training the teacher ..." >&2
python run_finetune.py "${COMMON_ARGS[@]}" \
    --output_dir "$WORK/teacher" >"$WORK/teacher.log" 2>&1 \
    || { tail -5 "$WORK/teacher.log" >&2; exit 1; }

echo "check_distill: (b) distilling student_1l_16 (packed, taps) ..." >&2
python run_distill.py "${COMMON_ARGS[@]}" \
    --student student_1l_16 --teacher_checkpoint "$WORK/teacher/ckpt" \
    --alpha_hidden 1.0 --alpha_attn 0.5 \
    --packing --packing_max_segments 4 \
    --output_dir "$WORK/student" >"$WORK/student.log" 2>&1 \
    || { tail -5 "$WORK/student.log" >&2; exit 1; }

python - "$WORK/student/distill_summary.json" <<'EOF'
import json, sys
s = json.load(open(sys.argv[1]))
assert s["loss_first"] is not None and s["loss_last"] is not None, s
assert s["loss_last"] < s["loss_first"], \
    f"KD mix loss did not decrease: {s['loss_first']} -> {s['loss_last']}"
assert s["projections"], "width-differing student must carry projections"
print(f"check_distill: KD loss {s['loss_first']:.3f} -> "
      f"{s['loss_last']:.3f}, student acc {s.get('test_accuracy')}, "
      f"teacher acc {s.get('teacher_test_accuracy')}")
EOF

serve_and_burst() {
    # serve_and_burst <ckpt> <config> <tag> <out_mode_json>
    local ckpt="$1" config="$2" tag="$3" out="$4"
    rm -f "$WORK/port"
    python run_server.py --force_cpu \
        --model_config_file "$config" --vocab_file "$WORK/vocab.txt" \
        --task_checkpoint "classify=$ckpt" \
        --class_names negative positive \
        --buckets 32,64 --batch_rows 4 --serve_dtype float32 \
        --packing on --port 0 --host 127.0.0.1 \
        --port_file "$WORK/port" >"$WORK/serve_$tag.log" 2>&1 &
    SERVER_PID=$!
    for _ in $(seq 1 600); do
        [ -s "$WORK/port" ] && break
        kill -0 "$SERVER_PID" 2>/dev/null || {
            echo "check_distill: $tag server died during warmup" >&2
            tail -5 "$WORK/serve_$tag.log" >&2
            exit 1
        }
        sleep 0.2
    done
    local port; port="$(cat "$WORK/port")"
    # satellite: /healthz must carry the served model's parameter count
    python - "$port" "$tag" "$WORK/params_$tag" <<'EOF'
import json, sys, urllib.request
port, tag, out = sys.argv[1:]
with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                            timeout=10) as r:
    h = json.loads(r.read())
n = h["tasks"]["classify"]["model_params"]
assert isinstance(n, int) and n > 0, h["tasks"]["classify"]
open(out, "w").write(str(n))
print(f"check_distill: {tag} /healthz model_params={n}")
EOF
    python tools/loadtest.py --url "http://127.0.0.1:$port" \
        --label "$tag" --model_tag "$tag" \
        --meta dtype=f32 --meta n_chips=1 \
        --rates 15 --duration 2 --tasks classify --out "$out"
    kill "$SERVER_PID" 2>/dev/null || true
    wait "$SERVER_PID" 2>/dev/null || true
    SERVER_PID=""
}

echo "check_distill: (c) serving teacher + student, short bursts ..." >&2
serve_and_burst "$WORK/teacher/ckpt" "$WORK/model_config.json" \
    teacher "$WORK/mode_teacher.json"
serve_and_burst "$WORK/student/ckpt" "$WORK/student/model_config.json" \
    student_1l_16 "$WORK/mode_student.json"

python - "$WORK/params_teacher" "$WORK/params_student_1l_16" <<'EOF'
import sys
t, s = (int(open(p).read()) for p in sys.argv[1:])
assert s < t, f"student ({s} params) not smaller than teacher ({t})"
print(f"check_distill: compression real — {t} -> {s} params")
EOF
python - "$WORK/mode_student.json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["meta"]["model_tag"] == "student_1l_16", doc["meta"]
EOF

echo "check_distill: (d) assembling the DISTILL artifact ..." >&2
read -r T_ACC S_ACC <<<"$(python -c "
import json
s = json.load(open('$WORK/student/distill_summary.json'))
print(s['teacher_test_accuracy'], s['test_accuracy'])")"
python tools/loadtest.py --assemble "$WORK/DISTILL_smoke.json" \
    "$WORK/mode_teacher.json" "$WORK/mode_student.json" \
    --kind distill --accuracy "teacher=$T_ACC" \
    --accuracy "student_1l_16=$S_ACC"
python tools/loadtest.py --validate "$WORK/DISTILL_smoke.json"

echo "check_distill: (e) accuracy floor gates ..." >&2
python tools/loadtest.py --check_distill "$WORK/DISTILL_smoke.json" 0.25

echo "check_distill: negative control (--inject broken_student) ..." >&2
python run_distill.py "${COMMON_ARGS[@]}" \
    --student student_1l_16 --teacher_checkpoint "$WORK/teacher/ckpt" \
    --packing --packing_max_segments 4 --inject broken_student \
    --output_dir "$WORK/broken" >"$WORK/broken.log" 2>&1 \
    || { tail -5 "$WORK/broken.log" >&2; exit 1; }
BROKEN_ACC="$(python -c "
import json
print(json.load(open('$WORK/broken/distill_summary.json'))['test_accuracy'])")"
python tools/loadtest.py --assemble "$WORK/DISTILL_broken.json" \
    "$WORK/mode_teacher.json" "$WORK/mode_student.json" \
    --kind distill --accuracy "teacher=$T_ACC" \
    --accuracy "student_1l_16=$BROKEN_ACC"
if python tools/loadtest.py --check_distill "$WORK/DISTILL_broken.json" \
    0.25; then
    echo "check_distill: FAIL — accuracy gate did NOT trip on the" \
         "broken_student injection (delta vs teacher: $T_ACC ->" \
         "$BROKEN_ACC)" >&2
    exit 1
fi
echo "check_distill: gate tripped on broken_student as required" >&2

echo "check_distill: PASS" >&2
