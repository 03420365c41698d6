#!/usr/bin/env bash
# Serving CI gate: start the server on an ephemeral port with tiny
# checkpoints for EVERY task in the registry, fire a mixed burst across
# all of them through tools/loadtest.py --task_mix, and fail unless
# (a) the server's served-task set EXACTLY matches registry.all_tasks()
#     (a registered-but-unserved or served-but-unregistered task is a
#     coverage hole, not a warning),
# (b) at least one request came back 2xx, and
# (c) the produced SERVE artifact is schema-valid.
#
# Then two fleet legs (round 17):
# (d) 2-replica mixed burst — /healthz must show BOTH replicas in the
#     fleet table, the burst must answer through the work-stealing
#     dispatcher, and SIGTERM must drain every replica to exit 0;
# (e) int8 smoke — quantized squad+classify serving answers a burst, the
#     offline quantcheck gate passes on clean scales AND trips (exit
#     nonzero) on an injected broken scale: a negative control that the
#     accuracy gate actually gates;
# (f) request tracing (round 18) — the mixed burst must export >=1
#     schema-valid request trace via --save_traces covering the full
#     admit -> queue_wait -> dispatch -> compute -> respond lifecycle,
#     and tools/trace_summary.py --requests must summarize it (exit 0).
#
#   scripts/check_serve.sh
#
# Fast by design (short bursts, tiny fixture): this only proves the stack
# serves. Serving speed is not measured on this runtime (PERF.md section 7).
set -euo pipefail
cd "$(dirname "$0")/.."

WORK="$(mktemp -d)"
SERVER_PID=""
cleanup() {
    [ -n "$SERVER_PID" ] && kill "$SERVER_PID" 2>/dev/null || true
    rm -rf "$WORK"
}
trap cleanup EXIT

REGISTRY_TASKS="$(python - <<'EOF'
from bert_pytorch_tpu.tasks.registry import all_tasks
print(",".join(all_tasks()))
EOF
)"
echo "check_serve: registry tasks: $REGISTRY_TASKS" >&2

echo "check_serve: building fixture (one checkpoint per task) ..." >&2
python scripts/make_serving_fixture.py --out "$WORK/fixture" >&2

# serve_args.txt is the fixture's ready-made argument list: config,
# vocab, per-task options, and one --task_checkpoint per registered task
mapfile -t SERVE_ARGS < "$WORK/fixture/serve_args.txt"
python run_server.py --force_cpu \
    "${SERVE_ARGS[@]}" \
    --buckets 32,64 --batch_rows 4 \
    --serve_dtype float32 --packing on \
    --port 0 --host 127.0.0.1 --port_file "$WORK/port" &
SERVER_PID=$!

for _ in $(seq 1 600); do
    [ -s "$WORK/port" ] && break
    kill -0 "$SERVER_PID" 2>/dev/null || {
        echo "check_serve: server died during warmup" >&2
        exit 1
    }
    sleep 0.2
done
[ -s "$WORK/port" ] || { echo "check_serve: server never became ready" >&2; exit 1; }
PORT="$(cat "$WORK/port")"

# coverage gate: served set == registered set, from the live /healthz;
# the machine-readable top-level status (round 20, SLO plane) must read
# "ok" on a clean warm server — operators and tools/loadtest.py
# --require_healthy key off this exact field
SERVED_TASKS="$(python - "$PORT" <<'EOF'
import json, sys, urllib.request
with urllib.request.urlopen(f"http://127.0.0.1:{sys.argv[1]}/healthz",
                            timeout=10) as r:
    doc = json.loads(r.read())
assert doc.get("status") == "ok", \
    f"clean warm server must report status=ok, got {doc.get('status')!r}"
print(",".join(sorted(doc["tasks"])))
EOF
)"
if [ "$SERVED_TASKS" != "$REGISTRY_TASKS" ]; then
    echo "check_serve: FAIL — served tasks [$SERVED_TASKS] != registered" \
         "tasks [$REGISTRY_TASKS] (register the task AND serve it)" >&2
    exit 1
fi
echo "check_serve: server warm on :$PORT serving [$SERVED_TASKS] — firing mixed burst" >&2

# loadtest exits 1 on zero 2xx responses — that IS the gate's second half;
# --task_mix all = every registered task, equal weight
python tools/loadtest.py --url "http://127.0.0.1:$PORT" \
    --label smoke --rates "${CHECK_SERVE_RATE:-15}" \
    --duration "${CHECK_SERVE_DURATION:-2}" --task_mix all \
    --save_traces "$WORK/traces" \
    --out "$WORK/smoke.json"

python tools/loadtest.py --assemble "$WORK/SERVE_smoke.json" "$WORK/smoke.json"
python tools/loadtest.py --validate "$WORK/SERVE_smoke.json"

# leg (f): the burst must have left >=1 schema-valid request trace whose
# span set covers the whole lifecycle — proving the tracing path is live
# end to end (admission, packer, dispatcher, engine, respond), not just
# unit-tested
TRACE_FILE="$WORK/traces/traces_smoke.json"
if [ ! -s "$TRACE_FILE" ]; then
    echo "check_serve: FAIL — mixed burst exported no request traces" \
         "(expected $TRACE_FILE from --save_traces)" >&2
    exit 1
fi
python - "$TRACE_FILE" <<'EOF'
import json, sys
with open(sys.argv[1], encoding="utf-8") as f:
    events = json.load(f)["traceEvents"]
by = {}
for ev in events:
    assert ev["ph"] == "X" and ev["name"].startswith("req/"), ev
    assert isinstance(ev["ts"], (int, float)), ev
    assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0, ev
    by.setdefault(ev["args"]["trace_id"], set()).add(ev["name"])
want = {"req/admit", "req/queue_wait", "req/dispatch", "req/compute",
        "req/respond"}
full = [tid for tid, names in by.items() if want <= names]
assert full, (f"no exported trace covers the full lifecycle "
              f"{sorted(want)}; saw {len(by)} trace(s)")
print(f"check_serve: {len(full)}/{len(by)} exported trace(s) cover the "
      "full admit->respond lifecycle", file=sys.stderr)
EOF
python tools/trace_summary.py --requests --trace "$TRACE_FILE" >&2

# graceful drain (docs/RESILIENCE.md): SIGTERM must stop admission,
# finish in-flight requests, flush metrics, and exit 0 — a nonzero exit
# here is a crash, not a drain
echo "check_serve: burst OK — drilling graceful drain (SIGTERM)" >&2
kill -TERM "$SERVER_PID"
DRAIN_RC=0
wait "$SERVER_PID" || DRAIN_RC=$?
SERVER_PID=""
if [ "$DRAIN_RC" -ne 0 ]; then
    echo "check_serve: FAIL — SIGTERM drain exited $DRAIN_RC (want 0)" >&2
    exit 1
fi
echo "check_serve: single-replica leg OK — drilling the 2-replica fleet" >&2

# -- leg (d): 2-replica fleet, mixed burst through the work-stealing
# dispatcher, then a full-fleet SIGTERM drain ---------------------------------
python run_server.py --force_cpu \
    "${SERVE_ARGS[@]}" \
    --buckets 32,64 --batch_rows 4 \
    --serve_dtype float32 --serve_replicas 2 --packing on \
    --port 0 --host 127.0.0.1 --port_file "$WORK/port2" &
SERVER_PID=$!
for _ in $(seq 1 600); do
    [ -s "$WORK/port2" ] && break
    kill -0 "$SERVER_PID" 2>/dev/null || {
        echo "check_serve: 2-replica server died during warmup" >&2
        exit 1
    }
    sleep 0.2
done
[ -s "$WORK/port2" ] || { echo "check_serve: 2-replica server never became ready" >&2; exit 1; }
PORT2="$(cat "$WORK/port2")"

# the /healthz fleet table must show BOTH replicas with their compiled
# bucket sets — a 1-entry table means scale-out silently collapsed
python - "$PORT2" <<'EOF'
import json, sys, urllib.request
with urllib.request.urlopen(f"http://127.0.0.1:{sys.argv[1]}/healthz",
                            timeout=10) as r:
    doc = json.loads(r.read())
reps = doc.get("replicas") or []
assert doc.get("serve_replicas") == 2, doc.get("serve_replicas")
assert len(reps) == 2, f"want 2 replicas in /healthz, got {len(reps)}"
for rep in reps:
    assert rep.get("compiled_buckets"), f"replica missing buckets: {rep}"
print(f"check_serve: /healthz fleet table OK: "
      f"{[rep['name'] for rep in reps]}", file=sys.stderr)
EOF

python tools/loadtest.py --url "http://127.0.0.1:$PORT2" \
    --label smoke2r --rates "${CHECK_SERVE_RATE:-15}" \
    --duration "${CHECK_SERVE_DURATION:-2}" --task_mix all \
    --out "$WORK/smoke2r.json"

echo "check_serve: 2-replica burst OK — drilling full-fleet drain (SIGTERM)" >&2
kill -TERM "$SERVER_PID"
DRAIN_RC=0
wait "$SERVER_PID" || DRAIN_RC=$?
SERVER_PID=""
if [ "$DRAIN_RC" -ne 0 ]; then
    echo "check_serve: FAIL — 2-replica SIGTERM drain exited $DRAIN_RC (want 0)" >&2
    exit 1
fi

# -- leg (e): int8 smoke + quantcheck accuracy gate (positive AND
# negative control) -----------------------------------------------------------
echo "check_serve: drilling int8 quantized serving" >&2
python run_server.py --force_cpu \
    "${SERVE_ARGS[@]}" \
    --buckets 32,64 --batch_rows 4 \
    --serve_dtype int8 --packing on \
    --port 0 --host 127.0.0.1 --port_file "$WORK/port8" &
SERVER_PID=$!
for _ in $(seq 1 600); do
    [ -s "$WORK/port8" ] && break
    kill -0 "$SERVER_PID" 2>/dev/null || {
        echo "check_serve: int8 server died during warmup (accuracy gate trip?)" >&2
        exit 1
    }
    sleep 0.2
done
[ -s "$WORK/port8" ] || { echo "check_serve: int8 server never became ready" >&2; exit 1; }
PORT8="$(cat "$WORK/port8")"
python tools/loadtest.py --url "http://127.0.0.1:$PORT8" \
    --label smoke8 --rates "${CHECK_SERVE_RATE:-15}" \
    --duration "${CHECK_SERVE_DURATION:-2}" --task_mix all \
    --out "$WORK/smoke8.json"
kill "$SERVER_PID" 2>/dev/null || true
wait "$SERVER_PID" 2>/dev/null || true
SERVER_PID=""

# offline gate: clean scales pass ...
python tools/quantcheck.py --force_cpu \
    --model_config_file "$WORK/fixture/model_config.json" \
    --task_checkpoint "squad=$WORK/fixture/squad_ckpt" \
    --task_checkpoint "classify=$WORK/fixture/classify_ckpt" \
    --out "$WORK/quantcheck.json"
# ... and a corrupted scale MUST trip it (exit nonzero) — if the gate
# waves a broken quantization through, the gate itself is the bug
if python tools/quantcheck.py --force_cpu \
    --model_config_file "$WORK/fixture/model_config.json" \
    --task_checkpoint "squad=$WORK/fixture/squad_ckpt" \
    --inject broken_scale >"$WORK/quantcheck_broken.log" 2>&1; then
    echo "check_serve: FAIL — quantcheck passed an injected broken scale" >&2
    cat "$WORK/quantcheck_broken.log" >&2
    exit 1
fi
echo "check_serve: quantcheck gate OK (clean passes, broken scale trips)" >&2

echo "check_serve: OK — all $(echo "$REGISTRY_TASKS" | tr ',' '\n' | wc -l) registered tasks served, burst answered, artifact validates, request traces exported + summarized, SIGTERM drained to exit 0; 2-replica fleet burst + drain OK; int8 smoke + quantcheck gate OK"
