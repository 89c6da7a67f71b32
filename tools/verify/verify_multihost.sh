#!/bin/bash
# Round-5 drive: batched multihost serving. 2 OS processes (leader +
# follower, dp=2 over the process boundary), 4 distinct concurrent
# requests + a seeded re-post + /api/embed; /metrics must prove >1
# request per lockstep round and the seeded completion must reproduce.
# Prints PASS/FAIL.
set -u
cd "$(dirname "$0")/../.."
mkdir -p /tmp/v5
COORD_PORT=$((20000 + RANDOM % 8000))
SERVE_PORT=$((COORD_PORT + 1))
COORD=127.0.0.1:$COORD_PORT

spawn() {
  local pid=$1
  REPO="$PWD" PYTHONPATH="$PWD" \
  XLA_FLAGS=--xla_force_host_platform_device_count=1 \
  JAX_PLATFORMS=cpu JAX_COORDINATOR=$COORD JAX_NUM_PROCESSES=2 \
  JAX_PROCESS_ID=$pid SERVE_BACKEND=tpu SERVE_COORDINATOR=$COORD \
  MODEL_CONFIG=tiny SERVE_MAX_SEQ=128 SERVE_MH_WINDOW_MS=300 \
  SERVE_ADDR=127.0.0.1:$SERVE_PORT \
  python -m p2p_llm_chat_tpu.serve.api > /tmp/v5/mh_$pid.log 2>&1 &
  echo $! > /tmp/v5/mh_$pid.pid
}

spawn 0
spawn 1

up=0
for i in $(seq 1 120); do
  if curl -sf http://127.0.0.1:$SERVE_PORT/api/version >/dev/null 2>&1; then up=1; break; fi
  sleep 1
done
if [ "$up" != 1 ]; then echo "FAIL: front never came up"; tail -20 /tmp/v5/mh_0.log; exit 1; fi
echo "front up"

# warm round
curl -s -X POST http://127.0.0.1:$SERVE_PORT/api/generate \
  -d '{"model":"tiny","prompt":"warm","stream":false,"options":{"num_predict":8}}' > /tmp/v5/mh_warm.json
grep -q '"done": *true' /tmp/v5/mh_warm.json && echo "warm ok" || { echo "FAIL warm"; cat /tmp/v5/mh_warm.json; exit 1; }

for i in 1 2 3 4 5; do
  curl -s http://127.0.0.1:$SERVE_PORT/metrics | grep serve_multihost > /tmp/v5/mh_metrics_before.txt
  [ -s /tmp/v5/mh_metrics_before.txt ] && break; sleep 1
done
grep -q serve_multihost_requests /tmp/v5/mh_metrics_before.txt || { echo "FAIL: metrics-before empty"; exit 1; }

# 4 distinct concurrent requests (one sampled with a fixed seed)
PIDS=""
for i in 0 1 2 3; do
  case $i in
    3) body='{"model":"tiny","prompt":"delta hawk","stream":false,"options":{"num_predict":8,"temperature":0.8,"top_k":16,"seed":1234}}';;
    *) body="{\"model\":\"tiny\",\"prompt\":\"request number $i\",\"stream\":false,\"options\":{\"num_predict\":8}}";;
  esac
  curl -s -X POST http://127.0.0.1:$SERVE_PORT/api/generate -d "$body" > /tmp/v5/mh_r$i.json &
  PIDS="$PIDS $!"
done
wait $PIDS
for i in 0 1 2 3; do
  grep -q '"done": *true' /tmp/v5/mh_r$i.json || { echo "FAIL req $i"; cat /tmp/v5/mh_r$i.json; exit 1; }
done
echo "4 concurrent ok"

# seed reproducibility: same seeded request again must return identical text
curl -s -X POST http://127.0.0.1:$SERVE_PORT/api/generate \
  -d '{"model":"tiny","prompt":"delta hawk","stream":false,"options":{"num_predict":8,"temperature":0.8,"top_k":16,"seed":1234}}' > /tmp/v5/mh_r3b.json
python - <<'EOF'
import json
a = json.load(open('/tmp/v5/mh_r3.json'))['response']
b = json.load(open('/tmp/v5/mh_r3b.json'))['response']
assert a == b, (a, b)
print('seed-reproducible ok:', repr(a[:40]))
EOF

for i in 1 2 3 4 5; do
  # embeddings over the mesh
curl -s -X POST http://127.0.0.1:$SERVE_PORT/api/embed \
  -d '{"model":"tiny","input":["alpha","bravo","charlie"]}' > /tmp/v5/mh_embed.json
python - <<'PYEOF'
import json
d = json.load(open('/tmp/v5/mh_embed.json'))
assert len(d["embeddings"]) == 3 and len(d["embeddings"][0]) > 0
print('embed ok:', len(d["embeddings"]), 'vectors dim', len(d["embeddings"][0]))
PYEOF
curl -s http://127.0.0.1:$SERVE_PORT/metrics | grep serve_multihost > /tmp/v5/mh_metrics_after.txt
  [ -s /tmp/v5/mh_metrics_after.txt ] && break; sleep 1
done
echo "--- metrics after:"; cat /tmp/v5/mh_metrics_after.txt
python - <<'EOF'
def load(p):
    d = {}
    for ln in open(p):
        parts = ln.split()
        if len(parts) == 2 and not ln.startswith('#'):
            d[parts[0]] = float(parts[1])
    return d
b, a = load('/tmp/v5/mh_metrics_before.txt'), load('/tmp/v5/mh_metrics_after.txt')
served = a['serve_multihost_requests'] - b['serve_multihost_requests']
rounds = a['serve_multihost_batched_rounds'] - b['serve_multihost_batched_rounds']
print(f'served={served} rounds={rounds}')
assert served == 5, served          # 4 concurrent + 1 seed-repro
assert rounds < served, (rounds, served)   # >1 request per model pass
print('BATCHING PROVEN: %.1f requests per lockstep round (concurrent window)' % (served/rounds))
EOF
rc=$?
kill $(cat /tmp/v5/mh_0.pid) $(cat /tmp/v5/mh_1.pid) 2>/dev/null
[ $rc -eq 0 ] && echo PASS || echo FAIL
exit $rc
