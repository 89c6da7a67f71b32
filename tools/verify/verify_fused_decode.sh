#!/bin/bash
# Drive the fused multi-step decode path end-to-end: real serve process,
# Ollama front, streamed + non-streamed generates, /metrics assertions.
set -u
mkdir -p /tmp/vf
cd "$(dirname "$0")/../.."
PORT=18433
SERVE_BACKEND=tpu MODEL_CONFIG=tiny SERVE_KV_QUANT=int8 \
  SERVE_QUANT=int8 SERVE_FUSE=4 SERVE_SLOTS=4 SERVE_MAX_SEQ=256 \
  SERVE_WARMUP=64,128 SERVE_ADDR=127.0.0.1:$PORT \
  python -m p2p_llm_chat_tpu.serve >/tmp/vf/serve.log 2>&1 &
SPID=$!
trap "kill $SPID 2>/dev/null" EXIT

for i in $(seq 1 120); do
  curl -sf "http://127.0.0.1:$PORT/api/version" >/dev/null 2>&1 && break
  sleep 1
done
curl -sf "http://127.0.0.1:$PORT/api/version" >/dev/null || { echo "FAIL: serve never came up"; tail -5 /tmp/vf/serve.log; exit 1; }
# wait for warmup (fused ladder compiles) so metrics include the probe
for i in $(seq 1 120); do
  grep -q "warmup compiled" /tmp/vf/serve.log && break
  sleep 1
done

# non-streamed generate
R1=$(curl -sf -X POST "http://127.0.0.1:$PORT/api/generate" \
  -d '{"prompt":"fused decode drive","stream":false,"options":{"num_predict":24}}')
echo "$R1" | grep -q '"done": true' || { echo "FAIL: generate: $R1"; exit 1; }
EVAL=$(echo "$R1" | python -c "import json,sys; print(json.load(sys.stdin)['eval_count'])")
[ "$EVAL" -ge 1 ] || { echo "FAIL: eval_count=$EVAL"; exit 1; }

# streamed generate (burst-coalesced NDJSON)
curl -sfN -X POST "http://127.0.0.1:$PORT/api/generate" \
  -d '{"prompt":"stream me a burst","options":{"num_predict":24,"temperature":0.7,"seed":3}}' \
  > /tmp/vf/stream.ndjson || { echo "FAIL: stream request"; exit 1; }
NLINES=$(wc -l < /tmp/vf/stream.ndjson)
tail -1 /tmp/vf/stream.ndjson | grep -q '"done": true' || { echo "FAIL: no final record"; exit 1; }

# 4 concurrent requests to hold the batch while fusing
PIDS=""
for i in 1 2 3 4; do
  curl -sf -X POST "http://127.0.0.1:$PORT/api/generate" \
    -d "{\"prompt\":\"concurrent $i\",\"stream\":false,\"options\":{\"num_predict\":32}}" \
    -o /tmp/vf/c$i.json & PIDS="$PIDS $!"
done
wait $PIDS
for i in 1 2 3 4; do
  grep -q '"done": true' /tmp/vf/c$i.json || { echo "FAIL: concurrent $i"; exit 1; }
done

M=$(curl -sf "http://127.0.0.1:$PORT/metrics")
for key in decode_fused_ticks_total decode_fused_steps_total decode_fused_mean_k decode_wall_ms decode_device_ms; do
  echo "$M" | grep -q "^$key" || { echo "FAIL: /metrics missing $key"; exit 1; }
done
FT=$(echo "$M" | grep "^decode_fused_ticks_total" | awk '{print $2}')
MK=$(echo "$M" | grep "^decode_fused_mean_k" | awk '{print $2}')
DD=$(echo "$M" | grep "^decode_device_ms" | awk '{print $2}')
python -c "import sys; ft=float('$FT'); mk=float('$MK'); dd=float('$DD'); sys.exit(0 if ft>0 and mk>1.0 and dd>0 else 1)" \
  || { echo "FAIL: fused metrics not engaged: ticks=$FT mean_k=$MK device_ms=$DD"; exit 1; }
echo "PASS: fused decode serve drive (stream lines=$NLINES, fused ticks=$FT, mean K=$MK, device step=${DD}ms)"
