#!/bin/bash
# Serve-plane verify: full feature stack (paged + int8 + spec + prefix)
# through the Ollama-compatible front, per the project verify skill.
set -u
cd "$(dirname "$0")/../.."
mkdir -p /tmp/v  # scratch for logs/pids

fail() { echo "FAIL: $1"; exit 1; }
trap 'kill "$(cat /tmp/v/serve.pid 2>/dev/null)" 2>/dev/null; true' EXIT

SERVE_ADDR=127.0.0.1:18411 SERVE_BACKEND=tpu MODEL_CONFIG=tiny \
  SERVE_QUANT=int8 SERVE_SPEC=3 \
  python -m p2p_llm_chat_tpu.serve >/tmp/v/serve.log 2>&1 &
echo $! > /tmp/v/serve.pid

ok=0
for i in $(seq 1 240); do
  grep -q "warmup compiled" /tmp/v/serve.log 2>/dev/null && ok=1 && break
  sleep 0.5
done
[ "$ok" = 1 ] || fail "serve never warmed up: $(tail -3 /tmp/v/serve.log)"

r=$(curl -sf -X POST http://127.0.0.1:18411/api/generate \
  -H 'Content-Type: application/json' \
  -d '{"model":"tiny","prompt":"Hello there, how are","stream":false,"options":{"num_predict":16,"seed":1}}')
echo "$r" | grep -q '"done": *true' || fail "generate: $r"

r=$(curl -sf -X POST http://127.0.0.1:18411/api/chat \
  -H 'Content-Type: application/json' \
  -d '{"model":"tiny","messages":[{"role":"user","content":"hi"}],"stream":false,"options":{"num_predict":8}}')
echo "$r" | grep -q '"done": *true' || fail "chat: $r"

m=$(curl -sf http://127.0.0.1:18411/metrics)
echo "$m" | grep -q "serve_prefix_admits_total" || fail "metrics missing prefix series"
# Pool drains back to total after requests complete.
free=$(echo "$m" | grep "^serve_kv_free_pages" | awk '{print $2}')
total=$(echo "$m" | grep "^serve_kv_total_pages" | awk '{print $2}')
[ -n "$free" ] && [ "$free" = "$total" ] || fail "pool not drained: free=$free total=$total"

echo "PASS: serve plane (paged+int8+spec+prefix) generate/chat/metrics"
kill "$(cat /tmp/v/serve.pid)" 2>/dev/null
exit 0
