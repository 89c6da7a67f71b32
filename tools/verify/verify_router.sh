#!/bin/bash
# Replica-router live drive: launcher fleet (2 replicas + router), Ollama
# contract through the router, aggregation, drain semantics.
cd "$(dirname "$0")/../.."
P=19434
python start_all.py --replicas 2 --users "" --serve-port $P \
  --dir-port 19080 --node-port-base 19081 --ui-port-base 19501 \
  > /tmp/v10/launcher.log 2>&1 &
LPID=$!
URL=http://127.0.0.1:$P
ok=0
for i in $(seq 1 60); do
  if curl -sf $URL/readyz >/dev/null 2>&1; then ok=1; break; fi
  sleep 0.5
done
[ $ok = 1 ] || { echo "FAIL: fleet never ready"; kill $LPID; exit 1; }
echo "fleet ready"
# Non-streamed generate through the router
R=$(curl -sf -X POST $URL/api/generate -d '{"model":"fake-llm","prompt":"router drive\n\nReply:","stream":false}')
echo "$R" | grep -q '"done": *true' && echo "$R" | grep -q 'router drive' \
  && echo "PASS generate" || { echo "FAIL generate: $R"; }
# Streamed NDJSON
N=$(curl -sfN -X POST $URL/api/generate -d '{"model":"fake-llm","prompt":"stream through router\n\nReply:"}' | wc -l)
[ "$N" -ge 2 ] && echo "PASS stream ($N lines)" || echo "FAIL stream"
# Chat
C=$(curl -sf -X POST $URL/api/chat -d '{"messages":[{"role":"user","content":"hi there"}],"stream":false}')
echo "$C" | grep -q '"role": *"assistant"' && echo "PASS chat" || echo "FAIL chat: $C"
# Spread: 10 requests, both replicas take traffic
for i in $(seq 1 10); do curl -sf -X POST $URL/api/generate -d "{\"prompt\":\"spread $i\\n\\nReply:\",\"stream\":false}" >/dev/null; done
REPS=$(curl -sf $URL/admin/replicas)
echo "replicas: $REPS"
python - "$REPS" <<'PY'
import json, sys
r = json.loads(sys.argv[1])["replicas"]
assert len(r) == 2 and all(x["ready"] for x in r), r
assert all(x["routed"] > 0 for x in r), ("spread", [x["routed"] for x in r])
print("PASS spread", [x["routed"] for x in r])
PY
# Metrics aggregation: replica labels + fleet total
M=$(curl -sf $URL/metrics)
echo "$M" | grep -q 'serve_requests_total{replica="0"}' \
  && echo "$M" | grep -q 'serve_requests_total{replica="1"}' \
  && echo "$M" | grep -qE '^serve_requests_total [0-9.]+' \
  && echo "PASS metrics aggregation" || echo "FAIL metrics"
echo "$M" | grep -E '^router_(requests|retries)_total|^retry_attempts_total' | head -3
# Drain replica 0: new work avoids it, its own /readyz flips, undrain restores
curl -sf -X POST $URL/admin/drain -d '{"replica":0}' >/dev/null
sleep 0.5
B0=$(curl -sf $URL/admin/replicas | python -c "import json,sys; print(json.load(sys.stdin)['replicas'][0]['routed'])")
for i in $(seq 1 5); do curl -sf -X POST $URL/api/generate -d "{\"prompt\":\"post drain $i\\n\\nReply:\",\"stream\":false}" >/dev/null; done
A0=$(curl -sf $URL/admin/replicas | python -c "import json,sys; print(json.load(sys.stdin)['replicas'][0]['routed'])")
[ "$B0" = "$A0" ] && echo "PASS drain routes away" || echo "FAIL drain ($B0 -> $A0)"
RZ=$(curl -s -o /dev/null -w '%{http_code}' http://127.0.0.1:$((P+1))/readyz)
[ "$RZ" = 503 ] && echo "PASS replica readyz draining (503)" || echo "FAIL replica readyz $RZ"
curl -sf -X POST $URL/admin/undrain -d '{"replica":0}' >/dev/null
RZ=$(curl -s -o /dev/null -w '%{http_code}' http://127.0.0.1:$((P+1))/readyz)
[ "$RZ" = 200 ] && echo "PASS undrain (200)" || echo "FAIL undrain $RZ"
kill $LPID 2>/dev/null; wait $LPID 2>/dev/null
echo DONE
