#!/bin/bash
# Verify the DHT lookup rung with real OS processes.
set -u
cd "$(dirname "$0")/../.."
mkdir -p /tmp/v  # scratch for logs/pids
rm -f /tmp/v/*.log /tmp/v/*.pid

fail() { echo "FAIL: $1"; exit 1; }
trap 'kill "$(cat /tmp/v/dir.pid 2>/dev/null)" 2>/dev/null; kill "$(cat /tmp/v/a.pid 2>/dev/null)" 2>/dev/null; kill "$(cat /tmp/v/b.pid 2>/dev/null)" 2>/dev/null; kill "$(cat /tmp/v/c.pid 2>/dev/null)" 2>/dev/null; true' EXIT

ADDR=127.0.0.1:18080 python -m p2p_llm_chat_tpu.directory >/tmp/v/dir.log 2>&1 &
echo $! > /tmp/v/dir.pid

# Node A: seed of the DHT chain.
MYNAMEIS=najy HTTP_ADDR=127.0.0.1:18081 DIRECTORY_URL=http://127.0.0.1:18080 \
  DHT_ADDR=127.0.0.1:18180 python -m p2p_llm_chat_tpu.node >/tmp/v/a.log 2>&1 &
echo $! > /tmp/v/a.pid

for i in $(seq 1 60); do
  curl -sf http://127.0.0.1:18081/me >/dev/null 2>&1 && break
  sleep 0.5
done
curl -sf http://127.0.0.1:18081/me | grep -q '"dht_addr": *"127.0.0.1:18180"' \
  || fail "node A /me missing dht_addr"

# Nodes B and C bootstrap off A's DHT addr. A and C NEVER exchange messages
# before the outage.
MYNAMEIS=cannan HTTP_ADDR=127.0.0.1:18082 DIRECTORY_URL=http://127.0.0.1:18080 \
  DHT_ADDR=127.0.0.1:18181 DHT_BOOTSTRAP=127.0.0.1:18180 \
  python -m p2p_llm_chat_tpu.node >/tmp/v/b.log 2>&1 &
echo $! > /tmp/v/b.pid
MYNAMEIS=carol HTTP_ADDR=127.0.0.1:18083 DIRECTORY_URL=http://127.0.0.1:18080 \
  DHT_ADDR=127.0.0.1:18182 DHT_BOOTSTRAP=127.0.0.1:18181 \
  python -m p2p_llm_chat_tpu.node >/tmp/v/c.log 2>&1 &
echo $! > /tmp/v/c.pid

for port in 18082 18083; do
  for i in $(seq 1 60); do
    curl -sf http://127.0.0.1:$port/me >/dev/null 2>&1 && break
    sleep 0.5
  done
done

# Normal directory-backed send still works (A -> B).
r=$(curl -sf -X POST http://127.0.0.1:18081/send \
  -H 'Content-Type: application/json' \
  -d '{"to_username":"cannan","content":"via directory"}')
echo "$r" | grep -q '"status": *"sent"' || fail "directory send A->B: $r"

# Give the DHT publishes a moment (background join threads), then KILL the
# directory.
sleep 2
kill "$(cat /tmp/v/dir.pid)" 2>/dev/null
sleep 0.5
curl -sf http://127.0.0.1:18080/lookup?username=carol >/dev/null 2>&1 \
  && fail "directory still up?"

# A -> C: never paired, directory dead. Must resolve via the DHT
# (A -> B -> C routing chain).
r=$(curl -s -X POST http://127.0.0.1:18081/send \
  -H 'Content-Type: application/json' \
  -d '{"to_username":"carol","content":"via DHT through the outage"}')
echo "$r" | grep -q '"status": *"sent"' || fail "DHT send A->C: $r"
grep -q "resolved via DHT" /tmp/v/a.log || fail "A did not use the DHT rung"

# C actually received it.
for i in $(seq 1 20); do
  inbox=$(curl -sf "http://127.0.0.1:18083/inbox?after=")
  echo "$inbox" | grep -q "via DHT through the outage" && break
  sleep 0.25
done
echo "$inbox" | grep -q "via DHT through the outage" || fail "C inbox empty: $inbox"

# Unknown user while directory is down -> 404 (clean error surface).
code=$(curl -s -o /dev/null -w '%{http_code}' -X POST http://127.0.0.1:18081/send \
  -H 'Content-Type: application/json' \
  -d '{"to_username":"nobody","content":"x"}')
[ "$code" = "404" ] || fail "unknown user gave $code, want 404"

echo "PASS: DHT rung end-to-end (directory-down resolve of never-paired peer)"
for f in /tmp/v/a.pid /tmp/v/b.pid /tmp/v/c.pid; do
  kill "$(cat $f)" 2>/dev/null
done
exit 0
