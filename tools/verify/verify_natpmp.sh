#!/bin/bash
# Verify NAT-PMP end-to-end: real node process + fake gateway process.
set -u
cd "$(dirname "$0")/../.."
mkdir -p /tmp/v  # scratch for logs/pids

fail() { echo "FAIL: $1"; exit 1; }
trap 'kill "$(cat /tmp/v/gw.pid 2>/dev/null)" 2>/dev/null; kill "$(cat /tmp/v/dir2.pid 2>/dev/null)" 2>/dev/null; kill "$(cat /tmp/v/n.pid 2>/dev/null)" 2>/dev/null; true' EXIT

python "$(dirname "$0")/fake_gw.py" 18351 >/tmp/v/gw.log 2>&1 &
echo $! > /tmp/v/gw.pid
ADDR=127.0.0.1:18090 python -m p2p_llm_chat_tpu.directory >/tmp/v/dir2.log 2>&1 &
echo $! > /tmp/v/dir2.pid
for i in $(seq 1 30); do grep -q ready /tmp/v/gw.log 2>/dev/null && break; sleep 0.2; done

MYNAMEIS=najy HTTP_ADDR=127.0.0.1:18091 DIRECTORY_URL=http://127.0.0.1:18090 \
  P2P_ADDR=127.0.0.1:18191 DHT_ADDR=off NATPMP=1 NATPMP_GATEWAY=127.0.0.1:18351 \
  python -m p2p_llm_chat_tpu.node >/tmp/v/n.log 2>&1 &
echo $! > /tmp/v/n.pid

for i in $(seq 1 60); do
  curl -sf http://127.0.0.1:18091/me 2>/dev/null | grep -q "198.51.100.42" && break
  sleep 0.5
done
me=$(curl -sf http://127.0.0.1:18091/me)
echo "$me" | grep -q "/ip4/198.51.100.42/tcp/18191/p2p/" \
  || fail "external addr not advertised: $me"
grep -q "mappings \[(2, 18191)" /tmp/v/gw.log || fail "gateway saw no TCP mapping"

# Directory record carries the external addr (eager re-register).
lookup=$(curl -sf "http://127.0.0.1:18090/lookup?username=najy")
echo "$lookup" | grep -q "198.51.100.42" || fail "directory record lacks external addr: $lookup"

# Node stop releases the mapping on the gateway.
kill "$(cat /tmp/v/n.pid)" 2>/dev/null
sleep 1.5
tail -1 /tmp/v/gw.log | grep -q "mappings \[\]" || fail "mapping not released: $(tail -1 /tmp/v/gw.log)"

echo "PASS: NAT-PMP end-to-end (map, advertise, register, release)"
kill "$(cat /tmp/v/gw.pid)" "$(cat /tmp/v/dir2.pid)" 2>/dev/null
exit 0
