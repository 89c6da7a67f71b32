#!/bin/bash
# Chunked-prefill verify: a long admission lands in fixed token-budget
# chunks interleaved with live decode ticks (docs/serving.md Round-7),
# driven through the Ollama-compatible front. Checks, in order: the
# warmup line advertises a compiled continuation-chunk ladder, a long
# prompt admitted OVER live streams actually chunks
# (prefill_chunks_total advances by the ladder length), fused decode
# stays live across the admission (decode_fused_mean_k > 1 — the
# pre-chunking policy collapsed it to 1 for the whole drain), and the
# new stall/TBT gauges publish. Bit-identity of chunked vs single-shot
# output is pinned by tests/test_chunked_prefill.py (ci.sh), not here.
set -u
cd "$(dirname "$0")/../.."
mkdir -p /tmp/v

fail() { echo "FAIL: $1"; exit 1; }
trap 'kill "$(cat /tmp/v/chunk.pid 2>/dev/null)" 2>/dev/null; true' EXIT

# tiny's max_seq_len is 256, so 256 is the long bucket: 4 chunks of 64.
SERVE_ADDR=127.0.0.1:18421 SERVE_BACKEND=tpu MODEL_CONFIG=tiny \
  SERVE_MAX_SEQ=256 SERVE_SLOTS=8 \
  SERVE_PREFILL_CHUNK=64 SERVE_WARMUP=128,256 SERVE_FUSE=4 \
  python -m p2p_llm_chat_tpu.serve >/tmp/v/chunk.log 2>&1 &
echo $! > /tmp/v/chunk.pid

ok=0
for i in $(seq 1 240); do
  grep -q "warmup compiled" /tmp/v/chunk.log 2>/dev/null && ok=1 && break
  sleep 0.5
done
[ "$ok" = 1 ] || fail "serve never warmed up: $(tail -3 /tmp/v/chunk.log)"
# The warmup line must report a non-empty continuation-program set (the
# ladder compiled BEFORE traffic — a lazy chunk compile mid-admission is
# the stall class chunking exists to remove).
grep -Eq "prefill chunk 64 \([1-9][0-9]* continuation" /tmp/v/chunk.log \
  || fail "warmup did not report the chunk ladder: \
$(grep 'warmup compiled' /tmp/v/chunk.log)"

# Two live streams decode while the long prompt arrives: the admission
# must interleave with their ticks, not stall them whole-prompt. (They
# land in the 128 bucket — itself chunked — so the baseline chunk count
# is read only after they admit.)
for i in 1 2; do
  curl -sN -X POST http://127.0.0.1:18421/api/generate \
    -H 'Content-Type: application/json' \
    -d '{"model":"tiny","prompt":"Draft a reply to: are we on for ten?","stream":true,"options":{"num_predict":96,"seed":'$i'}}' \
    >/tmp/v/chunk_stream$i.out &
  eval "s$i=$!"
done
sleep 2
chunks0=$(curl -sf http://127.0.0.1:18421/metrics \
  | grep "^prefill_chunks_total" | awk '{print $2}')
[ -n "$chunks0" ] || fail "metrics missing prefill_chunks_total"
long=$(python - <<'EOF'
head = "Summarize this long discussion thread about quarterly planning: "
print((head * 4)[:200])
EOF
)
r=$(curl -sf -X POST http://127.0.0.1:18421/api/generate \
  -H 'Content-Type: application/json' \
  -d '{"model":"tiny","prompt":"'"$long"'","stream":false,"options":{"num_predict":8,"seed":7}}')
echo "$r" | grep -q '"done": *true' || fail "long-prompt generate: $r"
wait $s1 $s2

m=$(curl -sf http://127.0.0.1:18421/metrics)
chunks=$(echo "$m" | grep "^prefill_chunks_total" | awk '{print $2}')
# 200-char prompt + BOS -> the 256 bucket -> 4 chunk dispatches of 64.
[ "$((chunks - chunks0))" -ge 4 ] \
  || fail "long admission did not chunk: $chunks0 -> $chunks"
echo "$m" | grep -q "^decode_stall_ms" || fail "metrics missing decode_stall_ms"
echo "$m" | grep -q "^inter_token_p95_ms" || fail "metrics missing inter_token_p95_ms"
# Fusion must have stayed live across the admission backlog.
k=$(echo "$m" | grep "^decode_fused_mean_k" | awk '{print $2}')
awk "BEGIN{exit !($k > 1)}" || fail "fused decode collapsed under admission: mean_k=$k"
stall=$(echo "$m" | grep "^decode_stall_ms" | awk '{print $2}')

echo "PASS: chunked prefill (ladder warmed, 4-chunk 256-bucket admission" \
     "over live streams, mean_k=$k, decode_stall_ms=$stall)"
kill "$(cat /tmp/v/chunk.pid)" 2>/dev/null
exit 0
