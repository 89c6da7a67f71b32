#!/bin/bash
# Round-5 drive: MoE serving depth. (1) tiny-moe full stack (streamed
# int8 + paged + int8 KV + spec + prefix) through the Ollama front;
# (2) a native MoE checkpoint through the streamed int8 loader
# ("quantized+fused (streaming, single-chip)" log line). PASS/FAIL.
set -u
cd "$(dirname "$0")/../.."
mkdir -p /tmp/v5
PORT=$((21000 + RANDOM % 5000))

# (2)'s fixture first: save a native tiny-moe checkpoint
python - <<'EOF'
import jax, jax.numpy as jnp
from p2p_llm_chat_tpu.models import mixtral
from p2p_llm_chat_tpu.models.checkpoint import save_checkpoint
from p2p_llm_chat_tpu.models.configs import get_config
cfg = get_config("tiny-moe")
params = mixtral.init_params(cfg, jax.random.PRNGKey(11), dtype=jnp.bfloat16)
save_checkpoint("/tmp/v5/moe_ckpt", params, cfg)
print("saved")
EOF
[ $? -eq 0 ] || { echo "FAIL: ckpt save"; exit 1; }

run_serve() {
  local extra_env=$1 log=$2
  env $extra_env SERVE_BACKEND=tpu SERVE_ADDR=127.0.0.1:$PORT \
      SERVE_KV_QUANT=int8 SERVE_QUANT=int8 SERVE_SPEC=2 \
      SERVE_SLOTS=4 SERVE_MAX_SEQ=128 SERVE_WARMUP=0 \
      python -m p2p_llm_chat_tpu.serve > $log 2>&1 &
  echo $!
}

drive() {
  local label=$1
  local up=0
  for i in $(seq 1 90); do
    curl -sf http://127.0.0.1:$PORT/api/version >/dev/null 2>&1 && { up=1; break; }
    sleep 1
  done
  [ $up = 1 ] || return 1
  curl -s -X POST http://127.0.0.1:$PORT/api/generate \
    -d '{"model":"m","prompt":"moe moe moe drive","stream":false,"options":{"num_predict":12}}' \
    > /tmp/v5/moe_resp_$label.json
  grep -q '"done": *true' /tmp/v5/moe_resp_$label.json || return 2
  curl -s http://127.0.0.1:$PORT/metrics | grep -E "serve_spec_accepted_total|serve_kv_free_pages" > /tmp/v5/moe_metrics_$label.txt
  grep -q serve_spec_accepted_total /tmp/v5/moe_metrics_$label.txt || return 3
  return 0
}

# Leg 1: random-init tiny-moe, full stack
PID=$(run_serve "MODEL_CONFIG=tiny-moe" /tmp/v5/moe_serve1.log)
drive init; rc=$?
kill $PID 2>/dev/null; wait $PID 2>/dev/null
[ $rc -eq 0 ] || { echo "FAIL leg1 rc=$rc"; tail -15 /tmp/v5/moe_serve1.log; exit 1; }
grep -q "quantized" /tmp/v5/moe_serve1.log && echo "leg1 ok: full-stack MoE served (spec+paged+int8)"

# Leg 2: native MoE checkpoint through the streamed int8 loader
PID=$(run_serve "CKPT_DIR=/tmp/v5/moe_ckpt" /tmp/v5/moe_serve2.log)
drive ckpt; rc=$?
kill $PID 2>/dev/null; wait $PID 2>/dev/null
[ $rc -eq 0 ] || { echo "FAIL leg2 rc=$rc"; tail -15 /tmp/v5/moe_serve2.log; exit 1; }
grep -q "quantized+fused (streaming, single-chip)" /tmp/v5/moe_serve2.log \
  && echo "leg2 ok: MoE checkpoint streamed to fused int8" \
  || { echo "FAIL leg2: streamed loader log line missing"; grep -i "load" /tmp/v5/moe_serve2.log | tail -5; exit 1; }
echo PASS
