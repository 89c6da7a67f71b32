#!/bin/bash
# Verify the single-chip streamed int8 checkpoint load end-to-end:
# build a tiny NATIVE checkpoint, serve it with SERVE_QUANT=int8 (takes
# weights.load_checkpoint_quantized), and generate through the front.
set -u
cd "$(dirname "$0")/../.."
mkdir -p /tmp/v

fail() { echo "FAIL: $1"; exit 1; }
trap 'kill "$(cat /tmp/v/serve_q.pid 2>/dev/null)" 2>/dev/null; true' EXIT

CKPT=/tmp/v/ckpt_tiny
rm -rf "$CKPT"
JAX_PLATFORMS=cpu python - <<'EOF' || exit 1
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
from p2p_llm_chat_tpu.models import llama
from p2p_llm_chat_tpu.models.checkpoint import save_checkpoint
from p2p_llm_chat_tpu.models.configs import get_config
cfg = get_config("tiny")
params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
save_checkpoint("/tmp/v/ckpt_tiny", params, cfg)
print("checkpoint saved")
EOF

SERVE_ADDR=127.0.0.1:18421 SERVE_BACKEND=tpu CKPT_DIR=$CKPT LLM_MODEL=tiny \
  SERVE_QUANT=int8 SERVE_KV_QUANT=int8 \
  python -m p2p_llm_chat_tpu.serve >/tmp/v/serve_q.log 2>&1 &
echo $! > /tmp/v/serve_q.pid

ok=0
for i in $(seq 1 240); do
  grep -q "warmup compiled" /tmp/v/serve_q.log 2>/dev/null && ok=1 && break
  sleep 0.5
done
[ "$ok" = 1 ] || fail "serve never warmed up: $(tail -3 /tmp/v/serve_q.log)"

grep -q "quantized+fused (streaming, single-chip)" /tmp/v/serve_q.log \
  || fail "serve did not take the streamed int8 loader: $(grep loaded /tmp/v/serve_q.log)"

r=$(curl -sf -X POST http://127.0.0.1:18421/api/generate \
  -H 'Content-Type: application/json' \
  -d '{"model":"tiny","prompt":"Hello","stream":false,"options":{"num_predict":12,"seed":7}}')
echo "$r" | grep -q '"done": *true' || fail "generate: $r"

echo "PASS: streamed int8 checkpoint load serves end-to-end"
kill "$(cat /tmp/v/serve_q.pid)" 2>/dev/null
exit 0
