#!/bin/bash
# Verify the graftcheck static-analysis gate end-to-end: the shipped
# tree must pass, and a seeded violation of each analyzer must fail the
# same invocation ci.sh runs (acceptance criterion: ci.sh fails when an
# unguarded write to a `# guarded-by:` attribute is introduced).
set -u
cd "$(dirname "$0")/../.."
mkdir -p /tmp/v

fail() { echo "FAIL: $1"; exit 1; }

# 1. Shipped tree is clean (the exact ci.sh invocation).
python -m tools.graftcheck p2p_llm_chat_tpu start_all.py tests \
  >/tmp/v/graftcheck_clean.log 2>&1 \
  || fail "shipped tree has findings: $(tail -3 /tmp/v/graftcheck_clean.log)"

# 2. Each seeded violation fixture flags (non-zero exit, right rule).
SEED=/tmp/v/graftcheck_seed
rm -rf "$SEED"; mkdir -p "$SEED"

seed_expect() {  # <fixture.py> <expected-rule>
  local fixture=$1 rule=$2
  python -m tools.graftcheck "$fixture" --root "$SEED" \
    >/tmp/v/graftcheck_seed.log 2>&1
  [ $? -eq 1 ] || fail "$fixture: expected exit 1"
  grep -q "$rule" /tmp/v/graftcheck_seed.log \
    || fail "$fixture: expected $rule, got $(cat /tmp/v/graftcheck_seed.log)"
}

cat > "$SEED/trace.py" <<'EOF'
import jax, numpy as np

@jax.jit
def step(x):
    return np.asarray(x) + 1
EOF
seed_expect "$SEED/trace.py" "trace-safety/host-sync"

cat > "$SEED/lock.py" <<'EOF'
import threading

class Store:
    def __init__(self):
        self._data = {}       # guarded-by: _mu
        self._mu = threading.Lock()

    def unguarded_write(self, k, v):
        self._data[k] = v
EOF
seed_expect "$SEED/lock.py" "lock-discipline/unguarded"

cat > "$SEED/envread.py" <<'EOF'
import os
addr = os.environ.get("SERVE_ADDR", "")
EOF
seed_expect "$SEED/envread.py" "env-hygiene/raw-read"

cat > "$SEED/test_marker.py" <<'EOF'
import pytest

@pytest.mark.sloow
def test_x():
    pass
EOF
seed_expect "$SEED/test_marker.py" "markers/unregistered"

# Round-13 analyzers: lock-order cycle, blocking-under-lock,
# metrics-contract drift, stream-close discipline.
cat > "$SEED/order.py" <<'EOF'
import threading

class A:
    def __init__(self):
        self._mu = threading.Lock()
        self.b = B(self)

    def m(self):
        with self._mu:
            self.b.poke()

    def poke2(self):
        with self._mu:
            pass

class B:
    def __init__(self, a: "A"):
        self._mu = threading.Lock()
        self.a = a

    def poke(self):
        with self._mu:
            pass

    def n(self):
        with self._mu:
            self.a.poke2()
EOF
seed_expect "$SEED/order.py" "lock-order/cycle"

mkdir -p "$SEED/serve"
cat > "$SEED/serve/block.py" <<'EOF'
import threading, time

class S:
    def __init__(self):
        self._mu = threading.Lock()

    def m(self):
        with self._mu:
            time.sleep(1.0)
EOF
seed_expect "$SEED/serve/block.py" "blocking/under-lock"

cat > "$SEED/serve/metrics_drift.py" <<'EOF'
AGGREGATION_TABLE = frozenset(("serve_ghost_total",))
EOF
seed_expect "$SEED/serve/metrics_drift.py" "metrics-contract/unexported"

cat > "$SEED/stream.py" <<'EOF'
def handler(req, Response):
    def gen():
        yield b"data"
        yield b"more"
    return Response(200, stream=gen())
EOF
seed_expect "$SEED/stream.py" "stream-close/no-finally"

# v3 analyzers: donated-buffer re-read, typo'd FAIL_POINTS site,
# Retry-After-less 503.
cat > "$SEED/donate.py" <<'EOF'
import jax

def _step(params, tokens, cache):
    return tokens

def run(params, toks, cache):
    step_j = jax.jit(_step, donate_argnums=(2,))
    out = step_j(params, toks, cache)
    return cache.k.sum()
EOF
seed_expect "$SEED/donate.py" "donation/use-after-donate"

# The failpoint fixture needs a registry in the seed root (registry
# rules disarm when no KNOWN_SITES module resolves — partial-run
# safety), plus an analyzed test file arming a typo'd site.
mkdir -p "$SEED/p2p_llm_chat_tpu/utils" "$SEED/tests"
cat > "$SEED/p2p_llm_chat_tpu/utils/failpoints.py" <<'EOF'
KNOWN_SITES = (
    "serve.api.parse",
)
EOF
cat > "$SEED/tests/test_chaos_seed.py" <<'EOF'
from p2p_llm_chat_tpu.utils import failpoints

def test_chaos():
    failpoints.arm("serve.api.parse", "raise")
    failpoints.arm("serve.api.prase", "raise")   # typo'd site
EOF
seed_expect "$SEED/tests/test_chaos_seed.py" "failpoints/unknown-site"

mkdir -p "$SEED/serve"
cat > "$SEED/serve/shed.py" <<'EOF'
from ..utils.http import Response

def shed(req):
    return Response(503, {"error": "full"})
EOF
seed_expect "$SEED/serve/shed.py" "http/503-no-retry-after"

# 3. ci.sh itself fails on a seeded in-tree violation: an unguarded
# write to a guarded-by attribute, appended to dht.py in a scratch
# copy of the tree (the real tree is never touched).
TREE=/tmp/v/graftcheck_tree
rm -rf "$TREE"; mkdir -p "$TREE"
cp -r p2p_llm_chat_tpu tools start_all.py ci.sh pytest.ini \
      docs "$TREE/"
mkdir -p "$TREE/tests"   # graftcheck target dir; tests themselves not needed
# Seed an unguarded METHOD on DHTNode (guarded-by is per-class, so the
# violation must live inside the class body).
python - "$TREE" <<'EOF'
import sys
tree = sys.argv[1]
p = f"{tree}/p2p_llm_chat_tpu/p2p/dht.py"
src = open(p).read()
marker = "    def close(self)"
assert marker in src, "seed anchor missing"
seeded = ("    def _seeded_violation(self):\n"
          "        self._store[0] = None\n\n" + marker)
open(p, "w").write(src.replace(marker, seeded, 1))
EOF
(cd "$TREE" && python -m tools.graftcheck p2p_llm_chat_tpu \
  >/tmp/v/graftcheck_ci.log 2>&1)
[ $? -eq 1 ] || fail "seeded tree: graftcheck did not flag the violation"
grep -q "lock-discipline/unguarded" /tmp/v/graftcheck_ci.log \
  || fail "seeded tree: wrong rule: $(cat /tmp/v/graftcheck_ci.log)"

# 4. Runtime lockcheck (GRAFTCHECK_LOCKCHECK=1): the rewritten class
# catches a deliberately unguarded write the moment it executes.
python - <<'EOF' >/tmp/v/lockcheck.log 2>&1 || fail "lockcheck leg: $(tail -3 /tmp/v/lockcheck.log)"
import importlib.util, os, sys, textwrap
sys.path.insert(0, os.getcwd())
from tools.graftcheck import lockcheck

src = textwrap.dedent("""
    import threading

    class Sched:
        def __init__(self):
            self._mu = threading.Lock()
            self._shed = 0        # guarded-by: _mu

        def ok(self):
            with self._mu:
                self._shed += 1

        def seeded_violation(self):
            self._shed += 1       # missing `with self._mu:`
""")
path = "/tmp/v/lockcheck_fixture.py"
open(path, "w").write(src)
spec = importlib.util.spec_from_file_location("lockcheck_fixture", path)
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
armed = lockcheck.instrument_module(mod, path)
assert armed == ["Sched._shed<-_mu"], armed
s = mod.Sched()
s.ok()                       # locked write passes
try:
    s.seeded_violation()
except lockcheck.LockcheckError:
    pass
else:
    raise SystemExit("unguarded write was NOT caught")
print("lockcheck: seeded unguarded write caught")
EOF

echo "PASS: graftcheck gates clean tree + flags seeded violations" \
     "(incl. lock-order/blocking/metrics/stream + runtime lockcheck" \
     "+ donation/failpoints/http)"
exit 0
