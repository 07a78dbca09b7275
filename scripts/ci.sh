#!/usr/bin/env bash
# Tier-1 CI: test suite + property-based scheduler invariants + serving
# smoke runs (single-engine and 2-replica router, both archs). The actual
# command lines live in the Makefile (single source).
#
#   scripts/ci.sh          # tests + properties + smokes
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== tier-1 pytest =="
make test

echo "== scheduler-policy property suite (seed 0) =="
make properties

echo "== serving smoke: LM (deepseek-7b) + DLRM =="
make smoke

echo "== router smoke: 2 replicas, LM (priority policy) + DLRM =="
make smoke-router

echo "== chunked-prefill smoke: chunked vs monolithic token identity =="
echo "==   (all-global arch + stateful RG-LRU/local-ring hybrid) =="
make smoke-chunked

echo "== work-stealing smoke: hot-spot steal + mid-run kill drain =="
make smoke-steal

echo "== quantized-serving smoke: w8a8 guardrail + mixed-precision pin =="
make smoke-quant

echo "== elastic-fleet smoke: flash crowd scale-up/down + fault drain =="
make smoke-elastic

echo "== prefix-cache smoke: warm-cache replay, token-identical hits =="
make smoke-prefix

echo "== fleet-prefix smoke: locality steering, remote hits, 0 lost =="
make smoke-fleet-prefix

echo "== autotune smoke: --prefill-chunk auto on the perf-model knee =="
make smoke-autotune

echo "CI OK"
