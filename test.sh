#!/usr/bin/env bash
# Tier-1 verify: the exact pytest line CI and the PR driver run.
# Tests run on the CPU: pin the platform so jax never probes for a TPU;
# Pallas kernels then run in interpret mode, derived from the platform.
#
# Tiers:
#   ./test.sh           full tier — whole suite (slow cells included), then
#                       the distributed tier and the docs gates
#   ./test.sh --fast    fast tier — deselects @pytest.mark.slow (the heavy
#                       pallas-interpret cells; markers in pyproject.toml)
#   ./test.sh --docs    docs tier only — intra-repo markdown links must
#                       resolve, public docstring coverage in
#                       src/repro/{core,kernels,serving} must hold at 100%,
#                       and the public API surface of repro.serving +
#                       repro.core.agcn.engine must match the checked-in
#                       docs/api_surface.txt (tools/check_api.py --update
#                       regenerates it on intentional changes)
#   ./test.sh --dist    distributed tier — tests/test_distributed.py under
#                       XLA_FLAGS=--xla_force_host_platform_device_count=4
#                       (mesh-sharded slab parity, cross-replica migration
#                       parity, router pinning/rebalance units); the full
#                       tier runs it too
#   ./test.sh --traces  traffic/trace tier — tests/test_traffic.py (traffic
#                       model properties, trace serialization, SLO
#                       controller units; Monte-Carlo cells are @slow) +
#                       tests/test_traces_golden.py (golden trace replay
#                       locks + the demand-vs-slo acceptance A/B); the
#                       full tier runs both via normal collection
# Extra args pass through to pytest (e.g. ./test.sh --fast -k streaming).
set -euo pipefail
cd "$(dirname "$0")"

export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
export JAX_PLATFORMS=cpu

FAST=0
DOCS=0
DIST=0
TRACES=0
ARGS=()
for a in "$@"; do
  case "$a" in
    --fast) FAST=1 ;;
    --docs) DOCS=1 ;;
    --dist) DIST=1 ;;
    --traces) TRACES=1 ;;
    *) ARGS+=("$a") ;;
  esac
done

run_dist() {
  # 4 fake host devices make the 1-D batch mesh real on CPU; the flag must
  # reach a *fresh* interpreter before jax initialises its backend
  XLA_FLAGS="--xla_force_host_platform_device_count=4${XLA_FLAGS:+ $XLA_FLAGS}" \
    python -m pytest -x -q tests/test_distributed.py ${ARGS[@]+"${ARGS[@]}"}
}

if [ "$DIST" = 1 ]; then
  run_dist
elif [ "$TRACES" = 1 ]; then
  python -m pytest -x -q tests/test_traffic.py tests/test_traces_golden.py \
    ${ARGS[@]+"${ARGS[@]}"}
elif [ "$DOCS" = 1 ]; then
  python tools/check_docs.py
  python tools/check_api.py
elif [ "$FAST" = 1 ]; then
  python -m pytest -x -q -m "not slow" ${ARGS[@]+"${ARGS[@]}"}
else
  python -m pytest -x -q ${ARGS[@]+"${ARGS[@]}"}
  # distributed tier rides the full tier (a separate interpreter: the
  # fake-device flag only takes effect before jax's backend initialises)
  run_dist
  # docs gates ride the full tier: broken intra-repo links, a public
  # docstring coverage regression in core/kernels/serving, or undeclared
  # public-API drift (docs/api_surface.txt) fail the build
  python tools/check_docs.py
  python tools/check_api.py
fi
