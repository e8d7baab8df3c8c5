#!/usr/bin/env bash
# Tier-1 verify: the exact pytest line CI and the PR driver run.
# Tests run on the CPU: pin the platform so jax never probes for a TPU;
# Pallas kernels then run in interpret mode, derived from the platform.
#
# Tiers:
#   ./test.sh           full tier — whole suite (slow cells included) plus a
#                       benchmarks.run smoke so BENCH json emission can't rot,
#                       plus the docs gates (link + docstring coverage)
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
  # BENCH json emission smoke: one timed iteration, must produce the artifact.
  # Emit into a temp dir so the 1-iteration junk timings never dirty the
  # *tracked* BENCH_kernels_bench.json (an empty dir also means no stale copy
  # can mask a rotted emission path)
  SMOKE_DIR=$(mktemp -d)
  trap 'rm -rf "$SMOKE_DIR"' EXIT
  python -m benchmarks.run --only kernels --smoke --out-dir "$SMOKE_DIR" > /dev/null
  test -s "$SMOKE_DIR/BENCH_kernels_bench.json"
  # the sconv_csr axis (dense vs CSR spatial conv at 25/50 joints across a
  # density sweep) must be emitted by the smoke run and present in the
  # *tracked* artifact — a regenerated BENCH_kernels_bench.json that loses
  # the variable-topology rows fails here
  python - "$SMOKE_DIR/BENCH_kernels_bench.json" <<'EOF'
import json, sys
for path in (sys.argv[1], "BENCH_kernels_bench.json"):
    names = {r["name"] for r in json.load(open(path))}
    for topo in ("ntu25", "ntu50"):
        for d in ("d25", "d50"):
            for impl in ("dense_ref", "csr_ref", "dense_pallas",
                         "csr_pallas"):
                want = f"kernels/sconv_csr/{topo}/{d}/{impl}"
                assert want in names, f"{path} missing {want}"
EOF
  # one-dispatch tick smoke: the throughput module's tick_fused axis must
  # run the fused serving tick end-to-end (S=4, reference backend) and
  # emit its rows; the tracked BENCH_throughput.json must carry the full
  # fused-vs-legacy axis at the serving slot counts
  python -m benchmarks.run --only throughput --smoke --backend reference \
    --out-dir "$SMOKE_DIR" > /dev/null
  python - "$SMOKE_DIR/BENCH_throughput.json" <<'EOF'
import json, sys
names = {r["name"] for r in json.load(open(sys.argv[1]))}
for path, wl in (("fused", "fifo"), ("fused", "preempt"),
                 ("legacy", "fifo"), ("legacy", "preempt")):
    want = f"throughput/measured/tick_fused/reference/S4/{path}/{wl}"
    assert want in names, f"smoke run missing {want}"
names = {r["name"] for r in json.load(open("BENCH_throughput.json"))}
for backend in ("reference", "pallas"):
    for S in (16, 64, 256):
        for path in ("fused", "legacy"):
            want = f"throughput/measured/tick_fused/{backend}/S{S}/{path}/fifo"
            assert want in names, f"tracked BENCH_throughput.json missing {want}"
EOF
  # adaptive-streaming axis: the smoke run must emit the ck x saliency
  # grid (S=16, reference) and the tracked artifact must carry it for
  # both backends — a regenerated BENCH_throughput.json that loses the
  # adaptive rows fails here
  python - "$SMOKE_DIR/BENCH_throughput.json" <<'EOF'
import json, sys
names = {r["name"] for r in json.load(open(sys.argv[1]))}
for ck in (0, 1):
    for sal in (0, 1):
        want = f"throughput/measured/ck_saliency/reference/S16/ck{ck}/sal{sal}"
        assert want in names, f"smoke run missing {want}"
names = {r["name"] for r in json.load(open("BENCH_throughput.json"))}
for backend in ("reference", "pallas"):
    for ck in (0, 1):
        for sal in (0, 1):
            want = f"throughput/measured/ck_saliency/{backend}/S16/ck{ck}/sal{sal}"
            assert want in names, \
                f"tracked BENCH_throughput.json missing {want}"
EOF
  # distributed tier rides the full tier (a separate interpreter: the
  # fake-device flag only takes effect before jax's backend initialises)
  run_dist
  # the tracked BENCH_sessions.json must carry the distributed axes: a
  # mesh-sharded row with its collective cost and a routed multi-replica
  # row with its rebalance count
  python - <<'EOF'
import json
rows = json.load(open("BENCH_sessions.json"))
assert any(r.get("mesh", 1) > 1 and "collective_ms_per_tick" in r
           for r in rows), "no mesh-sharded row in BENCH_sessions.json"
assert any(r.get("replicas", 1) > 1 and "rebalances" in r
           for r in rows), "no routed-replica row in BENCH_sessions.json"
EOF
  # docs gates ride the full tier: broken intra-repo links, a public
  # docstring coverage regression in core/kernels/serving, or undeclared
  # public-API drift (docs/api_surface.txt) fail the build
  python tools/check_docs.py
  python tools/check_api.py
fi
