#!/usr/bin/env bash
# Repo gate: the tier-1 test suite (exactly the ROADMAP.md verify
# command) plus a static lint pass. Run from anywhere; exits non-zero
# if either stage fails.
#
#   ./scripts/check.sh            # lint + full tier-1 suite
#   SKIP_TESTS=1 ./scripts/check.sh   # lint only (fast pre-commit)
set -u
cd "$(dirname "$0")/.."

rc=0

# --- stage 1: static checks -------------------------------------------
# pyflakes when the environment has it; otherwise fall back to a
# bytecode-compile sweep, which still catches syntax errors everywhere
# (including files the tests never import).
if python -c "import pyflakes" 2>/dev/null; then
    echo "== pyflakes =="
    python -m pyflakes distributed_inference_engine_tpu tests bench.py \
        examples scripts 2>/dev/null || rc=1
else
    echo "== compileall (pyflakes not installed) =="
    python -m compileall -q distributed_inference_engine_tpu tests \
        bench.py examples scripts || rc=1
fi

if [ "$rc" -ne 0 ]; then
    echo "check.sh: static checks FAILED" >&2
    exit "$rc"
fi

if [ "${SKIP_TESTS:-0}" = "1" ]; then
    echo "check.sh: static checks OK (tests skipped)"
    exit 0
fi

# --- stage 2a: graftlint ----------------------------------------------
# AST analysis of the serving stack: host-sync reads in the hot call
# graph, jit-stability hazards, async hygiene, docs<->code drift
# (subsumes the old scripts/lint_metrics.py check). Bare interpreter,
# no jax — drift fails in milliseconds. Any unsuppressed finding fails;
# NEW findings must be fixed or pragma'd with a reason, never silently
# baselined (refreshing the baseline takes an explicit, reviewed
# `python -m scripts.graftlint --update-baseline`).
echo "== graftlint (python -m scripts.graftlint) =="
python -m scripts.graftlint distributed_inference_engine_tpu bench.py \
    || rc=1
if [ "$rc" -ne 0 ]; then
    echo "check.sh: graftlint FAILED" >&2
    exit "$rc"
fi

# --- stage 2b: fast observability leg ---------------------------------
# registry/exposition/timeline/trace tests (-m obs) run standalone next:
# a telemetry regression fails here in seconds.
echo "== observability (-m 'obs and not slow') =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'obs and not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
if [ "$rc" -ne 0 ]; then
    echo "check.sh: observability leg FAILED" >&2
    exit "$rc"
fi

# --- stage 2c: fast chaos leg -----------------------------------------
# fault-injection / failover tests (-m chaos): seeded FaultPlan faults,
# deadline budgets, graceful drain, mid-stream kill + resume. A broken
# failure path fails here in seconds, before the full sweep.
echo "== chaos (-m 'chaos and not slow') =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'chaos and not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
if [ "$rc" -ne 0 ]; then
    echo "check.sh: chaos leg FAILED" >&2
    exit "$rc"
fi

# --- stage 2d: fast elastic-lifecycle leg -----------------------------
# serving-artifact round-trip/corruption/cold-start + supervisor
# respawn/crash-loop tests (-m elastic): a broken artifact or respawn
# path fails here before the full sweep.
echo "== elastic lifecycle (-m 'elastic and not slow') =="
timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'elastic and not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
if [ "$rc" -ne 0 ]; then
    echo "check.sh: elastic lifecycle leg FAILED" >&2
    exit "$rc"
fi

# --- stage 2e: fast fleet-serving leg ---------------------------------
# prefix-affinity routing, disaggregated pools through the coordinator,
# rebind on drain/respawn/stream-failover (-m fleet): a broken routing
# or handoff path fails here before the full sweep.
echo "== fleet serving (-m 'fleet and not slow') =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'fleet and not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
if [ "$rc" -ne 0 ]; then
    echo "check.sh: fleet serving leg FAILED" >&2
    exit "$rc"
fi

# --- stage 2f: fast autoscale leg -------------------------------------
# the SLO → fleet-size loop (-m autoscale): policy hysteresis/cooldown/
# guard rails, decision-ledger determinism, rolling upgrade with golden-
# probe rollback, fleet-level admission shed.
echo "== autoscaling (-m 'autoscale and not slow') =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'autoscale and not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
if [ "$rc" -ne 0 ]; then
    echo "check.sh: autoscale leg FAILED" >&2
    exit "$rc"
fi

# --- stage 2g: fast KV-fabric leg -------------------------------------
# fleet-wide KV page migration (-m fabric): export/import wire
# bit-parity across KV dtypes, checksum rejection, pre-warm-before-
# half-open ordering, failover import, fault fallback.
echo "== kv fabric (-m 'fabric and not slow') =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'fabric and not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
if [ "$rc" -ne 0 ]; then
    echo "check.sh: kv fabric leg FAILED" >&2
    exit "$rc"
fi

# --- stage 2g2: fast flight-recorder leg ------------------------------
# fleet flight recorder (-m slo): typed event rings (wrap mid-capture,
# canonical sequences), clock-sync merged-trace monotonicity with
# mixed-sign offsets, SLO burn-rate engine windows + ledger
# determinism, post-mortem bundle round-trip.
echo "== flight recorder (-m 'slo and not slow') =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'slo and not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
if [ "$rc" -ne 0 ]; then
    echo "check.sh: flight recorder leg FAILED" >&2
    exit "$rc"
fi

# --- stage 2: fast kernel-parity leg ----------------------------------
# Pallas kernel tests (-m kernels) run standalone FIRST: a broken kernel
# fails here in seconds instead of minutes into the full tier-1 sweep.
echo "== kernel parity (-m 'kernels and not slow') =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'kernels and not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
if [ "$rc" -ne 0 ]; then
    echo "check.sh: kernel parity leg FAILED" >&2
    exit "$rc"
fi

# --- stage 2h: fast streaming leg -------------------------------------
# sub-chunk streaming (-m streaming): device->host token ring round-trip,
# sub-chunk vs packed-harvest parity (greedy + sampled, stops trimmed
# identically), adaptive-chunk compile guard, mid-stream kill resume
# through the fabric path with no duplicate/missing token.
echo "== streaming (-m 'streaming and not slow') =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'streaming and not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
if [ "$rc" -ne 0 ]; then
    echo "check.sh: streaming leg FAILED" >&2
    exit "$rc"
fi

# --- stage 2i: fast multimodel leg ------------------------------------
# multi-model workers (-m multimodel): resident-budget LRU eviction,
# background stage never displacing dispatch, golden-probe-gated hot
# swap, model-qualified affinity keys + KV isolation, supervisor respawn
# reloading the full resident catalog.
echo "== multimodel (-m 'multimodel and not slow') =="
timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'multimodel and not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly || rc=1
if [ "$rc" -ne 0 ]; then
    echo "check.sh: multimodel leg FAILED" >&2
    exit "$rc"
fi

# --- stage 3: tier-1 tests (verbatim ROADMAP.md verify command) -------
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 1500 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)
exit $rc
