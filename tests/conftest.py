"""Test harness: force JAX onto a virtual 8-device CPU platform so all
mesh/sharding/collective code is exercised without a TPU (SURVEY.md §4 —
the multi-device-without-a-cluster strategy).

Must run before anything imports jax, hence module-level os.environ writes in
conftest. bench.py and chip_smoke.py do NOT import this: they run on the
chip and fail without one.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Persistent compilation cache: DISABLED on this jaxlib. It was the single
# biggest suite-time lever (VERDICT r1 item 8), but on the pinned CPU
# jaxlib executing a cache-deserialized executable intermittently segfaults
# (native crash in libstdc++ under dispatch) or silently returns WRONG
# numerics — two identical engines built in one test diverge because the
# second hits the entry the first just wrote. Measured: test_families alone
# crashed 5/8 runs with the cache on (fresh OR warm dir, thunk runtime on
# or off) and passed 5/5 with it off; full-suite runs died at ~18% with a
# corrupted-heap segfault/abort. A slower suite beats a coin-flip suite.
# Re-enable (restore jax_compilation_cache_dir + the two thresholds) only
# after validating deserialization on an upgraded jaxlib.
jax.config.update("jax_enable_compilation_cache", False)

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run the test in an event loop")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 run (-m 'not slow')")
    config.addinivalue_line(
        "markers", "kernels: Pallas kernel parity tests (fast standalone "
        "leg: pytest -m 'kernels and not slow')")
    config.addinivalue_line(
        "markers", "obs: observability tests (metrics registry, step "
        "timeline, trace propagation; fast leg: pytest -m 'obs and not "
        "slow')")
    config.addinivalue_line(
        "markers", "lint: graftlint static-analysis tests (rule fixtures, "
        "pragma/baseline mechanics, zero-findings gate on the real tree)")
    config.addinivalue_line(
        "markers", "chaos: fault-injection / failover tests (seeded "
        "FaultPlan, deadlines, drain, kill/respawn; fast leg: pytest -m "
        "'chaos and not slow')")
    config.addinivalue_line(
        "markers", "elastic: elastic worker lifecycle tests (serving "
        "artifact round-trip/corruption, supervisor respawn, crash-loop "
        "breaker; fast leg: pytest -m 'elastic and not slow')")
    config.addinivalue_line(
        "markers", "fleet: fleet-scale serving tests (prefix-affinity "
        "routing, prefill/decode pools through the coordinator, affinity "
        "rebind on drain/respawn/failover; fast leg: pytest -m 'fleet "
        "and not slow')")
    config.addinivalue_line(
        "markers", "fabric: KV fabric tests (export/import wire bit-parity "
        "across KV dtypes, checksum rejection, pre-warm-before-half-open, "
        "failover import, fault fallback; fast leg: pytest -m 'fabric and "
        "not slow')")
    config.addinivalue_line(
        "markers", "autoscale: SLO-driven autoscaling and rolling-upgrade "
        "tests (policy hysteresis/cooldown/guards, decision-ledger "
        "determinism, drain→swap→probe→rejoin, fleet admission shed; "
        "fast leg: pytest -m 'autoscale and not slow')")
    config.addinivalue_line(
        "markers", "streaming: sub-chunk streaming tests (device->host "
        "token ring round-trip, sub-chunk vs packed-harvest parity, "
        "adaptive-chunk compile guard, mid-stream failover resume; fast "
        "leg: pytest -m 'streaming and not slow')")
    config.addinivalue_line(
        "markers", "multimodel: multi-model worker tests (resident-budget "
        "LRU eviction, background stage never blocks dispatch, probe-gated "
        "hot swap, model-qualified affinity/KV isolation, respawn reloads "
        "the resident set; fast leg: pytest -m 'multimodel and not slow')")
    config.addinivalue_line(
        "markers", "slo: fleet flight-recorder tests (typed event rings, "
        "clock-sync trace merge, SLO burn-rate engine, post-mortem "
        "bundles, same-seed determinism; fast leg: pytest -m 'slo and "
        "not slow')")


def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests in a fresh event loop (pytest-asyncio is not
    in the baked image, so the harness provides its own minimal runner)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture(scope="module")
def shared(request):
    """One engine a distinct (dtype, ``EngineConfig``) of the test module's
    ``tiny_engine``, built the first time a case asks for it: its programs
    compile once a module, not once a case. A case leaves it idle
    (``generate`` / ``run_until_idle``) and reads its counters as
    differences (``grown``)."""
    built = {}

    def get(dtype="bfloat16", **cfg_kw):
        key = (dtype, tuple(sorted(cfg_kw.items())))
        if key not in built:
            built[key] = request.module.tiny_engine(dtype, **cfg_kw)
        engine = built[key]
        assert engine.n_live == 0 and engine.n_waiting == 0
        return engine

    return get


def grown(before, after):
    """What the counters of ``get_metrics()``, or of one group of it, grew
    by between two readings."""
    return {k: v - before[k] for k, v in after.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}
