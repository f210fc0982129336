"""Test bootstrap: force an 8-device virtual CPU mesh *before* jax import.

Mirrors the reference's test strategy (SURVEY.md §4): everything runs on
one machine — multi-chip sharding is validated on virtual CPU devices,
the control plane against in-memory sqlite with mocked backends.
"""

import os

# Force CPU regardless of the ambient JAX_PLATFORMS (e.g. a chip
# machine's): unit tests always run on the virtual 8-device CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compile cache (DTPU_TEST_NO_COMPILE_CACHE=1 turns it off):
# the suite's seconds are CPU compiles, six xdist workers on 8 cores under
# the driver's 1,470 s (docs/guides/testing.md). All processes share ONE
# directory (JAX's key holds the path: a directory a worker shares nothing)
# and an entry is written whole, a temporary file then ``os.replace``: JAX's
# plain ``write_bytes`` let a neighbour read half of one and die (PR 37).
_use_compile_cache = os.environ.get("DTPU_TEST_NO_COMPILE_CACHE") != "1"
if _use_compile_cache:
    # every cache load logs a harmless cpu_aot_loader machine-feature ERROR
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import asyncio  # noqa: E402
import inspect  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402


def share_compile_cache() -> str:
    """Turn the cache on at the suite's directory, ``tests/`` under the
    entry points' (the server children that tests start write theirs
    without a lock), with an atomic ``put``;
    ``tests/test_compile_cache_contract.py`` pins the ``jax._src`` names
    this stands on."""
    from jax._src import compilation_cache, lru_cache

    from dstack_tpu.utils.backend import compile_cache_dir, enable_compile_cache

    class AtomicPutCache(lru_cache.LRUCache):
        def put(self, key: str, val: bytes) -> None:
            entry = self.path / f"{key}{lru_cache._CACHE_SUFFIX}"
            if not entry.exists():
                tmp = self.path / f"{key}.{os.getpid()}.{threading.get_ident()}.tmp"
                tmp.write_bytes(val)
                os.replace(tmp, entry)

    compilation_cache.get_file_cache = lambda path: (
        AtomicPutCache(path, max_size=-1), path
    )
    return enable_compile_cache(os.path.join(compile_cache_dir(), "tests"))


if _use_compile_cache:
    share_compile_cache()


# ---- quick tier ----
# `pytest -m "not heavy" -q` is the smoke pass: the full control plane
# (server/agent/api/core) plus one representative per compute/serve
# area. Everything else under the JAX-compile-heavy trees is marked
# `heavy` at collection time. The FULL suite stays the default.
_QUICK_KEEP = (
    # one forward/backward + one sharded train step
    "test_llama.py::TestForward",
    "test_llama.py::TestTraining::test_loss_decreases_sharded",
    # one engine decode + one KV-quant structural check
    "test_engine.py::TestDecode",
    "test_engine.py::TestKVQuant::test_cache_layout",
    "test_engine.py::TestAdaptiveTurbo::test_ramp_and_snap_back",
    # one parallelism identity (ring attention vs local)
    "test_parallel.py::TestRingAttention::test_matches_local",
    # logical→mesh spec translation on partial meshes + the no-mesh
    # constrain path (the helpers sharded serving and shardcheck's
    # manifest stand on)
    "test_sharding_utils.py::TestFilterSpecForMesh",
    "test_sharding_utils.py::TestConstrain",
    # sampling-param device mirror lifecycle (the DTPU002 burn-down's
    # activation-publishes-a-fresh-mirror contract)
    "test_engine.py::TestDecodeStateMirror",
    # serving HTTP surface
    "test_openai_server.py::TestOpenAIServer::test_chat_completions",
    # prefix-registry lifecycle: the engine-side contract prefix-
    # affinity routing stands on (slot overwrite / reset / partial
    # overlap)
    "test_prefix_registry.py::TestPrefixRegistryLifecycle",
    # prefix-affinity routing units (tests/routing — never heavy-
    # marked; listed so a rename fails test_quick_tier loudly)
    "test_affinity.py::TestAffinityPick",
    "test_affinity.py::TestAffinityMap",
    # event-driven reconciliation invariants (tests/chaos — never
    # heavy-marked; listed so a rename fails test_quick_tier loudly)
    "test_chaos_wakeups.py::TestWakeupQueueSemantics",
    "test_chaos_wakeups.py::TestDuplicateDeliveryIdempotency",
    "test_chaos_wakeups.py::TestWorkerCrashMidBatch",
    # traffic-replay soak harness: schedule determinism + driver
    # outcome classification (tests/loadgen) and the seconds-scale
    # full-stack chaos soak (tests/chaos) — listed so a rename fails
    # test_quick_tier loudly
    "test_loadgen_schedule.py::TestScheduleDeterminism",
    "test_loadgen_driver.py::TestDriverOutcomes",
    "test_chaos_loadgen.py::TestSoakChaosAcceptance",
    # distributed tracing: span/ring/no-op contract (tests/obs) and
    # the trace-continuity-across-failover acceptance (tests/chaos) —
    # listed so a rename fails test_quick_tier loudly
    "test_tracing.py::TestSpanLifecycle",
    "test_tracing.py::TestDisabledIsNoop",
    "test_chaos_tracing.py::TestTraceContinuityAcrossFailover",
    # live SLO engine: bucket-delta estimator properties + alert
    # state-machine determinism (tests/obs) and the live-burn-through-
    # a-kill acceptance (tests/chaos) — listed so a rename fails
    # test_quick_tier loudly
    "test_slo.py::TestBucketEstimators",
    "test_slo.py::TestAlertDeterminism",
    "test_chaos_slo.py::TestLiveSLOChaosAcceptance",
    # engine flight recorder: ring/compile/no-op contract (tests/obs),
    # the steady-state recompile regression gate (tests/serve), and
    # the watchdog post-mortem acceptance (tests/chaos) — listed so a
    # rename fails test_quick_tier loudly
    "test_flight.py::TestCompileAccounting",
    "test_flight.py::TestDisabledIsNoop",
    "test_engine.py::TestSteadyStateRecompiles",
    "test_chaos_flight.py::TestFlightChaosAcceptance",
    # boot recorder: timeline/no-op/manifest contract (tests/obs) and
    # the mid-soak cold-replica scale-up acceptance (tests/chaos) —
    # listed so a rename fails test_quick_tier loudly
    "test_boot.py::TestBootTimeline",
    "test_boot.py::TestDisabledIsNoop",
    "test_boot.py::TestManifestDiff",
    "test_chaos_boot.py::TestBootChaosAcceptance",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        p = str(item.fspath)
        if ("/tests/compute/" in p or "/tests/serve/" in p) and not any(
            k in item.nodeid for k in _QUICK_KEEP
        ):
            item.add_marker(pytest.mark.heavy)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests without pytest-asyncio (not in this image):
    each coroutine test gets a fresh event loop."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(fn(**kwargs))
        finally:
            loop.close()
        return True
    return None
