"""Test bootstrap: force an 8-virtual-device CPU platform BEFORE jax import.

This is the test-cluster analog of the reference's LocalTransport trick
(test/InternalTestCluster.java:330 runs a multi-node cluster inside one
JVM): we get a multi-device mesh inside one process so every sharding/
collective path is exercised without TPU hardware.
"""

import os

# CPU with 8 virtual devices, whatever the shell points JAX at: the
# sharding tests need a mesh, and CI determinism beats running unit
# tests on one chip. Both must be set BEFORE jax is imported.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# the persistent compile cache stays OFF under test — here and in the
# worker processes tests start, hence the environment (Node.__init__
# points the cache at <checkout>/.jax_cache): utils/trace_guard.py
# counts compiles from JAX's loggers and several tests assert those
# counts from a cold start
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multiproc: boots real OS processes (TCP-transport cluster)")
    config.addinivalue_line(
        "markers",
        "slow: bench-scale scenarios excluded from tier-1 (-m 'not slow')")


@pytest.fixture()
def tmp_data_path(tmp_path):
    return str(tmp_path / "data")


@pytest.fixture()
def race_guarded():
    """Arm the runtime race sanitizer (utils/race_guard.py): every
    mutation of a declared-shared structure asserts its lock is held;
    a slipped lock increments the trip counter instead of corrupting
    the structure. Tests assert `race_guarded.trips() == 0` after
    hammering the hot paths from many threads."""
    from elasticsearch_tpu.utils import race_guard

    race_guard.arm()
    race_guard.reset_counters()
    yield race_guard
    race_guard.disarm()
    race_guard.reset_counters()


@pytest.fixture()
def trace_guarded(monkeypatch):
    """Arm the runtime guard + a clean resident slate: implicit
    device<->host transfers raise, compiles are counted, and
    nodes_stats exposes both while armed. Shared by the graftlint
    runtime-complement tests and the streaming write path's
    zero-recompile-across-refresh assertions."""
    # module-level device constants (ops/topk NEG_INF etc.) are
    # legitimate one-time transfers — finish imports BEFORE arming,
    # exactly like the env-armed bench path (Node.__init__ arms after
    # every module is loaded)
    import elasticsearch_tpu.node  # noqa: F401
    from elasticsearch_tpu.search import executor as ex
    from elasticsearch_tpu.search import resident
    from elasticsearch_tpu.utils import trace_guard

    resident.reset()
    # the jit caches are process-global: another test file compiling
    # the same plan shape first would satisfy the cold dispatch from
    # cache, zeroing the recompile counter this test asserts is LIVE —
    # start from a genuinely cold compile whatever ran before
    ex._segment_program_packed.clear_cache()
    ex._resident_step_program.clear_cache()
    ex._pack_program_packed.clear_cache()
    ex._resident_pack_program.clear_cache()
    monkeypatch.setenv("ES_TPU_RESIDENT_LOOP", "1")
    trace_guard.arm()
    trace_guard.reset_counters()
    yield trace_guard
    trace_guard.disarm()
    monkeypatch.delenv("ES_TPU_RESIDENT_LOOP", raising=False)
    resident.reset()
