"""Test configuration: force an 8-virtual-device CPU platform BEFORE jax
initializes, so every sharding/mesh test exercises real multi-device
partitioning without TPU hardware (SURVEY.md §5 rebuild implication)."""

import os

# Force the 8-virtual-device CPU platform. A pytest plugin imports jax
# before this conftest runs, and jax reads JAX_PLATFORMS at import — so
# update jax.config too (valid until first backend init). The env var is
# still set: subprocesses the tests spawn inherit it. XLA_FLAGS is read at
# backend init, which has not happened yet.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: the suite's cost is almost entirely XLA
# compile time, and programs are unchanged between runs unless the model
# code changed — re-runs skip straight to execution (measured ~2x on first
# re-run, more as the cache warms). Keyed by HLO hash, so stale entries are
# impossible; delete the directory to reclaim disk. An operator's
# JAX_COMPILATION_CACHE_DIR places it, and is then HIDDEN from the code
# under test: with the variable set, compile_cache.resolve_store puts every
# test server's AOT store into one shared directory, and the suite's
# miss-then-hit assertions need one store per models tree.
# GORDO_TEST_NO_COMPILE_CACHE=1 runs the suite cold — the
# jaxlib-segfault-isolation knob (intermittent native crashes in
# cache-enabled compiles late in long-lived processes were observed on
# jaxlib 0.9.0; see tests/ring_fleet_child.py).
if os.environ.get("GORDO_TEST_NO_COMPILE_CACHE", "0") != "1":
    _cache_dir = os.environ.pop("JAX_COMPILATION_CACHE_DIR", None) or (
        os.path.join(os.path.dirname(__file__), ".jax_compilation_cache")
    )
    jax.config.update("jax_compilation_cache_dir", _cache_dir)
else:
    # a shell-profile JAX_COMPILATION_CACHE_DIR would silently re-enable
    # the cache jax-side and void the isolation experiment — as would the
    # slow CLI build tests, whose commands call the product's
    # enable_persistent_compile_cache (GORDO_COMPILE_CACHE=off is that
    # helper's own documented opt-out)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    os.environ["GORDO_COMPILE_CACHE"] = "off"
    jax.config.update("jax_compilation_cache_dir", None)

import numpy as np
import pytest

from gordo_components_tpu.analysis import lockcheck

def pytest_collection_modifyitems(session, config, items):
    """Run the compile-heaviest modules FIRST. jaxlib 0.9.0 intermittently
    segfaults inside native XLA:CPU compiles issued late in a long-lived
    process (observed 5x across full-suite runs, always ~300+ tests in,
    always at a transformer-family compile — with the persistent
    compilation cache on AND off, so the cache is exonerated; fresh
    processes compile the same programs clean every time, incl. the
    driver's dryrun). Fronting the transformer/attention modules issues
    their fresh program builds while the process is young; the suite tail
    then runs small or already-traced programs. Stable sort — relative
    order inside each group is unchanged.

    Round 5 sharpened the model: the crash point moved EARLIER as more
    modules were fronted (88% -> 72%/79% -> 59%, the last inside a tiny
    scaler-transform jit), i.e. the trigger tracks the number of live
    executables accumulated in the process, not the weight of the
    victim compile. Ordering alone therefore cannot protect a growing
    suite — see the periodic ``jax.clear_caches()`` hook below, which
    attacks the accumulation itself. The front list is kept so the
    heavyweight programs compile while the process is young (their
    compiles are also the slowest to RE-compile if a later test needs
    them after a cache clear; the persistent on-disk compilation cache
    keeps that cheap)."""
    front = (
        "test_plant_memory.py",  # the single heaviest compiles (plant
        # shapes at 1000-4000 tags) — crashed the suite at 79% when left
        # in the tail
        "test_transformer.py",
        "test_flash_attention.py",
        "test_serving_engine.py",
        "test_models.py",
        "test_fleet.py",
        "test_fleet_parity.py",
        "test_fleet_scale.py",
        "test_builder.py",
    )
    items.sort(
        key=lambda item: 0 if item.fspath.basename in front else 1
    )


_tests_since_cache_clear = 0


def pytest_runtest_teardown(item, nextitem):
    """Every ~70 tests, drop JAX's in-process executable caches.

    jaxlib 0.9.0's native XLA:CPU intermittently SIGSEGV/SIGABRTs on a
    fresh compile once a long-lived process has accumulated enough live
    executables (see pytest_collection_modifyitems — the crash point
    moved EARLIER as more compiles were front-loaded, implicating the
    accumulation, not any specific program). Periodically clearing the
    caches bounds the live-executable count; re-compiles of reused
    programs hit the persistent on-disk compilation cache, so the cost
    is deserialization, not fresh XLA runs."""
    global _tests_since_cache_clear
    _tests_since_cache_clear += 1
    if _tests_since_cache_clear >= 70:
        _tests_since_cache_clear = 0
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


# -- runtime lock-order validation (GORDO_LOCKCHECK=1) -----------------------
# The named locks wrapped by analysis/lockcheck record real acquisition
# orders while the suite exercises the concurrency paths; any order the
# declared hierarchy (analysis/locks.py) forbids fails the test that
# produced it — static analysis proposes, this runtime witness confirms.


@pytest.fixture(autouse=True)
def _lockcheck_guard():
    if not lockcheck.enabled:
        yield
        return
    before = len(lockcheck.violations())
    yield
    fresh = lockcheck.violations()[before:]
    assert not fresh, (
        "runtime lock-order violations (GORDO_LOCKCHECK):\n"
        + "\n".join(fresh)
    )


@pytest.fixture(autouse=True, scope="session")
def _lockcheck_cycle_guard():
    yield
    if lockcheck.enabled:
        problems = lockcheck.report()
        assert not problems, (
            "lock-order problems at session end (GORDO_LOCKCHECK):\n"
            + "\n".join(problems)
        )


# -- thread hygiene ----------------------------------------------------------
# Module-scoped leak detector for the engine/router/client concurrency
# suites (opted in via ``pytestmark = pytest.mark.usefixtures(...)``):
# after the module's teardown, no non-daemon thread may survive and no
# gordo supervisor thread (bucket collectors, control plane, worker
# supervisors, client I/O loops) may still be running. Collector threads
# of merely-dropped engines exit via their weakref backstop within one
# 5 s idle tick, so the check polls under a bounded deadline.


@pytest.fixture(scope="module")
def thread_hygiene():
    import gc
    import threading
    import time as _time

    before = set(threading.enumerate())
    yield
    gc.collect()

    _GORDO_THREADS = (
        "gordo-bucket-collector", "gordo-control-plane", "gordo-client-io",
        "gordo-worker", "gordo-drain", "gordo-router-stop",
        "gordo-autopilot-scale",
    )

    def offenders():
        out = []
        for thread in threading.enumerate():
            if thread in before or not thread.is_alive():
                continue
            if not thread.daemon:
                out.append(thread)
            elif thread.name.startswith(_GORDO_THREADS):
                out.append(thread)
        return out

    # a dropped (not close()d) engine's collector exits via its 5 s
    # idle-tick weakref backstop — but under cold-cache compile load
    # that tick can land late (observed >12 s on a loaded 2-core rig),
    # so JOIN the stragglers under a generous deadline instead of
    # sleep-polling a tight one; a real leak still fails, just slower
    deadline = _time.monotonic() + 30.0
    while True:
        leaked = offenders()
        if not leaked or _time.monotonic() >= deadline:
            break
        gc.collect()
        for thread in leaked:
            thread.join(timeout=max(0.1, deadline - _time.monotonic()))
    leaked = [
        f"{'non-daemon' if not t.daemon else 'supervisor'} {t.name!r}"
        for t in offenders()
    ]
    assert not leaked, (
        "threads leaked past module teardown: " + ", ".join(leaked)
    )
