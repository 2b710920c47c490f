"""Cross-machine megabatching (docs/ARCHITECTURE.md §15): the resident
stacked program, the bounded fill window, residency promotion/demotion
(the generalized hot cache), the fallback table, and error isolation —
one bad machine in a fused batch fails only its own waiters."""

import threading
import time

import numpy as np
import pytest

from gordo_components_tpu.models.synthetic_fleet import build_models
from gordo_components_tpu.server.engine import (
    ServingEngine,
    _fill_window_us,
    _megabatch_enabled,
    _megabatch_residency_cap,
)

# module-wide thread-hygiene gate (tests/conftest.py): after this
# module's teardown no non-daemon thread and no gordo supervisor
# (collector/control-plane/worker/client-io) may still be running
pytestmark = pytest.mark.usefixtures("thread_hygiene")


@pytest.fixture(scope="module")
def models():
    """Six same-architecture machines with distinct weights (one fit +
    perturbed replicas — megabatching is about dispatch shape, not
    training quality)."""
    return build_models(6, 64, 4)


@pytest.fixture(scope="module")
def X():
    rng = np.random.default_rng(5)
    return rng.normal(size=(64, 4)).astype(np.float32) * 2 + 4


def _bits(result):
    return tuple(
        np.asarray(arr).tobytes()
        for arr in (
            result.model_input,
            result.model_output,
            result.tag_anomaly_scores,
            result.total_anomaly_score,
        )
    )


def _assert_close(a, b):
    for x, y in zip(a, b):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-4, atol=1e-5
        )


import contextlib


@contextlib.contextmanager
def _held_bucket(bucket, expected_pending):
    """Deterministic fill-window setup: hold the bucket's leader latch so
    concurrent submits queue as followers, then release — whichever
    follower wins leadership sees ``expected_pending`` queued requests
    (concurrency evidence) and opens its fill window instead of
    bypassing. Races between barrier release and leader election made
    the unheld version flaky on 2-CPU CI boxes."""
    with bucket._cond:
        assert not bucket._busy
        bucket._busy = True
    try:
        yield
        deadline = time.perf_counter() + 10.0
        while time.perf_counter() < deadline:
            with bucket._cond:
                if (
                    sum(len(v) for v in bucket._pending.values())
                    >= expected_pending
                ):
                    break
            time.sleep(0.002)
        else:  # pragma: no cover
            raise AssertionError("followers never queued")
    finally:
        with bucket._cond:
            bucket._busy = False
            bucket._cond.notify_all()


# -- knobs -------------------------------------------------------------------


def test_megabatch_env_parsing(monkeypatch):
    import os

    monkeypatch.delenv("GORDO_MEGABATCH", raising=False)
    assert _megabatch_enabled()  # default ON
    for off in ("0", "false", "OFF", "no"):
        monkeypatch.setenv("GORDO_MEGABATCH", off)
        assert not _megabatch_enabled()
    monkeypatch.setenv("GORDO_MEGABATCH", "1")
    assert _megabatch_enabled()

    monkeypatch.delenv("GORDO_MEGABATCH_RESIDENCY", raising=False)
    assert _megabatch_residency_cap() == 128
    monkeypatch.setenv("GORDO_MEGABATCH_RESIDENCY", "12")
    assert _megabatch_residency_cap() == 12
    monkeypatch.setenv("GORDO_MEGABATCH_RESIDENCY", "-3")
    assert _megabatch_residency_cap() == 0  # clamps; 0 = megabatch off
    monkeypatch.setenv("GORDO_MEGABATCH_RESIDENCY", "garbage")
    assert _megabatch_residency_cap() == 128  # never fails a boot

    monkeypatch.delenv("GORDO_FILL_WINDOW_US", raising=False)
    # core-aware default: tighter with spare cores, wider on small hosts
    monkeypatch.setattr(os, "cpu_count", lambda: 8)
    assert _fill_window_us() == 250
    monkeypatch.setattr(os, "cpu_count", lambda: 2)
    assert _fill_window_us() == 1000
    monkeypatch.setenv("GORDO_FILL_WINDOW_US", "500")
    assert _fill_window_us() == 500
    monkeypatch.setenv("GORDO_FILL_WINDOW_US", "-1")
    assert _fill_window_us() == 0
    monkeypatch.setenv("GORDO_FILL_WINDOW_US", "garbage")
    assert _fill_window_us() == 1000


def test_shard_mode_falls_back(models):
    """The fallback table's shard row: a mesh-sharded engine disables
    megabatching outright (its fused program would re-pay the
    cross-device gather per slot) and the hot cache keeps its role."""
    from gordo_components_tpu.parallel.mesh import fleet_mesh

    engine = ServingEngine(
        models, mesh=fleet_mesh(8), megabatch=True, fill_window_us=5000
    )
    assert not engine.megabatch
    stats = engine.stats()["megabatch"]
    assert not stats["enabled"]
    assert stats["fill_window_us"] == 0  # no fused path, no added wait
    assert all(not b._mega_enabled and not b._fill_s for b in engine._buckets)
    engine.close()


# -- parity ------------------------------------------------------------------


def test_fused_program_bit_identical_to_cold_at_matched_batches(models, X):
    """The fused path's parity contract: given the SAME batch (same
    machines, same inputs, same batch size) the megabatch program and the
    per-machine cold program produce bit-identical outputs. (Across
    different coalesced batch SIZES, float accumulation order may differ
    at ~1e-7 — a pre-existing property of cold micro-batching, not of
    megabatching; megabatch_smoke gates the same invariant end to end.)"""
    import jax

    engine = ServingEngine(models, fill_window_us=0)
    assert engine.megabatch
    names = engine.machines()
    bucket, _ = engine._by_name[names[0]]
    x_padded, _ = engine._prepare(bucket, X)
    rows = x_padded.shape[0]
    for k in (1, 2, 4):
        idxs = np.asarray([i % len(names) for i in range(k)], np.int32)
        xs = np.stack([x_padded] * k)
        cold = jax.device_get(
            bucket._program(rows, k)(bucket.stacked, idxs, xs)
        )
        fused = jax.device_get(
            bucket._mega_program(rows, k)(bucket.stacked, idxs, xs)
        )
        for a, b in zip(cold, fused):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), k
    engine.close()


def test_concurrent_spread_traffic_fuses_and_matches_reference(models, X):
    """12 threads spread across 6 machines: every answer matches the
    megabatch-off engine's, and the fused dispatch count is well below
    the request count (fusion ratio > 1.5 — the ISSUE 7 gate)."""
    reference = ServingEngine(models, megabatch=False)
    assert not reference.megabatch
    names = reference.machines()
    ref = {n: reference.anomaly(n, X) for n in names}
    reference.close()

    engine = ServingEngine(models, fill_window_us=3000)
    engine.warmup()
    engine.quiesce()
    errors = []
    barrier = threading.Barrier(12)

    def work(t):
        try:
            barrier.wait(timeout=30)
            for i in range(10):
                name = names[(t + i) % len(names)]
                _assert_close(engine.anomaly(name, X), ref[name])
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[:3]
    engine.quiesce()
    stats = engine.stats()["megabatch"]
    assert stats["requests"] >= 120
    assert stats["fusion_ratio"] > 1.5, stats
    # fill windows actually closed (either way) under this load
    assert stats["fill_timeout_total"] + stats["fill_size_total"] > 0
    engine.close()


# -- fill window -------------------------------------------------------------


def test_idle_request_bypasses_fill_window(models, X):
    """A lone request on an idle bucket must not wait out the window:
    sequential p50 is unchanged by megabatching."""
    engine = ServingEngine(models, fill_window_us=200_000)
    name = engine.machines()[0]
    engine.anomaly(name, X)  # compile
    started = time.perf_counter()
    engine.anomaly(name, X)
    elapsed = time.perf_counter() - started
    assert elapsed < 0.15, f"idle request waited {elapsed:.3f}s"
    stats = engine.stats()["megabatch"]
    assert stats["fill_timeout_total"] == stats["fill_size_total"] == 0
    engine.close()


def test_full_pending_batch_size_triggers_before_timeout(models, X):
    """A pending queue that reaches max_batch closes the fill window
    immediately (size trigger), long before a large timeout."""
    engine = ServingEngine(models, fill_window_us=10_000_000, max_batch=3)
    names = engine.machines()
    for n in names:
        engine.anomaly(n, X)
    engine.quiesce()
    bucket = engine._buckets[0]
    errors = []

    def work(i):
        try:
            engine.anomaly(names[i % len(names)], X)
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    started = time.perf_counter()
    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    with _held_bucket(bucket, expected_pending=4):
        for t in threads:
            t.start()
    for t in threads:
        t.join(timeout=60)
    elapsed = time.perf_counter() - started
    assert not errors, errors[:3]
    assert elapsed < 8.0, "size trigger did not pre-empt the 10s window"
    stats = engine.stats()["megabatch"]
    assert stats["fill_size_total"] >= 1, stats
    engine.close()


def test_fill_window_records_megabatch_stage(models, X):
    """The leader's fill wait is attributed to the ``megabatch`` stage in
    its request's span timeline."""
    from gordo_components_tpu.observability import spans

    engine = ServingEngine(models, fill_window_us=5000)
    names = engine.machines()
    engine.anomaly(names[0], X)
    engine.quiesce()
    bucket = engine._buckets[0]
    timelines = []

    def work(i):
        timeline, token = spans.begin(f"trace-{i}")
        try:
            engine.anomaly(names[i % len(names)], X)
        finally:
            spans.end(token)
            timelines.append(timeline)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(3)]
    with _held_bucket(bucket, expected_pending=3):
        for t in threads:
            t.start()
    for t in threads:
        t.join(timeout=60)
    stages = {
        span.name for timeline in timelines for span in timeline.spans
    }
    assert "megabatch" in stages, stages
    engine.close()


# -- residency (the generalized hot cache) -----------------------------------


def test_partial_residency_promotes_after_hits_and_bounds_set(models, X):
    """Fleets beyond the residency cap start with an empty resident set:
    traffic serves cold, machines earn slots after 2 hits (the hot-cache
    threshold), and the set never exceeds the cap."""
    engine = ServingEngine(
        models, megabatch_residency=2, fill_window_us=0
    )
    names = engine.machines()
    bucket = engine._buckets[0]
    assert not bucket._mega_full and len(bucket._mega_slots) == 0

    cold = engine.anomaly(names[0], X)
    engine.quiesce()
    assert len(bucket._mega_slots) == 0  # one hit: not yet
    engine.anomaly(names[0], X)
    engine.quiesce()
    assert 0 in bucket._mega_slots  # second hit promotes
    fused = engine.anomaly(names[0], X)
    engine.quiesce()
    assert engine.stats()["megabatch"]["requests"] == 1
    # resident-stack scores bit-identical to the cold path's (same shape)
    assert _bits(fused) == _bits(cold)

    # fill the cap; a third machine cannot evict a fresh working set
    for _ in range(2):
        engine.anomaly(names[1], X)
        engine.quiesce()
    assert len(bucket._mega_slots) == 2
    for _ in range(4):
        engine.anomaly(names[2], X)
        engine.quiesce()
    assert len(bucket._mega_slots) == 2  # freshness guard held
    assert 2 not in bucket._mega_slots
    engine.close()


def test_demoted_machine_backs_off_and_reearns_residency(models, X):
    """Demotion pulls a machine out of the fused program; its traffic
    falls back cold (correct answers throughout) and re-promotion needs
    exponentially more hits — no promote/demote oscillation."""
    engine = ServingEngine(models, fill_window_us=0)
    names = engine.machines()
    bucket = engine._buckets[0]
    idx = engine._by_name[names[0]][1]
    reference = engine.anomaly(names[0], X)
    engine.quiesce()

    bucket._mega_demote(idx)
    assert idx not in bucket._mega_slots
    assert bucket._mega_demotions[idx] == 1
    served = engine.anomaly(names[0], X)  # cold fallback
    engine.quiesce()
    assert _bits(served) == _bits(reference)
    # threshold after one demotion is 16 hits: 15 more stay cold
    for _ in range(14):
        engine.anomaly(names[0], X)
        engine.quiesce()
    assert idx not in bucket._mega_slots
    engine.anomaly(names[0], X)
    engine.quiesce()
    assert idx in bucket._mega_slots  # re-earned at the 16th hit
    engine.close()


def test_demotion_mid_fill_window_falls_back_cold(models, X):
    """'Quarantine mid-fill': a machine pulled from residency WHILE a
    leader's fill window is open still serves — the routing decision runs
    at drain time, after the window closes, so the fused batch falls back
    to the cold path and every waiter gets a correct answer."""
    engine = ServingEngine(models, fill_window_us=250_000)
    names = engine.machines()
    bucket = engine._buckets[0]
    idx = engine._by_name[names[0]][1]
    ref = {n: engine.anomaly(n, X) for n in names[:2]}
    engine.quiesce()
    mega_before = engine.stats()["megabatch"]["requests"]

    results, errors = {}, []

    def work(name):
        try:
            results[name] = engine.anomaly(name, X)
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [
        threading.Thread(target=work, args=(n,)) for n in names[:2]
    ]
    with _held_bucket(bucket, expected_pending=2):
        for t in threads:
            t.start()
    # wait for a leader to open its fill window, then demote mid-fill
    deadline = time.perf_counter() + 5.0
    while not bucket._filling and time.perf_counter() < deadline:
        time.sleep(0.002)
    assert bucket._filling, "no leader opened a fill window"
    bucket._mega_demote(idx)
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    for name, result in results.items():
        _assert_close(result, ref[name])
    engine.quiesce()
    # the drained batch contained a non-resident machine -> whole batch
    # served cold; no fused dispatch can have included the demoted one
    assert engine.stats()["megabatch"]["requests"] == mega_before
    engine.close()


def test_promotion_lands_while_fill_windows_cycle(models, X):
    """Residency promotion (collector side) composes with open fill
    windows (leader side): concurrent rounds over a capped bucket neither
    deadlock nor serve wrong answers, and the machines end resident."""
    engine = ServingEngine(
        models, megabatch_residency=2, fill_window_us=20_000
    )
    names = engine.machines()[:2]
    ref = {n: engine.anomaly(n, X) for n in names}
    engine.quiesce()
    errors = []
    barrier = threading.Barrier(4)

    def work(t):
        try:
            barrier.wait(timeout=30)
            for i in range(6):
                name = names[(t + i) % len(names)]
                _assert_close(engine.anomaly(name, X), ref[name])
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[:3]
    engine.quiesce()
    bucket = engine._buckets[0]
    assert len(bucket._mega_slots) == 2  # both promoted under load
    engine.close()


# -- error handling ----------------------------------------------------------


def test_mega_enqueue_failure_falls_back_to_cold_batch(models, X):
    """An enqueue-time megabatch failure rescores the SAME batch through
    the cold path — callers never see an error the per-machine path could
    have avoided."""
    engine = ServingEngine(models, fill_window_us=0)
    name = engine.machines()[0]
    reference = engine.anomaly(name, X)
    engine.quiesce()
    bucket = engine._buckets[0]

    def exploding(rows, k):
        raise RuntimeError("injected mega enqueue failure")

    bucket._mega_program = exploding
    try:
        served = engine.anomaly(name, X)
    finally:
        del bucket._mega_program
    assert _bits(served) == _bits(reference)
    engine.close()


def test_one_bad_machine_in_fused_batch_fails_only_its_own_waiters(
    models, X
):
    """Error isolation (the ISSUE 7 contract): a fused batch whose device
    execution fails is rescored one request at a time; the machine whose
    isolated retry ALSO fails errors only its own waiters — everyone else
    gets correct results — and the culprit is demoted from residency so
    it stops poisoning fused batches."""
    engine = ServingEngine(models, fill_window_us=100_000)
    names = engine.machines()
    bucket = engine._buckets[0]
    bad_idx = engine._by_name[names[0]][1]
    ref = {n: engine.anomaly(n, X) for n in names[:3]}
    engine.quiesce()

    orig_fetch = bucket._fetch
    orig_program = bucket._program

    def poisoned_fetch(job):
        if job.kind == "mega":
            raise RuntimeError("injected fused execution failure")
        return orig_fetch(job)

    def poisoned_program(rows, k):
        program = orig_program(rows, k)

        def run(stacked, idxs, xs):
            if bad_idx in np.asarray(idxs):
                raise RuntimeError("injected bad-machine failure")
            return program(stacked, idxs, xs)

        return run

    bucket._fetch = poisoned_fetch
    bucket._program = poisoned_program
    outcomes, errors = {}, {}
    barrier = threading.Barrier(3)

    def work(name):
        try:
            barrier.wait(timeout=30)
            outcomes[name] = engine.anomaly(name, X)
        except RuntimeError as exc:
            errors[name] = str(exc)

    try:
        threads = [
            threading.Thread(target=work, args=(n,)) for n in names[:3]
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        del bucket._fetch
        del bucket._program

    # requests were concurrent but fusion is timing-dependent; the bad
    # machine must have failed (fused or solo), the others must have
    # correct answers regardless of which dispatch they rode
    assert names[0] in errors, (outcomes.keys(), errors)
    for name in names[1:3]:
        assert name in outcomes, errors
        _assert_close(outcomes[name], ref[name])
    # the culprit was demoted out of the fused program
    assert bad_idx not in bucket._mega_slots
    # and the engine keeps serving it (cold) once the fault clears
    healed = engine.anomaly(names[0], X)
    _assert_close(healed, ref[names[0]])
    engine.close()


def test_broken_fused_path_demotes_instead_of_looping(models, X):
    """A fused execution that keeps failing while every isolated cold
    retry succeeds (the 'bad fused program / bad resident stack' shape)
    must not loop fail-then-repair forever: the batch's machines are
    demoted, so subsequent traffic routes cold until they re-earn
    residency under backoff."""
    engine = ServingEngine(models, fill_window_us=0)
    names = engine.machines()
    bucket = engine._buckets[0]
    ref = engine.anomaly(names[0], X)
    engine.quiesce()
    mega_before = engine.stats()["megabatch"]["requests"]

    orig_fetch = bucket._fetch

    def poisoned(job):
        if job.kind == "mega":
            raise RuntimeError("injected fused-path failure")
        return orig_fetch(job)

    bucket._fetch = poisoned
    try:
        # first request hits the broken fused path, repairs via the
        # isolated retry, AND demotes — the caller still gets an answer
        served = engine.anomaly(names[0], X)
        engine.quiesce()
        assert _bits(served) == _bits(ref)
        assert engine._by_name[names[0]][1] not in bucket._mega_slots
        # later requests route cold directly: no more fused dispatches,
        # no more repairs, even with the poison still in place
        again = engine.anomaly(names[0], X)
        engine.quiesce()
        assert _bits(again) == _bits(ref)
    finally:
        del bucket._fetch
    assert engine.stats()["megabatch"]["requests"] == mega_before
    engine.close()


# -- stats / integration -----------------------------------------------------


def test_stats_reports_megabatch_block(models, X):
    engine = ServingEngine(models, fill_window_us=1234)
    stats = engine.stats()["megabatch"]
    assert stats["enabled"]
    assert stats["fill_window_us"] == 1234
    assert stats["residency_cap"] == 128
    assert stats["resident_machines"] == len(models)  # full residency
    assert stats["dispatches"] == 0 and stats["requests"] == 0
    assert stats["fusion_ratio"] is None
    engine.anomaly(engine.machines()[0], X)
    engine.quiesce()
    stats = engine.stats()["megabatch"]
    assert stats["dispatches"] == 1 and stats["requests"] == 1
    assert stats["fusion_ratio"] == 1.0
    engine.close()


def test_warmup_precompiles_mega_program_partial_mode(models):
    """Partial-residency buckets boot with no residents, so warmup's live
    request scores cold — warmup_mega must still pre-pay the fused
    program's compile, and the first real promotion must not compile."""
    engine = ServingEngine(
        models, megabatch_residency=2, fill_window_us=0
    )
    engine.warmup()
    bucket = engine._buckets[0]
    mega_keys = [k for k in bucket._programs if k[0] == "mega"]
    assert mega_keys, "warmup compiled no megabatch program"
    assert all(k not in bucket._fresh_programs for k in mega_keys)
    engine.close()
