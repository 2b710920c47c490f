"""Fleet-scale hot paths (docs/ARCHITECTURE.md §22): the host-RAM spill
tier between device residency and the store, FLEET_INDEX lazy boot,
incremental ring updates, bounded machine-label cardinality, and
manifest batching — the structures the capacity harness drives."""

import json
import os
import threading
import time

import numpy as np
import pytest
from werkzeug.test import Client

from gordo_components_tpu.models.synthetic_fleet import build_models
from gordo_components_tpu.server.engine import ServingEngine
from gordo_components_tpu.server.host_cache import HostTierCache

pytestmark = pytest.mark.usefixtures("thread_hygiene")


@pytest.fixture(scope="module")
def models():
    """Three same-architecture machines, distinct weights — spill parity
    is about the dispatch path, not training quality."""
    return build_models(3, 64, 4)


@pytest.fixture(scope="module")
def X():
    rng = np.random.default_rng(7)
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


def _lazy_of(models):
    """Engine-level lazy loaders over in-memory models (the server wraps
    the verified store path in the same shape)."""
    def loader(model):
        def load():
            return {
                "model": model,
                "target_cols": None,
                "precision": None,
                "quantized": None,
                "context": None,
                "nbytes": 0,
            }
        return load

    return {name: loader(model) for name, model in models.items()}


# -- HostTierCache unit ------------------------------------------------------
class TestHostTierCache:
    def test_lru_eviction_order(self):
        cache = HostTierCache(cap_bytes=300)
        cache.put("a", "A", 100)
        cache.put("b", "B", 100)
        cache.put("c", "C", 100)
        assert cache.resident() == ("a", "b", "c")
        # touching "a" promotes it; the next over-cap put evicts "b",
        # the least recently used
        assert cache.get("a") == "A"
        cache.put("d", "D", 100)
        assert cache.resident() == ("c", "a", "d")
        assert cache.get("b") is None
        assert cache.evictions == 1
        assert cache.stats()["bytes"] == 300

    def test_one_put_can_evict_many(self):
        cache = HostTierCache(cap_bytes=300)
        for name in ("a", "b", "c"):
            cache.put(name, name.upper(), 100)
        cache.put("big", "BIG", 250)
        assert cache.resident() == ("big",)
        assert cache.evictions == 3

    def test_oversize_entry_served_uncached(self):
        cache = HostTierCache(cap_bytes=100)
        assert cache.put("whale", "W", 101) is False
        assert cache.get("whale") is None
        # a whale must not flush the tier either
        cache.put("a", "A", 50)
        assert cache.put("whale", "W", 101) is False
        assert cache.resident() == ("a",)

    def test_cap_zero_disables_cleanly(self):
        cache = HostTierCache(cap_bytes=0)
        assert not cache.enabled
        assert cache.put("a", "A", 10) is False
        assert cache.get("a") is None
        assert cache.prefetch("a", lambda: ("A", 10)) is False
        # get_or_load still serves — it just pays the loader every time
        loads = []
        for _ in range(3):
            value = cache.get_or_load(
                "a", lambda: (loads.append(1) or "A", 10)
            )
            assert value == "A"
        assert len(loads) == 3
        assert cache.stats()["entries"] == 0

    def test_replacing_put_updates_byte_ledger(self):
        cache = HostTierCache(cap_bytes=300)
        cache.put("a", "A", 100)
        cache.put("a", "A2", 250)
        assert cache.stats()["bytes"] == 250
        assert cache.get("a") == "A2"
        cache.drop("a")
        assert cache.stats()["bytes"] == 0

    def test_prefetch_loads_async(self):
        cache = HostTierCache(cap_bytes=1 << 20)
        assert cache.prefetch("a", lambda: ("A", 10)) is True
        assert cache.quiesce(timeout=10.0)
        # a hint for an already-cached name is a counted skip
        assert cache.prefetch("a", lambda: ("A", 10)) is False
        assert cache.get("a") == "A"
        assert cache.stats()["prefetches"] == 1

    def test_prefetch_race_with_demotion(self):
        """A drop() landing while a prefetch load is in flight must end
        consistent: the fresh load re-caches (fresh bytes), the ledger
        balances, and a subsequent drop fully clears."""
        cache = HostTierCache(cap_bytes=1 << 20)
        loading = threading.Event()
        release = threading.Event()

        def slow_load():
            loading.set()
            assert release.wait(10.0)
            return "FRESH", 64

        assert cache.prefetch("m", slow_load) is True
        assert loading.wait(10.0)
        # demotion races the in-flight load: nothing cached yet
        assert cache.drop("m") is False
        release.set()
        assert cache.quiesce(timeout=10.0)
        # the load won the race — fresh entry, consistent ledger
        assert cache.get("m") == "FRESH"
        assert cache.stats()["bytes"] == 64
        assert cache.drop("m") is True
        assert cache.stats()["bytes"] == 0
        assert cache.stats()["entries"] == 0


# -- spill tier through the engine -------------------------------------------
class TestSpillTier:
    def test_spill_scores_byte_identical_to_eager(self, models, X):
        """The §22 parity gate: a lazily-registered machine served
        through the spill tier scores BYTE-identically to the same
        machine stacked eagerly (same ``machine_score`` closure)."""
        eager = ServingEngine(models, megabatch=False)
        lazy = ServingEngine(
            {}, lazy=_lazy_of(models), megabatch=False, host_cache_mb=64
        )
        try:
            for name in models:
                assert lazy.has_lazy(name)
                want = _bits(eager.anomaly(name, X))
                got_cold = _bits(lazy.anomaly(name, X))  # store path
                got_hit = _bits(lazy.anomaly(name, X))   # host-cache hit
                assert got_cold == want
                assert got_hit == want
            stats = lazy.host_cache.stats()
            assert stats["loads"] == len(models)
            assert stats["hits"] >= len(models)
        finally:
            eager.quiesce()
            lazy.quiesce()

    def test_demoted_machine_reloads_and_matches(self, models, X):
        """drop() (demotion / generation change) forces the next request
        back through the store path — and the rescore still matches."""
        name = sorted(models)[0]
        lazy = ServingEngine(
            {}, lazy=_lazy_of(models), megabatch=False, host_cache_mb=64
        )
        try:
            first = _bits(lazy.anomaly(name, X))
            assert lazy.host_cache.drop(name) is True
            again = _bits(lazy.anomaly(name, X))
            assert again == first
            assert lazy.host_cache.stats()["loads"] == 2
        finally:
            lazy.quiesce()

    def test_cap_zero_engine_always_pays_store_path(self, models, X):
        eager = ServingEngine(models, megabatch=False)
        lazy = ServingEngine(
            {}, lazy=_lazy_of(models), megabatch=False, host_cache_mb=0
        )
        try:
            name = sorted(models)[0]
            want = _bits(eager.anomaly(name, X))
            for _ in range(3):
                assert _bits(lazy.anomaly(name, X)) == want
            stats = lazy.host_cache.stats()
            assert not stats["enabled"]
            assert stats["loads"] == 3
            assert stats["hits"] == 0
            assert lazy.stats()["spill"]["lazy_machines"] == len(models)
        finally:
            eager.quiesce()
            lazy.quiesce()

    def test_engine_prefetch_hints_are_advisory(self, models, X):
        lazy = ServingEngine(
            {}, lazy=_lazy_of(models), megabatch=False, host_cache_mb=64
        )
        try:
            names = sorted(models)
            out = lazy.prefetch(names + ["no-such-machine"])
            assert out["unknown"] == 1
            assert out["queued"] + out["skipped"] == len(names)
            assert lazy.host_cache.quiesce(timeout=30.0)
            assert set(lazy.host_cache.resident()) == set(names)
            # prefetched machines serve without another store load
            loads = lazy.host_cache.stats()["loads"]
            lazy.anomaly(names[0], X)
            assert lazy.host_cache.stats()["loads"] == loads
        finally:
            lazy.quiesce()


# -- FLEET_INDEX sidecar ------------------------------------------------------
class TestFleetIndex:
    def test_round_trip(self, tmp_path):
        from gordo_components_tpu.store import generations as gens

        machines = {
            "m-a": {"path": "m-a", "generation": "gen-0001",
                    "precision": "f32"},
            "m-b": {"path": "m-b", "generation": None, "precision": None},
        }
        root = str(tmp_path)
        gens.write_fleet_index(root, machines)
        assert gens.read_fleet_index(root) == machines

    def test_damaged_index_reads_none(self, tmp_path):
        from gordo_components_tpu.store import generations as gens

        root = str(tmp_path)
        path = os.path.join(root, gens.FLEET_INDEX_FILE)
        assert gens.read_fleet_index(root) is None  # absent
        with open(path, "w") as fh:
            fh.write("{not json")
        assert gens.read_fleet_index(root) is None  # unreadable
        with open(path, "w") as fh:
            json.dump({"format_version": 999, "machines": {}}, fh)
        assert gens.read_fleet_index(root) is None  # wrong version

    def test_build_index_shares_the_scan_rule(self, tmp_path):
        from gordo_components_tpu.store import generations as gens

        root = str(tmp_path)
        # a generation-rooted machine, a flat legacy dir, a hidden dir
        # and a junk dir — only the first two are fleet members
        gen_root = tmp_path / "m-gen" / "gen-0001"
        gen_root.mkdir(parents=True)
        (gen_root / "definition.json").write_text("{}")
        (tmp_path / "m-gen" / "CURRENT").write_text("gen-0001")
        flat = tmp_path / "m-flat"
        flat.mkdir()
        (flat / "definition.json").write_text("{}")
        (tmp_path / ".hidden").mkdir()
        (tmp_path / "junk").mkdir()
        index = gens.build_fleet_index(root)
        assert sorted(index) == ["m-flat", "m-gen"]
        assert index["m-gen"]["generation"] == "gen-0001"
        assert index["m-flat"]["generation"] is None


# -- manifest batching --------------------------------------------------------
class TestManifestBatching:
    def test_precomputed_manifest_commits(self, tmp_path):
        from gordo_components_tpu.store.atomic import atomic_commit
        from gordo_components_tpu.store.manifest import (
            manifest_for_dir,
            verify_artifact,
        )

        # hash once (template), reuse the payload for a byte-identical
        # bulk commit — the manifest-batching seam bulk fleet
        # generation rides
        template = tmp_path / "template"
        template.mkdir()
        (template / "definition.json").write_text('{"x": 1}')
        payload = manifest_for_dir(str(template))
        dest = tmp_path / "machine" / "gen-0001"
        with atomic_commit(str(dest), manifest=payload) as staging:
            with open(os.path.join(staging, "definition.json"), "w") as fh:
                fh.write('{"x": 1}')
        verify_artifact(str(dest))  # commit is verifiable

    def test_mismatched_manifest_aborts_commit(self, tmp_path):
        from gordo_components_tpu.store.atomic import atomic_commit
        from gordo_components_tpu.store.errors import ArtifactIncomplete
        from gordo_components_tpu.store.manifest import manifest_for_dir

        template = tmp_path / "template"
        template.mkdir()
        (template / "definition.json").write_text('{"x": 1}')
        payload = manifest_for_dir(str(template))
        dest = tmp_path / "machine" / "gen-0001"
        with pytest.raises(ArtifactIncomplete):
            with atomic_commit(str(dest), manifest=payload) as staging:
                with open(
                    os.path.join(staging, "definition.json"), "w"
                ) as fh:
                    fh.write('{"x": 1, "drifted": true}')  # other size
        assert not dest.exists()  # destination untouched


# -- incremental ring ---------------------------------------------------------
class TestIncrementalRing:
    def test_join_leave_match_a_rebuilt_ring(self):
        from gordo_components_tpu.router.placement import HashRing

        incremental = HashRing([])
        for i in range(8):
            incremental.add(f"w{i}")
        incremental.remove("w3")
        incremental.remove("w6")
        rebuilt = HashRing([f"w{i}" for i in range(8) if i not in (3, 6)])
        assert incremental._points == rebuilt._points
        assert incremental._owners == rebuilt._owners
        for machine in (f"m-{i}" for i in range(64)):
            assert (
                incremental.preference(machine, 3)
                == rebuilt.preference(machine, 3)
            )

    def test_version_bumps_exactly_on_membership_change(self):
        from gordo_components_tpu.router.placement import HashRing

        ring = HashRing(["a", "b"])
        version = ring.version
        ring.add("a")  # already present: no change
        assert ring.version == version
        ring.add("c")
        assert ring.version == version + 1
        ring.remove("nope")  # absent: no change
        assert ring.version == version + 1
        ring.remove("c")
        assert ring.version == version + 2

    def test_candidates_cover_every_worker_once(self):
        from gordo_components_tpu.router.placement import Placement

        workers = [f"w{i}" for i in range(16)]
        placement = Placement(workers, replicas=2)
        for machine in (f"m-{i}" for i in range(32)):
            candidates = placement.candidates(machine)
            assert sorted(candidates) == sorted(workers)
            assert len(set(candidates)) == len(candidates)
            # the head is the ring's preferred worker
            assert candidates[0] == placement.ring.preference(machine, 1)[0]


# -- bounded machine-label cardinality ---------------------------------------
class TestMetricsCardinality:
    def test_counter_collapses_to_top_k_plus_other(self, monkeypatch):
        from gordo_components_tpu.observability.registry import (
            Registry,
            bound_machine_cardinality,
        )

        monkeypatch.setenv("GORDO_METRICS_MACHINE_CARDINALITY", "3")
        reg = Registry()
        counter = reg.counter(
            "gordo_test_card_total", "t", labels=("machine",)
        )
        for i, count in enumerate([50, 40, 30, 5, 3, 2]):
            counter.labels(f"m-{i}").inc(count)
        out = bound_machine_cardinality(counter, counter.collect())
        got = {key[0]: value for key, value in out.items()}
        # top-3 by traffic survive; the tail SUMS into "other"
        assert got == {"m-0": 50, "m-1": 40, "m-2": 30, "other": 10}

    def test_gauge_other_takes_max_not_sum(self, monkeypatch):
        from gordo_components_tpu.observability.registry import (
            Registry,
            bound_machine_cardinality,
        )

        monkeypatch.setenv("GORDO_METRICS_MACHINE_CARDINALITY", "1")
        reg = Registry()
        gauge = reg.gauge("gordo_test_age_seconds", "t", labels=("machine",))
        for i, value in enumerate([9.0, 3.0, 7.0]):
            gauge.labels(f"m-{i}").set(value)
        out = bound_machine_cardinality(gauge, gauge.collect())
        got = {key[0]: value for key, value in out.items()}
        # summing per-machine ages would fabricate a value no machine
        # reported; the worst straggler is the honest aggregate
        assert got == {"m-0": 9.0, "other": 7.0}

    def test_histogram_other_merges_le_wise(self, monkeypatch):
        from gordo_components_tpu.observability.registry import (
            Registry,
            bound_machine_cardinality,
        )

        monkeypatch.setenv("GORDO_METRICS_MACHINE_CARDINALITY", "1")
        reg = Registry()
        hist = reg.histogram(
            "gordo_test_lat_seconds", "t", labels=("machine",)
        )
        for _ in range(5):
            hist.labels("hot").observe(0.01)
        hist.labels("cold-1").observe(0.02)
        hist.labels("cold-2").observe(0.03)
        out = bound_machine_cardinality(hist, hist.collect())
        got = {key[0]: value for key, value in out.items()}
        assert set(got) == {"hot", "other"}
        assert got["other"]["count"] == 2
        assert got["other"]["sum"] == pytest.approx(0.05)
        assert got["other"]["buckets"][-1][1] == 2  # +Inf bucket

    def test_exposition_stays_bounded(self, monkeypatch):
        from gordo_components_tpu.observability.exposition import (
            parse_prometheus_text,
            render_prometheus,
        )
        from gordo_components_tpu.observability.registry import Registry

        monkeypatch.setenv("GORDO_METRICS_MACHINE_CARDINALITY", "4")
        reg = Registry()
        counter = reg.counter(
            "gordo_test_req_total", "t", labels=("machine",)
        )
        for i in range(500):
            counter.labels(f"m-{i:04d}").inc(i + 1)
        text = render_prometheus(reg)
        samples = parse_prometheus_text(text)
        values = {
            labels.get("machine")
            for labels, _ in samples["gordo_test_req_total"]
        }
        assert len(values) == 5  # top-4 + "other", at ANY fleet size
        assert "other" in values

    def test_machine_literally_named_other_folds_into_aggregate(
        self, monkeypatch
    ):
        from gordo_components_tpu.observability.registry import (
            Registry,
            bound_machine_cardinality,
        )

        monkeypatch.setenv("GORDO_METRICS_MACHINE_CARDINALITY", "2")
        reg = Registry()
        counter = reg.counter(
            "gordo_test_col_total", "t", labels=("machine",)
        )
        # a REAL machine named "other" ranks top — it must fold into the
        # aggregate, never be kept verbatim where collapsed losers would
        # merge into (and corrupt) its series
        for name, count in (("other", 100), ("a", 50), ("b", 10), ("c", 5)):
            counter.labels(name).inc(count)
        out = bound_machine_cardinality(counter, counter.collect())
        got = {key[0]: value for key, value in out.items()}
        assert got == {"a": 50, "other": 115}

    def test_cap_zero_disables_the_bound(self, monkeypatch):
        from gordo_components_tpu.observability.registry import (
            Registry,
            bound_machine_cardinality,
        )

        monkeypatch.setenv("GORDO_METRICS_MACHINE_CARDINALITY", "0")
        reg = Registry()
        counter = reg.counter(
            "gordo_test_un_total", "t", labels=("machine",)
        )
        for i in range(10):
            counter.labels(f"m-{i}").inc()
        out = bound_machine_cardinality(counter, counter.collect())
        assert len(out) == 10


# -- lazy fleet boot e2e ------------------------------------------------------
@pytest.fixture(scope="module")
def fleet_root(tmp_path_factory):
    """Three real committed machines + a FLEET_INDEX sidecar."""
    from gordo_components_tpu.builder import provide_saved_model
    from gordo_components_tpu.store import generations as gens

    root = tmp_path_factory.mktemp("capacity-fleet")
    data_config = {
        "type": "RandomDataset",
        "train_start_date": "2023-01-01T00:00:00+00:00",
        "train_end_date": "2023-01-04T00:00:00+00:00",
        "tag_list": [f"cap-tag-{i}" for i in range(4)],
    }
    model_config = {
        "Pipeline": {
            "steps": [
                "MinMaxScaler",
                {"DenseAutoEncoder": {"kind": "feedforward_symmetric",
                                      "dims": [8], "epochs": 1,
                                      "batch_size": 32}},
            ]
        }
    }
    for i in range(3):
        provide_saved_model(
            f"cap-{i}", model_config, data_config,
            str(root / f"cap-{i}"),
            evaluation_config={"cv_mode": "build_only"},
        )
    gens.write_fleet_index(
        str(root), gens.build_fleet_index(str(root))
    )
    return str(root)


class TestLazyBoot:
    def _payload(self):
        rng = np.random.default_rng(11)
        return json.dumps(
            {"X": (rng.normal(size=(16, 4)) * 2 + 4).tolist()}
        )

    def test_lazy_boot_serves_identically_to_eager(
        self, fleet_root, monkeypatch
    ):
        from gordo_components_tpu.server import build_app
        from gordo_components_tpu.server.server import scan_models_root

        monkeypatch.setenv("GORDO_BOOT_EAGER", "1")
        monkeypatch.setenv("GORDO_HOST_CACHE_MB", "64")
        eager = build_app(
            scan_models_root(fleet_root), project="cap",
            models_root=fleet_root, lazy_boot=False,
        )
        lazy = build_app(
            {}, project="cap", models_root=fleet_root, lazy_boot=True,
        )
        # one eager warm machine, the rest behind the spill tier — and
        # the whole fleet visible either way
        assert len(lazy._state.machines) == 1
        assert len(lazy._state.lazy_names) == 2
        payload = self._payload()
        ec, lc = Client(eager), Client(lazy)
        for i in range(3):
            url = f"/gordo/v0/cap/cap-{i}/prediction"
            kwargs = {"data": payload,
                      "content_type": "application/json"}
            want = ec.post(url, **kwargs)
            got = lc.post(url, **kwargs)
            assert want.status_code == got.status_code == 200
            assert want.get_json() == got.get_json()
        eager._state.engine.quiesce()
        lazy._state.engine.quiesce()

    def test_lazy_boot_without_index_falls_back_to_scan(
        self, fleet_root, tmp_path, monkeypatch
    ):
        import shutil

        from gordo_components_tpu.server import build_app
        from gordo_components_tpu.store import generations as gens

        # same fleet, no index: the boot must degrade to the eager scan
        # (a damaged index must never make a fleet unbootable)
        root = tmp_path / "no-index"
        shutil.copytree(fleet_root, root)
        (root / gens.FLEET_INDEX_FILE).unlink()
        monkeypatch.setenv("GORDO_HOST_CACHE_MB", "64")
        app = build_app(
            {}, project="cap", models_root=str(root), lazy_boot=True,
        )
        assert app.lazy_boot is False
        assert len(app._state.machines) == 3
        assert not app._state.lazy_names
        app._state.engine.quiesce()

    def test_reload_drops_stale_bundle_on_index_generation_change(
        self, fleet_root, tmp_path, monkeypatch
    ):
        """A lazy machine whose index `generation` moved was rebuilt —
        /reload must drop its cached spill bundle so the next touch
        pays the verified store path instead of serving stale bytes."""
        import shutil

        from gordo_components_tpu.server import build_app
        from gordo_components_tpu.store import generations as gens

        root = tmp_path / "reload-fleet"
        shutil.copytree(fleet_root, root)
        monkeypatch.setenv("GORDO_BOOT_EAGER", "1")
        monkeypatch.setenv("GORDO_HOST_CACHE_MB", "64")
        app = build_app(
            {}, project="cap", models_root=str(root), lazy_boot=True,
        )
        name = sorted(app._state.lazy_names)[0]
        engine = app._state.engine
        payload = self._payload()
        client = Client(app)
        url = f"/gordo/v0/cap/{name}/prediction"
        first = client.post(url, data=payload,
                            content_type="application/json")
        assert first.status_code == 200
        assert name in engine.host_cache.resident()
        # rebuild signal: same membership, bumped generation in the index
        index = gens.read_fleet_index(str(root))
        index[name]["generation"] = "gen-9999"
        gens.write_fleet_index(str(root), index)
        body = client.post("/reload").get_json()
        assert name in body["refreshed"]
        # the stale bundle is gone; the next request reloads fresh bytes
        # through the store path and answers identically (same artifact)
        assert name not in engine.host_cache.resident()
        again = client.post(url, data=payload,
                            content_type="application/json")
        assert again.status_code == 200
        assert again.get_json() == first.get_json()
        app._state.engine.quiesce()

    def test_prefetch_endpoint_hints_the_host_cache(
        self, fleet_root, monkeypatch
    ):
        from gordo_components_tpu.server import build_app

        monkeypatch.setenv("GORDO_BOOT_EAGER", "1")
        monkeypatch.setenv("GORDO_HOST_CACHE_MB", "64")
        app = build_app(
            {}, project="cap", models_root=fleet_root, lazy_boot=True,
        )
        lazy_names = sorted(app._state.lazy_names)
        response = Client(app).post(
            "/prefetch",
            data=json.dumps({"machines": lazy_names + ["ghost"]}),
            content_type="application/json",
        )
        assert response.status_code == 200
        body = response.get_json()
        assert body["queued"] == len(lazy_names)
        assert body["unknown"] == 1
        engine = app._state.engine
        assert engine.host_cache.quiesce(timeout=30.0)
        assert set(engine.host_cache.resident()) == set(lazy_names)
        engine.quiesce()
