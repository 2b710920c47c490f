"""Horizontal serving tier: consistent-hash placement, routing,
worker supervision/eject, rolling generation adoption, graceful drain.

Routing/control tests run against lightweight thread-backed fake workers
(real HTTP over loopback, no models) through the SAME supervisor +
control-plane + router code paths the production subprocess tier uses —
the worker protocol is the seam. One end-to-end test scores through the
router against real ModelServer workers.
"""

import json
import socket
import threading
import time

import pytest
from werkzeug.serving import make_server
from werkzeug.wrappers import Request, Response

from gordo_components_tpu.router import (
    ControlPlane,
    FleetRouter,
    HashRing,
    Placement,
    WorkerSpec,
    WorkerSupervisor,
    assemble_fleet,
    jittered_interval,
    worker_specs,
)

# module-wide thread-hygiene gate (tests/conftest.py): after this
# module's teardown no non-daemon thread and no gordo supervisor
# (collector/control-plane/worker/client-io) may still be running
pytestmark = pytest.mark.usefixtures("thread_hygiene")

KEYS = [f"machine-{i:03d}" for i in range(200)]


# -- consistent-hash ring ----------------------------------------------------

def test_ring_deterministic_across_restarts():
    """Placement is a pure function of (workers, key): a rebuilt ring — a
    restarted router — computes the identical table, so restarts cause
    zero residency churn (ISSUE 8 satellite)."""
    workers = ["worker-0", "worker-1", "worker-2", "worker-3"]
    first = {key: HashRing(workers).primary(key) for key in KEYS}
    second = {key: HashRing(list(reversed(workers))).primary(key)
              for key in KEYS}
    assert first == second
    # replica sets too, not just primaries
    ring_a, ring_b = HashRing(workers), HashRing(workers)
    for key in KEYS[:50]:
        assert ring_a.preference(key, 3) == ring_b.preference(key, 3)


def test_ring_spreads_keys():
    ring = HashRing(["worker-0", "worker-1", "worker-2", "worker-3"])
    owners = {key: ring.primary(key) for key in KEYS}
    counts = {w: sum(1 for o in owners.values() if o == w)
              for w in ring.workers()}
    assert set(counts) == {"worker-0", "worker-1", "worker-2", "worker-3"}
    # 200 keys over 4 workers: every worker owns a real share (the bound
    # is loose — vnodes=64 keeps the spread far tighter in practice)
    assert all(count >= 20 for count in counts.values()), counts


def test_ring_bounded_movement_on_leave():
    """Removing a worker moves ONLY the keys it owned; every other key's
    placement is untouched (the property that keeps an eject from
    cold-starting the whole fleet's residency)."""
    ring = HashRing(["worker-0", "worker-1", "worker-2"])
    before = {key: ring.primary(key) for key in KEYS}
    ring.remove("worker-1")
    for key in KEYS:
        after = ring.primary(key)
        if before[key] == "worker-1":
            assert after != "worker-1"
        else:
            assert after == before[key], f"{key} moved without cause"


def test_ring_bounded_movement_on_join():
    """A joining worker only STEALS keys; no key moves between
    incumbents."""
    ring = HashRing(["worker-0", "worker-1", "worker-2"])
    before = {key: ring.primary(key) for key in KEYS}
    ring.add("worker-3")
    moved = 0
    for key in KEYS:
        after = ring.primary(key)
        if after != before[key]:
            assert after == "worker-3", f"{key} moved between incumbents"
            moved += 1
    # it must actually take ~1/4 of the keyspace, not nothing
    assert 10 <= moved <= 120, moved


def test_ring_preference_distinct_and_ordered():
    ring = HashRing(["worker-0", "worker-1", "worker-2"])
    for key in KEYS[:50]:
        pref = ring.preference(key, 3)
        assert len(pref) == 3 and len(set(pref)) == 3
        assert pref[0] == ring.primary(key)
    # n beyond the worker count returns them all, once
    assert len(ring.preference("machine-000", 10)) == 3


# -- weighted arcs (layout plans, §27) ---------------------------------------

def test_ring_weight_shifts_share_with_bounded_movement():
    """Raising one worker's weight grows its key share, and ONLY keys
    flowing to/from that worker move — incumbents never trade keys
    among themselves (the property that lets a layout plan rebalance a
    live fleet without a residency cold start)."""
    ring = HashRing(["worker-0", "worker-1", "worker-2"])
    before = {key: ring.primary(key) for key in KEYS}
    share_before = sum(1 for o in before.values() if o == "worker-1")
    assert ring.set_weight("worker-1", 2.0) is True
    after = {key: ring.primary(key) for key in KEYS}
    share_after = sum(1 for o in after.values() if o == "worker-1")
    assert share_after > share_before
    for key in KEYS:
        if before[key] != after[key]:
            assert after[key] == "worker-1", f"{key} moved between others"
    # shrinking back: only worker-1's keys are shed
    ring.set_weight("worker-1", 0.5)
    shrunk = {key: ring.primary(key) for key in KEYS}
    for key in KEYS:
        if after[key] != shrunk[key]:
            assert after[key] == "worker-1", f"{key} moved without cause"


def test_ring_weight_is_deterministic_and_clamped():
    a = HashRing(["worker-0", "worker-1"])
    b = HashRing(["worker-1", "worker-0"])
    a.set_weight("worker-0", 1.5)
    b.set_weight("worker-0", 1.5)
    assert {k: a.primary(k) for k in KEYS} == {k: b.primary(k) for k in KEYS}
    # same value again: no-op, no version churn
    version = a.version
    assert a.set_weight("worker-0", 1.5) is False
    assert a.version == version
    # the guard rails: a weight cannot starve or monopolize the ring
    a.set_weight("worker-1", 0.001)
    assert a.weights()["worker-1"] == pytest.approx(0.1)
    a.set_weight("worker-1", 100.0)
    assert a.weights()["worker-1"] == pytest.approx(8.0)


def test_placement_set_worker_weights_reverts_absent():
    """The reconciler seam: declared weights win, workers missing from
    the new declaration revert to 1.0 (how rollback clears a plan)."""
    placement = Placement(
        ["worker-0", "worker-1", "worker-2"], hot_rps=0,
    )
    assert placement.set_worker_weights(
        {"worker-0": 2.0, "worker-2": 0.5}
    ) is True
    assert placement.worker_weights() == {
        "worker-0": 2.0, "worker-2": 0.5,
    }
    assert placement.stats()["weights"] == {
        "worker-0": 2.0, "worker-2": 0.5,
    }
    # idempotent re-apply: the reconciler converges, it never churns
    assert placement.set_worker_weights(
        {"worker-0": 2.0, "worker-2": 0.5}
    ) is False
    assert placement.set_worker_weights({}) is True
    assert placement.worker_weights() == {}


# -- placement: hot replication ----------------------------------------------

def test_placement_replication_fanout():
    """A hot machine fans out over `replicas` distinct workers; cold
    machines stay pinned to exactly one."""
    placement = Placement(
        ["worker-0", "worker-1", "worker-2"], replicas=2,
        hot_rps=0, hot=["machine-007"],
    )
    assert len(placement.replica_set("machine-007")) == 2
    assert len(placement.replica_set("machine-001")) == 1
    # candidates: the full failover tail follows the replica set
    assert len(placement.candidates("machine-001")) == 3


def test_placement_hot_rotation():
    """Successive candidate lists for a hot machine rotate the replica
    set, spreading its load; the replica MEMBERSHIP stays fixed."""
    placement = Placement(
        ["worker-0", "worker-1", "worker-2"], replicas=2,
        hot_rps=0, hot=["machine-007"],
    )
    replica_set = set(placement.replica_set("machine-007"))
    firsts = {placement.candidates("machine-007")[0] for _ in range(6)}
    assert firsts == replica_set  # both replicas take the lead in turn
    for _ in range(4):
        assert set(placement.candidates("machine-007")[:2]) == replica_set


def test_placement_rate_promotion_and_hysteresis():
    clock = {"now": 0.0}
    placement = Placement(
        ["worker-0", "worker-1"], replicas=2,
        hot_rps=10.0, hot_window_s=1.0, clock=lambda: clock["now"],
    )
    # 20 requests in one window = 20 rps -> hot
    for _ in range(20):
        placement.note_request("machine-001")
        clock["now"] += 0.04
    assert placement.is_hot("machine-001")
    # rate decays below half the threshold -> demoted (hysteresis)
    clock["now"] += 5.0
    placement.note_request("machine-001")
    assert not placement.is_hot("machine-001")


def test_placement_table_deterministic():
    a = Placement(["worker-0", "worker-1", "worker-2"], hot_rps=0)
    b = Placement(["worker-2", "worker-1", "worker-0"], hot_rps=0)
    assert a.table(KEYS[:40]) == b.table(KEYS[:40])


# -- probe jitter ------------------------------------------------------------

def test_worker_platform_env(monkeypatch):
    """A worker that cannot get the chip would serve from the CPU without
    saying so; where the TPU runtime is installed and the operator named
    no platform, workers are pinned to the TPU so they die at boot
    instead. An operator's choice (cpu for a CPU tier) is inherited."""
    import importlib.util

    from gordo_components_tpu.router.workers import worker_platform_env

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert worker_platform_env() == {}
    monkeypatch.delenv("JAX_PLATFORMS")
    found = {"libtpu": object()}
    monkeypatch.setattr(importlib.util, "find_spec", found.get)
    assert worker_platform_env() == {"JAX_PLATFORMS": "tpu"}
    found.clear()
    assert worker_platform_env() == {}


def test_jittered_interval_bounds():
    """±10% exactly at the extremes, never outside (the thundering-herd
    satellite): injectable rng pins the bounds instead of sampling."""
    assert jittered_interval(2.0, rng=lambda a, b: a) == pytest.approx(1.8)
    assert jittered_interval(2.0, rng=lambda a, b: b) == pytest.approx(2.2)
    assert jittered_interval(2.0, rng=lambda a, b: 0.0) == pytest.approx(2.0)
    for _ in range(100):
        assert 1.8 <= jittered_interval(2.0) <= 2.2
    assert jittered_interval(0.0) == 0.0


# -- fake-worker fleet harness -----------------------------------------------

def _free_port() -> int:
    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


class _ThreadWorker:
    """Thread-backed werkzeug server satisfying the worker protocol
    (start/alive/pid/terminate/kill) — the test seam for the supervisor,
    control plane, and router."""

    def __init__(self, spec: WorkerSpec, app):
        self.spec = spec
        self._app = app
        self._server = None
        self._thread = None

    def start(self):
        self._server = make_server(
            self.spec.host, self.spec.port, self._app, threaded=True
        )
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True
        )
        self._thread.start()

    @property
    def pid(self):
        return None

    def alive(self):
        return self._server is not None

    def terminate(self, grace: float = 5.0):
        if self._server is not None:
            self._server.shutdown()
            self._thread.join(timeout=5)
            self._server = None

    kill = terminate


class _FakeWorkerState:
    """Per-worker scripted behavior + request record."""

    def __init__(self, name):
        self.name = name
        self.requests = []
        self.reloads = 0
        self.fail_reload = False
        self.generation = "gen-0000"
        self.lock = threading.Lock()


def _fake_app(state: _FakeWorkerState):
    @Request.application
    def app(request):
        def reply(payload, status=200, headers=None):
            response = Response(
                json.dumps(payload), status=status,
                mimetype="application/json",
            )
            response.headers["X-Gordo-Worker"] = state.name
            for key, value in (headers or {}).items():
                response.headers[key] = value
            return response

        if request.path == "/healthz":
            return reply({
                "ok": True, "status": "ok", "live": True, "ready": True,
                "store": {"generations": {"m": state.generation}},
            })
        if request.path == "/models":
            return reply({"models": ["machine-000", "machine-001"]})
        if request.path == "/reload":
            with state.lock:
                if state.fail_reload:
                    return reply({"error": "injected reload failure"},
                                 status=500)
                state.reloads += 1
                state.generation = "gen-0001"
            return reply({"added": [], "refreshed": ["m"], "errors": {}})
        with state.lock:
            state.requests.append(request.path)
        return reply({"worker": state.name, "path": request.path})

    return app


def _build_fleet(n=3, respawn=False, **kwargs):
    """A router over n fake thread-backed workers, started and ready."""
    states = {}
    specs = [
        WorkerSpec(f"worker-{i}", i, "127.0.0.1", _free_port())
        for i in range(n)
    ]

    def factory(spec):
        state = states.get(spec.name)
        if state is None:
            state = states[spec.name] = _FakeWorkerState(spec.name)
        return _ThreadWorker(spec, _fake_app(state))

    router = assemble_fleet(
        specs, factory, project="proj", respawn=respawn,
        breaker_recovery=0.5, **kwargs,
    )
    router.supervisor.start_all()
    assert router.supervisor.wait_ready(timeout=10) == sorted(
        s.name for s in specs
    )
    return router, states


def _score(client_session, base, machine, project="proj"):
    import requests

    return requests.post(
        f"{base}/gordo/v0/{project}/{machine}/prediction",
        data=json.dumps({"X": [[0.0]]}),
        headers={"Content-Type": "application/json"},
        timeout=10,
    )


@pytest.fixture
def router_base():
    """A live router over 3 fake workers; yields (base_url, router,
    states) and tears the tier down."""
    router, states = _build_fleet(3)
    server = make_server("127.0.0.1", 0, router, threaded=True)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{server.server_port}"
    try:
        yield base, router, states
    finally:
        server.shutdown()
        thread.join(timeout=5)
        router.supervisor.stop_all()
        router.close()


def test_router_routes_by_placement(router_base):
    """Every request for a machine lands on its PLACED worker — sticky
    (residency stays warm), verified via the X-Gordo-Worker echo."""
    base, router, states = router_base
    for machine in ("machine-000", "machine-001", "machine-777"):
        expected = router.placement.replica_set(machine)[0]
        for _ in range(3):
            response = _score(None, base, machine)
            assert response.status_code == 200
            assert response.headers["X-Gordo-Worker"] == expected
    # and the forwards actually spread by machine, not all to one worker
    owners = {
        router.placement.replica_set(f"machine-{i:03d}")[0]
        for i in range(30)
    }
    assert len(owners) > 1


def test_router_reroutes_around_dead_worker(router_base):
    """Killing a worker mid-fleet re-routes its machines to survivors
    with zero client-visible errors; the untouched machines keep their
    placement."""
    base, router, states = router_base
    machine = "machine-000"
    owner = router.placement.replica_set(machine)[0]
    survivor_machine = next(
        f"machine-{i:03d}" for i in range(100)
        if router.placement.replica_set(f"machine-{i:03d}")[0] != owner
    )
    router.supervisor.worker(owner).terminate()  # hard down, no respawn
    for _ in range(5):
        response = _score(None, base, machine)
        assert response.status_code == 200
        assert response.headers["X-Gordo-Worker"] != owner
    untouched = router.placement.replica_set(survivor_machine)[0]
    assert _score(None, base, survivor_machine).headers[
        "X-Gordo-Worker"
    ] == untouched


def test_router_healthz_degrades_not_dies(router_base):
    import requests

    base, router, states = router_base
    assert requests.get(f"{base}/healthz", timeout=5).json()["status"] == "ok"
    router.supervisor.worker("worker-1").terminate()
    body = requests.get(f"{base}/healthz", timeout=5).json()
    assert body["status"] == "degraded"
    assert body["ready"] is True
    assert body["workers"]["worker-1"]["routable"] is False


def test_rolling_reload_canary_then_sweep(router_base):
    """POST /reload canaries ONE worker, verifies it, then sweeps the
    rest — every worker reloads exactly once, canary first."""
    import requests

    base, router, states = router_base
    result = requests.post(f"{base}/reload", timeout=30).json()
    assert result["aborted"] is False
    assert result["canary"] in states
    assert all(state.reloads == 1 for state in states.values())
    assert all(entry["ok"] for entry in result["workers"].values())
    # generations adopted fleet-wide, reported per worker by the verify
    for entry in result["workers"].values():
        assert entry["verified"]["generations"] == {"m": "gen-0001"}


def test_rolling_reload_canary_abort(router_base):
    """A failing canary ABORTS the rollout: no other worker reloads, the
    fleet keeps serving the old generation."""
    import requests

    base, router, states = router_base
    canary = sorted(states)[0]
    states[canary].fail_reload = True
    result = requests.post(f"{base}/reload", timeout=30).json()
    assert result["aborted"] is True
    assert result["canary"] == canary
    assert all(state.reloads == 0 for state in states.values())
    assert all(
        state.generation == "gen-0000" for state in states.values()
    )


def test_rollout_refuses_concurrent_runs(router_base):
    """A second rollout while one is in progress answers busy instead of
    interleaving — two sweeps at once would reload several workers
    simultaneously and break the 1/N capacity contract."""
    base, router, states = router_base
    rollout = router.rollout
    assert rollout._op_lock.acquire(blocking=False)  # simulate in-flight
    try:
        result = rollout.rolling_reload()
        assert result["aborted"] is True and result.get("busy") is True
        rollback = rollout.rollback() if router.models_root else None
    finally:
        rollout._op_lock.release()
    assert all(state.reloads == 0 for state in states.values())
    # lock released: the next rollout proceeds normally
    result = rollout.rolling_reload()
    assert result["aborted"] is False


def test_control_plane_ejects_and_respawns_dead_worker():
    """A dead worker process is quarantined and respawned by the probe
    sweep; a healthy probe then recovers it into routability."""
    router, states = _build_fleet(2, respawn=True)
    try:
        control, supervisor = router.control, router.supervisor
        control.probe_once()
        assert control.routable("worker-0")
        supervisor.worker("worker-1").terminate()
        results = control.probe_once()  # sees the corpse: eject+respawn
        assert results["worker-1"]["state"] == "dead"
        assert supervisor.respawn_counts()["worker-1"] == 1
        assert control.quarantine.is_quarantined("worker-1")
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if control.probe_once()["worker-1"]["state"] == "ok":
                break
            time.sleep(0.1)
        assert not control.quarantine.is_quarantined("worker-1")
        assert control.routable("worker-1")
    finally:
        router.supervisor.stop_all()
        router.close()


def test_supervisor_respawn_preserves_slot():
    """Respawn keeps the spec (name, port): the ring, placement table,
    and cached base URLs survive a worker restart untouched."""
    router, states = _build_fleet(2)
    try:
        supervisor = router.supervisor
        old = supervisor.worker("worker-0")
        spec_before = old.spec
        old.terminate()
        fresh = supervisor.respawn("worker-0")
        assert fresh is not old
        assert fresh.spec == spec_before
        assert supervisor.alive("worker-0")
    finally:
        router.supervisor.stop_all()
        router.close()


# -- graceful drain (server-side satellites) ---------------------------------

def test_admission_close_sheds_and_drains():
    from gordo_components_tpu.resilience.admission import (
        AdmissionController, AdmissionRejected,
    )

    gate = AdmissionController(max_inflight=2, max_queue=2)
    held = gate.admit()
    gate.close("draining for shutdown")
    with pytest.raises(AdmissionRejected) as excinfo:
        gate.admit()
    assert "draining" in str(excinfo.value)
    assert gate.drain(0.05) is False  # one still in flight
    held.release()
    assert gate.drain(1.0) is True
    assert gate.stats()["closed"] == "draining for shutdown"
    gate.reopen()
    gate.admit().release()  # admits again


def test_admission_close_wakes_queued_waiters():
    """close() must wake a queued waiter immediately — not leave it
    burning its full queue timeout against a gate that can never admit."""
    from gordo_components_tpu.resilience.admission import (
        AdmissionController, AdmissionRejected,
    )

    gate = AdmissionController(max_inflight=1, max_queue=2,
                               queue_timeout=30.0)
    held = gate.admit()
    outcome = {}

    def waiter():
        started = time.monotonic()
        try:
            gate.admit()
            outcome["result"] = "admitted"
        except AdmissionRejected:
            outcome["result"] = "shed"
        outcome["waited"] = time.monotonic() - started

    thread = threading.Thread(target=waiter)
    thread.start()
    time.sleep(0.2)  # let it queue
    gate.close("bye")
    thread.join(timeout=5)
    assert outcome["result"] == "shed"
    assert outcome["waited"] < 5.0  # nowhere near the 30s queue timeout
    held.release()


def test_router_e2e_real_workers_and_graceful_drain(tmp_path_factory):
    """Full stack: two REAL ModelServer workers behind the router —
    scoring routes to the placed worker (verified via X-Gordo-Worker),
    and a graceful drain of that worker (the SIGTERM sequence: admission
    close → in-flight drain → engine quiesce) re-routes every subsequent
    request to the survivor with zero client-visible errors."""
    import requests as req

    from gordo_components_tpu.builder import provide_saved_model
    from gordo_components_tpu.server import build_app

    model_dir = provide_saved_model(
        "mach-1",
        {"Pipeline": {"steps": [
            "MinMaxScaler",
            {"DenseAutoEncoder": {"kind": "feedforward_symmetric",
                                  "dims": [4], "epochs": 1,
                                  "batch_size": 32}},
        ]}},
        {
            "type": "RandomDataset",
            "train_start_date": "2023-01-01T00:00:00+00:00",
            "train_end_date": "2023-01-03T00:00:00+00:00",
            "tag_list": ["tag-a", "tag-b", "tag-c"],
        },
        str(tmp_path_factory.mktemp("router-e2e") / "mach-1"),
        evaluation_config={"cv_mode": "build_only"},
    )
    specs = [
        WorkerSpec(f"worker-{i}", i, "127.0.0.1", _free_port())
        for i in range(2)
    ]
    apps = {}

    def factory(spec):
        app = apps.get(spec.name)
        if app is None:
            app = apps[spec.name] = build_app(
                {"mach-1": model_dir}, project="proj",
                worker_id=spec.worker_id,
            )
        return _ThreadWorker(spec, app)

    router = assemble_fleet(specs, factory, project="proj", respawn=False)
    router.supervisor.start_all()
    assert len(router.supervisor.wait_ready(timeout=30)) == 2
    server = make_server("127.0.0.1", 0, router, threaded=True)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{server.server_port}"
    try:
        owner = router.placement.replica_set("mach-1")[0]
        payload = json.dumps({"X": [[0.1, 0.2, 0.3]] * 2})
        headers = {"Content-Type": "application/json"}

        def score():
            return req.post(
                f"{base}/gordo/v0/proj/mach-1/prediction",
                data=payload, headers=headers, timeout=30,
            )

        response = score()
        assert response.status_code == 200
        owner_id = str(router.supervisor.specs[owner].worker_id)
        assert response.headers["X-Gordo-Worker"] == owner_id
        assert "model-output" in response.json()["data"]

        # graceful drain of the owner: every later request must land on
        # the survivor, 200, no errors — the zero-drop restart contract
        assert apps[owner].quiesce(drain_timeout=5.0) is True
        drained_health = req.get(
            f"{router.supervisor.specs[owner].base_url}/healthz",
            timeout=5,
        )
        assert drained_health.status_code == 503
        assert drained_health.headers.get("X-Gordo-Draining") == "1"
        assert drained_health.json()["status"] == "draining"
        for _ in range(4):
            response = score()
            assert response.status_code == 200
            assert response.headers["X-Gordo-Worker"] != owner_id
    finally:
        server.shutdown()
        thread.join(timeout=5)
        router.supervisor.stop_all()
        router.close()


def test_client_draining_retry_is_immediate():
    """A 503 stamped X-Gordo-Draining retries promptly instead of paying
    the shed backoff (the rolling-restart window is deliberate and
    short)."""
    from gordo_components_tpu.client import Client

    client = Client("http://localhost:9", retry_backoff=5.0)
    try:
        # draining marker → retry_after 0 → delay floored near zero
        delay = client._retry_delay(1, time.monotonic(), retry_after=0.0)
        assert delay is not None and delay <= 0.05
        # ordinary shed keeps the real backoff
        assert client._retry_delay(
            1, time.monotonic(), retry_after=3.0
        ) >= 3.0
    finally:
        client.close()
