#!/usr/bin/env python
"""Fleet-scale capacity harness (docs/ARCHITECTURE.md §22, ROADMAP item 5).

The north star says "heavy traffic from millions of users"; every drill
before this stopped at 8 machines. This harness makes the claim
measurable on any rig: it generates a synthetic fleet of 10k-100k TINY
machines (a realistic shape spread over a few template architectures),
commits it through the REAL model store — one generation per machine,
manifest batching so the byte-identical artifact set is hashed once —
writes the `FLEET_INDEX.json` boot sidecar, and drives the fleet through
the full router tier with production-shaped traffic: heavy-tailed (Zipf)
machine popularity, a diurnal rate envelope, an extra hot-key boost, and
optional replay of flight-recorder timelines as load scripts. Along the
way it measures exactly the economies ISSUE 14 names:

- boot: full-scan eager boot vs `FLEET_INDEX` lazy boot (≥5x gate);
- spill tier: host-cache hit vs store path per lazy machine (≥3x gate);
- placement: `Placement.candidates` latency at fleet-scale worker
  counts, incremental ring join vs full rebuild;
- metrics: `/metrics` exposition size and per-family machine-label
  cardinality (bounded at ANY fleet size);
- SLO attainment + the host-cache hit/miss/eviction ledger under load.

Usage (see also `tools/capacity_smoke.py`, which imports this module):

    python tools/capacity_harness.py full --machines 10000
    python tools/capacity_harness.py build --root /tmp/fleet --machines 2000
    python tools/capacity_harness.py serve --root /tmp/fleet --seconds 8 \
        --record /tmp/load.jsonl
    python tools/capacity_harness.py serve --root /tmp/fleet \
        --replay /tmp/load.jsonl

Knobs: `GORDO_CAPACITY_MACHINES` (fleet size when --machines is not
given) and `GORDO_CAPACITY_SECONDS` (seconds per traffic phase) size the
run; `GORDO_HOST_CACHE_MB` / `GORDO_BOOT_EAGER` shape the spill tier
under test. Fleet generation exports `GORDO_STORE_FSYNC=0` (bulk
synthetic commits want atomicity, not power-cut durability).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import shutil
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# -- fleet shape spread -------------------------------------------------------
# Three template architectures with a realistic size skew: most machines
# are small, a minority mid-sized, a tail larger. Tags differ so payload
# width exercises distinct engine buckets per template.
TEMPLATES: Tuple[Dict[str, Any], ...] = (
    {"key": "t0", "tags": 3, "dims": [4], "share": 0.60},
    {"key": "t1", "tags": 6, "dims": [8], "share": 0.30},
    {"key": "t2", "tags": 9, "dims": [8, 4], "share": 0.10},
)
TEMPLATES_DIR = ".templates"  # hidden: the server scan rule skips it


def machine_name(i: int, template_key: str) -> str:
    return f"cap-{i:06d}-{template_key}"


def template_of(name: str) -> str:
    return name.rsplit("-", 1)[-1]


def default_machines(fallback: int) -> int:
    try:
        return int(os.environ.get("GORDO_CAPACITY_MACHINES", str(fallback)))
    except ValueError:
        return fallback


def default_seconds(fallback: float = 8.0) -> float:
    try:
        return float(os.environ.get("GORDO_CAPACITY_SECONDS", str(fallback)))
    except ValueError:
        return fallback


# -- fleet generation ---------------------------------------------------------
def build_templates(root: str) -> List[Dict[str, Any]]:
    """Train the template machines (once per fleet root; cached under
    ``<root>/.templates`` which the server scan rule skips). Returns one
    record per template: artifact dir, manifest payload, file list."""
    from gordo_components_tpu.builder import provide_saved_model
    from gordo_components_tpu.store.generations import resolve_artifact_dir
    from gordo_components_tpu.store.manifest import MANIFEST_FILE

    out = []
    base = os.path.join(root, TEMPLATES_DIR)
    os.makedirs(base, exist_ok=True)
    for template in TEMPLATES:
        key = template["key"]
        tdir = os.path.join(base, key)
        if not os.path.isdir(tdir) or not os.listdir(tdir):
            tags = [f"tag-{key}-{j}" for j in range(template["tags"])]
            provide_saved_model(
                f"template-{key}",
                {"DiffBasedAnomalyDetector": {"base_estimator": {
                    "Pipeline": {"steps": [
                        "MinMaxScaler",
                        {"DenseAutoEncoder": {
                            "kind": "feedforward_symmetric",
                            "dims": template["dims"],
                            "epochs": 1, "batch_size": 32,
                        }},
                    ]},
                }}},
                {
                    "type": "RandomDataset",
                    "train_start_date": "2023-01-01T00:00:00+00:00",
                    "train_end_date": "2023-01-02T00:00:00+00:00",
                    "tag_list": tags,
                },
                tdir,
                evaluation_config={"cv_mode": "build_only"},
            )
        artifact = resolve_artifact_dir(tdir)
        with open(os.path.join(artifact, MANIFEST_FILE)) as fh:
            manifest = json.load(fh)
        files = sorted(manifest.get("files", {}))
        out.append({
            **template,
            "dir": tdir,
            "artifact": artifact,
            "manifest": manifest,
            "files": files,
        })
    return out


def generate_fleet(
    root: str,
    n_machines: int,
    templates: Optional[List[Dict[str, Any]]] = None,
    hardlink: bool = True,
    progress: Optional[Callable[[int], None]] = None,
) -> Dict[str, Any]:
    """Commit ``n_machines`` synthetic machines through the real store —
    one ``gen-0001`` generation each, the template's own manifest reused
    as the batched payload (the byte-identical file set is hashed once,
    at template build) — then write the ``FLEET_INDEX.json`` sidecar.

    ``hardlink=True`` links artifact files to the template's inodes
    (artifacts are immutable by contract; 10k machines cost inode count,
    not bytes); falls back to copies when the filesystem refuses.
    Commit-path fsyncs are disabled for the bulk run (atomicity kept)."""
    from gordo_components_tpu.store import generations as store_generations

    os.environ["GORDO_STORE_FSYNC"] = "0"
    templates = templates or build_templates(root)
    os.makedirs(root, exist_ok=True)
    started = time.perf_counter()
    index: Dict[str, Dict[str, Any]] = {}
    counts = {t["key"]: 0 for t in templates}
    # deterministic shape spread: machine i draws its template from the
    # cumulative share table
    cumulative: List[Tuple[float, Dict[str, Any]]] = []
    acc = 0.0
    for template in templates:
        acc += template["share"]
        cumulative.append((acc, template))
    rng = random.Random(1405)

    def pick_template() -> Dict[str, Any]:
        roll = rng.random() * acc
        for bound, template in cumulative:
            if roll <= bound:
                return template
        return cumulative[-1][1]

    for i in range(n_machines):
        template = pick_template()
        name = machine_name(i, template["key"])
        machine_root = os.path.join(root, name)

        def write_fn(staging: str, template=template) -> None:
            for fname in template["files"]:
                src = os.path.join(template["artifact"], fname)
                dst = os.path.join(staging, fname)
                if hardlink:
                    try:
                        os.link(src, dst)
                        continue
                    except OSError:
                        pass
                shutil.copyfile(src, dst)

        gen = store_generations.commit_generation(
            machine_root, write_fn, name=name,
            manifest=template["manifest"],
        )
        index[name] = {"path": name, "generation": gen, "precision": "f32"}
        counts[template["key"]] += 1
        if progress and (i + 1) % 1000 == 0:
            progress(i + 1)
    store_generations.write_fleet_index(root, index)
    elapsed = time.perf_counter() - started
    return {
        "machines": n_machines,
        "templates": counts,
        "gen_seconds": round(elapsed, 3),
        "machines_per_s": round(n_machines / elapsed, 1) if elapsed else 0,
        "index": os.path.join(
            root, store_generations.FLEET_INDEX_FILE
        ),
    }


# -- boot economics -----------------------------------------------------------
def boot_scan(root: str):
    """Eager full-scan boot: scan + verify + deserialize + stack the
    WHOLE fleet, exactly what a pre-§22 server did. Returns
    ``(server, seconds)``."""
    from gordo_components_tpu.server import build_app
    from gordo_components_tpu.server.server import scan_models_root

    started = time.perf_counter()
    dirs = scan_models_root(root)
    app = build_app(dirs, project="capacity", models_root=root,
                    lazy_boot=False)
    return app, time.perf_counter() - started


def boot_lazy(root: str, eager: int = 8, host_cache_mb: Optional[int] = None):
    """Index-sidecar lazy boot: O(read FLEET_INDEX) + the ``eager``-sized
    warm subset; everything else serves through the host-RAM spill tier
    with first-touch verification. Returns ``(server, seconds)``."""
    from gordo_components_tpu.server import build_app

    os.environ["GORDO_BOOT_EAGER"] = str(eager)
    if host_cache_mb is not None:
        os.environ["GORDO_HOST_CACHE_MB"] = str(host_cache_mb)
    started = time.perf_counter()
    app = build_app({}, project="capacity", models_root=root,
                    lazy_boot=True)
    return app, time.perf_counter() - started


# -- spill-tier economy -------------------------------------------------------
def spill_economy(app, names: Sequence[str], repeats: int = 3) -> Dict[str, Any]:
    """Per-machine store path vs host-cache hit, measured TWO ways (§22
    acceptance: hit serves a demoted machine ≥3x faster than the store
    path): the bundle seam alone (disk read + verify + deserialize +
    lift vs an LRU dict read) and the END-TO-END serve
    (``engine.anomaly`` with the cache dropped vs resident — what a
    demoted machine's next request actually pays)."""
    engine = app._state.engine
    store_ms: List[float] = []
    hit_ms: List[float] = []
    serve_cold_ms: List[float] = []
    serve_warm_ms: List[float] = []
    payloads = {
        t["key"]: json.loads(payload_for(t["key"]))["X"] for t in TEMPLATES
    }
    for name in names:
        X = payloads[template_of(name)]
        engine.host_cache.drop(name)
        t0 = time.perf_counter()
        engine.anomaly(name, X)
        serve_cold_ms.append((time.perf_counter() - t0) * 1000)
        for _ in range(repeats):
            t0 = time.perf_counter()
            engine.anomaly(name, X)
            serve_warm_ms.append((time.perf_counter() - t0) * 1000)
        engine.host_cache.drop(name)
        t0 = time.perf_counter()
        engine.spill_bundle(name)
        store_ms.append((time.perf_counter() - t0) * 1000)
        for _ in range(repeats):
            t0 = time.perf_counter()
            engine.spill_bundle(name)
            hit_ms.append((time.perf_counter() - t0) * 1000)
    store_p50 = _percentile(store_ms, 0.50)
    hit_p50 = _percentile(hit_ms, 0.50)
    cold_p50 = _percentile(serve_cold_ms, 0.50)
    warm_p50 = _percentile(serve_warm_ms, 0.50)
    return {
        "probes": len(names),
        "store_ms_p50": round(store_p50, 3),
        "store_ms_p99": round(_percentile(store_ms, 0.99), 3),
        "hit_ms_p50": round(hit_p50, 4),
        "hit_ms_p99": round(_percentile(hit_ms, 0.99), 4),
        "bundle_speedup_x": (
            round(store_p50 / hit_p50, 1) if hit_p50 else None
        ),
        "serve_store_ms_p50": round(cold_p50, 3),
        "serve_hit_ms_p50": round(warm_p50, 3),
        "speedup_x": round(cold_p50 / warm_p50, 1) if warm_p50 else None,
        "host_cache": engine.host_cache.stats(),
    }


# -- metrics cardinality ------------------------------------------------------
def metrics_bound(app=None) -> Dict[str, Any]:
    """Render the process registry's Prometheus exposition and report its
    size plus the worst per-family machine-label cardinality — the §22
    bound says no family may exceed top-K + ``other`` at ANY fleet
    size."""
    from gordo_components_tpu.observability.exposition import (
        parse_prometheus_text, render_prometheus,
    )
    from gordo_components_tpu.observability.registry import (
        REGISTRY, machine_cardinality_cap,
    )

    text = render_prometheus(REGISTRY)
    parse_prometheus_text(text)  # must stay valid v0.0.4
    per_family: Dict[str, set] = {}
    for line in text.splitlines():
        if line.startswith("#") or 'machine="' not in line:
            continue
        family = line.split("{", 1)[0]
        value = line.split('machine="', 1)[1].split('"', 1)[0]
        per_family.setdefault(family, set()).add(value)
    worst = max((len(v) for v in per_family.values()), default=0)
    cap = machine_cardinality_cap()
    return {
        "exposition_bytes": len(text.encode()),
        "machine_labeled_families": len(per_family),
        "max_machine_values": worst,
        "cardinality_cap": cap,
        # the §22 bound: ≤ top-K + the one "other" aggregate
        "bounded": cap <= 0 or worst <= cap + 1,
    }


# -- placement micro-bench ----------------------------------------------------
def placement_microbench(
    workers: int = 64, lookups: int = 20000, fleet: int = 100000
) -> Dict[str, Any]:
    """Control-plane O(1)-path numbers at fleet scale: per-request
    ``candidates()`` latency over ``workers`` ring members, and the cost
    of one worker JOIN — incremental sorted-merge vs the full from-
    scratch rebuild it replaced."""
    from gordo_components_tpu.router.placement import HashRing, Placement

    names = [f"w-{i:03d}" for i in range(workers)]
    placement = Placement(names[:-1], replicas=2)
    machines = [
        machine_name(i, TEMPLATES[i % 3]["key"])
        for i in range(0, fleet, max(1, fleet // lookups))
    ]
    # warm the membership cache, then measure lookups
    placement.candidates(machines[0])
    samples_us: List[float] = []
    for machine in machines:
        t0 = time.perf_counter()
        placement.candidates(machine)
        samples_us.append((time.perf_counter() - t0) * 1e6)
    # incremental join vs full rebuild
    t0 = time.perf_counter()
    placement.add_worker(names[-1])
    join_ms = (time.perf_counter() - t0) * 1000
    t0 = time.perf_counter()
    HashRing(names)
    rebuild_ms = (time.perf_counter() - t0) * 1000
    return {
        "workers": workers,
        "lookups": len(machines),
        "candidates_us_p50": round(_percentile(samples_us, 0.50), 1),
        "candidates_us_p99": round(_percentile(samples_us, 0.99), 1),
        "join_incremental_ms": round(join_ms, 3),
        "join_full_rebuild_ms": round(rebuild_ms, 3),
    }


# -- production-shaped traffic ------------------------------------------------
class ZipfSampler:
    """Heavy-tailed machine popularity: machine rank r drawn with
    probability ∝ 1/r^s (s≈1 = classic web-like skew), over a shuffled
    rank→machine mapping so popularity is not correlated with name
    order. The head of the distribution is the fleet's hot working set;
    the tail is what keeps the spill tier honest."""

    def __init__(self, machines: Sequence[str], s: float = 1.1,
                 seed: int = 7):
        self.machines = list(machines)
        rng = random.Random(seed)
        rng.shuffle(self.machines)
        weights = [1.0 / ((r + 1) ** s) for r in range(len(self.machines))]
        total = sum(weights)
        self._cdf: List[float] = []
        acc = 0.0
        for w in weights:
            acc += w / total
            self._cdf.append(acc)
        self._rng = random.Random(seed + 1)

    def sample(self) -> str:
        roll = self._rng.random()
        lo, hi = 0, len(self._cdf) - 1
        while lo < hi:
            mid = (lo + hi) // 2
            if self._cdf[mid] < roll:
                lo = mid + 1
            else:
                hi = mid
        return self.machines[lo]

    def head(self, k: int) -> List[str]:
        return self.machines[:k]


def diurnal_rate(base_rps: float, t: float, period: float) -> float:
    """The compressed day: rate swings 0.4x..1.6x of base over one
    ``period`` (the harness maps a 24h curve onto seconds)."""
    return base_rps * (1.0 + 0.6 * math.sin(2 * math.pi * t / period))


def payload_for(template_key: str, rows: int = 8) -> str:
    tags = next(t["tags"] for t in TEMPLATES if t["key"] == template_key)
    rng = random.Random(hash(template_key) & 0xFFFF)
    X = [[round(rng.random(), 4) for _ in range(tags)] for _ in range(rows)]
    return json.dumps({"X": X})


def run_load(
    base_url: str,
    machines: Sequence[str],
    seconds: float,
    threads: int = 8,
    base_rps: float = 120.0,
    hot_boost: int = 4,
    project: str = "capacity",
    record: Optional[List[Tuple[float, str]]] = None,
    script: Optional[Sequence[Tuple[float, str]]] = None,
    tenant: Optional[str] = None,
) -> Dict[str, Any]:
    """Drive production-shaped load: Zipf machine choice (the hottest
    machine boosted ``hot_boost``x — the hot-key scenario), a diurnal
    rate envelope, ``threads`` concurrent closed-loop clients. With
    ``script`` (a ``[(offset_s, machine), ...]`` load script — e.g. one
    extracted from flight-recorder timelines) the machine SEQUENCE and
    relative timing replay instead. ``record`` collects this run's
    ``(offset, machine)`` schedule for later replay. ``tenant`` stamps
    every request with ``X-Gordo-Tenant`` (the §25 QoS principal) and
    the result's ``status_counts`` splits refusals by code — 429 quota
    vs 503 shed, the contract the QoS gates assert."""
    import requests

    sampler = ZipfSampler(machines)
    hot = sampler.head(1)[0]
    latencies_ms: List[float] = []
    failures: List[str] = []
    status_counts: Dict[str, int] = {}
    lock = threading.Lock()
    stop = threading.Event()
    started = time.perf_counter()
    sent = [0]

    payloads = {t["key"]: payload_for(t["key"]) for t in TEMPLATES}
    script_queue: Optional[List[Tuple[float, str]]] = (
        sorted(script) if script else None
    )
    script_pos = [0]

    def next_machine() -> Optional[Tuple[float, str]]:
        """(not-before offset, machine) — scripted replay pops the
        script in order; shaped mode samples Zipf + hot boost with the
        diurnal envelope deciding pacing."""
        now = time.perf_counter() - started
        if script_queue is not None:
            with lock:
                if script_pos[0] >= len(script_queue):
                    return None
                entry = script_queue[script_pos[0]]
                script_pos[0] += 1
            return entry
        rate = max(1.0, diurnal_rate(base_rps, now, max(seconds, 1.0)))
        with lock:
            slot = sent[0]
            sent[0] += 1
        not_before = slot / rate
        if slot % (hot_boost + 1) == 0:
            return not_before, hot
        return not_before, sampler.sample()

    request_headers = {"Content-Type": "application/json"}
    if tenant:
        request_headers["X-Gordo-Tenant"] = tenant

    def client() -> None:
        session = requests.Session()
        while not stop.is_set():
            item = next_machine()
            if item is None:
                return
            not_before, machine = item
            now = time.perf_counter() - started
            if not_before > now:
                wait = min(not_before - now, 0.5)
                if stop.wait(wait):
                    return
            if time.perf_counter() - started >= seconds:
                return
            t0 = time.perf_counter()
            try:
                response = session.post(
                    f"{base_url}/gordo/v0/{project}/{machine}"
                    "/anomaly/prediction",
                    data=payloads[template_of(machine)],
                    headers=request_headers,
                    timeout=30,
                )
                ok = response.status_code == 200
                tag = str(response.status_code)
            except Exception as exc:  # transport failure = a failure row
                ok, tag = False, type(exc).__name__
            elapsed_ms = (time.perf_counter() - t0) * 1000
            with lock:
                status_counts[tag] = status_counts.get(tag, 0) + 1
                if ok:
                    latencies_ms.append(elapsed_ms)
                else:
                    failures.append(f"{machine}: {tag}")
                if record is not None:
                    record.append(
                        (round(time.perf_counter() - started, 4), machine)
                    )

    workers = [
        threading.Thread(target=client, daemon=True) for _ in range(threads)
    ]
    for worker in workers:
        worker.start()
    deadline = started + seconds + 30
    for worker in workers:
        worker.join(timeout=max(0.1, deadline - time.perf_counter()))
    stop.set()
    wall = time.perf_counter() - started
    n = len(latencies_ms)
    return {
        "requests": n,
        "failures": len(failures),
        "failure_sample": failures[:5],
        "wall_s": round(wall, 2),
        "rps": round(n / wall, 1) if wall else 0.0,
        "p50_ms": round(_percentile(latencies_ms, 0.50), 2),
        "p99_ms": round(_percentile(latencies_ms, 0.99), 2),
        "distinct_machines": len(
            {m for _, m in record} if record else set()
        ) or None,
        "status_counts": dict(sorted(status_counts.items())),
        "mode": "replay" if script_queue is not None else "shaped",
    }


# -- multi-tenant QoS mix (§25) -----------------------------------------------
# the canonical three-principal mix every QoS gate drives: a premium
# interactive tenant, an unmetered bulk tenant, and an "abusive" tenant
# declared with a small token bucket (20 rps, burst 10) it will blow
# through. Boot the tier with this in GORDO_TENANTS before calling
# qos_mix.
QOS_TENANTS = "premium:interactive;batch:bulk;abuser:standard:20:10"


def qos_mix(
    base_url: str,
    machines: Sequence[str],
    seconds: float,
    interactive_threads: int = 3,
    bulk_threads: int = 12,
    abusive_threads: int = 6,
    project: str = "capacity",
) -> Dict[str, Any]:
    """The §25 tenant mix, all principals CONCURRENTLY through one tier:
    ``premium`` (interactive class) at modest closed-loop concurrency,
    ``batch`` (bulk class) saturating at ``bulk_threads``, and
    ``abuser`` hammering past its declared token-bucket rate. Returns
    per-tenant attainment — rps, p99, and the ok / 503-shed / 429-quota
    split — which is per-CLASS attainment, since each tenant is its
    class's only principal in :data:`QOS_TENANTS`."""
    roles = {
        "premium": {"threads": interactive_threads, "base_rps": 40.0},
        "batch": {"threads": bulk_threads, "base_rps": 100000.0},
        "abuser": {"threads": abusive_threads, "base_rps": 100000.0},
    }
    results: Dict[str, Any] = {}

    def drive(name: str, cfg: Dict[str, Any]) -> None:
        results[name] = run_load(
            base_url, machines, seconds, threads=cfg["threads"],
            base_rps=cfg["base_rps"], project=project, tenant=name,
        )

    drivers = [
        threading.Thread(target=drive, args=(name, cfg), daemon=True)
        for name, cfg in roles.items()
    ]
    for driver in drivers:
        driver.start()
    for driver in drivers:
        driver.join(timeout=seconds + 60)
    for name, result in results.items():
        counts = result.get("status_counts", {})
        total = sum(counts.values())
        result["attainment"] = (
            round(counts.get("200", 0) / total, 4) if total else None
        )
        result["shed_503"] = counts.get("503", 0)
        result["quota_429"] = counts.get("429", 0)
    return results


# -- flight-recorder replay ---------------------------------------------------
def script_from_flightrec(payload: Dict[str, Any]) -> List[Tuple[float, str]]:
    """A load script from a ``/debug/requests`` body: each recorded
    timeline whose meta names a machine becomes one ``(offset_s,
    machine)`` row, offsets rebased to the earliest request — the
    flight recorder's last N requests replayed as traffic."""
    rows: List[Tuple[float, str]] = []
    for entry in payload.get("requests", []):
        # machine either stamped directly or embedded in the recorded
        # request path (/gordo/v0/<project>/<machine>/...)
        machine = entry.get("machine")
        if not machine:
            parts = str(entry.get("path", "")).strip("/").split("/")
            if len(parts) >= 4 and parts[0] == "gordo":
                machine = parts[3]
        started = entry.get("started")
        if machine and isinstance(started, (int, float)):
            rows.append((float(started), str(machine)))
    if not rows:
        return []
    rows.sort()
    base = rows[0][0]
    return [(round(t - base, 4), machine) for t, machine in rows]


def save_script(path: str, rows: Sequence[Tuple[float, str]]) -> None:
    with open(path, "w") as fh:
        for offset, machine in rows:
            fh.write(json.dumps({"t": offset, "machine": machine}) + "\n")


def load_script(path: str) -> List[Tuple[float, str]]:
    rows: List[Tuple[float, str]] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            rows.append((float(row["t"]), str(row["machine"])))
    return rows


# -- router tier --------------------------------------------------------------
class _ThreadWorker:
    """Thread-backed worker satisfying the supervisor protocol — the same
    seam the router tests use, so the harness drives the REAL router,
    placement, control-plane, and ModelServer code in one process."""

    def __init__(self, spec, app):
        self.spec = spec
        self._app = app
        self._server = None
        self._thread = None

    def start(self):
        from werkzeug.serving import make_server

        self._server = make_server(
            self.spec.host, self.spec.port, self._app, threaded=True
        )
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name=f"capacity-{self.spec.name}", daemon=True,
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


def _free_port() -> int:
    import socket

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


class RouterTier:
    """The full serving tier, in-process: N lazy-booted ModelServer
    workers behind the real router/placement/supervisor stack."""

    def __init__(self, root: str, n_workers: int = 2, eager: int = 8,
                 host_cache_mb: Optional[int] = None):
        import logging

        from werkzeug.serving import make_server

        from gordo_components_tpu.router import WorkerSpec, assemble_fleet
        from gordo_components_tpu.server import build_app

        # per-request access logs at harness request volumes are noise
        logging.getLogger("werkzeug").setLevel(logging.WARNING)

        os.environ["GORDO_BOOT_EAGER"] = str(eager)
        if host_cache_mb is not None:
            os.environ["GORDO_HOST_CACHE_MB"] = str(host_cache_mb)
        specs = [
            WorkerSpec(f"cap-worker-{i}", i, "127.0.0.1", _free_port())
            for i in range(n_workers)
        ]
        self.apps: Dict[str, Any] = {}

        def factory(spec):
            app = self.apps.get(spec.name)
            if app is None:
                app = self.apps[spec.name] = build_app(
                    {}, project="capacity", models_root=root,
                    worker_id=spec.worker_id, lazy_boot=True,
                )
            return _ThreadWorker(spec, app)

        # models_root wired through: the router-side organs that need a
        # store to act on (rollout, fleet reconciler §26) come up live
        self.router = assemble_fleet(
            specs, factory, project="capacity", models_root=root,
            respawn=False,
        )
        self.router.supervisor.start_all()
        ready = self.router.supervisor.wait_ready(timeout=120)
        if len(ready) != n_workers:
            self.close()
            raise RuntimeError(f"workers ready: {ready}")
        self._server = make_server(
            "127.0.0.1", 0, self.router, threaded=True
        )
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="capacity-router",
            daemon=True,
        )
        self._thread.start()
        self.base_url = f"http://127.0.0.1:{self._server.server_port}"

    def engines(self):
        return [app._state.engine for app in self.apps.values()]

    def warm(self, machines: Sequence[str]) -> None:
        """Pre-pay each template's per-arch spill program compile on
        EVERY worker (one score per template, directly against the
        worker) so traffic numbers measure the tier, not first-compile —
        then quiesce the prefetch queues."""
        import requests

        for name, app in self.apps.items():
            state = app._state
            # one eager machine per template (warms the stacked bucket
            # program) plus one lazy one (warms the spill program)
            warm_set: Dict[Tuple[str, bool], str] = {}
            for machine in state.machines:
                warm_set.setdefault((template_of(machine), True), machine)
            for machine in sorted(state.lazy_names):
                warm_set.setdefault((template_of(machine), False), machine)
            spec = self.router.supervisor.specs[name]
            for (key, _), machine in sorted(warm_set.items()):
                requests.post(
                    f"{spec.base_url}/gordo/v0/capacity/{machine}"
                    "/anomaly/prediction",
                    data=payload_for(key),
                    headers={"Content-Type": "application/json"},
                    timeout=120,
                )

    def prefetch(self, machines: Sequence[str]) -> Dict[str, Any]:
        """Placement-hint fan-out: each worker is hinted the machines
        the ring places on it — the async host-cache warm path (§22)."""
        import requests

        out: Dict[str, Any] = {}
        by_worker: Dict[str, List[str]] = {}
        for machine in machines:
            owner = self.router.placement.replica_set(machine)[0]
            by_worker.setdefault(owner, []).append(machine)
        for worker, names in by_worker.items():
            spec = self.router.supervisor.specs[worker]
            out[worker] = requests.post(
                f"{spec.base_url}/prefetch",
                data=json.dumps({"machines": names}),
                headers={"Content-Type": "application/json"},
                timeout=30,
            ).json()
        for engine in self.engines():
            engine.host_cache.quiesce(timeout=30)
        return out

    def slo(self) -> Dict[str, Any]:
        """Worst-objective SLO view across the workers: attainment
        minimum + breach total, read off each worker's /slo."""
        import requests

        worst: Optional[float] = None
        breaches = 0
        for spec in self.router.supervisor.specs.values():
            body = requests.get(f"{spec.base_url}/slo", timeout=10).json()
            for objective in body.get("objectives", []):
                attainment = objective.get("attainment")
                if attainment is not None:
                    worst = (
                        attainment if worst is None
                        else min(worst, attainment)
                    )
                breaches += int(objective.get("breaches", 0) or 0)
        return {"worst_attainment": worst, "breaches": breaches}

    def close(self):
        server = getattr(self, "_server", None)
        if server is not None:
            server.shutdown()
            self._thread.join(timeout=5)
        self.router.supervisor.stop_all()
        self.router.close()


def _percentile(samples: Sequence[float], q: float) -> float:
    if not samples:
        return 0.0
    ordered = sorted(samples)
    return ordered[min(len(ordered) - 1, int(round(q * (len(ordered) - 1))))]


# -- orchestrated runs --------------------------------------------------------
def full_run(
    root: str,
    n_machines: int,
    seconds: float,
    workers: int = 2,
    threads: int = 8,
    eager: int = 8,
    host_cache_mb: int = 64,
    measure_scan_boot: bool = True,
    spill_probes: int = 12,
    log: Callable[[str], None] = lambda s: print(s, flush=True),
) -> Dict[str, Any]:
    """The whole §22 story end to end; returns the report dict the smoke
    gates read."""
    report: Dict[str, Any] = {"machines": n_machines}

    log(f"[1/6] generating {n_machines}-machine synthetic fleet at {root}")
    if not os.path.isfile(os.path.join(root, "FLEET_INDEX.json")):
        report["generate"] = generate_fleet(
            root, n_machines,
            progress=lambda done: log(f"    {done}/{n_machines} committed"),
        )
        log(f"    committed in {report['generate']['gen_seconds']}s "
            f"({report['generate']['machines_per_s']}/s, "
            "manifest batched, fsync off)")
    else:
        log("    fleet already present; reusing")

    log("[2/6] boot economics: FLEET_INDEX lazy boot vs full-scan boot")
    lazy_app, lazy_s = boot_lazy(root, eager=eager,
                                 host_cache_mb=host_cache_mb)
    total = len(lazy_app._state.machines) + len(lazy_app._state.lazy_names)
    report["boot"] = {
        "lazy_s": round(lazy_s, 3),
        "machines_visible": total,
    }
    if total != n_machines:
        raise AssertionError(
            f"lazy boot sees {total} machines, generated {n_machines}"
        )
    if measure_scan_boot:
        scan_app, scan_s = boot_scan(root)
        report["boot"]["scan_s"] = round(scan_s, 3)
        report["boot"]["speedup_x"] = round(scan_s / lazy_s, 1)
        scan_total = len(scan_app._state.machines)
        if scan_total != n_machines:
            raise AssertionError(
                f"scan boot loaded {scan_total} of {n_machines}"
            )
        del scan_app
        log(f"    scan {scan_s:.1f}s vs lazy {lazy_s:.2f}s = "
            f"{report['boot']['speedup_x']}x")
    else:
        log(f"    lazy {lazy_s:.2f}s (scan boot skipped)")

    log("[3/6] spill-tier economy: host-cache hit vs store path")
    lazy_names = sorted(lazy_app._state.lazy_names)
    rng = random.Random(22)
    probes = rng.sample(lazy_names, min(spill_probes, len(lazy_names)))
    # warm each template's spill program first so the economy numbers
    # measure the tier, not first-compile
    for key in {template_of(n) for n in probes}:
        warm = next(n for n in lazy_names if template_of(n) == key)
        lazy_app._state.engine.anomaly(
            warm, json.loads(payload_for(key))["X"]
        )
    report["spill"] = spill_economy(lazy_app, probes)
    log(f"    store p50 {report['spill']['store_ms_p50']}ms vs hit p50 "
        f"{report['spill']['hit_ms_p50']}ms = "
        f"{report['spill']['speedup_x']}x")
    del lazy_app

    log("[4/6] placement lookups at fleet scale")
    report["placement"] = placement_microbench()
    log(f"    candidates p99 {report['placement']['candidates_us_p99']}us; "
        f"join {report['placement']['join_incremental_ms']}ms vs rebuild "
        f"{report['placement']['join_full_rebuild_ms']}ms")

    log(f"[5/7] router tier: {workers} lazy workers, shaped load "
        f"{seconds}s x {threads} threads, then flight-recorder replay")
    # §25: boot the tier with the canonical tenant table so the QoS mix
    # phase has declared principals; the shaped/replay phases run bare
    # (default tenant, standard class) and behave exactly as before
    saved_tenants = os.environ.get("GORDO_TENANTS")
    os.environ["GORDO_TENANTS"] = QOS_TENANTS
    tier = RouterTier(root, n_workers=workers, eager=eager,
                      host_cache_mb=host_cache_mb)
    try:
        all_machines = sorted(
            set().union(*(
                set(app._state.lazy_names) | set(app._state.machines)
                for app in tier.apps.values()
            ))
        )
        # warm per-arch programs, then hint each worker its share of the
        # Zipf head — traffic starts against a prefetched host cache
        sampler = ZipfSampler(all_machines)
        tier.warm(all_machines)
        report["prefetch"] = tier.prefetch(sampler.head(32))
        tier.slo()  # baseline evaluation tick: the scrape-driven SLO
        # engine computes attainment from deltas between ticks
        recorded: List[Tuple[float, str]] = []
        report["traffic"] = run_load(
            tier.base_url, all_machines, seconds, threads=threads,
            record=recorded,
        )
        report["traffic"]["distinct_machines"] = len(
            {m for _, m in recorded}
        )
        report["slo"] = tier.slo()
        report["host_cache"] = [
            engine.host_cache.stats() for engine in tier.engines()
        ]
        log(f"    shaped: {report['traffic']['rps']} rps, p50 "
            f"{report['traffic']['p50_ms']}ms p99 "
            f"{report['traffic']['p99_ms']}ms, "
            f"{report['traffic']['failures']} failures, "
            f"{report['traffic']['distinct_machines']} machines")
        # flight-recorder replay: the last N recorded timelines, rebased,
        # replayed as a load script through the same tier
        import requests

        spec = next(iter(tier.router.supervisor.specs.values()))
        debug = requests.get(
            f"{spec.base_url}/debug/requests?limit=200", timeout=10
        ).json()
        script = script_from_flightrec(debug)
        if script:
            report["replay"] = run_load(
                tier.base_url, all_machines, seconds=min(seconds, 6.0),
                threads=threads, script=script,
            )
            report["replay"]["script_rows"] = len(script)
            log(f"    replay: {report['replay']['requests']} of "
                f"{len(script)} recorded timelines replayed, p99 "
                f"{report['replay']['p99_ms']}ms")
        log("[6/7] multi-tenant QoS mix (§25): premium + bulk "
            "saturation + abusive tenants, concurrently")
        report["qos"] = qos_mix(
            tier.base_url, sampler.head(8), min(seconds, 6.0)
        )
        for name in ("premium", "batch", "abuser"):
            row = report["qos"].get(name, {})
            log(f"    {name}: {row.get('rps')} rps ok, p99 "
                f"{row.get('p99_ms')}ms, shed_503 {row.get('shed_503')}, "
                f"quota_429 {row.get('quota_429')}")
    finally:
        tier.close()
        if saved_tenants is None:
            os.environ.pop("GORDO_TENANTS", None)
        else:
            os.environ["GORDO_TENANTS"] = saved_tenants

    log("[7/7] metrics exposition bound")
    report["metrics"] = metrics_bound()
    log(f"    {report['metrics']['exposition_bytes']} bytes, worst "
        f"machine cardinality {report['metrics']['max_machine_values']} "
        f"(cap {report['metrics']['cardinality_cap']}, bounded="
        f"{report['metrics']['bounded']})")
    return report


# -- CLI ----------------------------------------------------------------------
def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_build = sub.add_parser("build", help="generate a synthetic fleet")
    p_build.add_argument("--root", required=True)
    p_build.add_argument("--machines", type=int,
                         default=default_machines(10000))

    p_boot = sub.add_parser("boot", help="boot economics at a fleet root")
    p_boot.add_argument("--root", required=True)
    p_boot.add_argument("--skip-scan", action="store_true")
    p_boot.add_argument("--eager", type=int, default=8)

    p_serve = sub.add_parser("serve", help="drive the router tier")
    p_serve.add_argument("--root", required=True)
    p_serve.add_argument("--seconds", type=float,
                         default=default_seconds())
    p_serve.add_argument("--workers", type=int, default=2)
    p_serve.add_argument("--threads", type=int, default=8)
    p_serve.add_argument("--record", help="save the load script here")
    p_serve.add_argument("--replay", help="replay this load script")

    p_full = sub.add_parser("full", help="the whole §22 story, one run")
    p_full.add_argument("--root", default=None)
    p_full.add_argument("--machines", type=int,
                        default=default_machines(10000))
    p_full.add_argument("--seconds", type=float, default=default_seconds())
    p_full.add_argument("--workers", type=int, default=2)
    p_full.add_argument("--threads", type=int, default=8)
    p_full.add_argument("--host-cache-mb", type=int, default=64)
    p_full.add_argument("--skip-scan-boot", action="store_true")

    args = parser.parse_args(argv)
    if args.cmd == "build":
        print(json.dumps(generate_fleet(args.root, args.machines), indent=2))
        return 0
    if args.cmd == "boot":
        app, lazy_s = boot_lazy(args.root, eager=args.eager)
        out = {"lazy_s": round(lazy_s, 3)}
        if not args.skip_scan:
            _, scan_s = boot_scan(args.root)
            out["scan_s"] = round(scan_s, 3)
            out["speedup_x"] = round(scan_s / lazy_s, 1)
        print(json.dumps(out, indent=2))
        return 0
    if args.cmd == "serve":
        tier = RouterTier(args.root, n_workers=args.workers)
        try:
            machines = sorted(
                set().union(*(
                    set(app._state.lazy_names) | set(app._state.machines)
                    for app in tier.apps.values()
                ))
            )
            recorded: List[Tuple[float, str]] = []
            script = load_script(args.replay) if args.replay else None
            out = run_load(
                tier.base_url, machines, args.seconds,
                threads=args.threads, record=recorded, script=script,
            )
            out["slo"] = tier.slo()
            if args.record:
                save_script(args.record, recorded)
                out["recorded_to"] = args.record
            print(json.dumps(out, indent=2))
        finally:
            tier.close()
        return 0
    # full
    import tempfile

    root = args.root or tempfile.mkdtemp(prefix="gordo-capacity-")
    report = full_run(
        root, args.machines, args.seconds, workers=args.workers,
        threads=args.threads, host_cache_mb=args.host_cache_mb,
        measure_scan_boot=not args.skip_scan_boot,
    )
    print(json.dumps(report, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
