"""Serving-latency benchmark: p50/p99 anomaly-scoring latency (ms).

The north star's serving half (BASELINE.json: p50 anomaly score < 5 ms on
a v5e chip). Builds a fleet of dense-AE machines, stacks them into the
serving engine (one device pytree + one jitted program per architecture ×
row bucket — NOT one compiled model per machine), then measures
``engine.anomaly`` latency for single requests and sustained concurrent
load (micro-batched).

The bench reports three numbers:

- ``value`` — on-device dispatch+compute per request, measured by
  pipelining dispatches and syncing once (the north-star comparison).
- ``end_to_end_p50_ms`` — one host↔device sync per request.
- ``link_rtt_ms`` — the measured 4-byte host↔device round-trip floor, so
  the reader can decompose end_to_end ≈ link_rtt + device themselves.

Backend: runs on the accelerator JAX finds and exits non-zero when there
is none; ``JAX_PLATFORMS=cpu`` asks for the CPU on purpose. The blocks
that boot ``gordo run-server`` worker subprocesses (``multi_worker``,
``multihost``) run only on the CPU backend: on a chip this process holds
the device, so they are reported as ``"not run: needs its own process"``.

``vs_baseline`` is the 5 ms north-star target divided by ``value`` (>1 ⇒
faster than target); it is null on any non-TPU run — the target is a TPU
anchor.

End-to-end percentiles are STEADY-STATE: a separately-reported ``warmup``
pass (three full round-robin sweeps) absorbs first-dispatch compiles,
hot-cache promotion gathers, and first hot dispatches first. ``saturation`` ramps concurrent client
counts (1..32 workers) over mixed-machine traffic and reports rps + tail
latency per rung; ``rps_at_p99_lt_5ms`` is the saturation headline.

Env overrides: BENCH_SERVE_MACHINES (100), BENCH_SERVE_ROWS (144 = one day
at 10-min resolution), BENCH_SERVE_TAGS (10), BENCH_SERVE_REQUESTS (200),
BENCH_SERVE_SHARD (0 — shard stacked params over all devices, the
HBM capacity mode; measures the gather-hop latency cost vs replicated),
BENCH_SERVE_COLDSTART (1 — include the two-boot persistent-compile-cache
block; 0 skips it), BENCH_SERVE_WARM_KB (override the derived batch-warm
bound — see warm_batch_bound), BENCH_SERVE_XMACHINE (1 — include the
cross-machine megabatch saturation block; 0 skips it),
BENCH_SERVE_MULTIWORKER (1 — include the 1-vs-N worker-process router
block; 0 skips it), BENCH_SERVE_PRECISION (1 — include the
precision-ladder f32/bf16/int8 A/B block; 0 skips it),
BENCH_SERVE_WORKERS (2 — the N rung),
BENCH_SERVE_MW_MACHINES (8) / BENCH_SERVE_MW_REQUESTS (40 per thread)
— the multi-worker block's fleet and load sizes,
BENCH_SERVE_MW_PASSES (3 — timed passes per rung, median reported),
BENCH_SERVE_AUTOPILOT (1 — include the closed-loop autopilot A/B under
the shifting ramp→spike→idle mix; 0 skips it) /
BENCH_SERVE_AP_MACHINES (8 — that block's fleet size),
BENCH_SERVE_CAPACITY (1 — include the 10k-machine fleet-scale capacity
block, §22: index boot, spill tier, incremental ring, bounded scrape;
0 skips its ~5 minutes) / GORDO_CAPACITY_MACHINES (10000) /
GORDO_CAPACITY_SECONDS (8),
BENCH_SERVE_TELEMETRY (1 — include the telemetry warehouse block, §24:
scrape latency, warehouse write cost, sketch coverage, cost-ledger
headline; 0 skips it) / GORDO_TELEMETRY_BENCH_MACHINES (300) /
GORDO_TELEMETRY_BENCH_SECONDS (6). The engine's own
GORDO_MEGABATCH / GORDO_FILL_WINDOW_US / GORDO_MEGABATCH_RESIDENCY knobs
apply as in production (ARCHITECTURE §15).
"""

from __future__ import annotations

import copy
import json
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# the concurrent-load ramp, and therefore the deepest micro-batch any
# rung can coalesce: the batch-program warm loop below derives its bound
# from THIS tuple (and the engine's max_batch), so adding a rung can
# never silently desynchronize the warmed program set (ADVICE r5)
SATURATION_WORKERS = (1, 2, 4, 8, 16, 32)


def warm_batch_bound(engine) -> int:
    """Deepest power-of-two dispatch batch worth pre-compiling: bounded by
    the deepest saturation rung (queue depth can't exceed the worker
    count) AND the engine's own ``max_batch`` (programs past it are dead
    weight — the engine never coalesces that many). ``BENCH_SERVE_WARM_KB``
    overrides (a deliberate oversized warm is a measurement tool)."""
    from gordo_components_tpu.server.engine import _round_up_pow2

    raw = os.environ.get("BENCH_SERVE_WARM_KB")
    if raw:
        return max(1, int(raw))
    return min(
        _round_up_pow2(max(SATURATION_WORKERS)),
        _round_up_pow2(engine.max_batch),
    )


def effective_env() -> dict:
    """The knobs that actually shaped this run — resolved values, not
    just whichever env vars happened to be set. BENCH_HISTORY.jsonl rows
    previously carried ``"env": {}`` whenever nothing was overridden,
    which made a serial-dispatch CPU row indistinguishable from a
    depth-2 TPU row and perf trajectories unattributable."""
    import jax

    from gordo_components_tpu import wire
    from gordo_components_tpu.observability.flightrec import RECORDER
    from gordo_components_tpu.server.engine import (
        _dispatch_depth,
        _fill_window_us,
        _megabatch_enabled,
        _megabatch_residency_cap,
    )

    return {
        "device": jax.devices()[0].platform,
        "n_devices": len(jax.devices()),
        "dispatch_depth": _dispatch_depth(),
        "shard": os.environ.get("BENCH_SERVE_SHARD", "0") == "1",
        # cross-machine megabatching knobs as the engine resolved them
        # (shard-mode engines disable megabatching regardless)
        "megabatch": _megabatch_enabled(),
        "fill_window_us": _fill_window_us(),
        "megabatch_residency": _megabatch_residency_cap(),
        # the transport formats this build serves/measures (the wire
        # block reports each one's encode/decode/bytes)
        "wire_formats": ["json", "fast_json", "npz"],
        "npz_content_type": wire.NPZ_CONTENT_TYPE,
        "flightrec": RECORDER.enabled,
        # the SLO engine knobs that shaped the run's slo block (§18) —
        # resolved by the engine itself, so the history row can never
        # record a default the engine doesn't actually use
        "slo": _slo_knob_summary(),
        # fleet-scale hot-path knobs (§22): the spill tier's byte cap
        # and the bounded machine-label cardinality that shaped the
        # capacity block and the exposition sizes in this row
        "host_cache_mb": int(os.environ.get("GORDO_HOST_CACHE_MB", "256")),
        "metrics_machine_cardinality": _machine_cardinality_cap(),
    }


def _slo_knob_summary() -> dict:
    from gordo_components_tpu.observability import slo as slo_engine

    return slo_engine.knob_summary()


def _machine_cardinality_cap() -> int:
    from gordo_components_tpu.observability.registry import (
        machine_cardinality_cap,
    )

    return machine_cardinality_cap()


def begin_slo_watch():
    """An evaluator whose baseline sample predates the measured traffic,
    so the end-of-run burn rates cover exactly this run. The bench
    drives ``engine.anomaly`` directly (no HTTP layer), so alongside the
    standard server objectives (which stay zero here — honest about what
    the bench exercises) it watches an ENGINE-level latency objective
    over the dispatch histogram the run actually feeds. None when the
    engine is knobbed off."""
    from gordo_components_tpu.observability import slo as slo_engine

    if not slo_engine.enabled():
        return None
    threshold_s, target = slo_engine.latency_knobs()
    objectives = slo_engine.server_objectives() + [
        slo_engine.Objective(
            name="engine-dispatch-latency",
            kind="latency",
            metric="gordo_engine_dispatch_seconds",
            target=target,
            threshold_s=threshold_s,
            description=(
                f"bench: {target:.0%} of device dispatches under "
                f"{threshold_s * 1000:.0f} ms"
            ),
        )
    ]
    return slo_engine.SLOEvaluator(objectives)


def end_slo_watch(evaluator) -> dict:
    """Final tick + snapshot: objective attainment and fast/slow burn
    rates at end of run — the history-row `slo` block."""
    if evaluator is None:
        return {"enabled": False}
    evaluator.tick()
    snapshot = evaluator.snapshot()
    return {
        "enabled": True,
        "objectives": [
            {
                "name": objective["name"],
                "target": objective["target"],
                "attainment": objective["attainment"],
                "good": objective["good"],
                "total": objective["total"],
                "burn_rates": {
                    window: stats["burn_rate"]
                    for window, stats in objective["windows"].items()
                },
                "breaches": {
                    window: stats["breaches"]
                    for window, stats in objective["windows"].items()
                },
            }
            for objective in snapshot["objectives"]
        ],
    }


def free_port() -> int:
    """One free-port probe for every multi-process block (TOCTOU-racy,
    like any probe — worker boot retries absorb the rare collision)."""
    import socket

    with socket.socket() as sock:
        sock.bind(("127.0.0.1", 0))
        return sock.getsockname()[1]


def append_history(line: dict) -> None:
    """Best-effort append to BENCH_HISTORY.jsonl (GORDO_BENCH_HISTORY
    overrides the destination; tests point it at /dev/null). Shared by
    bench.py and bench_serving.py so both artifacts' history rows land in
    the one cross-round record."""
    try:
        path = os.environ.get("GORDO_BENCH_HISTORY") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_HISTORY.jsonl"
        )
        with open(path, "a") as fh:
            fh.write(json.dumps(line) + "\n")
    except Exception:
        pass  # history is never worth failing an artifact over


def resolve_sizes() -> dict:
    """The one place BENCH_SERVE_* env sizes and their defaults are
    resolved — shared by the standalone ``main()`` and bench.py's embedded
    serving block, so the two runs of the "same metric" can never silently
    measure different shapes."""
    return dict(
        machines=int(os.environ.get("BENCH_SERVE_MACHINES", "100")),
        rows=int(os.environ.get("BENCH_SERVE_ROWS", "144")),
        tags=int(os.environ.get("BENCH_SERVE_TAGS", "10")),
        n_requests=int(os.environ.get("BENCH_SERVE_REQUESTS", "200")),
    )


def build_models(n_machines: int, rows: int, tags: int):
    """One quick real fit, then ``n_machines`` weight-perturbed replicas:
    serving latency depends on stacked shapes, not on training quality.
    Split from :func:`build_engine` so a caller measuring both the
    replicated and the mesh-sharded engine (bench.py) fits only once."""
    import jax

    from gordo_components_tpu.serializer import pipeline_from_definition

    config = {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "Pipeline": {
                            "steps": [
                                "MinMaxScaler",
                                {
                                    "DenseAutoEncoder": {
                                        "kind": "feedforward_hourglass",
                                        "epochs": 2,
                                        "batch_size": 64,
                                    }
                                },
                            ]
                        }
                    },
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }
    rng = np.random.default_rng(0)
    X = rng.normal(size=(max(rows, 256), tags)).astype(np.float32) * 2 + 4
    proto = pipeline_from_definition(config)
    proto.cross_validate(X, n_splits=2)
    proto.fit(X)

    models = {}
    for i in range(n_machines):
        model = copy.deepcopy(proto)
        est = model.base_estimator.regressor.steps[-1][1]
        key = jax.random.PRNGKey(i)
        est.params_ = jax.tree_util.tree_map(
            lambda p: p * (1.0 + 0.01 * float(jax.random.uniform(key, ()))),
            est.params_,
        )
        models[f"machine-{i:04d}"] = model
    return models


def build_engine(n_machines: int, rows: int, tags: int, shard=None, models=None):
    """A serving engine over ``models`` (built via :func:`build_models` when
    not given). ``shard`` (default: the BENCH_SERVE_SHARD env var) selects
    the mesh-sharded HBM capacity mode."""
    from gordo_components_tpu.server.engine import ServingEngine

    if models is None:
        models = build_models(n_machines, rows, tags)
    if shard is None:
        shard = os.environ.get("BENCH_SERVE_SHARD", "0") == "1"
    mesh = None
    if shard:
        from gordo_components_tpu.parallel.mesh import fleet_mesh

        mesh = fleet_mesh()
    return ServingEngine(models, mesh=mesh)


def measure(
    machines: int = 100,
    rows: int = 144,
    tags: int = 10,
    n_requests: int = 200,
    shard=None,
    models=None,
) -> dict:
    """The whole serving measurement as a library call (bench.py embeds
    this as its ``serving`` block so the driver-captured artifact carries
    the serving half of the north star — VERDICT r3 #2). The caller owns
    backend probing; ``shard`` (default: the BENCH_SERVE_SHARD env var)
    switches the engine to the mesh-sharded HBM capacity mode; ``models``
    (from :func:`build_models`) skips the fit when measuring both modes."""
    import jax

    if models is None:
        models = build_models(machines, rows, tags)
    engine = build_engine(machines, rows, tags, shard=shard, models=models)
    names = engine.machines()
    rng = np.random.default_rng(1)
    X = rng.normal(size=(rows, tags)).astype(np.float32) * 2 + 4

    # -- warm-up pass, measured and reported SEPARATELY (VERDICT r4 weak
    # #3: a 540 ms CPU p99 turned out to be first-dispatch compiles and
    # hot-cache promotion gathers landing inside the percentile window).
    # THREE round-robin passes over the whole fleet: pass 1 pays every
    # first-dispatch compile; pass 2 is each machine's 2nd cold hit, which
    # (shard mode) triggers its promotion gather up to hot_cap; pass 3 is
    # the promoted machines' first HOT dispatch — the hot program's
    # compile (measured 169 ms on the CPU mesh, i.e. the entire former
    # "steady-state" p99). Steady state below starts only after the
    # cache's working set is settled AND every program it uses has run.
    warmup_lat = []
    for _ in range(3):
        for name in names:
            started = time.perf_counter()
            engine.anomaly(name, X)
            warmup_lat.append(time.perf_counter() - started)
        # promotions ride the fetch stage under pipelined dispatch: drain
        # it between passes so pass N+1 sees pass N's cache state, exactly
        # as the pre-pipeline warmup narrative describes
        engine.quiesce()
    warmup_ms = np.asarray(warmup_lat) * 1000.0

    # -- host↔device link round-trip floor ------------------------------------
    tiny = np.ones((1,), np.float32)
    roundtrip = jax.jit(lambda v: v * 2)
    jax.device_get(roundtrip(tiny))
    rtts = []
    for _ in range(30):
        started = time.perf_counter()
        jax.device_get(roundtrip(tiny))
        rtts.append(time.perf_counter() - started)
    link_rtt = float(np.percentile(np.asarray(rtts) * 1000.0, 50))

    # -- end-to-end single-request latency over the whole fleet -------------
    latencies = []
    for i in range(n_requests):
        name = names[i % len(names)]
        started = time.perf_counter()
        scored = engine.anomaly(name, X)
        latencies.append(time.perf_counter() - started)
    assert np.isfinite(scored.total_anomaly_score).all()
    lat_ms = np.asarray(latencies) * 1000.0
    e2e_p50 = float(np.percentile(lat_ms, 50))
    e2e_p99 = float(np.percentile(lat_ms, 99))

    # -- on-device scoring cost: pipelined dispatches (sync once at the
    # end), so the per-call number excludes the per-sync round trip
    bucket, idx = engine._by_name[names[0]]
    x_padded, _ = engine._prepare(bucket, X)
    program = bucket._program(x_padded.shape[0], 1)
    # donating engines (TPU) CONSUME the request stack: this raw-program
    # loop must hand each call its own buffer (an async device_put enqueue,
    # like the real dispatch path's implicit put of a fresh np.stack) —
    # re-dispatching a donated array raises. Non-donating engines keep the
    # single resident buffer, the historical measurement.
    xs_host = x_padded[None]
    xs_resident = None if bucket._donate else jax.device_put(xs_host)

    def xs_arg():
        return jax.device_put(xs_host) if bucket._donate else xs_resident

    idxs_dev = jax.device_put(np.asarray([idx], np.int32))
    jax.block_until_ready(program(bucket.stacked, idxs_dev, xs_arg()))
    n_pipe = max(n_requests, 100)
    shard_mode = engine.mesh is not None
    started = time.perf_counter()
    if shard_mode:
        # sharded executions carry collectives; un-awaited pipelining would
        # interleave their in-process rendezvous (CPU backend) — await each
        # dispatch, so this number includes the per-call gather cost
        for _ in range(n_pipe):
            jax.block_until_ready(
                program(bucket.stacked, idxs_dev, xs_arg())
            )
    else:
        outs = [
            program(bucket.stacked, idxs_dev, xs_arg())
            for _ in range(n_pipe)
        ]
        jax.block_until_ready(outs)
    device_ms = (time.perf_counter() - started) / n_pipe * 1000.0

    # -- sustained concurrent load (micro-batching path), ramped over
    # client counts to find the saturation point (VERDICT r4 #8): for each
    # worker count, mixed-machine traffic through engine.anomaly with
    # per-request latencies, so the curve reports rps AND tail latency and
    # ``rps_at_p99_lt_5ms`` is a first-class metric next to p50. The
    # 16-worker rung keeps the legacy ``concurrent_rps`` comparable.
    def one(i: int) -> float:
        name = names[i % len(names)]
        started = time.perf_counter()
        engine.anomaly(name, X)
        return time.perf_counter() - started

    # concurrent requests coalesce into power-of-two dispatch batches, and
    # each batch size's FIRST execution compiles a new program — which
    # batch sizes occur is timing-dependent, so warm every possible one
    # (cold and hot variants) deterministically before any timed rung, or
    # a rung's p99 measures XLA compile time, not serving. The bound is
    # DERIVED (deepest rung ∧ engine.max_batch — see warm_batch_bound),
    # not a literal, so the rung list and the warm set cannot drift
    rows_padded = x_padded.shape[0]
    kb = 1
    max_kb = warm_batch_bound(engine)
    while kb <= max_kb:
        # host copy per program call: donating engines consume the stack
        # (see the device-loop note above), so each warm dispatch gets its
        # own implicit device_put — exactly what a live dispatch does
        xs_kb = np.repeat(x_padded[None], kb, axis=0)
        idxs_kb = jax.device_put(np.full((kb,), idx, np.int32))
        jax.block_until_ready(
            bucket._program(rows_padded, kb)(bucket.stacked, idxs_kb, xs_kb)
        )
        if bucket._mega_enabled:
            # megabatched engines serve live traffic through the fused
            # program — warm ITS batch shapes too, or the first fused
            # k>1 dispatch pays an XLA compile inside a timed rung
            jax.block_until_ready(
                bucket._mega_program(rows_padded, kb)(
                    bucket._warm_mega_stack(),
                    np.zeros((kb,), np.int32),
                    np.repeat(x_padded[None], kb, axis=0),
                )
            )
        if shard_mode and engine.hot_cap and bucket._hot:
            hot_idx = next(iter(bucket._hot))
            jax.block_until_ready(
                bucket._hot_program(rows_padded, kb)(
                    bucket._hot[hot_idx], np.repeat(x_padded[None], kb, axis=0)
                )
            )
        kb *= 2
    saturation = []
    for workers in SATURATION_WORKERS:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            # settle the pool's threads before timing
            list(pool.map(one, range(min(n_requests, 2 * workers))))
            started = time.perf_counter()
            lats = list(pool.map(one, range(n_requests)))
            elapsed = time.perf_counter() - started
        lat_arr = np.asarray(lats) * 1000.0
        saturation.append({
            "workers": workers,
            "rps": round(n_requests / elapsed, 1),
            "p50_ms": round(float(np.percentile(lat_arr, 50)), 3),
            "p99_ms": round(float(np.percentile(lat_arr, 99)), 3),
        })
    throughput = next(
        s["rps"] for s in saturation if s["workers"] == 16
    )
    under_target = [s for s in saturation if s["p99_ms"] < 5.0]
    # the 5 ms SLO is a TPU anchor (like vs_baseline): a CPU rung slipping
    # under it must not populate a TPU-anchored headline, so non-TPU runs
    # carry null and read the per-rig curve in ``saturation`` instead
    rps_at_p99_lt_5ms = (
        (max(s["rps"] for s in under_target) if under_target else 0.0)
        if jax.devices()[0].platform == "tpu"
        else None
    )

    # -- precision ladder (ISSUE 11 / §19): the same fleet at f32, bf16,
    # and int8, each through its own engine — 12-thread spread rps +
    # latency per rung, parity error vs the f32 reference, and the
    # resident-machine capacity each rung buys at fixed device memory.
    # BENCH_SERVE_PRECISION=0 skips; replicated mode only (the ladder's
    # residency-compounding case).
    precision_block = None
    if (
        engine.mesh is None
        and os.environ.get("BENCH_SERVE_PRECISION", "1") == "1"
    ):
        precision_block = measure_precision(models, X, n_requests)

    # -- cross-machine megabatch saturation (ISSUE 7): 12 client threads
    # SPREAD over >= 8 distinct machines — each thread walks its own
    # offset through the spread set, so concurrent dispatch windows
    # almost always contain several different machines. The main
    # saturation ramp above round-robins one shared counter, which lets
    # per-dispatch overhead hide inside repeat-machine micro-batches;
    # this block is the workload megabatching exists for, and reports
    # the engine's fused-batch stats delta next to rps.
    cross_machine = None
    if os.environ.get("BENCH_SERVE_XMACHINE", "1") == "1":
        cross_machine = measure_cross_machine(engine, names, X, n_requests)

    # -- shard mode: hot-machine cache latency (ROADMAP #3) -----------------
    # repeat-machine traffic promotes an unsharded copy after 2 cold hits;
    # subsequent requests skip the per-dispatch cross-device gather. This
    # is the engine-path p50 for the cache's design case, measured through
    # engine.anomaly (not a raw program), so it includes dispatch overhead.
    hot_p50 = None
    if shard_mode and engine.hot_cap:
        hot_name = names[0]
        for _ in range(2):  # 2 cold hits promote
            engine.anomaly(hot_name, X)
        engine.quiesce()  # promotion rides the fetch stage
        engine.anomaly(hot_name, X)  # first hot dispatch
        hot_lat = []
        for _ in range(50):
            started = time.perf_counter()
            engine.anomaly(hot_name, X)
            hot_lat.append(time.perf_counter() - started)
        hot_p50 = float(np.percentile(np.asarray(hot_lat) * 1000.0, 50))
        assert engine.stats()["hot_requests"] >= 50

    # -- wire-format breakdown: serialization-vs-dispatch time and payload
    # bytes/request per response format (legacy per-element json, the fast
    # printf-json fallback, binary npz) — so later rounds can see where
    # HOST time goes once device dispatch is sub-ms. Encode = server cost
    # per response, decode = client cost per chunk.
    from gordo_components_tpu import wire

    arrays = {
        "model-input": scored.model_input,
        "model-output": scored.model_output,
        "tag-anomaly-scores": scored.tag_anomaly_scores,
        "total-anomaly-score": scored.total_anomaly_score,
    }

    def _timed(fn, reps=30):
        out = fn()
        started = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - started) / reps * 1000.0, out

    legacy_encode_ms, legacy_body = _timed(
        lambda: json.dumps(
            {"data": {k: np.asarray(v).tolist() for k, v in arrays.items()}}
        )
    )
    legacy_decode_ms, _ = _timed(lambda: json.loads(legacy_body))
    fast_encode_ms, fast_body = _timed(
        lambda: wire.encode_scored_json(arrays)
    )
    fast_decode_ms, _ = _timed(lambda: json.loads(fast_body))
    npz_encode_ms, npz_blob = _timed(lambda: wire.encode_npz(arrays))
    npz_decode_ms, _ = _timed(lambda: wire.decode_npz(npz_blob))
    wire_formats = {
        "request_shape": [rows, tags],
        "json": {
            "encode_ms": round(legacy_encode_ms, 4),
            "decode_ms": round(legacy_decode_ms, 4),
            "bytes": len(legacy_body.encode()),
        },
        "fast_json": {
            "encode_ms": round(fast_encode_ms, 4),
            "decode_ms": round(fast_decode_ms, 4),
            "bytes": len(fast_body.encode()),
        },
        "npz": {
            "encode_ms": round(npz_encode_ms, 4),
            "decode_ms": round(npz_decode_ms, 4),
            "bytes": len(npz_blob),
        },
    }

    # -- cold start: boot cost with and without the persistent compile
    # cache (ROADMAP #3 / ISSUE 6). Two boots against one cache root: the
    # first pays the compiles and writes AOT executables back, the second
    # must be load-not-compile (compiles_at_boot 0, cache hits > 0) — the
    # number /reload and rollback pay when adopting a generation.
    # Replicated runs only: measure_cold_start boots replicated engines,
    # and bench.py's shard-mode measure() calls must not re-pay (or
    # mislabel) the identical replicated measurement a second time.
    cold_start = None
    if not shard_mode and os.environ.get("BENCH_SERVE_COLDSTART", "1") == "1":
        cold_start = measure_cold_start(models, rows, tags)

    stats = engine.stats()
    on_tpu = jax.devices()[0].platform == "tpu"
    return {
        "metric": "serving_p50_ms",
        "value": round(device_ms, 3),
        "unit": (
            f"ms/request on-device anomaly scoring, pipelined "
            f"({jax.devices()[0].platform}, {machines} machines, "
            f"{rows}x{tags} request; see end_to_end/link_rtt fields)"
        ),
        # the 5 ms north-star target is a TPU anchor: a CPU-measured value
        # must not be compared against it
        "vs_baseline": round(5.0 / device_ms, 2) if on_tpu else None,
        # steady-state percentiles: measured AFTER the reported warmup
        # pass, so first-dispatch compiles and promotion gathers can never
        # masquerade as tail latency (VERDICT r4 weak #3)
        "end_to_end_p50_ms": round(e2e_p50, 3),
        "end_to_end_p99_ms": round(e2e_p99, 3),
        "warmup": {
            "requests": len(warmup_lat),
            "p50_ms": round(float(np.percentile(warmup_ms, 50)), 3),
            "max_ms": round(float(warmup_ms.max()), 3),
            "note": (
                "three round-robin passes over the fleet: pays every "
                "first-dispatch compile, (shard mode) the hot-cache "
                "promotion gathers, and the hot program's first dispatch; "
                "excluded from steady-state percentiles"
            ),
        },
        "link_rtt_ms": round(link_rtt, 3),
        "concurrent_rps": round(throughput, 1),
        "saturation": saturation,
        # best rps among the rungs whose p99 beat the 5 ms target — the
        # highest throughput achievable under the SLO, wherever on the
        # worker curve it lands. 0.0 = no rung qualified; null = non-TPU
        # run (the SLO is a TPU anchor, like vs_baseline)
        "rps_at_p99_lt_5ms": rps_at_p99_lt_5ms,
        # 12 threads spread over >= 8 distinct machines: rps/latency plus
        # this block's fused-dispatch delta (fusion_ratio > 1 ⇔ fewer
        # device dispatches than requests). None = BENCH_SERVE_XMACHINE=0
        "cross_machine": cross_machine,
        # the precision ladder (§19): per-rung rps/p50/p99 at 12-thread
        # spread, parity error vs f32, and resident-machine capacity at
        # fixed memory. None = BENCH_SERVE_PRECISION=0 or shard mode
        "precision": precision_block,
        # engine-resolved megabatch config + lifetime fusion counters
        "megabatch": stats["megabatch"],
        # per-format serialization cost vs the device dispatch cost above
        # (``value``): the host-side half of each request, which pipelined
        # dispatch overlaps with device compute (ARCHITECTURE §12)
        "wire_formats": wire_formats,
        "serialization_vs_dispatch": {
            "device_dispatch_ms": round(device_ms, 4),
            "serialize_json_ms": round(legacy_encode_ms, 4),
            "serialize_fast_json_ms": round(fast_encode_ms, 4),
            "serialize_npz_ms": round(npz_encode_ms, 4),
        },
        "dispatch_depth": stats["dispatch_depth"],
        "compiled_programs": stats["compiled_programs"],
        "max_dispatch_batch": stats["max_dispatch_batch"],
        "shard_mesh_devices": stats["shard_mesh_devices"],
        # shard mode only: end-to-end engine p50 for repeat-machine traffic
        # served from the hot cache (None in replicated mode / cache off)
        "hot_machine_p50_ms": (
            round(hot_p50, 3) if hot_p50 is not None else None
        ),
        "hot_requests": stats["hot_requests"],
        # boot economics: warmup wall time, first-request latency, and
        # fresh-XLA-compile count for a cold vs a warmed persistent
        # compile cache (None = BENCH_SERVE_COLDSTART=0)
        "cold_start": cold_start,
    }


def measure_precision(models, X, n_requests: int) -> dict:
    """The precision-ladder A/B (§19): ONE fleet served at each rung
    (f32 / bf16 / int8) through three otherwise-identical replicated
    engines. Per rung: 12-thread spread throughput + latency (the
    megabatch workload, where the ladder's smaller gathers pay off),
    the worst-machine parity error against the f32 reference on the
    normalized total-score ruler (with its declared budget beside it),
    and the residency economics — stacked bytes per machine and how
    many machines fit a fixed 1 GiB of device memory at that rung, the
    capacity half of the ladder's payoff."""
    import jax

    from gordo_components_tpu import precision as precision_mod
    from gordo_components_tpu.server.engine import ServingEngine, _round_up_pow2

    names = sorted(models)
    spread = names[: min(max(8, 12), len(names))]
    threads = 12
    per_thread = max(4, n_requests // threads)
    rounds = 3
    gib = 1 << 30
    rungs = ("f32", "bf16", "int8")
    out: dict = {
        "workers": threads, "machines": len(spread), "rounds": rounds,
        "rungs": {},
    }
    engines = {
        rung: ServingEngine(models, precisions={name: rung for name in names})
        for rung in rungs
    }
    try:
        for rung, engine in engines.items():
            # settle: every first-dispatch compile + the fused batch
            # shapes a 12-thread rung can coalesce (same rationale as
            # the main saturation warm loop)
            for _ in range(2):
                for name in spread:
                    engine.anomaly(name, X)
                engine.quiesce()
            bucket, _ = engine._by_name[spread[0]]
            x_padded, _ = engine._prepare(bucket, X)
            rows_padded = x_padded.shape[0]
            kb = 1
            while kb <= min(warm_batch_bound(engine), 16):
                if bucket._mega_enabled:
                    jax.block_until_ready(
                        bucket._mega_program(rows_padded, kb)(
                            bucket._warm_mega_stack(),
                            np.zeros((kb,), np.int32),
                            np.repeat(x_padded[None], kb, axis=0),
                        )
                    )
                kb *= 2

        def sweep(engine):
            def one(t: int):
                lat = []
                for i in range(per_thread):
                    name = spread[(t + i) % len(spread)]
                    started = time.perf_counter()
                    engine.anomaly(name, X)
                    lat.append(time.perf_counter() - started)
                return lat

            with ThreadPoolExecutor(max_workers=threads) as pool:
                list(pool.map(one, range(threads)))  # settle threads
                started = time.perf_counter()
                lat_lists = list(pool.map(one, range(threads)))
            elapsed = time.perf_counter() - started
            engine.quiesce()
            lats = [v for lat in lat_lists for v in lat]
            return len(lats) / elapsed, lats

        # INTERLEAVED rounds (the perf_smoke overhead-gate trick): every
        # rung sees the same box in every round, so a scheduler/GC
        # straggler degrades one round of every rung instead of one
        # rung's whole measurement — per-rung rps is the median round
        rps_rounds: dict = {rung: [] for rung in rungs}
        lat_pool: dict = {rung: [] for rung in rungs}
        for _ in range(rounds):
            for rung in rungs:
                rps, lats = sweep(engines[rung])
                rps_rounds[rung].append(rps)
                lat_pool[rung].extend(lats)
        # on-device cost of one fused 8-request dispatch per rung,
        # pipelined (sync once per rep) — the rung-comparison anchor.
        # The threaded rps above is host-overhead-bound and carries this
        # rig's multi-x scheduler noise; this is the same pipelined-
        # dispatch ruler as the bench's headline ``value`` metric, where
        # the ladder's smaller weight gathers actually land. Reps are
        # INTERLEAVED across rungs (median of 5) so box-state drift
        # degrades one rep of every rung, never one rung's measurement.
        k = 8
        dispatch_setup = {}
        for rung in rungs:
            bucket, _ = engines[rung]._by_name[spread[0]]
            x_padded, _ = engines[rung]._prepare(bucket, X)
            rows_padded = x_padded.shape[0]
            if bucket._mega_enabled:
                program = bucket._mega_program(rows_padded, k)
                stack = bucket._warm_mega_stack()
            else:
                program = bucket._program(rows_padded, k)
                stack = bucket.stacked
            slots = np.arange(k, dtype=np.int32)
            xs = np.repeat(x_padded[None], k, axis=0)
            jax.block_until_ready(program(stack, slots, xs))
            dispatch_setup[rung] = (program, stack, slots, xs)
        dispatch_reps: dict = {rung: [] for rung in rungs}
        for _ in range(5):
            for rung in rungs:
                program, stack, slots, xs = dispatch_setup[rung]
                n_pipe = 80
                started = time.perf_counter()
                outs = [program(stack, slots, xs) for _ in range(n_pipe)]
                jax.block_until_ready(outs)
                dispatch_reps[rung].append(
                    (time.perf_counter() - started) / n_pipe * 1000.0
                )

        reference: dict = {}
        for rung in rungs:
            engine = engines[rung]
            # parity vs the f32 reference (worst machine), on the same
            # normalized ruler the smoke gate uses
            worst = 0.0
            for name in spread:
                total = engine.anomaly(name, X).total_anomaly_score
                if rung == "f32":
                    reference[name] = total
                else:
                    worst = max(worst, precision_mod.parity_error(
                        reference[name], total
                    ))
            stacked_bytes = sum(
                int(np.asarray(leaf).nbytes)
                for b in engine._buckets
                for leaf in jax.tree_util.tree_leaves(b.stacked)
            )
            per_machine = stacked_bytes / max(1, len(names))
            lat_ms = np.asarray(lat_pool[rung]) * 1000.0
            out["rungs"][rung] = {
                "device_dispatch_ms": round(
                    float(np.median(dispatch_reps[rung])), 3
                ),
                "rps": round(float(np.median(rps_rounds[rung])), 1),
                "rps_rounds": [round(r, 1) for r in rps_rounds[rung]],
                "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                "parity_error_vs_f32": (
                    None if rung == "f32" else float(f"{worst:.3g}")
                ),
                "parity_budget": (
                    None if rung == "f32"
                    else precision_mod.error_budget(rung)
                ),
                "stacked_bytes_per_machine": int(per_machine),
                # the residency-compounding headline: machines resident
                # per fixed GiB of device memory at this rung
                "machines_per_gib": int(gib / per_machine),
            }
    finally:
        for engine in engines.values():
            engine.close()
    f32_rung = out["rungs"].get("f32") or {}
    if f32_rung.get("device_dispatch_ms"):
        # headline speedups ride the pipelined DEVICE dispatch (the
        # stable ruler); the rps twin is reported per rung above for
        # the concurrency view, noise and all
        for rung in ("bf16", "int8"):
            row = out["rungs"].get(rung) or {}
            if not row:
                continue
            out[f"{rung}_dispatch_speedup_x"] = round(
                f32_rung["device_dispatch_ms"] / row["device_dispatch_ms"], 3
            )
            # the acceptance headline: rung vs f32 at 12-thread
            # SATURATION (median interleaved round) — where the ladder's
            # halved/quartered weight traffic relieves the contended
            # memory path
            out[f"{rung}_saturation_speedup_x"] = round(
                row["rps"] / f32_rung["rps"], 3
            )
            out[f"capacity_gain_{rung}_x"] = round(
                row["machines_per_gib"] / f32_rung["machines_per_gib"], 2
            )
    import jax as _jax

    if _jax.devices()[0].platform != "tpu":
        out["note"] = (
            "CPU-backend run: saturation speedups come from halved/"
            "quartered weight traffic under 12-thread memory contention; "
            "single-stream device_dispatch_ms carries bf16's XLA:CPU "
            "conversion overhead instead (no bf16 compute units here — "
            "that half of the win is a TPU anchor, like vs_baseline). "
            "rps_rounds shows this rig's per-round scheduler noise."
        )
    return out


def measure_cross_machine(engine, names, X, n_requests: int) -> dict:
    """The cross-machine saturation sweep: 12 threads, each pinned to its
    own round-robin offset over ``spread`` distinct machines, so almost
    every coalesced dispatch window holds requests for several DIFFERENT
    machines. Reports throughput/latency plus the engine's fused-dispatch
    delta for exactly this block — ``fusion_ratio`` (requests per device
    dispatch) is the megabatch acceptance headline; on engines with
    megabatching off (or shard mode) the same numbers quantify the
    per-machine baseline the fused path is compared against."""
    workers = 12
    spread = list(names[: min(max(8, workers), len(names))])
    per_thread = max(4, n_requests // workers)

    def one(t: int):
        lat = []
        for i in range(per_thread):
            name = spread[(t + i) % len(spread)]
            started = time.perf_counter()
            engine.anomaly(name, X)
            lat.append(time.perf_counter() - started)
        return lat

    with ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(one, range(workers)))  # settle threads + programs
        engine.quiesce()  # the settle pass must not leak into the deltas
        before = engine.stats()
        started = time.perf_counter()
        lat_lists = list(pool.map(one, range(workers)))
    elapsed = time.perf_counter() - started
    engine.quiesce()  # fused-batch stats ride the fetch stage
    after = engine.stats()
    lat_ms = np.asarray([v for lat in lat_lists for v in lat]) * 1000.0
    total = int(lat_ms.size)
    dispatches = after["dispatches"] - before["dispatches"]
    requests = after["batched_requests"] - before["batched_requests"]
    mb_before, mb_after = before["megabatch"], after["megabatch"]
    mega_dispatches = mb_after["dispatches"] - mb_before["dispatches"]
    mega_requests = mb_after["requests"] - mb_before["requests"]
    return {
        "workers": workers,
        "machines": len(spread),
        "requests": total,
        "rps": round(total / elapsed, 1),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        # fused-batch stats for THIS block only (deltas): dispatches <
        # requests ⇔ fusion ratio > 1 — the ISSUE 7 acceptance shape
        "dispatches": dispatches,
        "fusion_ratio": (
            round(requests / dispatches, 3) if dispatches else None
        ),
        "megabatch": {
            "enabled": mb_after["enabled"],
            "dispatches": mega_dispatches,
            "requests": mega_requests,
            "fusion_ratio": (
                round(mega_requests / mega_dispatches, 3)
                if mega_dispatches
                else None
            ),
            "fill_timeout_total": (
                mb_after["fill_timeout_total"]
                - mb_before["fill_timeout_total"]
            ),
            "fill_size_total": (
                mb_after["fill_size_total"] - mb_before["fill_size_total"]
            ),
            "fill_window_us": mb_after["fill_window_us"],
            "resident_machines": mb_after["resident_machines"],
        },
    }


_MW_DATA_CONFIG = {
    "type": "RandomDataset",
    "train_start_date": "2023-01-01T00:00:00+00:00",
    "train_end_date": "2023-01-04T00:00:00+00:00",
    "tag_list": [f"mw-tag-{i}" for i in range(6)],
}
_MW_MODEL_CONFIG = {
    "Pipeline": {
        "steps": [
            "MinMaxScaler",
            {"DenseAutoEncoder": {"kind": "feedforward_symmetric",
                                  "dims": [8], "epochs": 1,
                                  "batch_size": 32}},
        ]
    }
}


def measure_multi_worker() -> dict:
    """Horizontal serving tier (ISSUE 8): 1 vs N full worker PROCESSES
    behind the consistent-hash router, 12 client threads spread over the
    machine set — the GIL-escape measurement. Every in-process number
    above shares one interpreter; this block is the only one where N
    engines score truly concurrently. Reports rps/p50/p99 per worker
    count plus each worker's own fused-dispatch (megabatch) ratio, so
    the horizontal win and the per-worker fusion cost of splitting
    traffic are visible side by side — placement pins each machine to
    one worker precisely so fusion survives the split.

    Env: BENCH_SERVE_WORKERS (2) — the N rung; BENCH_SERVE_MW_MACHINES
    (8); BENCH_SERVE_MW_REQUESTS (40) — requests per thread per pass;
    BENCH_SERVE_MW_PASSES (3) — timed passes per rung, MEDIAN reported.
    Workers are real ``gordo run-server`` subprocesses sharing one
    models tree + compile-cache store (the second rung boots warm).

    Noise note (ISSUE 14 satellite): BENCH_r06 recorded scaling_x 0.66
    from a SINGLE timed pass per rung inside the full bench run.
    Standalone reruns on the same 2-core rig measured 1.24x and 1.33x
    (2 workers faster, as designed), with no memory pressure and
    ok_fraction 1.0 in every rung — the 0.66 was one-shot scheduler
    noise on a box where 12 client threads + router + workers share 2
    cores, not router forward overhead and not a worker regression.
    This block now reports the median of ``BENCH_SERVE_MW_PASSES``
    timed passes (per-pass values in ``rps_passes``) so a single noisy
    pass can no longer flip the headline."""
    import tempfile

    import requests

    from gordo_components_tpu.builder import provide_saved_model
    from gordo_components_tpu.router import (
        SubprocessWorker,
        assemble_fleet,
        server_worker_argv,
        worker_specs,
    )

    n_workers = int(os.environ.get("BENCH_SERVE_WORKERS", "2"))
    n_machines = int(os.environ.get("BENCH_SERVE_MW_MACHINES", "8"))
    per_thread = int(os.environ.get("BENCH_SERVE_MW_REQUESTS", "40"))
    passes = max(1, int(os.environ.get("BENCH_SERVE_MW_PASSES", "3")))
    threads = 12
    rows = 24

    rng = np.random.default_rng(3)
    payload = json.dumps(
        {"X": (rng.normal(size=(rows, 6)) * 2 + 4).tolist()}
    )
    headers = {"Content-Type": "application/json"}
    out: dict = {
        "workers_compared": sorted({1, max(1, n_workers)}),
        "machines": n_machines,
        "threads": threads,
        "request_shape": [rows, 6],
        "rungs": {},
    }
    with tempfile.TemporaryDirectory() as tmp:
        root = os.path.join(tmp, "models")
        os.makedirs(root)
        names = [f"mw-{i:03d}" for i in range(n_machines)]
        for name in names:
            provide_saved_model(
                name, _MW_MODEL_CONFIG, _MW_DATA_CONFIG,
                os.path.join(root, name),
                evaluation_config={"cv_mode": "build_only"},
            )
        for count in out["workers_compared"]:
            specs = [
                spec._replace(port=free_port())
                for spec in worker_specs(count, 0)
            ]

            def factory(spec):
                return SubprocessWorker(
                    spec,
                    server_worker_argv(spec, root, project="bench"),
                    stdout=__import__("subprocess").DEVNULL,
                    stderr=__import__("subprocess").DEVNULL,
                )

            router = assemble_fleet(
                specs, factory, project="bench", models_root=root,
                respawn=False,
            )
            from werkzeug.serving import make_server
            import logging as _logging
            import threading as _threading

            _logging.getLogger("werkzeug").setLevel(_logging.WARNING)
            router.supervisor.start_all()
            ready = router.supervisor.wait_ready(timeout=600)
            front = make_server("127.0.0.1", 0, router, threaded=True)
            front_thread = _threading.Thread(
                target=front.serve_forever, daemon=True
            )
            front_thread.start()
            base = f"http://127.0.0.1:{front.server_port}"
            try:
                if len(ready) != count:
                    out["rungs"][str(count)] = {
                        "error": f"only {len(ready)}/{count} workers ready"
                    }
                    continue

                def one(t: int):
                    lat = []
                    with requests.Session() as session:
                        for i in range(per_thread):
                            name = names[(t + i) % len(names)]
                            started = time.perf_counter()
                            response = session.post(
                                f"{base}/gordo/v0/bench/{name}/prediction",
                                data=payload, headers=headers, timeout=60,
                            )
                            if response.status_code == 200:
                                lat.append(
                                    time.perf_counter() - started
                                )
                    return lat

                pass_rps: list = []
                pass_lat: list = []
                with ThreadPoolExecutor(max_workers=threads) as pool:
                    # settle pass: worker-side batch-shape compiles and
                    # connection setup stay out of the timed window
                    list(pool.map(one, range(threads)))
                    # median of N timed passes: one pass per rung let a
                    # single scheduler hiccup flip the scaling headline
                    # on this 2-core rig (the BENCH_r06 0.66 reading —
                    # see the docstring's noise note)
                    for _ in range(passes):
                        started = time.perf_counter()
                        lat_lists = list(pool.map(one, range(threads)))
                        elapsed = time.perf_counter() - started
                        lat = np.asarray(
                            [v for lat in lat_lists for v in lat]
                        ) * 1000.0
                        pass_rps.append(
                            lat.size / elapsed if elapsed else 0.0
                        )
                        pass_lat.append(lat)
                median_at = int(np.argsort(pass_rps)[len(pass_rps) // 2])
                lat_ms = pass_lat[median_at]
                median_rps = pass_rps[median_at]
                per_worker: dict = {}
                for spec in specs:
                    try:
                        body = requests.get(
                            f"{spec.base_url}/metrics", timeout=10
                        ).json()
                        mega = body["engine"]["megabatch"]
                        per_worker[spec.name] = {
                            "fusion_ratio": mega.get("fusion_ratio"),
                            "fused_dispatches": mega.get("dispatches"),
                            "fused_requests": mega.get("requests"),
                        }
                    except Exception as exc:
                        per_worker[spec.name] = {"error": repr(exc)}
                out["rungs"][str(count)] = {
                    "requests": int(lat_ms.size),
                    "ok_fraction": round(
                        lat_ms.size / (threads * per_thread), 3
                    ),
                    "rps": round(median_rps, 1),
                    "rps_passes": [round(v, 1) for v in pass_rps],
                    "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                    "per_worker": per_worker,
                }
            finally:
                front.shutdown()
                front_thread.join(timeout=5)
                router.control.stop()
                router.supervisor.stop_all(grace=10)
                router.close()
    rungs = out["rungs"]
    one_rung = rungs.get("1")
    top_rung = rungs.get(str(max(out["workers_compared"])))
    if (
        one_rung and top_rung
        and "rps" in one_rung and "rps" in top_rung
        and one_rung["rps"]
    ):
        # the headline: HTTP-path throughput gained by going multi-process
        out["scaling_x"] = round(top_rung["rps"] / one_rung["rps"], 2)
    return out


def measure_multihost() -> dict:
    """Multi-host mesh serving (ISSUE 15, ARCHITECTURE §23): 1 un-meshed
    worker vs N PROCESS SHARDS of the same fleet at 12-thread
    saturation. The mesh rung partitions the stacked machine axis by the
    deterministic shard plan — each worker stacks only its owned slice
    (half the device residency per host at N=2) and the router walks the
    owning shard's workers first — so the comparison prices exactly what
    the layout changes: owner-routed scoring against the single-host
    wall. Reports rps/p50/p99 per rung (median of
    ``BENCH_SERVE_MW_PASSES`` timed passes, same hardening as the
    multi_worker block), each shard's owned-machine count, and the
    owned/fallback request split off ``gordo_mesh_requests_total`` — a
    nonzero steady-state fallback share means placement and the plan
    disagree (it must be zero with every shard healthy).

    Env: BENCH_SERVE_MESH_SHARDS (2) — the N rung;
    BENCH_SERVE_MESH_MACHINES (8; the `mesh-NNN` name set splits 4/4 on
    the 2-shard ring); BENCH_SERVE_MH_REQUESTS (40) — requests per
    thread per pass; BENCH_SERVE_MW_PASSES (3). Workers are real
    ``gordo run-server`` subprocesses sharing one models tree +
    compile-cache store.

    Reading note (same class as the multi_worker block's): on the
    2-core CI rig the N-shard rung oversubscribes cores (12 client
    threads + router + N jax processes), so `scaling_x` there prices
    scheduler contention, not the layout — what sharding BUYS is
    per-host device residency (each host stacks 1/N of the fleet,
    `machines_per_shard`), which a one-host CPU rig cannot exhibit.
    The honest rig-local gates are `ok_fraction` 1.0 and
    `fallback_requests` 0 with every shard healthy."""
    import tempfile

    import requests

    from gordo_components_tpu.builder import provide_saved_model
    from gordo_components_tpu.parallel.shard_plan import FleetShardPlan
    from gordo_components_tpu.router import (
        SubprocessWorker,
        assemble_fleet,
        server_worker_argv,
        worker_specs,
    )

    n_shards = max(2, int(os.environ.get("BENCH_SERVE_MESH_SHARDS", "2")))
    n_machines = int(os.environ.get("BENCH_SERVE_MESH_MACHINES", "8"))
    per_thread = int(os.environ.get("BENCH_SERVE_MH_REQUESTS", "40"))
    passes = max(1, int(os.environ.get("BENCH_SERVE_MW_PASSES", "3")))
    threads = 12
    rows = 24

    names = [f"mesh-{i:03d}" for i in range(n_machines)]
    plan = FleetShardPlan(n_shards)
    rng = np.random.default_rng(7)
    payload = json.dumps(
        {"X": (rng.normal(size=(rows, 6)) * 2 + 4).tolist()}
    )
    headers = {"Content-Type": "application/json"}
    out: dict = {
        "shards_compared": [1, n_shards],
        "machines": n_machines,
        "machines_per_shard": plan.counts(names),
        "threads": threads,
        "request_shape": [rows, 6],
        "rungs": {},
    }
    with tempfile.TemporaryDirectory() as tmp:
        root = os.path.join(tmp, "models")
        os.makedirs(root)
        for name in names:
            provide_saved_model(
                name, _MW_MODEL_CONFIG, _MW_DATA_CONFIG,
                os.path.join(root, name),
                evaluation_config={"cv_mode": "build_only"},
            )
        for count in out["shards_compared"]:
            meshed = count > 1
            specs = [
                spec._replace(port=free_port())
                for spec in worker_specs(count, 0)
            ]

            def factory(spec):
                extra = (
                    ["--mesh-shards", str(count),
                     "--mesh-shard", str(spec.worker_id % count)]
                    if meshed else []
                )
                return SubprocessWorker(
                    spec,
                    server_worker_argv(
                        spec, root, project="bench", extra=extra
                    ),
                    stdout=__import__("subprocess").DEVNULL,
                    stderr=__import__("subprocess").DEVNULL,
                )

            router = assemble_fleet(
                specs, factory, project="bench", models_root=root,
                respawn=False,
                mesh_shards=count if meshed else 0,
            )
            from werkzeug.serving import make_server
            import logging as _logging
            import threading as _threading

            _logging.getLogger("werkzeug").setLevel(_logging.WARNING)
            router.supervisor.start_all()
            ready = router.supervisor.wait_ready(timeout=600)
            front = make_server("127.0.0.1", 0, router, threaded=True)
            front_thread = _threading.Thread(
                target=front.serve_forever, daemon=True
            )
            front_thread.start()
            base = f"http://127.0.0.1:{front.server_port}"
            try:
                if len(ready) != count:
                    out["rungs"][str(count)] = {
                        "error": f"only {len(ready)}/{count} workers ready"
                    }
                    continue

                def one(t: int):
                    lat = []
                    with requests.Session() as session:
                        for i in range(per_thread):
                            name = names[(t + i) % len(names)]
                            started = time.perf_counter()
                            response = session.post(
                                f"{base}/gordo/v0/bench/{name}/prediction",
                                data=payload, headers=headers, timeout=60,
                            )
                            if response.status_code == 200:
                                lat.append(
                                    time.perf_counter() - started
                                )
                    return lat

                pass_rps: list = []
                pass_lat: list = []
                with ThreadPoolExecutor(max_workers=threads) as pool:
                    # settle pass: worker-side batch-shape compiles and
                    # connection setup stay out of the timed window
                    list(pool.map(one, range(threads)))
                    for _ in range(passes):
                        started = time.perf_counter()
                        lat_lists = list(pool.map(one, range(threads)))
                        elapsed = time.perf_counter() - started
                        lat = np.asarray(
                            [v for lat in lat_lists for v in lat]
                        ) * 1000.0
                        pass_rps.append(
                            lat.size / elapsed if elapsed else 0.0
                        )
                        pass_lat.append(lat)
                median_at = int(np.argsort(pass_rps)[len(pass_rps) // 2])
                lat_ms = pass_lat[median_at]
                per_shard: dict = {}
                for spec in specs:
                    try:
                        body = requests.get(
                            f"{spec.base_url}/metrics", timeout=10
                        ).json()
                        mesh = (body.get("engine") or {}).get("mesh")
                        series = (
                            body.get("registry", {})
                            .get("gordo_mesh_requests_total", {})
                            .get("series", {})
                        )
                        per_shard[spec.name] = {
                            "mesh": mesh,
                            "owned_requests": sum(
                                v for k, v in series.items()
                                if 'path="owned"' in k
                            ),
                            "fallback_requests": sum(
                                v for k, v in series.items()
                                if 'path="fallback"' in k
                            ),
                        }
                    except Exception as exc:
                        per_shard[spec.name] = {"error": repr(exc)}
                out["rungs"][str(count)] = {
                    "requests": int(lat_ms.size),
                    "ok_fraction": round(
                        lat_ms.size / (threads * per_thread), 3
                    ),
                    "rps": round(pass_rps[median_at], 1),
                    "rps_passes": [round(v, 1) for v in pass_rps],
                    "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
                    "per_shard": per_shard,
                }
            finally:
                front.shutdown()
                front_thread.join(timeout=5)
                router.control.stop()
                router.supervisor.stop_all(grace=10)
                router.close()
    rungs = out["rungs"]
    one_rung = rungs.get("1")
    top_rung = rungs.get(str(n_shards))
    if (
        one_rung and top_rung
        and "rps" in one_rung and "rps" in top_rung
        and one_rung["rps"]
    ):
        # the headline: throughput gained by sharding the fleet across
        # process shards vs the single-host wall
        out["scaling_x"] = round(top_rung["rps"] / one_rung["rps"], 2)
    return out


def measure_autopilot() -> dict:
    """Closed-loop autopilot A/B (ISSUE 12 acceptance): the SAME shifting
    load mix — ramp → spike → idle — driven twice over identical fresh
    engines, once at the hand-set defaults and once with the autopilot
    ticking. The controller reads real signals (an engine-dispatch SLO
    evaluator + the flight recorder's span shares) and turns the real
    actuators (dispatch depth, fill window) through
    ``engine.apply_tuning``; nothing is scripted. Reported per phase:
    rps / p50 / p99 and client-side SLO attainment (fraction of requests
    under the latency objective's threshold — computed from the same
    latency samples, so both modes share one ruler). Headlines:
    ``spike_rps_x`` (autopilot ÷ defaults, >1 = faster) and
    ``spike_p99_x`` (defaults ÷ autopilot, >1 = tighter tail) on the
    spike phase — the phase static configuration leaves on the table.
    ``BENCH_SERVE_AUTOPILOT=0`` skips the block."""
    from gordo_components_tpu.autopilot import (
        AIMD,
        Actuator,
        Autopilot,
        SignalReader,
        Thresholds,
    )
    from gordo_components_tpu.autopilot import policy as ap_policy
    from gordo_components_tpu.observability import slo as slo_engine
    from gordo_components_tpu.observability import spans
    from gordo_components_tpu.observability.flightrec import RECORDER
    from gordo_components_tpu.server.engine import ServingEngine

    n_machines = int(os.environ.get("BENCH_SERVE_AP_MACHINES", "8"))
    rows, tags = 64, 6
    phases = (
        ("ramp", 4, 2.5),
        ("spike", 12, 5.0),
        ("idle", 1, 1.5),
    )
    threshold_s, _target = slo_engine.latency_knobs()
    models = build_models(n_machines, rows, tags)
    rng = np.random.default_rng(11)
    X = rng.normal(size=(rows, tags)).astype(np.float32) * 2 + 4

    def run_mode(autopilot_on: bool) -> dict:
        engine = ServingEngine(models)
        names = engine.machines()
        for name in names:  # warm compiles out of the measured window
            engine.anomaly(name, X)
        engine.quiesce()
        RECORDER.clear()
        pilot = None
        if autopilot_on:
            evaluator = slo_engine.SLOEvaluator(
                [
                    slo_engine.Objective(
                        name="bench-dispatch",
                        kind="latency",
                        metric="gordo_engine_dispatch_seconds",
                        target=0.99,
                        threshold_s=threshold_s,
                    )
                ],
                fast_window=5.0, slow_window=30.0, min_interval=0.0,
            )
            # aggressive settling constants: the bench's phases are
            # seconds long, production's are minutes (the knobs)
            thresholds = Thresholds(burn_high=1.0, burn_low=0.25)
            reader = SignalReader(
                slo=evaluator, recorder=RECORDER,
                engine_stats=engine.stats,
            )
            tuning = engine.current_tuning
            aimd = AIMD(step=0.5, backoff=0.5)
            pilot = Autopilot(
                reader,
                [
                    Actuator(
                        name="dispatch_depth",
                        read=lambda: tuning()["dispatch_depth"],
                        apply=lambda v: engine.apply_tuning(
                            dispatch_depth=v
                        ),
                        decide=ap_policy.depth_rule(thresholds),
                        bounds=ap_policy.Bounds(1, 8),
                        aimd=aimd, cooldown=0.6, confirm=2,
                    ),
                    Actuator(
                        name="fill_window",
                        read=lambda: tuning()["fill_window_us"],
                        apply=lambda v: engine.apply_tuning(
                            fill_window_us=v
                        ),
                        decide=ap_policy.fill_rule(thresholds),
                        bounds=ap_policy.Bounds(0, 4000),
                        aimd=aimd, cooldown=0.6, confirm=2,
                    ),
                ],
                role="bench", min_interval=0.2, enabled=True,
            )

        def one(t: int, stop_at: float) -> list:
            lat = []
            i = 0
            while time.perf_counter() < stop_at:
                name = names[(t + i) % len(names)]
                i += 1
                timeline, token = spans.begin(
                    f"bench-ap-{t}-{i}", endpoint="anomaly"
                )
                started = time.perf_counter()
                try:
                    engine.anomaly(name, X)
                    lat.append(time.perf_counter() - started)
                finally:
                    timeline.finish(status="200")
                    spans.end(token)
                    RECORDER.record(timeline)
            return lat

        # the controller is scrape-driven in production; here a ticker
        # thread stands in for the scraper so evaluation runs DURING the
        # phases (pool.map blocks the driver thread)
        import threading

        ticker_stop = threading.Event()
        ticker_thread = None
        if pilot is not None:
            def ticker():
                while not ticker_stop.is_set():
                    try:
                        pilot.maybe_tick()
                    except Exception:
                        pass
                    ticker_stop.wait(0.1)

            ticker_thread = threading.Thread(
                target=ticker, name="bench-ap-ticker", daemon=True
            )
            ticker_thread.start()

        out: dict = {}
        try:
            for phase_name, threads, seconds in phases:
                stop_at = time.perf_counter() + seconds
                started = time.perf_counter()
                with ThreadPoolExecutor(max_workers=threads) as pool:
                    lat_lists = list(
                        pool.map(
                            lambda t: one(t, stop_at), range(threads)
                        )
                    )
                elapsed = time.perf_counter() - started
                lat = np.asarray(
                    [v for lst in lat_lists for v in lst]
                )
                out[phase_name] = {
                    "requests": int(lat.size),
                    "rps": round(lat.size / elapsed, 1),
                    "p50_ms": round(
                        float(np.percentile(lat, 50)) * 1000, 3
                    ) if lat.size else None,
                    "p99_ms": round(
                        float(np.percentile(lat, 99)) * 1000, 3
                    ) if lat.size else None,
                    "slo_attainment": round(
                        float((lat <= threshold_s).mean()), 4
                    ) if lat.size else None,
                }
        finally:
            ticker_stop.set()
            if ticker_thread is not None:
                ticker_thread.join(timeout=5)
            out["final_tuning"] = engine.current_tuning()
            if pilot is not None:
                out["decisions"] = pilot.snapshot()["decisions"]
            engine.close()
        return out

    out: dict = {
        "machines": n_machines,
        "request_shape": [rows, tags],
        "phases": [
            {"name": name, "threads": threads, "seconds": seconds}
            for name, threads, seconds in phases
        ],
        "slo_threshold_ms": round(threshold_s * 1000, 1),
        "modes": {},
    }
    out["modes"]["defaults"] = run_mode(False)
    out["modes"]["autopilot"] = run_mode(True)
    spike_a = out["modes"]["autopilot"].get("spike") or {}
    spike_d = out["modes"]["defaults"].get("spike") or {}
    if spike_a.get("rps") and spike_d.get("rps"):
        out["spike_rps_x"] = round(spike_a["rps"] / spike_d["rps"], 3)
    if spike_a.get("p99_ms") and spike_d.get("p99_ms"):
        out["spike_p99_x"] = round(
            spike_d["p99_ms"] / spike_a["p99_ms"], 3
        )
    out["autopilot_wins"] = bool(
        out.get("spike_rps_x", 0) > 1.0 or out.get("spike_p99_x", 0) > 1.0
    )
    return out


def measure_cold_start(models, rows: int, tags: int) -> dict:
    """Boot the serving engine twice against ONE throwaway compile-cache
    root and report each boot's warmup wall time, first-request latency,
    fresh-compile count, and cache counters. Replicated (single-device)
    engines only — the cache's design case is the latency-mode boot path;
    shard-mode executables may not serialize on every backend and would
    report an honest-but-noisy partial warm here."""
    import tempfile

    from gordo_components_tpu.compile_cache import CompileCacheStore
    from gordo_components_tpu.observability.registry import REGISTRY
    from gordo_components_tpu.server.engine import ServingEngine

    def fresh_compiles() -> float:
        for metric in REGISTRY.metrics():
            if metric.name == "gordo_engine_compile_seconds":
                return sum(s["count"] for s in metric.stats().values())
        return 0

    rng = np.random.default_rng(7)
    X = rng.normal(size=(rows, tags)).astype(np.float32) * 2 + 4
    out: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        root = os.path.join(tmp, "compile-cache")
        for label in ("cold_boot", "warm_boot"):
            store = CompileCacheStore(root)
            before = fresh_compiles()
            started = time.perf_counter()
            engine = ServingEngine(models, compile_cache=store)
            engine.warmup(rows)
            warmup_s = time.perf_counter() - started
            name = engine.machines()[0]
            started = time.perf_counter()
            engine.anomaly(name, X)
            first_ms = (time.perf_counter() - started) * 1000.0
            engine.close()
            out[label] = {
                "warmup_s": round(warmup_s, 3),
                "first_request_ms": round(first_ms, 3),
                # fresh XLA compiles this boot paid (the acceptance gate:
                # 0 on the warm boot — coldstart_smoke enforces it)
                "compiles_at_boot": int(fresh_compiles() - before),
                "cache": dict(store.counters),
            }
        speedup = (
            out["cold_boot"]["warmup_s"] / out["warm_boot"]["warmup_s"]
            if out["warm_boot"]["warmup_s"] > 0
            else None
        )
        out["warmup_speedup"] = round(speedup, 2) if speedup else None
    return out


def measure_capacity() -> dict:
    """Fleet-scale capacity block (ISSUE 14 acceptance, ARCHITECTURE
    §22): the whole capacity story at a 10k-machine synthetic fleet via
    ``tools/capacity_harness.full_run`` — every §22 optimization with
    its before/after number from the harness itself:

    - boot: FLEET_INDEX lazy boot (after) vs full-scan boot (before);
    - spill tier: serving a demoted machine from host RAM (after) vs
      the store path (before), both bundle-seam and end-to-end;
    - placement: incremental vnode-arc join (after) vs full ring
      rebuild (before), plus candidates() p50/p99 at a 64-worker ring;
    - traffic: heavy-tailed diurnal hot-key-skewed load plus a
      flight-recorder-replay pass through 2 lazy workers behind the
      real router, with SLO attainment and zero-failure accounting;
    - qos: the §25 tenant mix (premium interactive + saturating bulk +
      quota-abusing tenant, concurrently) with per-class attainment
      and the 503-shed vs 429-quota split;
    - metrics: exposition bytes + worst machine-label cardinality
      (bounded top-K + `other` at any fleet size).

    Env: GORDO_CAPACITY_MACHINES (10000 here; the 2k default belongs to
    capacity_smoke), GORDO_CAPACITY_SECONDS (8) per traffic phase;
    BENCH_SERVE_CAPACITY=0 skips the block — fleet generation plus the
    full-scan boot comparison takes ~5 minutes at 10k machines."""
    import shutil
    import tempfile

    from tools import capacity_harness as ch

    machines = int(os.environ.get("GORDO_CAPACITY_MACHINES", "10000"))
    seconds = float(os.environ.get("GORDO_CAPACITY_SECONDS", "8"))
    root = tempfile.mkdtemp(prefix="gordo-bench-capacity-")
    try:
        report = ch.full_run(
            root, machines, seconds, workers=2, threads=8
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)
    boot = report.get("boot", {})
    spill = report.get("spill", {})
    placement = report.get("placement", {})
    report["headlines"] = {
        # before/after, one line per §22 optimization
        "boot_scan_vs_lazy_s": [boot.get("scan_s"), boot.get("lazy_s")],
        "boot_speedup_x": boot.get("speedup_x"),
        "spill_store_vs_hit_ms": [
            spill.get("serve_store_ms_p50"), spill.get("serve_hit_ms_p50")
        ],
        "spill_speedup_x": spill.get("speedup_x"),
        "ring_rebuild_vs_incremental_ms": [
            placement.get("join_full_rebuild_ms"),
            placement.get("join_incremental_ms"),
        ],
        "exposition_bytes": report.get("metrics", {}).get(
            "exposition_bytes"
        ),
        "slo_breaches": report.get("slo", {}).get("breaches"),
        # §25: per-class attainment under the three-principal mix (each
        # tenant is its class's only principal in the canonical table)
        "qos_attainment": {
            name: report.get("qos", {}).get(name, {}).get("attainment")
            for name in ("premium", "batch", "abuser")
        },
        "qos_quota_429s": report.get("qos", {}).get("abuser", {}).get(
            "quota_429"
        ),
    }
    return report


def measure_telemetry() -> dict:
    """Telemetry warehouse block (ISSUE 16, ARCHITECTURE §24): the
    observability plane's own cost and coverage at a shaped Zipf load
    through the real 2-worker router tier —

    - scrape latency: wall time of the merged ``/telemetry`` view and
      of the ``?view=export`` layout-input render (router fan-out +
      merge + schema-sized JSON, the price a scraper pays per poll);
    - warehouse write economy: on-disk bytes, record count, and bytes
      per record after the load (what the GORDO_TELEMETRY_MB budget
      actually buys in retained history);
    - traffic sketch coverage: tracked machines vs fleet size and the
      hot machine's 1m EWMA rate;
    - the measured-cost ledger headline: per-rung stacked device
      bytes, host-cache tier bytes, and compile seconds banked.

    Env: BENCH_SERVE_TELEMETRY=0 skips;
    GORDO_TELEMETRY_BENCH_MACHINES (300) and
    GORDO_TELEMETRY_BENCH_SECONDS (6) size the run."""
    import shutil
    import tempfile

    import requests

    from gordo_components_tpu.observability import telemetry as tel
    from gordo_components_tpu.observability import traffic as traffic_mod
    from tools import capacity_harness as ch

    machines_n = int(
        os.environ.get("GORDO_TELEMETRY_BENCH_MACHINES", "300")
    )
    seconds = float(os.environ.get("GORDO_TELEMETRY_BENCH_SECONDS", "6"))
    saved = {
        k: os.environ.get(k)
        for k in ("GORDO_TELEMETRY", "GORDO_TELEMETRY_INTERVAL")
    }
    os.environ["GORDO_TELEMETRY"] = "1"
    os.environ["GORDO_TELEMETRY_INTERVAL"] = "0"  # every scrape ticks
    root = tempfile.mkdtemp(prefix="gordo-bench-telemetry-")
    tier = None
    try:
        ch.generate_fleet(root, machines_n)
        machines = sorted(
            name for name in os.listdir(root)
            if name.startswith("cap-")
        )
        tier = ch.RouterTier(root, n_workers=2, eager=8)
        tier.warm(machines)
        traffic_mod.ACCOUNTANT.reset()
        traffic_mod.ACCOUNTANT.tick()  # EWMA baseline for the load
        load = ch.run_load(tier.base_url, machines, seconds, threads=6)

        t0 = time.perf_counter()
        view = requests.get(
            f"{tier.base_url}/telemetry", params={"window": 600},
            timeout=30,
        ).json()
        view_ms = (time.perf_counter() - t0) * 1000
        t0 = time.perf_counter()
        doc = requests.get(
            f"{tier.base_url}/telemetry",
            params={"window": 600, "view": "export"}, timeout=30,
        ).json()
        export_ms = (time.perf_counter() - t0) * 1000

        warehouse = view.get("warehouse") or {}
        records = int(warehouse.get("records") or 0)
        traffic_view = view.get("traffic") or {}
        top = traffic_view.get("machines") or []
        engine_costs = (view.get("costs") or {}).get("engine") or {}
        compile_costs = (view.get("costs") or {}).get("compile") or {}
        return {
            "machines": machines_n,
            "load": load,
            "view_scrape_ms": round(view_ms, 2),
            "export_scrape_ms": round(export_ms, 2),
            "export_valid": not tel.validate_layout_input(doc),
            "export_machines": len(doc.get("machines") or ()),
            "warehouse": warehouse,
            "tracked_machines": len(top),
            "hot_rate_1m": (top[0].get("rates") or {}).get("1m")
            if top else None,
            "rungs": {
                rung: {
                    "device_bytes": entry.get("device_bytes"),
                    "requests": entry.get("requests"),
                }
                for rung, entry in (
                    engine_costs.get("rungs") or {}
                ).items()
            },
            "host_cache_bytes": (
                engine_costs.get("host_cache") or {}
            ).get("bytes"),
            "compile_seconds_total": compile_costs.get("seconds_total"),
            "headlines": {
                "rps": load.get("rps"),
                "view_scrape_ms": round(view_ms, 2),
                "export_scrape_ms": round(export_ms, 2),
                "warehouse_bytes": warehouse.get("bytes"),
                "warehouse_records": records,
                "bytes_per_record": (
                    round(warehouse.get("bytes", 0) / records, 1)
                    if records else None
                ),
                "tracked_machines": len(top),
                "export_valid": not tel.validate_layout_input(doc),
            },
        }
    finally:
        if tier is not None:
            tier.close()
        traffic_mod.ACCOUNTANT.reset()
        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value
        shutil.rmtree(root, ignore_errors=True)


def measure_layout() -> dict:
    """Fleet layout compiler block (ISSUE 19, ARCHITECTURE §27): the
    name-hash vs computed-plan A/B on one skewed-Zipf fleet through the
    real 2-worker router tier —

    - measured p99 under the identical seeded Zipf schedule before and
      after the plan is applied live (committed as ``FleetSpec.layout``
      and converged through the reconciler's weights + ``/layout``
      seams — the same path production takes);
    - megabatch residency hit rate per phase: the mega-path share of
      ``gordo_engine_requests_total``, i.e. what the plan's
      expected-hit-rate pins actually bought vs 2-hit LRU promotion;
    - projected machines-per-GiB at the 0.02 parity budget (the §19
      ladder byte ratios applied to the measured per-rung cost
      ledger), computed plan vs name-hash baseline;
    - plan provenance: fingerprint, ring weights, move count, and the
      compiler's own cost block.

    Env: BENCH_SERVE_LAYOUT=0 skips; GORDO_LAYOUT_BENCH_MACHINES (48)
    and GORDO_LAYOUT_BENCH_SECONDS (5) size the run."""
    import shutil
    import tempfile

    import requests

    from gordo_components_tpu.layout import compiler as layout_compiler
    from gordo_components_tpu.observability import traffic as traffic_mod
    from tools import capacity_harness as ch

    machines_n = int(os.environ.get("GORDO_LAYOUT_BENCH_MACHINES", "48"))
    seconds = float(os.environ.get("GORDO_LAYOUT_BENCH_SECONDS", "5"))
    residency_cap = 4  # partial residency, so pins have slots to steer
    saved = {
        k: os.environ.get(k)
        for k in ("GORDO_TELEMETRY", "GORDO_TELEMETRY_INTERVAL",
                  "GORDO_FLEET_INTERVAL", "GORDO_FLEET_COOLDOWN",
                  "GORDO_FLEET_REPAIR_BUDGET",
                  "GORDO_MEGABATCH_RESIDENCY", "GORDO_LAYOUT_REDERIVE")
    }
    os.environ["GORDO_TELEMETRY"] = "1"
    os.environ["GORDO_TELEMETRY_INTERVAL"] = "0"
    os.environ["GORDO_FLEET_INTERVAL"] = "0.2"
    os.environ["GORDO_FLEET_COOLDOWN"] = "0"
    os.environ["GORDO_FLEET_REPAIR_BUDGET"] = "8"
    os.environ["GORDO_MEGABATCH_RESIDENCY"] = str(residency_cap)
    # the A/B authors its own plan; staleness re-derive would replace
    # it mid-measurement
    os.environ["GORDO_LAYOUT_REDERIVE"] = "0"
    root = tempfile.mkdtemp(prefix="gordo-bench-layout-")
    tier = None
    session = requests.Session()

    def mega_share(mark: dict) -> tuple:
        """(mega-path request share since ``mark``, fresh totals) from
        the workers' gordo_engine_requests_total counters."""
        totals: dict = {}
        for spec in tier.router.supervisor.specs.values():
            body = session.get(
                f"{spec.base_url}/metrics", timeout=30
            ).json()
            series = (
                body.get("registry", {})
                .get("gordo_engine_requests_total", {})
                .get("series", {})
            )
            for label, count in series.items():
                totals[label] = totals.get(label, 0.0) + count
        delta = {
            label: count - mark.get(label, 0.0)
            for label, count in totals.items()
        }
        requests_total = sum(delta.values())
        mega = sum(
            count for label, count in delta.items()
            if 'path="mega"' in label
        )
        share = mega / requests_total if requests_total > 0 else None
        return share, totals

    try:
        ch.generate_fleet(root, machines_n)
        machines = sorted(
            name for name in os.listdir(root)
            if name.startswith("cap-")
        )
        # all-eager boot: the A/B measures placement economics, not the
        # spill tier
        tier = ch.RouterTier(root, n_workers=2, eager=machines_n)
        tier.warm(machines)
        # unmeasured shape warm (fused widths + promotions), then reset
        # accounting so the export sees only the measured baseline
        ch.run_load(tier.base_url, machines, min(3.0, seconds), threads=6)
        traffic_mod.ACCOUNTANT.reset()
        traffic_mod.ACCOUNTANT.tick()

        share_baseline, mark = mega_share({})
        load_baseline = ch.run_load(
            tier.base_url, machines, seconds, threads=6,
        )
        share_baseline, mark = mega_share(mark)

        doc = session.get(
            f"{tier.base_url}/telemetry",
            params={"window": "10m", "view": "export"}, timeout=30,
        ).json()
        plan = layout_compiler.compile_plan(
            doc, residency_cap=residency_cap,
        )
        budgeted = layout_compiler.compile_plan(
            doc, residency_cap=residency_cap, parity_budget=0.02,
        )
        committed = session.post(
            f"{tier.base_url}/fleet/apply", json={"layout": plan},
            timeout=30,
        ).json()
        converged = False
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            session.get(f"{tier.base_url}/fleet", timeout=300)
            diff = session.get(
                f"{tier.base_url}/fleet/diff", timeout=300
            ).json()
            if diff.get("divergences") == []:
                converged = True
                break
            time.sleep(0.25)

        _, mark = mega_share({})  # re-mark: converge traffic excluded
        load_plan = ch.run_load(
            tier.base_url, machines, seconds, threads=6,
        )
        share_plan, _ = mega_share(mark)

        gib_baseline = budgeted["cost"]["baseline"]["machines_per_gib"]
        gib_plan = budgeted["cost"]["plan"]["machines_per_gib"]
        return {
            "machines": machines_n,
            "fingerprint": plan["fingerprint"],
            "committed": bool(committed.get("committed")),
            "converged": converged,
            "weights": plan["weights"],
            "moves": len(plan["moves"]),
            "cost": plan["cost"],
            "baseline": load_baseline,
            "plan": load_plan,
            "residency_hit_rate": {
                "baseline": round(share_baseline, 4)
                if share_baseline is not None else None,
                "plan": round(share_plan, 4)
                if share_plan is not None else None,
            },
            "machines_per_gib": {
                "baseline": gib_baseline,
                "plan": gib_plan,
                "parity_budget": 0.02,
                "downgraded": len(budgeted["precision"]),
            },
            "headlines": {
                "p99_ms_baseline": load_baseline.get("p99_ms"),
                "p99_ms_plan": load_plan.get("p99_ms"),
                "hit_rate_baseline": round(share_baseline, 4)
                if share_baseline is not None else None,
                "hit_rate_plan": round(share_plan, 4)
                if share_plan is not None else None,
                "machines_per_gib_baseline": gib_baseline,
                "machines_per_gib_plan": gib_plan,
                "moves": len(plan["moves"]),
                "converged": converged,
            },
        }
    finally:
        if tier is not None:
            tier.close()
        traffic_mod.ACCOUNTANT.reset()
        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value
        shutil.rmtree(root, ignore_errors=True)


def main() -> None:
    from gordo_components_tpu.utils.backend import (
        enable_persistent_compile_cache,
        require_accelerator,
    )

    on_cpu = require_accelerator("bench_serving.py").platform == "cpu"
    enable_persistent_compile_cache()

    # SLO watch brackets the whole run: the baseline sample lands before
    # the first measured request, so end-of-run burn rates attribute to
    # THIS run's traffic (guarded — the watch must never cost a run)
    try:
        slo_watch = begin_slo_watch()
    except Exception:
        slo_watch = None
    result = measure(**resolve_sizes())
    # the two blocks below boot real `gordo run-server` subprocesses. A
    # chip belongs to one process, and this one has just executed on it:
    # the workers could not get the device, so on a chip the blocks are
    # named as not run instead of failing or measuring CPU workers
    needs_own_process = "not run: needs its own process"
    # horizontal serving tier: 1 vs N worker PROCESSES behind the router
    # at 12-thread saturation (the only block measuring true
    # multi-process concurrency; BENCH_SERVE_MULTIWORKER=0 skips it)
    if os.environ.get("BENCH_SERVE_MULTIWORKER", "1") == "1":
        result["multi_worker"] = (
            measure_multi_worker() if on_cpu else needs_own_process
        )
    # multi-host mesh serving: 1 un-meshed worker vs N process shards of
    # the same fleet at saturation — the §23 layout headline
    # (BENCH_SERVE_MULTIHOST=0 skips it)
    if os.environ.get("BENCH_SERVE_MULTIHOST", "1") == "1":
        result["multihost"] = (
            measure_multihost() if on_cpu else needs_own_process
        )
    # closed-loop autopilot A/B: the shifting ramp→spike→idle mix at
    # hand-set defaults vs with the controller turning depth/fill live
    # (ISSUE 12; BENCH_SERVE_AUTOPILOT=0 skips it)
    if os.environ.get("BENCH_SERVE_AUTOPILOT", "1") == "1":
        result["autopilot"] = measure_autopilot()
    # fleet-scale capacity: the §22 before/after numbers (index boot,
    # spill tier, incremental ring, bounded scrape) from a 10k-machine
    # synthetic fleet through the real router tier (ISSUE 14;
    # BENCH_SERVE_CAPACITY=0 skips — it takes ~5 minutes)
    if os.environ.get("BENCH_SERVE_CAPACITY", "1") == "1":
        result["capacity"] = measure_capacity()
    # telemetry warehouse: scrape latency, warehouse write economy,
    # sketch coverage, and the cost-ledger headline at a shaped Zipf
    # load (ISSUE 16, §24; BENCH_SERVE_TELEMETRY=0 skips it)
    if os.environ.get("BENCH_SERVE_TELEMETRY", "1") == "1":
        result["telemetry"] = measure_telemetry()
    # fleet layout compiler A/B: the same skewed-Zipf schedule under
    # the name-hash ring vs the live-applied computed plan — measured
    # p99, megabatch residency hit rate, and projected machines-per-GiB
    # at the parity budget (ISSUE 19, §27; BENCH_SERVE_LAYOUT=0 skips)
    if os.environ.get("BENCH_SERVE_LAYOUT", "1") == "1":
        result["layout"] = measure_layout()
    # the run's own engine telemetry (program cache, compile/dispatch
    # histograms) rides along — same block bench.py embeds
    from gordo_components_tpu.observability.registry import REGISTRY

    result["metrics"] = REGISTRY.snapshot()
    # objective attainment + burn rates at end of run (§18): the
    # serving history now says not just how fast, but whether the run
    # MET its declared latency/availability objectives
    try:
        result["slo"] = end_slo_watch(slo_watch)
    except Exception:
        pass
    # one attributable history row per standalone run: explicit BENCH_*
    # overrides AND the resolved knobs (dispatch depth, device, shard
    # mode, wire formats) that shaped the numbers. The whole block is
    # guarded — assembling the row (effective_env touches jax) must
    # never cost a completed run its artifact print below.
    try:
        append_history({
            "metric": "serving_p50_ms",
            "env": {
                k: os.environ[k]
                for k in ("BENCH_SERVE_MACHINES", "BENCH_SERVE_ROWS",
                          "BENCH_SERVE_TAGS", "BENCH_SERVE_REQUESTS",
                          "BENCH_SERVE_SHARD",
                          "BENCH_SERVE_MESH_SHARDS",
                          "BENCH_SERVE_MESH_MACHINES",
                          "GORDO_DISPATCH_DEPTH", "GORDO_MEGABATCH",
                          "GORDO_FILL_WINDOW_US",
                          "GORDO_MEGABATCH_RESIDENCY")
                if k in os.environ
            },
            "effective": effective_env(),
            "value": result.get("value"),
            "end_to_end_p50_ms": result.get("end_to_end_p50_ms"),
            "end_to_end_p99_ms": result.get("end_to_end_p99_ms"),
            "concurrent_rps": result.get("concurrent_rps"),
            # boot economics headline: compile-on-boot vs load-on-boot
            "cold_start": result.get("cold_start"),
            # cross-machine fused-batch stats (the megabatch headline)
            "cross_machine": result.get("cross_machine"),
            # the precision ladder's per-rung rps/parity/capacity (§19)
            "precision": result.get("precision"),
            # horizontal tier: 1 vs N worker processes at 12-thread
            # saturation + per-worker fusion ratios (the GIL-escape
            # headline)
            "multi_worker": result.get("multi_worker"),
            # multi-host mesh tier: 1 vs N process shards at saturation
            # + per-shard owned/fallback split (the §23 layout headline)
            "multihost": result.get("multihost"),
            # objective attainment + burn rates at end of run (§18)
            "slo": result.get("slo"),
            # closed-loop controller A/B on the shifting load mix (§20)
            "autopilot": result.get("autopilot"),
            # fleet-scale capacity headlines: §22 before/after numbers
            # (index boot, spill tier, incremental ring, bounded scrape)
            "capacity": (result.get("capacity") or {}).get("headlines"),
            # telemetry warehouse headlines: scrape cost, write
            # economy, sketch coverage, export validity (§24)
            "telemetry": (result.get("telemetry") or {}).get("headlines"),
            # layout compiler A/B headlines: name-hash vs computed plan
            # on p99 / residency hit rate / machines-per-GiB (§27)
            "layout": (result.get("layout") or {}).get("headlines"),
        })
    except Exception:
        pass  # history is never worth failing an artifact over
    print(json.dumps(result))


if __name__ == "__main__":
    main()
