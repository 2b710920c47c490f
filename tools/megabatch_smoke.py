#!/usr/bin/env python
"""Megabatch smoke: the cross-machine fused-dispatch gates on the CPU
backend (``make megabatch-smoke``).

Checks (ISSUE 7 acceptance):

- **fused == per-machine, bit-identical**: the megabatch program and the
  cold gather-by-idx program produce byte-identical outputs for the SAME
  batch (machines, inputs, batch size), at every batch size the smoke
  drives, and a megabatch-on engine's sequential scores are byte-identical
  to a megabatch-off engine's. (Across different coalesced batch SIZES
  float accumulation order may differ ~1e-7 — a pre-existing property of
  cold micro-batching, gated here with allclose.)
- **fusion ratio > 1.5 under concurrent multi-machine load**: 12 client
  threads spread across 8 distinct machines produce FEWER device
  dispatches than requests (requests per fused dispatch > 1.5), with every
  answer matching the per-machine reference, and the fill window's
  timeout/size counters accounting for every fused dispatch window.
- **fallback honesty**: shard-mode engines report megabatching disabled
  (the fallback row of the ARCHITECTURE §15 table) and still serve.

Exit codes: 0 = all checks passed, 1 = at least one failed.
"""

from __future__ import annotations

import os
import sys
from concurrent.futures import ThreadPoolExecutor

# runnable straight from a checkout (python tools/megabatch_smoke.py)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# 8 virtual devices so the shard-mode fallback check exercises a real mesh
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_failures = []


def check(ok: bool, what: str) -> None:
    print(f"  {'ok' if ok else 'FAIL'}: {what}")
    if not ok:
        _failures.append(what)


def _bits(result) -> tuple:
    import numpy as np

    return tuple(
        np.asarray(a).tobytes()
        for a in (result.model_input, result.model_output,
                  result.tag_anomaly_scores, result.total_anomaly_score)
    )


def fused_path_bit_identity(models, X) -> None:
    import jax
    import numpy as np

    from gordo_components_tpu.server.engine import ServingEngine

    print("\n[1/3] fused path == per-machine path, bit-identical")
    reference = ServingEngine(models, megabatch=False)
    names = reference.machines()
    ref_bits = {n: _bits(reference.anomaly(n, X)) for n in names}
    reference.close()

    engine = ServingEngine(models, fill_window_us=0)
    check(engine.megabatch, "megabatching on by default (replicated)")
    # sequential requests ride singleton fused dispatches: byte parity
    # with the megabatch-off engine across the whole fleet
    same = all(_bits(engine.anomaly(n, X)) == ref_bits[n] for n in names)
    check(same, "sequential fused scores byte-identical to megabatch-off")
    engine.quiesce()
    check(engine.stats()["megabatch"]["requests"] >= len(names),
          "sequential requests served via the fused program")

    # matched-batch parity at every coalescible batch size: the honest
    # fused-vs-cold claim (identical machines, inputs, AND batch size)
    bucket, _ = engine._by_name[names[0]]
    x_padded, _ = engine._prepare(bucket, X)
    rows = x_padded.shape[0]
    for k in (1, 2, 4, 8):
        idxs = np.asarray([i % len(names) for i in range(k)], np.int32)
        xs = np.stack([x_padded] * k)
        cold = jax.device_get(
            bucket._program(rows, k)(bucket.stacked, idxs, xs)
        )
        fused = jax.device_get(
            bucket._mega_program(rows, k)(bucket.stacked, idxs, xs)
        )
        same = all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(cold, fused)
        )
        check(same, f"k={k}: fused program byte-identical to cold program")
    engine.close()


def concurrent_fusion_ratio(models, X) -> None:
    import numpy as np

    from gordo_components_tpu.server.engine import ServingEngine

    print("\n[2/3] fusion ratio > 1.5 at 12 threads across 8 machines")
    reference = ServingEngine(models, megabatch=False)
    names = reference.machines()
    ref = {n: reference.anomaly(n, X) for n in names}
    reference.close()

    engine = ServingEngine(models, fill_window_us=3000)
    engine.warmup()
    engine.quiesce()
    before = engine.stats()
    workers, per_thread = 12, 12
    spread = names[:8]
    failures = []

    def one(t: int) -> None:
        for i in range(per_thread):
            name = spread[(t + i) % len(spread)]
            scored = engine.anomaly(name, X)
            for a, b in zip(scored, ref[name]):
                if not np.allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
                ):
                    failures.append(name)
                    return

    with ThreadPoolExecutor(max_workers=workers) as pool:
        list(pool.map(one, range(workers)))
    engine.quiesce()
    check(not failures,
          f"every concurrent answer matches the per-machine reference "
          f"(mismatches: {sorted(set(failures))})")
    after = engine.stats()
    requests = after["batched_requests"] - before["batched_requests"]
    dispatches = after["dispatches"] - before["dispatches"]
    mb = after["megabatch"]
    mega_requests = mb["requests"] - before["megabatch"]["requests"]
    mega_dispatches = mb["dispatches"] - before["megabatch"]["dispatches"]
    ratio = mega_requests / mega_dispatches if mega_dispatches else 0.0
    check(dispatches < requests,
          f"fused dispatch count < request count "
          f"({dispatches} dispatches for {requests} requests)")
    check(ratio > 1.5,
          f"fusion ratio > 1.5 (got {ratio:.2f} = "
          f"{mega_requests}/{mega_dispatches})")
    fills = mb["fill_timeout_total"] + mb["fill_size_total"]
    check(fills > 0,
          f"fill windows engaged under load (timeout "
          f"{mb['fill_timeout_total']}, size {mb['fill_size_total']})")
    check(mb["resident_machines"] == len(names),
          f"all {len(names)} machines resident in the stacked program")
    print(f"  [info] fusion ratio {ratio:.2f}, "
          f"{mega_dispatches} fused dispatches / {mega_requests} requests, "
          f"residency {mb['resident_machines']}/{mb['residency_cap']}")
    engine.close()


def shard_mode_falls_back(models, X) -> None:
    from gordo_components_tpu.parallel.mesh import fleet_mesh
    from gordo_components_tpu.server.engine import ServingEngine

    print("\n[3/3] shard mode falls back to the per-machine paths")
    engine = ServingEngine(models, mesh=fleet_mesh(8))
    stats = engine.stats()["megabatch"]
    check(not stats["enabled"], "megabatch reports disabled in shard mode")
    check(stats["fill_window_us"] == 0, "no fill window in shard mode")
    name = engine.machines()[0]
    scored = engine.anomaly(name, X)
    check(scored.total_anomaly_score.shape[0] > 0,
          "shard engine serves through the per-machine path")
    check(engine.stats()["megabatch"]["requests"] == 0,
          "no fused dispatches in shard mode")
    engine.close()


def main() -> int:
    import numpy as np

    from gordo_components_tpu.models.synthetic_fleet import build_models

    print("megabatch smoke: fused-path bit-identity + cross-machine "
          "fusion ratio + fallback honesty")
    models = build_models(8, 64, 4)
    X = np.random.default_rng(23).normal(size=(64, 4)).astype(np.float32)
    X = X * 2 + 4
    fused_path_bit_identity(models, X)
    concurrent_fusion_ratio(models, X)
    shard_mode_falls_back(models, X)
    if _failures:
        print(f"\nMEGABATCH SMOKE FAILED: {len(_failures)} check(s)",
              file=sys.stderr)
        return 1
    print("\nmegabatch smoke passed: fused dispatches are bit-identical "
          "to the per-machine path, concurrent cross-machine load fuses "
          "well past the 1.5x gate, and shard mode falls back cleanly")
    return 0


if __name__ == "__main__":
    sys.exit(main())
