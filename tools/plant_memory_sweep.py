"""Plant-scale HBM prediction sweep (VERDICT r3 #3).

Config 5 (``plant_10ktag_bf16``) has never executed anywhere: CPU is
measured-impractical. To keep the first real TPU run from burning scarce
chip time discovering an OOM, this
sweep compiles the EXACT fleet training program (``fleet_executable`` —
the program fleet-build runs) across tag scales on the CPU backend and reads
XLA's own ``memory_analysis()`` of each compiled executable: argument +
output + temp bytes. Nothing executes — compile + static analysis only —
so plant-shape compiles finish in seconds-to-minutes even though running
them on CPU takes hours.

What the first run of this sweep found (2026-07-30, r4):

- peak temp is ONE training step's fwd+bwd activations and scales
  linearly in tags AND in batch size: ~4.1 GiB per 1k tags at the old
  batch_size=64 → ~41 GiB at 10k tags, 2.6x over v5e's 16 GB HBM. The
  plant config as shipped in rounds 2-3 would have OOMed on first
  contact.
- ``remat`` is provably applied (the StableHLO carries the recompute +
  optimization barriers) but XLA:CPU's buffer assignment does not
  exploit it — temp is unchanged. Remat savings are a TPU-only effect
  and CANNOT be measured here; and even on TPU, remat alone cannot fix
  the plant config, because recomputing a single layer's internals also
  scales with tags (~1.6 GiB/1k tags).
- the lever that measurably works is BATCH SIZE: temp is linear in
  B x F, so batch_size 64 → 16 cuts the step peak 4x (measured, not
  inferred). The plant config below ships batch_size=16.

Caveats, recorded with the numbers:
- the XLA:CPU partitioner's buffer assignment is not the TPU's; treat
  the extrapolation as an estimate with the fitted residual as its
  error bar. Measured here: CPU stores the bf16 model's activations as
  f32 (the f32 build compiles to slightly LESS temp than bf16), so the
  CPU number is a conservative ~2x ceiling on the TPU-bf16 peak;
- ``attention_impl="dense"`` stands in for "flash" (a Pallas kernel
  compiled in CPU interpret mode reports interpreter buffers, not the
  TPU kernel's VMEM tiles). With 7 patches per window the attention
  internals are noise; dense is a strict upper bound on flash;
- everything else is the plant config below: bf16 compute, remat,
  n_splits=1, rows=384, epochs 3.

Outputs a JSON line (and a human table on stderr) with per-scale bytes
for batch sizes {64, 16}, least-squares linear fits bytes(tags), the
10k-tag predictions ± max fit residual, and the v5e HBM headroom check.
"""

from __future__ import annotations

import json
import os
import sys
import time

# CPU-pin BEFORE jax is imported (it reads JAX_PLATFORMS then): this sweep
# is a CPU-only static analysis by design
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)  # the package is not pip-installed
import jax  # noqa: E402

import numpy as np  # noqa: E402

V5E_HBM_BYTES = 16 * 2**30


# the plant-scale machine: a PatchTST autoencoder over 10k tags that asks
# for remat. batch_size=16, NOT 64: the step peak is linear in batch x tags
# — B=64 needs ~41 GiB at 10k tags (2.6x v5e HBM, guaranteed OOM); B=16
# fits with headroom (module docstring).
PLANT_ESTIMATOR = {
    "kind": "patchtst",
    "lookback_window": 32,
    "d_model": 64,
    "n_layers": 2,
    "epochs": 3,
    "batch_size": 16,
    "compute_dtype": "bfloat16",
    "attention_impl": "flash",
    "remat": True,
}


def plant_model(batch_size: int, remat: bool = True):
    """The plant config as a model definition, with the sweep's overrides:
    ``batch_size`` is the swept lever, and ``attention_impl`` becomes
    "dense" (see module docstring caveat on interpret-mode Pallas)."""
    estimator = dict(
        PLANT_ESTIMATOR,
        batch_size=batch_size,
        attention_impl="dense",
        remat=remat,
    )
    return {
        "DiffBasedAnomalyDetector": {
            "base_estimator": {
                "TransformedTargetRegressor": {
                    "regressor": {
                        "Pipeline": {
                            "steps": [
                                "MinMaxScaler",
                                {"PatchTSTAutoEncoder": estimator},
                            ]
                        }
                    },
                    "transformer": "MinMaxScaler",
                }
            }
        }
    }


def compiled_bytes(
    tags: int, batch_size: int, remat: bool = True, rows: int = 384
) -> dict:
    """Compile the 1-machine fleet program at this scale; return XLA's
    buffer-assignment byte counts (no execution)."""
    from gordo_components_tpu.parallel.build_fleet import (
        _analyze_model,
        _spec_for,
    )
    from gordo_components_tpu.parallel.fleet import fleet_executable
    from gordo_components_tpu.serializer import pipeline_from_definition

    probe = pipeline_from_definition(plant_model(batch_size, remat))
    spec = _spec_for(_analyze_model(probe), tags, tags, n_splits=1)
    started = time.perf_counter()
    compiled, _ = fleet_executable(spec, 1, rows, tags, tags)
    compile_s = time.perf_counter() - started
    ma = compiled.memory_analysis()
    return {
        "tags": tags,
        "batch_size": batch_size,
        "remat": remat,
        "compile_s": round(compile_s, 1),
        "arg_bytes": int(ma.argument_size_in_bytes),
        "out_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "total_bytes": int(
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
        ),
    }


def linear_fit_predict(scales, totals, target: int):
    """Least-squares bytes(tags) = a*tags + b; returns the prediction at
    ``target`` tags and the max |residual| over the fitted points as the
    error bar."""
    a, b = np.polyfit(np.asarray(scales, float), np.asarray(totals, float), 1)
    residuals = [abs(a * s + b - t) for s, t in zip(scales, totals)]
    return float(a * target + b), float(max(residuals)), float(a), float(b)


def main() -> None:
    scales = [
        int(s)
        for s in os.environ.get("SWEEP_TAGS", "1000,2000,4000").split(",")
    ]
    batch_sizes = [
        int(b) for b in os.environ.get("SWEEP_BATCH", "64,16").split(",")
    ]
    target = int(os.environ.get("SWEEP_TARGET", "10000"))
    rows_by = {}
    for batch_size in batch_sizes:
        for tags in scales:
            row = compiled_bytes(tags, batch_size)
            rows_by[(tags, batch_size)] = row
            sys.stderr.write(
                f"tags={tags:>6} B={batch_size:<3}  "
                f"total={row['total_bytes'] / 2**30:7.3f} GiB  "
                f"(temp {row['temp_bytes'] / 2**30:.3f})  "
                f"compile {row['compile_s']}s\n"
            )
            sys.stderr.flush()

    out = {"scales": scales, "rows": list(rows_by.values())}
    for batch_size in batch_sizes:
        totals = [rows_by[(s, batch_size)]["total_bytes"] for s in scales]
        pred, err, slope, _ = linear_fit_predict(scales, totals, target)
        key = f"b{batch_size}"
        out[f"predicted_{target}tag_gib_{key}"] = round(pred / 2**30, 3)
        out[f"fit_err_gib_{key}"] = round(err / 2**30, 3)
        out[f"bytes_per_tag_{key}"] = round(slope, 1)
        # the CPU-f32 number is the conservative ceiling; TPU-bf16 stores
        # activations natively and lands ~half of it
        out[f"fits_v5e_hbm_cpu_bound_{key}"] = bool(
            pred + err < V5E_HBM_BYTES
        )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
