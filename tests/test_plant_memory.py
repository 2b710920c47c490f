"""Plant-scale memory prediction (VERDICT r3 #3).

Compile-only static analysis of the exact fleet program at growing tag
counts, so the 10k-tag plant config's HBM fit is a measured prediction
with error bars instead of a hope — and the first real TPU run can't burn
scarce chip time discovering an OOM. See tools/plant_memory_sweep.py
for the full sweep + what it found (r4: the old batch_size=64 plant
config needed ~41 GiB — guaranteed OOM on a 16 GB v5e; batch_size is the
lever that measurably works, remat savings being invisible to XLA:CPU's
buffer assignment).
"""

import os
import sys
from pathlib import Path

import pytest

_REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO_ROOT / "tools"))

V5E_HBM = 16 * 2**30


@pytest.mark.slow
def test_plant_memory_linear_and_fits_v5e():
    from plant_memory_sweep import compiled_bytes, linear_fit_predict

    # two points suffice for the linearity + prediction checks while
    # keeping this test's compile budget ~1-2 min
    scales = [500, 1000]
    b64 = {s: compiled_bytes(s, batch_size=64) for s in scales}
    b16 = {s: compiled_bytes(s, batch_size=16) for s in scales}

    # 1) temp is linear in tags: doubling tags ~doubles the total
    for rows in (b64, b16):
        ratio = rows[1000]["total_bytes"] / rows[500]["total_bytes"]
        assert 1.8 < ratio < 2.2, ratio

    # 2) the batch-size lever works as measured in r4: B=64 -> B=16 cuts
    # the peak ~4x (the step fwd+bwd dominates and is linear in B x F)
    shrink = b64[1000]["total_bytes"] / b16[1000]["total_bytes"]
    assert 3.0 < shrink < 5.0, shrink

    # 3) extrapolated to the plant target, the SHIPPED config (B=16) fits
    # v5e HBM even under the conservative CPU-f32 ceiling, while the old
    # B=64 config provably did not — the regression this test pins
    pred16, err16, _, _ = linear_fit_predict(
        scales, [b16[s]["total_bytes"] for s in scales], 10_000
    )
    pred64, err64, _, _ = linear_fit_predict(
        scales, [b64[s]["total_bytes"] for s in scales], 10_000
    )
    assert pred16 + err16 < V5E_HBM, (
        f"plant config predicted {pred16 / 2**30:.1f} GiB > 16 GiB v5e HBM"
    )
    assert pred64 > V5E_HBM  # documents why batch_size=64 was wrong


@pytest.mark.slow
def test_bench_plant_config_uses_safe_batch_size():
    """The sweep tool's plant config must keep the batch size the sweep
    proved fits; silently bumping it back to 64 re-introduces a guaranteed
    OOM."""
    from plant_memory_sweep import PLANT_ESTIMATOR

    assert PLANT_ESTIMATOR["batch_size"] <= 16
    assert PLANT_ESTIMATOR["remat"] is True
