"""The one generator of the benchmark's traffic: seeded synthetic sensor series.

A copy of the semantics of the program's ``RandomDataProvider`` (600-900 raw
points per tag over the date range: a smooth random walk plus a sinusoid plus
an offset). numpy only: the provider (``provider.py``) wraps it for the
program, and the plain reference (``benchmarks/reference/data.py``) reads the
same raw points without importing anything of the program.
"""

from __future__ import annotations

import hashlib
from typing import Tuple

import numpy as np


def tag_seed(tag_name: str, seed: int) -> int:
    digest = hashlib.md5(tag_name.encode()).digest()
    return (int.from_bytes(digest[:8], "little") ^ int(seed)) & (2**63 - 1)


def raw_series(
    tag_name: str, seed: int, start_ns: int, end_ns: int,
    min_size: int, max_size: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """``(timestamps in ns since the epoch, float64 values)`` of one tag:
    ``n`` points evenly spaced over the half-open ``[start, end)``."""
    rng = np.random.default_rng(tag_seed(tag_name, seed))
    n = int(rng.integers(min_size, max_size + 1))
    t_ns = start_ns + np.arange(n, dtype=np.int64) * ((end_ns - start_ns) // n)
    phase = np.linspace(0.0, 8.0 * np.pi, n)
    values = (
        np.cumsum(rng.normal(scale=0.1, size=n))
        + np.sin(phase + rng.uniform(0, 2 * np.pi))
        + rng.uniform(-5, 5)
    )
    return t_ns, values.astype(np.float64)
