"""Plain reference for fetch + assembly: raw points -> the joined ``X``.

Independent of the program's ``join_timeseries``: numpy only. Semantics as
the configuration states them: mean per ``resolution`` bin (bins anchored at
the start date, closed on the left), linear interpolation across empty bins
(at most ``interpolation_limit`` of them in a row), rows with any tag missing
dropped (inner join), float32 at the end.
"""

from __future__ import annotations

from typing import List

import numpy as np

from benchmarks.data.series import raw_series


def assemble(
    tag_names: List[str], seed: int, start_ns: int, end_ns: int,
    resolution_ns: int, min_size: int, max_size: int,
    interpolation_limit_bins: int,
) -> np.ndarray:
    n_bins = -(-(end_ns - start_ns) // resolution_ns)
    grid = np.arange(n_bins, dtype=np.float64)
    columns = []
    for name in tag_names:
        t_ns, values = raw_series(name, seed, start_ns, end_ns, min_size, max_size)
        bins = (t_ns - start_ns) // resolution_ns
        filled = np.flatnonzero(np.bincount(bins, minlength=n_bins))
        sums = np.bincount(bins, weights=values, minlength=n_bins)
        counts = np.bincount(bins, minlength=n_bins)
        means = sums[filled] / counts[filled]
        if np.max(np.diff(filled), initial=1) - 1 > interpolation_limit_bins:
            raise ValueError(
                f"tag {name}: a gap longer than the interpolation limit; the "
                "plain reference does not model partly filled gaps"
            )
        column = np.interp(grid, filled.astype(np.float64), means)
        # the resampled frame of a tag spans its first to its last filled bin
        column[: filled[0]] = np.nan
        column[filled[-1] + 1 :] = np.nan
        columns.append(column)
    joined = np.stack(columns, axis=1)
    joined = joined[~np.isnan(joined).any(axis=1)]
    return joined.astype(np.float32)
