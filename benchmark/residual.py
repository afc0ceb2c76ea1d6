"""The check's arithmetic: the reference solve testers' backward error in
float64 on the host, copied from the repository's ``chip_smoke.py``
(``_norm_inf``, ``_solve_error``, ``_sample``) so that no later change to
the program can move the yardstick.

    ||B_S - A_S X||_inf / (n ||A||_inf ||X||_inf)

over a seeded sample S of rows (the whole of A where it is small).  The
sampled figure is a lower bound of the full one, but every entry of X
enters every sampled row, so a wrong entry anywhere in X shows.
"""

from __future__ import annotations

import numpy as np

SAMPLE_ROWS = 512


def norm_inf(m: np.ndarray) -> float:
    return float(np.abs(m).sum(axis=1).max())


def solve_error(a_rows, b_rows, x, a_norm: float, n: int) -> float:
    x = np.asarray(x, np.float64)
    r = np.asarray(b_rows, np.float64) - np.asarray(a_rows, np.float64) @ x
    denom = n * a_norm * norm_inf(x)
    if not np.isfinite(denom) or denom == 0.0:
        return float("inf")
    return norm_inf(r) / denom


def sample_rows(n: int, seed, k: int = SAMPLE_ROWS) -> np.ndarray:
    return np.sort(np.random.default_rng(seed).choice(n, size=min(k, n), replace=False))
