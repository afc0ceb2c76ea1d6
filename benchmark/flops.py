"""Work of one call, as the reference testers count it.

Operation counts are LAPACK Working Note 41's, the forms BLAS++'s
``Gflop<T>`` and SLATE's testers use (real arithmetic: one multiply and
one add each count one; a division or square root counts as a multiply).
They depend on the problem's shape only, never on how the program
computes it, so a share of the roofline built on them moves when the
program gets faster and not when it changes its algorithm.

Each operation module (``operations/<op>.py``) takes its count from
here and states its least bytes: what any implementation must move
through HBM at least once.
"""

from __future__ import annotations


def fmuls_potrf(n: float) -> float:
    return n * (((1.0 / 6.0) * n + 0.5) * n + 1.0 / 3.0)


def fadds_potrf(n: float) -> float:
    return n * (((1.0 / 6.0) * n) * n - 1.0 / 6.0)


def fmuls_potrs(n: float, nrhs: float) -> float:
    return nrhs * n * (n + 1.0)


def fadds_potrs(n: float, nrhs: float) -> float:
    return nrhs * n * (n - 1.0)


def fmuls_getrf(m: float, n: float) -> float:
    if m < n:
        return 0.5 * n * m * m - m**3 / 6.0 + 0.5 * n * m - 0.5 * m * m + 2.0 * m / 3.0
    return 0.5 * m * n * n - n**3 / 6.0 + 0.5 * m * n - 0.5 * n * n + 2.0 * n / 3.0


def fadds_getrf(m: float, n: float) -> float:
    if m < n:
        return 0.5 * n * m * m - m**3 / 6.0 - 0.5 * n * m + m / 6.0
    return 0.5 * m * n * n - n**3 / 6.0 - 0.5 * m * n + n / 6.0


def fmuls_getrs(n: float, nrhs: float) -> float:
    return nrhs * n * n


def fadds_getrs(n: float, nrhs: float) -> float:
    return nrhs * n * (n - 1.0)


def potrf(n: int) -> float:
    return fmuls_potrf(n) + fadds_potrf(n)


def getrf(n: int) -> float:
    return fmuls_getrf(n, n) + fadds_getrf(n, n)


def posv(n: int, nrhs: int) -> float:
    return potrf(n) + fmuls_potrs(n, nrhs) + fadds_potrs(n, nrhs)


def gesv(n: int, nrhs: int) -> float:
    return getrf(n) + fmuls_getrs(n, nrhs) + fadds_getrs(n, nrhs)


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bytes_per_s: float, chips: int) -> float:
    """The roofline's least time: the larger of operations over the
    chips' peak and bytes over their HBM bandwidth."""
    return max(flops / (peak_flops * chips), nbytes / (peak_bytes_per_s * chips))
