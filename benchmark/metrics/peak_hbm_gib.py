"""The fullest chip's peak HBM, read from the device allocator's
statistics when the window closes, before the check runs: the arrays'
peak (``peak_bytes_in_use``) plus the peak reserved for programs'
temporaries (``peak_bytes_reserved``).  The two peaks may fall at
different times, so the sum is an upper bound of the true peak."""


def read(run):
    return run.peak_bytes / 2**30
