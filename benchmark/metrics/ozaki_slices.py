"""Mean slice count of the int8 Ozaki path over the window's good calls,
from the count each call returns beside its product
(``operations/gemm.py``).  Nothing to read where the answers carry no
count."""

import numpy as np


def read(run):
    counts = getattr(run.cell.operation, "slice_counts", None)
    got = [s for c in run.calls if c.ok for s in counts(c.x)] if counts else []
    return float(np.mean(got)) if got else None
