"""95th percentile of per-call time, issue to solution on the host, over
every call in the window (failed ones too)."""

import numpy as np


def read(run):
    return float(np.percentile([c.latency_s for c in run.calls], 95)) * 1e3
