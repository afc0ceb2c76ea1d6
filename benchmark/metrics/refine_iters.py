"""Mean GMRES steps per call over the window's good calls, from the step
count each call returns beside its answer (``operations/gesv_mxp.py``).
Nothing to read where the answers carry no step count."""

import numpy as np


def read(run):
    steps = [ans[1] for c in run.calls if c.ok for ans in c.x or []
             if isinstance(ans, tuple)]
    return float(np.mean(steps)) if steps else None
