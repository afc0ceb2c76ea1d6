"""The factor's least time over the device self time under the ``bulk``
phase scope (the trailing updates), mean over the chips.

Least time is that of every call served in the traced window: the
factor's LAPACK Working Note 41 flops (potrf for posv, getrf for gesv;
``benchmark/flops.py``) over the bfloat16 peak, or the operand read and
written once over HBM bandwidth, whichever is larger, as
``call_roofline`` counts it.  The trailing update does all of the
factor's flops but a share of about nb / n (under 2.5 % at the cells' n),
so the reading runs above the update's true share by at most that.
Nothing to read where the trace carries no scopes or no op under
``bulk``."""

from benchmark import flops
from benchmark.flops import least_seconds

FACTOR = {"posv": flops.potrf, "gesv": flops.getrf}


def read(run):
    scopes = (run.trace or {}).get("scopes")
    op = run.cell.config["op"]
    if not scopes or not scopes["phase_s"].get("bulk") or op not in FACTOR:
        return None
    n, itemsize = run.cell.traffic["n"], run.cell.dtype.itemsize
    work = run.cell.problems * FACTOR[op](n)
    nbytes = run.cell.problems * 2.0 * n * n * itemsize
    peak, bw = run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"]
    least = sum(least_seconds(work, nbytes, peak, bw, run.chips) for c in run.calls if c.ok)
    return 100.0 * least / scopes["phase_s"]["bulk"]
