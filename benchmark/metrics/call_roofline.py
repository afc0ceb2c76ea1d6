"""Least time of the calls served in the traced window over the chips'
busy time.  Least time is the larger of tester flops over the peak and
least bytes over HBM bandwidth; float32 work is held to the bfloat16
peak, since no float matmul on the chip runs faster."""

from benchmark.flops import least_seconds


def read(run):
    if run.trace is None:
        return None
    peak, bw = run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"]
    least = sum(least_seconds(c.flops, c.nbytes, peak, bw, run.chips)
                for c in run.calls if c.ok)
    return 100.0 * least / run.trace["busy_s"]
