"""Least time of the int8 products of the calls served in the traced
window over the chips' busy time: for each good call S(S+1)/2 * 2 m n k
operations at the slice count S it returns (``operations/gemm.py``),
over ``peaks.json``'s ``int8_ops``.  Nothing to read without a trace or
where the answers carry no count."""


def read(run):
    op = run.cell.operation
    if run.trace is None or not hasattr(op, "int8_ops"):
        return None
    work = [op.int8_ops(run.cell.traffic, s) for c in run.calls if c.ok for s in op.slice_counts(c.x)]
    if not work:
        return None
    return 100.0 * sum(work) / (run.peaks["int8_ops"] * run.chips) / run.trace["busy_s"]
