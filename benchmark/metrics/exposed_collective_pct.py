"""Share of the traced window in which a collective (all-reduce,
collective-permute, all-gather, reduce-scatter, all-to-all) ran on a
chip and no other operation did, mean over the chips.  Nothing to read
where no collective ran."""


def read(run):
    if run.trace is None or run.trace["collective_s"] == 0.0:
        return None
    return 100.0 * run.trace["exposed_collective_s"] / run.trace["window_s"]
