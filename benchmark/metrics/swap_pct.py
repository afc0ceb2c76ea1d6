"""Share of the traced window a chip spent in device ops under the
``swap`` phase scope (the pivoted factor's row interchanges), mean over
the chips.  Nothing to read where the trace carries no scopes or no op
under ``swap``."""


def read(run):
    scopes = (run.trace or {}).get("scopes")
    if not scopes or not scopes["phase_s"].get("swap"):
        return None
    return 100.0 * scopes["phase_s"]["swap"] / scopes["window_s"]
