"""Share of the traced window a chip spent in device ops under the
``panel`` phase scope (diagonal-block factors, panel solves, the pivot
search), mean over the chips.  Nothing to read where the trace carries
no scopes or no op under ``panel``."""


def read(run):
    scopes = (run.trace or {}).get("scopes")
    if not scopes or not scopes["phase_s"].get("panel"):
        return None
    return 100.0 * scopes["phase_s"]["panel"] / scopes["window_s"]
