"""Share of the traced window in which a chip sat idle while the
innermost open host span was one of the Router's (``slate_tpu/serve.*``:
admission, stacking, lookup, dispatch, the info sync, unstacking), mean
over the chips.  Nothing to read where the trace carries no scopes or
no idle time under such a span."""


def read(run):
    scopes = (run.trace or {}).get("scopes")
    if not scopes or not scopes["serve_idle_s"]:
        return None
    return 100.0 * scopes["serve_idle_s"] / scopes["window_s"]
