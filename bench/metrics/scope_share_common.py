"""Device seconds a chip under one named scope (``run["trace"]["scopes"]``,
the cell file's ``scopes``) over the traced window; ``None`` where the
trace has no such reading or the scope ran nothing."""


def scope_share(run, scope):
    tr = run.get("trace") or {}
    seconds = (tr.get("scopes") or {}).get(scope)
    if not seconds or not tr.get("window_s"):
        return None
    return seconds / tr["window_s"]
