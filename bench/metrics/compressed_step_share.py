"""Share of the window's engine steps dispatched through the compressed
collective variant (the policy's per-step gate)."""


def read(run):
    steps = run["steps"]
    if not steps["n"] or not run.get("policy"):
        return None
    return steps["compressed"] / steps["n"]
