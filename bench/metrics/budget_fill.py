"""Real tokens over token-budget rows, summed over the window's steps."""


def read(run):
    steps = run["steps"]
    if not steps["n"]:
        return None
    return steps["real_tokens"] / (steps["n"] * steps["budget"])
