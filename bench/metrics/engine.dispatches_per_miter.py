"""Device calls of the resolution engine's kernels (the program's
dispatch counters, every kernel and platform) per million simulated
iterations in the window."""


def read(run):
    calls = sum(run.get("dispatches", {}).values())
    iters = sum(u["iterations"] for u in run["units"])
    return calls / (iters / 1e6) if calls and iters else None
