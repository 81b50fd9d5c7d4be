"""Seconds the resolution engine spent in its wavefront-solve phase (its
own host-clock span, device round trips included) per million simulated
iterations in the window."""


def read(run):
    s = run.get("walls", {}).get("solve")
    iters = sum(u["iterations"] for u in run["units"])
    return s / (iters / 1e6) if s and iters else None
