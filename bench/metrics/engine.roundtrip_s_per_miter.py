"""Seconds the resolution engine spent in device calls (the program's
``roundtrip`` span: from the jitted call on host arrays through the copy
of its results back to the host, inside ``replay`` or ``solve``) per
million simulated iterations in the window."""


def read(run):
    s = run.get("walls", {}).get("roundtrip")
    iters = sum(u["iterations"] for u in run["units"])
    return s / (iters / 1e6) if s and iters else None
