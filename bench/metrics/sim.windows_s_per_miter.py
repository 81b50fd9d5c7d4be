"""Seconds the simulator spent building trace windows and their masks
(the program's ``windows`` span: address windows, burst and
participation masks, the flattened access stream) per million simulated
iterations in the window."""


def read(run):
    s = run.get("walls", {}).get("windows")
    iters = sum(u["iterations"] for u in run["units"])
    return s / (iters / 1e6) if s and iters else None
