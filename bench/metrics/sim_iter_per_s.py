"""Simulated loop iterations of the simulations completed in the window,
over the window's time to the last completion."""


def read(run):
    iters = sum(u["iterations"] for u in run["units"])
    return iters / run["window_s"] if run["window_s"] > 0 else None
