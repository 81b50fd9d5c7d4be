"""The server's own decode wall per step (``Result.decode_s``: host clock
from the first decode step to the last step's logits), median over the
window's batches."""

import statistics


def read(run):
    v = [u["decode_s_per_step"] * 1e3 for u in run["units"]]
    return statistics.median(v) if v else None
