"""95th percentile (nearest rank) over every request completed in the
window of its time to first token: from its admission (its batch's start;
the loop is closed) until its first token reaches the caller, on the
benchmark's clock.  The server hands a batch's tokens back together when
``serve`` returns, so that is when the first token arrives."""

from harness import nearest_rank


def read(run):
    lat = [u["latency_s"] * 1e3 for u in run["units"]
           for _ in range(u["requests"])]
    return nearest_rank(lat, 0.95) if lat else None
