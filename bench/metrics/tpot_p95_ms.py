"""95th percentile (nearest rank) over every request completed in the
window of its time per output token as the caller sees it: its time from
admission to the return of its tokens, on the benchmark's clock, over the
number of tokens it asked for and received.  A request that waits for
its batch's longest pays for the steps it does not use."""

from harness import nearest_rank


def read(run):
    v = [u["latency_s"] * 1e3 / n for u in run["units"]
         for n in u["request_tokens"] if n > 0]
    return nearest_rank(v, 0.95) if v else None
