"""The server's own prefill wall (``Result.prefill_s``: host clock around
the batched prompt forward, ended by block_until_ready), 95th
percentile (nearest rank) over the window's requests."""

from harness import nearest_rank


def read(run):
    v = [u["prefill_s"] * 1e3 for u in run["units"]
         for _ in range(u["requests"])]
    return nearest_rank(v, 0.95) if v else None
