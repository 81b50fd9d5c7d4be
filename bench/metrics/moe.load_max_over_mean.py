"""The straggler factor of the held experts: the server's
``moe.pairs_max_expert`` (the sum over layer-steps of the busiest held
expert's pairs) over the held mean (``moe.pairs_held`` over the number of
held experts).  1 is a perfectly even load; the busiest expert's grouped
matmul is as long as this factor times an even share.  A program without
the counters gives nothing."""


def read(run):
    c = run.get("counts") or {}
    held = c.get("moe.pairs_held", 0)
    if not held or "moe.pairs_max_expert" not in c:
        return None
    return c["moe.pairs_max_expert"] / (held / run["experts_held"])
