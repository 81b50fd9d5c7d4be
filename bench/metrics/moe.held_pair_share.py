"""Share (%) of the (token, expert) pairs that the router chose which
went to an expert held on this chip: the server's ``moe.pairs_held`` over
``moe.pairs_routed``, counted over the window's prefills and decode steps.
Uniform routing over 8 held of 64 experts reads 12.5 %.  A program
without the counters gives nothing."""


def read(run):
    c = run.get("counts") or {}
    routed = c.get("moe.pairs_routed", 0)
    return 100.0 * c.get("moe.pairs_held", 0) / routed if routed else None
