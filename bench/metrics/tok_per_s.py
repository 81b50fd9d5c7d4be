"""Generated tokens that requests asked for and received, over the
window's time to the last completed batch.  Tokens decoded past a
request's own length do not count."""


def read(run):
    toks = sum(u["useful_tokens"] for u in run["units"])
    return toks / run["window_s"] if run["window_s"] > 0 else None
