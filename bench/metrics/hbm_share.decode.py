"""Share (%) of the chip's HBM bandwidth that the decode steps' least
traffic would take at the server's own decode wall: the bytes every step
must read (the configuration's ``decode_step_bytes``: the weights held
here once, the latent cache up to each row's length) summed over the
window's steps, over the server's decode wall (``Result.decode_s`` times
the steps), over the bandwidth in ``hbm.json``.  The bytes are a lower
bound, so the share cannot pass 100 %; an unlisted device kind is an
error."""

import json
import os

HBM = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "hbm.json")


def read(run):
    units = [u for u in run["units"] if "decode_bytes" in u]
    wall = sum(u["decode_s_per_step"] * u["decode_steps"] for u in units)
    if not units or wall <= 0:
        return None
    with open(HBM) as f:
        table = json.load(f)["devices"]
    dev = run["device"]
    bw = float(table[dev["kind"]]["hbm_bytes_per_s"]) * dev["count"]
    return 100.0 * sum(u["decode_bytes"] for u in units) / wall / bw
