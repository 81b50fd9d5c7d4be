"""Share of the traced window in which no operation ran on the device:
1 - busy / window, from the profiler trace (see xplane.py)."""

from xplane import idle_share as read  # noqa: F401
