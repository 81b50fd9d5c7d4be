"""Model FLOPs utilization: the forward FLOPs that the completed
requests needed (flops.py, from the configuration's shapes: prompt and
requested tokens only) over the window, as a share of the chip's bf16
peak (peaks.json)."""

from flops import mfu as read  # noqa: F401
