"""device_idle_share.ft16: per cent of the traced window with no device
operation running (mean over the cell's chips) in the fat-tree fleet
cell."""
from harness.readers import idle_share as read  # noqa: F401
