"""device_idle_share.grid: per cent of the traced window with no device
operation running (mean over the cell's chips), in the throughput cells."""
from harness.readers import idle_share as read  # noqa: F401
