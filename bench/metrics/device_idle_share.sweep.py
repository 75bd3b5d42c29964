"""device_idle_share.sweep: per cent of the traced window with no device
operation running, in the sweep cell."""
from harness.readers import idle_share as read  # noqa: F401
