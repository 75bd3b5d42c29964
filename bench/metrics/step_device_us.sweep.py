"""step_device_us.sweep: device busy microseconds per engine step (steps
summed over the batch's simulations) over the traced calls."""
from harness.readers import step_device_us as read  # noqa: F401
