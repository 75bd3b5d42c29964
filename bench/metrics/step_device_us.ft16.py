"""step_device_us.ft16: device busy microseconds per engine step (steps
summed over the campaign's simulations) over the traced campaign."""
from harness.readers import step_device_us as read  # noqa: F401
