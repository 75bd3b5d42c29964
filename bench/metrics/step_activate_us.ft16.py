"""step_activate_us.ft16: device self microseconds per engine step in the
``activate`` phase of ``_step`` (endpoint cache, route choice, channel
counts) over the traced campaign (the base of step_device_us.ft16)."""
from harness.program_trace import phase_us


def read(ctx):
    return phase_us(ctx, "activate")
