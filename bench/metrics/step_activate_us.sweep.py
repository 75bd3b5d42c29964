"""step_activate_us.sweep: device self microseconds per engine step in the
``activate`` phase of ``_step`` (endpoint cache, activation, controller
requests and preinstall), over the traced calls (the base of
step_device_us.sweep)."""
from harness.program_trace import phase_us


def read(ctx):
    return phase_us(ctx, "activate")
