"""step_advance_us.sweep: device self microseconds per engine step in the
``advance`` phase of ``_step`` (dt-min, stall, energy and the clock), over
the traced calls (the base of step_device_us.sweep)."""
from harness.program_trace import phase_us


def read(ctx):
    return phase_us(ctx, "advance")
