"""step_complete_us.sweep: device self microseconds per engine step in the
``complete`` phase of ``_step`` (completions), over the traced calls (the
base of step_device_us.sweep)."""
from harness.program_trace import phase_us


def read(ctx):
    return phase_us(ctx, "complete")
