"""step_admit_us.sweep: device self microseconds per engine step in the
``admit_place`` phase of ``_step`` (admission, placement and migration),
over the traced calls (the base of step_device_us.sweep)."""
from harness.program_trace import phase_us


def read(ctx):
    return phase_us(ctx, "admit_place")
