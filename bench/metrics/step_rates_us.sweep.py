"""step_rates_us.sweep: device self microseconds per engine step in the
``rates`` phase of ``_step`` (the Eq. 3 rates and water-fill), over the
traced calls (the base of step_device_us.sweep)."""
from harness.program_trace import phase_us


def read(ctx):
    return phase_us(ctx, "rates")
