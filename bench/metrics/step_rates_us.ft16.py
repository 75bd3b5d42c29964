"""step_rates_us.ft16: device self microseconds per engine step in the
``rates`` phase of ``_step`` (Eq. 3 shares over the fabric's links) over
the traced campaign."""
from harness.program_trace import phase_us


def read(ctx):
    return phase_us(ctx, "rates")
