"""boundary_idle_share.grid: per cent of the traced window in which the
device idled inside one of the program's ``repro.fleet.*`` spans (cohort
set-up, chunk dispatch, done-flag sync, retire, refill) as the innermost
open span."""
from harness.program_trace import idle_share_in


def read(ctx):
    return idle_share_in(ctx, "repro.fleet.")
