"""Arithmetic the metric readers in ``metrics/`` share.  Each reader takes
the run's context (``core.run``) and returns a number, or None where it
finds nothing to read."""
from __future__ import annotations

import numpy as np


def window_spans(ctx, name: str):
    """Durations (s) of the benchmark's ``name`` spans inside the window."""
    return ctx["rec"].durations(name, since=ctx["w0"])


def idle_share(ctx):
    """Per cent of the traced window in which no operation ran on the
    device (mean over the cell's devices)."""
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def step_device_us(ctx):
    """Device busy time (us) per engine step over the traced units (steps
    summed over their simulations)."""
    tr = ctx["trace"]
    steps = sum(u["steps"] for u in ctx["units"] if u["traced"])
    if not tr or steps <= 0:
        return None
    return 1e6 * tr["busy_s"] / steps


def median_ms(durations):
    return 1e3 * float(np.median(durations)) if durations else None
