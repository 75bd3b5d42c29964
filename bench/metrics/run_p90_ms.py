"""run_p90_ms: the 90th percentile (linear interpolation) of the wall time
of every call in the window, build and result fetch included."""
import numpy as np

from harness.readers import window_spans


def read(ctx):
    calls = window_spans(ctx, "call")
    return 1e3 * float(np.percentile(calls, 90)) if calls else None
