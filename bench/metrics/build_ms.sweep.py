"""build_ms.sweep: median host time per call of ``Experiment(...)`` plus
``.build()`` (scenario lowering, routes, consts upload)."""
from harness.readers import median_ms, window_spans


def read(ctx):
    return median_ms(window_spans(ctx, "build"))
