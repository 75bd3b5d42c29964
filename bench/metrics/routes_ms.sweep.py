"""routes_ms.sweep: median over the traced calls of the host milliseconds
each spent in the program's ``repro.front.routes`` span (equal-hop route
enumeration and packing inside ``Scenario.build``)."""
from harness.program_trace import span_ms_per


def read(ctx):
    return span_ms_per(ctx, "repro.front.routes", "bench.call")
