"""chunk_ms.fleet: wall milliseconds per fleet chunk: the campaigns' wall
time over their ``FleetStats.chunks``, so one chunk plus its host boundary
(retire, refill, policy rows)."""
from harness.readers import window_spans


def read(ctx):
    chunks = sum(u.get("chunks", 0) for u in ctx["units"])
    wall = sum(window_spans(ctx, "campaign"))
    return 1e3 * wall / chunks if chunks else None
