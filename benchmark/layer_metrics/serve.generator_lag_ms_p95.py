"""The benchmark's own health: 95th percentile of (sent - due) over the
window's requests. A starved generator must not be read as a fast
server."""
from benchmark.spans import percentile


def read(ctx):
    return percentile(ctx["lag_ms"], 95) if ctx["lag_ms"] else None
