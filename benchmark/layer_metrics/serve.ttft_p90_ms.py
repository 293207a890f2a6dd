"""90th percentile (nearest rank), over every request due in the
window, of first token received minus the time the request was DUE, on
the client's clock; a failed request misses. What a user waits before
an answer starts: admission, the bucket's prefill and the burst that
the engine then runs before it fetches. It is read without a bound:
at a fixed load the engine settles into one of two regimes of burst
length, and this reads 28 % apart between them (PERF.md section 2)."""
from benchmark.spans import percentile


def read(ctx):
    return percentile(ctx["ttft_ms"], 90) if ctx["ttft_ms"] else None
