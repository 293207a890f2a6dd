"""Median milliseconds of the benchmark's span around
``handle.start_stream.remote(...)`` until its result (the stream id):
the router's and the replica's actor-call path as a client feels it,
before any token is computed."""
import statistics


def read(ctx):
    t0, t1 = ctx["window"]
    spans = ctx["recorder"].durations("serve.start_stream", t0, t1)
    return 1e3 * statistics.median(spans) if spans else None
