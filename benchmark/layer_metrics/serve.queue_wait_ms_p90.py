"""90th percentile (nearest rank) of the engine's ``engine.queue``
spans of the requests submitted in the measured window: from
``submit_stream`` until the loop admitted the request to a slot, which
it does between bursts only."""
from benchmark import program_spans
from benchmark.spans import percentile


def read(ctx):
    records = program_spans.since(ctx["window"][0])
    waits = [1e3 * (r[2] - r[1]) for r in program_spans.started_in(
        records or (), "engine.queue", ctx["window"])]
    return percentile(waits, 90) if waits else None
