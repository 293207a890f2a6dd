"""Median of the engine's ``engine.first_token`` spans of the requests
submitted in the measured window: from admission to a slot until the
request's first token was put on its stream. That is the prefill
launch and the whole of the next burst, with whose tokens the first
one is fetched."""
import statistics

from benchmark import program_spans


def read(ctx):
    records = program_spans.since(ctx["window"][0]) or ()
    submitted = {r[3] for r in program_spans.started_in(
        records, "engine.queue", ctx["window"])}
    holds = [1e3 * (r[2] - r[1]) for r in records
             if r[0] == "engine.first_token" and r[3] in submitted]
    return statistics.median(holds) if holds else None
