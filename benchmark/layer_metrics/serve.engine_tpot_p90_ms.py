"""90th percentile (nearest rank) of (t1 - t0) / (tokens - 1) over the
engine's ``engine.decode`` spans (more than one token) of the requests
whose ``engine.queue`` began in the measured window: the span runs from
a request's first hand-out to its last, so this is ``serve_tpot_p90_ms``
taken where the tokens are made, before the replica's poll and the
actor call's way back. ``[tpot_sides]`` prints the clients' side beside
it. None on a program whose ``engine.decode`` does not say how many
tokens it covers."""
from benchmark import request_path
from benchmark.spans import percentile


def read(ctx):
    tpots = [t for t, _ in request_path.engine_tpots(ctx).values()]
    if not tpots:
        return None
    request_path.say_tpot_sides(ctx)
    return percentile(tpots, 90)
