"""Median over the bursts that began in the measured window of the
time the engine's loop spent on the host in one round: its
``engine.admit``, ``engine.dispatch`` and ``engine.deliver`` spans,
i.e. the round less ``engine.fetch``, in which it waits for the
device."""
import statistics

from benchmark import program_spans


def read(ctx):
    t0, t1 = ctx["window"]
    host = [1e3 * sum(b[n][2] - b[n][1] for n in program_spans.HOST_SIDE)
            for b in program_spans.bursts(program_spans.since(t0) or ())
            if program_spans.whole(b) and t0 <= b["engine.admit"][1] < t1]
    return statistics.median(host) if host else None
