"""Median milliseconds of the benchmark's span around ``train.report``
in its loop, over the measured window: the controller's cost a step as
the loop feels it."""
import statistics


def read(ctx):
    t0, t1 = ctx["window"]
    spans = ctx["recorder"].durations("train.report", t0, t1)
    return 1e3 * statistics.median(spans) if spans else None
