"""Share of the traced window in which a prefill program ran on the
device: the ``jit_engine_prefill_b<bucket>`` executions on the trace's
``XLA Modules`` line, clipped to the window, over the window. A launch
costs the same whatever was admitted in it
(``serve.prefill_pad_share``)."""
from benchmark import program_spans, trace_reduce


def read(ctx):
    s = ctx.get("trace_summary")
    if not s:
        return None
    plane = program_spans.idlest_plane(s)
    everything = (float("-inf"), float("inf"))
    runs = [(start, end) for name, start, end in
            trace_reduce.module_runs(plane, everything)
            if program_spans.PREFILL_RUN.match(name)]
    if not runs:
        return None
    inside = trace_reduce.total(trace_reduce.merge(
        trace_reduce.clip(runs, s["window"])))
    return 100.0 * trace_reduce.share(inside / 1e9, s["window_s"],
                                      "prefill device share")
