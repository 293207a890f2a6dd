"""Of the idle seconds of the idlest device in the traced window, the
share that passed while the engine's loop was in ``engine.admit``,
``engine.dispatch`` or ``engine.deliver``, i.e. on the host and not
waiting for the device (``program_spans.idle_by_span``: the loop's
spans mapped onto the trace's clock and laid over the device's idle
gaps). The rest passed under ``engine.fetch`` (the transfer after the
device's last operation) or under no span of the loop. An attribution
of ``device_idle_share.serve``, not a goal of its own.
``[idle_by_program_span]`` prints the idle seconds under every
label."""
from benchmark import program_spans
from benchmark.common import say


def read(ctx):
    by_label = program_spans.idle_by_span(ctx)
    idle = sum(by_label.values()) if by_label else 0.0
    if idle <= 0.0:
        return None
    say("idle_by_program_span", **{
        k.replace(" ", "_"): v for k, v in
        sorted(by_label.items(), key=lambda x: -x[1])})
    return 100.0 * sum(by_label.get(n, 0.0)
                       for n in program_spans.HOST_SIDE) / idle
