"""Share of the decode work dispatched in the measured window that
reached a request: tokens that entered a request's output and survived
its trim (``kept_tokens`` of ``engine.deliver``) over ``batch_size`` x
steps (``steps`` of ``engine.dispatch``), summed over the bursts that
began in the window. The rest ran for idle slots and for steps past a
request's ``max_new`` (a burst runs the chunk that covers the shortest
remaining budget). First tokens come from a prefill and are not
counted. ``[engine_counters]`` prints the whole-life sums of the ring
beside the engine's cumulative counters."""
from benchmark import program_spans


def read(ctx):
    records = program_spans.since(float("-inf"))
    if not records:
        return None
    bursts = [b for b in program_spans.bursts(records)
              if program_spans.whole(b)]
    program_spans.say_counters(ctx, {
        "bursts": len(bursts),
        "decode_steps": sum(b["engine.dispatch"][5]["steps"]
                            for b in bursts),
        "decode_tokens_kept": sum(b["engine.deliver"][5]["kept_tokens"]
                                  for b in bursts)})
    t0, t1 = ctx["window"]
    window = [b for b in bursts if t0 <= b["engine.admit"][1] < t1]
    steps = sum(b["engine.dispatch"][5]["steps"] for b in window)
    if not steps:
        return None
    kept = sum(b["engine.deliver"][5]["kept_tokens"] for b in window)
    return 100.0 * kept / (int(ctx["engine"]["batch_size"]) * steps)
