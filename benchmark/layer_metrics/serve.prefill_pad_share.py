"""Share of the prefill positions computed in the measured window that
were padding: 1 - prompt tokens / (rows x bucket), summed over the
engine's ``engine.prefill_launch`` records that began in the window. A
launch runs all ``batch_size`` rows of its bucket whatever was
admitted, and a prompt fills its row only up to its own length; both
kinds of padding count. The engine's cumulative counters
(``engine_stats``: ``prefill_positions``, ``prefill_prompt_tokens``)
hold the same sums over its whole life; ``[engine_counters]`` prints
them beside the ring's."""
from benchmark import program_spans


def read(ctx):
    records = program_spans.since(float("-inf"))
    if not records:
        return None
    launches = [r[5] for r in records if r[0] == "engine.prefill_launch"]
    program_spans.say_counters(ctx, {
        "prefill_launches": len(launches),
        "prefill_useful_rows": sum(f["useful_rows"] for f in launches),
        "prefill_positions": sum(f["rows"] * f["bucket"] for f in launches),
        "prefill_prompt_tokens": sum(f["prompt_tokens"] for f in launches)})
    window = [r[5] for r in program_spans.started_in(
        records, "engine.prefill_launch", ctx["window"])]
    positions = sum(f["rows"] * f["bucket"] for f in window)
    if not positions:
        return None
    return 100.0 * (1.0 - sum(f["prompt_tokens"] for f in window)
                    / positions)
