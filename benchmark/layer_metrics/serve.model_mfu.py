"""The whole served model's share of the chip's peak over the traced
window: 2*N FLOPs for every prompt token whose request got its first
token in the traced window and for every output token that arrived in
it (benchmark/flops.forward_flops: what the tokens REQUIRE; padded
rows, idle slots and rewritten pools do not count), over window x
peak."""


def read(ctx):
    s, cell = ctx.get("trace_summary"), ctx["cell"]
    if not s or cell.peaks is None:
        return None
    t0, t1 = s["t0"], s["t1"]
    tokens = 0
    for r in ctx["served"]:
        if r.t_first is not None and t0 <= r.t_first < t1:
            tokens += len(r.req["prompt"])
        tokens += sum(n for t, n in r.frames if t0 <= t < t1)
    if not tokens:
        return None
    return 100.0 * ctx["flops"].forward_flops(cell.config, tokens) / (
        (t1 - t0) * cell.peaks["bf16_flops_per_s"] * cell.chips)
