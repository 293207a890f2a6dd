"""Entry kind "serve_described": ``drivers/serve_decoder.py``'s open
loop for a model whose WHOLE description comes from its weights module:
``weights.description(config)`` returns the ``DecoderConfig``, layers
included, so this driver knows no model's layer rule and the next
configuration needs no driver of its own (``serve_decoder``'s
``model_description`` builds the layers itself, by Solar-Open2's rule).

Imported: everything that does not name ``model_description``
(``drivers/serve.py``'s clients, open loop, warm-up, latencies, sample
and teacher-forced gaps; ``serve_decoder.Deployment``'s ``offer`` and
``close``). Repeated line for line, because no file the benchmark has
may change in the PR that adds a configuration: ``Deployment.__init__``,
which calls it, and ``run``, which names ``Deployment`` (PERF.md section
7 asks a ``benchmark`` issue to fold the three drivers into one).

Its own: ``output_checks``. Beside the widest gap of a served token
under the reference's best it compares the SHARE of compared tokens
that are not the reference's first choice. A model whose router scales
the picked experts' weights (2.5 here) turns one flipped pick at the
8th/9th expert into a logit gap as wide as float8 makes anywhere, so a
maximum over 4,000 tokens cannot tell a sound run from the fp8 control
(sound up to 0.70, fp8 from 0.72: PERF.md section 6, PR 31); the share
tells them apart 3 % : 33 %.
"""

from __future__ import annotations

import gc
import importlib
import time
from typing import Any, Dict, List

from benchmark import compile_watch, traffic_gen
from benchmark.common import load_json, say
from benchmark.drivers import serve_decoder
from benchmark.drivers.serve import (Served, check_sample, latencies,
                                     warm_requests, widest_gap)
from benchmark.spans import Recorder, percentile
from benchmark.tracing import WindowTracer


def output_checks(cell, sample: List[Served], n_failed: int,
                  control: str = ""):
    """[(name, value, limit)]: what decides ``correct`` in a cell of
    this driver. ``drivers/serve.py``'s two, and between them
    ``argmax_miss_share``: of the compared tokens (``control``: of the
    tokens the reference in that precision puts first), the share that
    the float32 reference does not put first. No finished request to
    sample reads as not a number, which fails."""
    limits = load_json("benchmark", "limits", cell.name + ".json")["limits"]
    t0 = time.perf_counter()
    widest = missed = float("nan")
    if sample:
        got = widest_gap(cell, sample, control)
        gaps = got["gaps"]
        widest, missed = float(gaps.max()), float((gaps > 0).mean())
        say("reference", seconds=time.perf_counter() - t0,
            requests=len(sample), served_tokens=got["served_tokens"],
            exact_argmax=int((gaps == 0).sum()), widest_gap=widest,
            argmax_miss_share=missed,
            longest=len(sample[0].req["prompt"]) + len(sample[0].tokens),
            control=control or "none")
    return [("widest_logit_gap", widest, limits["widest_logit_gap"]),
            ("argmax_miss_share", missed, limits["argmax_miss_share"]),
            ("requests_failed", float(n_failed), 0.0)]


class Deployment(serve_decoder.Deployment):
    """The served model, started and warmed: what set-up builds and the
    window drives."""

    def __init__(self, cell, rec: Recorder) -> None:
        import jax

        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.models.inference import InferenceConfig
        from ray_tpu.serve.llm import build_llm_app

        config, mix = cell.config, cell.traffic
        weights = importlib.import_module("benchmark." + config["weights"])
        # first of all: a program that cannot describe this model fails
        # here, before anything is started or put on the device
        mcfg = weights.description(config)
        self.cell, self.rec = cell, rec
        self.engine = engine = dict(mix["engine"])
        self.vocab = weights.dims(config)["v"]
        self.poll_s = float(mix["poll_timeout_s"])
        self.parts: Dict[str, float] = {}
        t0 = time.perf_counter()
        ray_tpu.init(num_workers=8, scheduler="tensor",
                     _system_config={"log_dir": cell.scratch("logs")})
        self.parts["import_and_init_s"] = time.perf_counter() - t0
        icfg = InferenceConfig(
            batch_size=int(engine["batch_size"]),
            page_size=int(engine["page_size"]),
            max_pages_per_seq=int(engine["max_pages_per_seq"]),
            num_pages=int(engine["num_pages"]),
            prefill_buckets=tuple(engine["prefill_buckets"]),
            max_new_tokens=int(mix["output_tokens"]["max"]),
            decode_chunk=int(engine["decode_chunk"]))
        t0 = time.perf_counter()
        params = jax.jit(lambda k: weights.init_params(
            config, k, mcfg.param_dtype))(weights.seed_key(cell.seed))
        jax.block_until_ready(params)
        self.parts["weights_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.handle = serve.run(build_llm_app(params, mcfg, icfg))
        del params
        warm: List[Served] = []
        for r in warm_requests(engine, self.vocab, cell.seed):
            warm += self.offer([r], time.perf_counter(), 1500.0)
        bad = [s.error or "short" for s in warm if not s.ok]
        if bad:
            self.close()
            raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
        self.parts["serve_start_and_programs_s"] = time.perf_counter() - t0
        rec.spans.clear()


def run(cell, t_process_start: float) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu.serve import core

    mix = cell.traffic
    flops_mod = importlib.import_module("benchmark." + cell.config["flops"])
    rec = Recorder()
    watch = compile_watch.CompileWatch()
    c0 = watch.snapshot()
    dep = Deployment(cell, rec)
    try:
        core.metrics.reset()
        setup_compile = watch.since(c0)
        lead_s = float(mix["lead_in_s"])
        lead = traffic_gen.serve_requests(mix, dep.vocab, cell.seed,
                                          lead_s, stream=1)
        for r in lead:
            r["id"], r["due_s"] = -1 - r["id"], r["due_s"] - lead_s
        requests = traffic_gen.serve_requests(mix, dep.vocab, cell.seed,
                                              cell.seconds)
        tracer = WindowTracer(cell, rec) if cell.trace else None
        t_open = time.perf_counter() + lead_s
        if tracer is not None:
            tracer.start(t_open)
        drain = float(mix["drain_limit_s"])
        everyone = dep.offer(lead + requests, t_open, cell.seconds + drain)
        t_end = time.perf_counter()
        in_window = watch.between(t_open, t_end)
        cache_hits = watch.hits_between(t_open, t_end)
        snap = core.metrics.snapshot()
        trace_summary = tracer.finish() if tracer is not None else None
        engine_stats = ray_tpu.get(dep.handle.engine_stats.remote(),
                                   timeout=30.0)
    finally:
        dep.close()
    engine, parts = dep.engine, dep.parts
    del dep
    gc.collect()
    stats = [d.memory_stats() or {} for d in cell.devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    in_use = max(int(s.get("bytes_in_use", 0)) for s in stats)
    # The replica's actor threads and its creation task outlive
    # serve.shutdown() and keep weights, pool and state on the device
    # (PERF.md, PR 21 and PR 24: 9.8 GB here). The program is done and
    # the peak is read; the reference needs the room for its float32
    # logits, so what is left on the device goes.
    import jax

    for leftover in jax.live_arrays():
        leftover.delete()
    # as drivers/serve.py: a jitted function first built inside the
    # window fails the run; eager primitives are counted
    unwarmed = [(n, s) for n, s in in_window
                if not compile_watch.eager_primitive(n)]
    if unwarmed:
        raise compile_watch.CompiledInWindow(
            f"jitted functions that set-up did not build were compiled "
            f"or read from the cache inside the measured window: "
            f"{unwarmed}")
    compiled = [secs for _, secs in in_window]
    served = [s for s in everyone if s.req["id"] >= 0]
    bad = [s.error or "short" for s in everyone
           if s.req["id"] < 0 and not s.ok]
    if bad:
        raise RuntimeError(f"lead-in requests failed: {bad[:3]}")

    setup_s = t_open - t_process_start
    say("setup", setup_s=setup_s, **parts, lead_in_s=lead_s,
        lead_in_requests=len(lead), **setup_compile)
    say("window", compiles=len(compiled), compile_s=sum(compiled),
        compile_max_s=max(compiled, default=0.0),
        read_from_cache=cache_hits,
        names=sorted({n for n, _ in in_window}))

    shed, resumed = int(snap["admission_shed"]), int(snap["resumed"])
    failed = [s for s in served if not s.ok]
    n_failed = min(len(served), len(failed) + shed + resumed)
    miss_ms = 1e3 * (cell.seconds + drain)      # a failure misses
    lat = latencies(served, miss_ms)
    ttft, tpot, lag = lat["ttft"], lat["tpot"], lat["lag"]
    out_tokens = sum(len(s.tokens) for s in served if s.ok)
    say("requests", due=len(served), ok=len(served) - len(failed),
        failed=n_failed, shed=shed, resumed=resumed,
        first_errors=[s.error for s in failed[:3]],
        served_to_s=t_end - t_open,
        prompt_tokens=sum(len(s.req["prompt"]) for s in served),
        output_tokens=out_tokens,
        output_tokens_per_s=out_tokens / (t_end - t_open))
    say("latency",
        **{f"{k}_p{q}_ms": percentile(v, q)
           for k, v in (("ttft", ttft), ("tpot", tpot))
           for q in (50, 80, 90, 95)},
        ttft_max_ms=max(ttft), tpot_max_ms=max(tpot),
        generator_lag_p95_ms=percentile(lag, 95),
        engine_stats={k: v for k, v in engine_stats.items()
                      if k != "moe_load_by_expert"})
    say("memory", peak_bytes_in_use=peak,
        bytes_in_use_after_shutdown=in_use)

    # the output check, after everything of the program is freed
    checks = output_checks(
        cell, check_sample(served, int(mix["check_requests"]), cell.seed),
        n_failed)

    ctx = {"cell": cell, "recorder": rec, "served": everyone,
           "flops": flops_mod.Counted(cell.config, engine_stats),
           "engine": engine,
           "window": (t_open, t_open + cell.seconds),
           "trace_summary": trace_summary, "lag_ms": lag,
           "ttft_ms": ttft, "engine_stats": engine_stats,
           "window_compile_s": sum(compiled)}
    return {
        "setup_s": setup_s,
        "end_to_end": {"serve_tpot_p90_ms": percentile(tpot, 90)},
        "attempted": len(served), "failed": n_failed,
        "checks": checks, "memory_peak_bytes": peak, "ctx": ctx,
    }
