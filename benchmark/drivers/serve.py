"""Entry kind "serve": a traffic mix offered in an open loop to the
handle that ``serve.run(build_llm_app(params, model_cfg, engine_cfg))``
returns, clients on ``start_stream`` / ``next_tokens``, in the process
that holds the chip.

Set-up warms through requests alone: one at a time until the engine
has built each of its programs, then ``lead_in_s`` seconds of the
mix's own traffic, which runs on into the window, so that the window
opens on a replica in its steady state. A request is timed from when
it was DUE, on the client's clock. The window takes the requests due
inside it; the run serves on past its close until they have all ended
or ``drain_limit_s`` has passed (what is unfinished then has
failed). Once everything is shut down and
``memory_peak_bytes`` is read, a sample of the finished requests (the
longest among them) is held, token by token, to the float32 reference
by teacher forcing.
"""

from __future__ import annotations

import gc
import importlib
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmark import compile_watch, flops, traffic_gen, weights
from benchmark.common import load_json, say
from benchmark.drivers.train import transformer_kwargs
from benchmark.spans import Recorder, percentile
from benchmark.tracing import WindowTracer


class Served:
    """One request as the client saw it; times are perf_counter."""

    __slots__ = ("req", "t_due", "t_sent", "t_first", "t_last", "tokens",
                 "frames", "error")

    def __init__(self, req: Dict[str, Any], t_due: float) -> None:
        self.req, self.t_due = req, t_due
        self.t_sent = 0.0
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        self.tokens: List[int] = []
        self.frames: List[Tuple[float, int]] = []   # (arrival, n tokens)
        self.error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return (self.error is None
                and len(self.tokens) == self.req["max_new"])


def _client(handle, rec: Recorder, s: Served, poll_s: float) -> None:
    import ray_tpu

    try:
        s.t_sent = time.perf_counter()
        with rec.span("serve.start_stream"):
            sid = ray_tpu.get(handle.start_stream.remote(
                s.req["prompt"], s.req["max_new"]), timeout=poll_s + 60.0)
        while True:
            with rec.span("serve.next_tokens"):
                frame = ray_tpu.get(
                    handle.next_tokens.remote(sid, poll_s),
                    timeout=poll_s + 60.0)
            now = time.perf_counter()
            got = frame.get("tokens") or ()
            if got:
                if s.t_first is None:
                    s.t_first = now
                s.t_last = now
                s.tokens.extend(int(t) for t in got)
                s.frames.append((now, len(got)))
            if frame.get("done"):
                return
    except Exception as e:  # noqa: BLE001 - the request has failed
        s.error = f"{type(e).__name__}: {e}"


def offer(handle, rec: Recorder, requests: List[Dict[str, Any]],
          t_open: float, deadline: float, poll_s: float) -> List[Served]:
    """The open loop: every request is sent at its due time by a thread
    of its own, whatever became of the earlier ones. Returns when all
    have ended or the deadline has passed."""
    served = [Served(r, t_open + r["due_s"]) for r in requests]
    threads = []
    for s in served:
        time.sleep(max(0.0, s.t_due - time.perf_counter()))
        t = threading.Thread(target=_client, args=(handle, rec, s, poll_s),
                             daemon=True, name=f"bench_client_{s.req['id']}")
        t.start()
        threads.append(t)
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    for s, t in zip(served, threads):
        if t.is_alive() and s.error is None:
            s.error = "unfinished at the drain limit"
    return served


def warm_requests(engine: Dict[str, Any], vocab: int, seed: int
                  ) -> List[Dict[str, Any]]:
    """Requests that make the engine build every program the mix can
    use, sent one at a time: a prompt in each prefill bucket, and for
    each decode chunk of 1, 2, 4, ... ``decode_chunk`` steps an output
    length that the loop covers with exactly that chunk (n + 1 tokens:
    the first comes from the prefill)."""
    rng = np.random.default_rng([abs(int(seed)), 11])
    buckets = sorted(engine["prefill_buckets"])
    chunks = [1 << i for i in range(int(engine["decode_chunk"])
                                    .bit_length())]
    out = []
    for i in range(max(len(buckets), len(chunks))):
        plen = buckets[min(i, len(buckets) - 1)]
        out.append({"id": -1 - i, "due_s": 0.0,
                    "prompt": rng.integers(1, vocab, plen).tolist(),
                    "max_new": chunks[min(i, len(chunks) - 1)] + 1})
    return out


def check_sample(served: List[Served], k: int, seed: int) -> List[Served]:
    """k finished requests drawn from the seed, the longest (prompt +
    output) always among them."""
    done = [s for s in served if s.ok]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.req["prompt"]) + len(s.tokens))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([abs(int(seed)), 13])
    picks = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in picks]


def widest_gap(cell, sample: List[Served], control: str = ""
               ) -> Dict[str, Any]:
    """Teacher-force the reference over each sampled prompt with its
    served tokens; the widest gap by which a served token's logit lies
    below the reference's best at its position. ``control`` (a lower
    precision of the reference, "fp8") judges in the served tokens'
    place, at each position of the same prompts and tokens, the token
    that the reference computed in that precision puts first."""
    import jax.numpy as jnp

    ref_mod = importlib.import_module(
        "benchmark.reference." + cell.config["reference"])
    plens = [len(s.req["prompt"]) for s in sample]
    total = [pl + len(s.tokens) for pl, s in zip(plens, sample)]
    width = -(-max(total) // 128) * 128
    rows = np.zeros((len(sample), width), np.int32)
    for i, s in enumerate(sample):
        rows[i, :total[i]] = s.req["prompt"] + s.tokens
    dtype = jnp.dtype(cell.config["run"]["param_dtype"])
    logits = ref_mod.teacher_forced_logits(cell.config, cell.seed, rows,
                                           "f32", dtype)
    judged = rows
    if control:
        first = np.asarray(jnp.argmax(ref_mod.teacher_forced_logits(
            cell.config, cell.seed, rows, control, dtype), axis=-1))
        judged = rows.copy()
        for i, (pl, tl) in enumerate(zip(plens, total)):
            # the token at position t comes from the logits at t - 1
            judged[i, pl:tl] = first[i, pl - 1:tl - 1]
    gaps, _ = ref_mod.served_token_gaps(logits, judged, plens, total)
    return {"gaps": gaps,
            "served_tokens": int(sum(len(s.tokens) for s in sample))}


def output_checks(cell, sample: List[Served], n_failed: int,
                  control: str = "") -> List[Tuple[str, float, float]]:
    """[(name, value, limit)]: what decides ``correct`` in a serving
    cell. No finished request to sample reads as not a number, which
    fails."""
    limits = load_json("benchmark", "limits", cell.name + ".json")["limits"]
    t0 = time.perf_counter()
    widest = float("nan")
    if sample:
        got = widest_gap(cell, sample, control)
        widest = float(got["gaps"].max())
        say("reference", seconds=time.perf_counter() - t0,
            requests=len(sample), served_tokens=got["served_tokens"],
            exact_argmax=int((got["gaps"] == 0).sum()), widest_gap=widest,
            longest=len(sample[0].req["prompt"]) + len(sample[0].tokens),
            control=control or "none")
    return [("widest_logit_gap", widest, limits["widest_logit_gap"]),
            ("requests_failed", float(n_failed), 0.0)]


class Deployment:
    """The served model, started and warmed: what set-up builds and the
    window drives."""

    def __init__(self, cell, rec: Recorder) -> None:
        import jax

        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.models.inference import InferenceConfig
        from ray_tpu.models.transformer import TransformerConfig
        from ray_tpu.serve.llm import build_llm_app

        config, mix = cell.config, cell.traffic
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
            **({"decode_chunk": int(engine["decode_chunk"])}
               if "decode_chunk" in engine else {}))
        engine["decode_chunk"] = icfg.decode_chunk
        mcfg = TransformerConfig(
            **transformer_kwargs(config, icfg.max_context))
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

    def offer(self, requests: List[Dict[str, Any]], t_open: float,
              allow_s: float) -> List[Served]:
        return offer(self.handle, self.rec, requests, t_open,
                     t_open + allow_s, self.poll_s)

    def close(self) -> None:
        import ray_tpu
        from ray_tpu import serve

        self.handle = None
        serve.shutdown()
        ray_tpu.shutdown()


def latencies(served: List[Served], miss_ms: float) -> Dict[str, List[float]]:
    """Per request, on the client's clock: first token minus DUE, and
    (last - first) / (tokens - 1). A failed request misses both."""
    return {
        "ttft": [1e3 * (s.t_first - s.t_due) if s.ok else miss_ms
                 for s in served],
        "tpot": [1e3 * (s.t_last - s.t_first) / (len(s.tokens) - 1)
                 if s.ok and len(s.tokens) > 1 else miss_ms
                 for s in served],
        "lag": [1e3 * (s.t_sent - s.t_due) for s in served],
    }


def run(cell, t_process_start: float) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu.serve import core

    mix = cell.traffic
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
    # Every jitted function that the window uses has to have been
    # built in set-up. What the engine dispatches eagerly, a primitive
    # at a time, compiles for each new shape in every process: the
    # program's own cost, counted below. Told apart by jax's name for
    # the program, never by seconds or by the persistent cache's
    # answer: a host that stands still, or the profiler, stretches a
    # 40 ms compilation past the second from which jax keeps it in the
    # cache, and later runs of the checkout then meet it there.
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

    # -- the end-to-end numbers ----------------------------------------
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
        engine_stats=engine_stats)
    say("memory", peak_bytes_in_use=peak,
        bytes_in_use_after_shutdown=in_use)

    # -- the output check, after everything of the program is freed ----
    checks = output_checks(
        cell, check_sample(served, int(mix["check_requests"]), cell.seed),
        n_failed)

    # "served" is everyone, the lead-in too: its last tokens arrive
    # inside the window and the readers sort by time
    ctx = {"cell": cell, "recorder": rec, "served": everyone,
           "flops": flops, "engine": engine,
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
