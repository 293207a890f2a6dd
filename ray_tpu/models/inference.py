"""LLM inference engine: paged KV cache + continuous batching.

Reference surface: the serving stack the reference framework runs
(vLLM-style engine: paged KV cache, page tables per sequence,
continuous batching that admits new requests as finished ones free
their slots — on GPU). TPU-native rebuild: the decode step is ONE
jitted program with fully static shapes (fixed batch slots, fixed page
geometry), paged attention is the Pallas kernel in
ops/paged_attention.py (arXiv:2604.15464 pattern, PAPERS.md), prefill
jits per prompt-length bucket so compile count stays bounded, and all
ragged-ness lives in page tables + sequence lengths (data, not shapes).

Three modules, arrows one way: serve/llm.py (deployments) -> this file
(requests, slots, the page allocator, admission, prefill launches,
decode bursts, the one fetch a burst, spans and counters) ->
models/decoder_forward.py (what the model described in
models/decoder.py computes, and the cache of what its layers keep
between steps) -> ops/. Pages, the page table and admission are the
full-context layers'; a window layer keeps a ring of pages that belongs
to the slot (decoder_forward), which the engine only counts
(``_ring``). The engine builds one family of programs for
every description and holds the cache as one donated value it never
looks into; what a description has no use for (the live-slot mask of a
dense decoder, the picks of a model without experts) falls away while
tracing.

    engine = InferenceEngine(params, model_cfg, InferenceConfig(...))
    fut = engine.submit([1, 2, 3], max_new_tokens=16)
    tokens = fut.result()
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import spans, trace_plane
from ray_tpu.models import decoder_forward as forward
from ray_tpu.models.decoder import describe
from ray_tpu.models.decoder_forward import (decode_chunk,  # noqa: F401
                                            decode_step, prefill,
                                            prefill_batch)


@dataclasses.dataclass(frozen=True)
class InferenceConfig:
    batch_size: int = 4            # concurrent decode slots
    page_size: int = 16
    max_pages_per_seq: int = 16    # max context = page_size * this
    num_pages: int = 128           # total physical pages (all slots)
    prefill_buckets: Tuple[int, ...] = (16, 32, 64, 128)
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    # max greedy steps fused into one device dispatch (lax.scan);
    # admission happens between chunks. Large chunks amortize the host
    # work of a dispatch. Idle
    # slots' dummy appends wrap within the reserved parking page, so
    # chunks may exceed page_size.
    decode_chunk: int = 32

    @property
    def max_context(self) -> int:
        return self.page_size * self.max_pages_per_seq


def _program(name: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` under a name of its own. A profile lists each
    execution as ``jit_<name>`` and jax's compile events say
    ``jit(<name>)``; a lambda or a ``functools.partial`` would read
    ``jit__lambda_`` or ``jit__unknown`` there. The bucket or step
    count a program was specialised for goes INTO its name, so a reader
    of the trace needs nothing else to tell the programs apart."""
    def call(*args):
        return fn(*args)

    call.__name__ = call.__qualname__ = name
    return jax.jit(call, **jit_kwargs)


# positions of a prefill launch past which further rows buy nothing: a
# launch has a fixed cost (every weight read once, the program's own
# overhead), and rows exist so that short prompts share it. A row this
# long takes five times that cost by itself (PERF.md, PR 32: launch
# times on the chip), so a dummy companion would cost more than a real
# one could save, and the row runs alone.
_LAUNCH_POSITIONS = 2048

_STREAM_END = object()


class TokenStream:
    """Iterator over tokens as the engine produces them (per sync
    burst), plus the final-list future for callers that want both.
    ``ident`` is the number of the engine's request, which its spans
    carry. The queue holds one ``(perf_counter at the hand-out,
    tokens)`` a hand-out, then ``_STREAM_END`` or the exception that
    ended the request."""

    def __init__(self, future: Future, ident: int):
        self._q: "queue.Queue" = queue.Queue()
        self.future = future
        self.ident = ident

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is _STREAM_END:
                return
            if isinstance(item, BaseException):
                raise item
            yield from item[1]  # one burst's new tokens

    def result(self, timeout: Optional[float] = None) -> List[int]:
        return self.future.result(timeout)


class _Request:
    __slots__ = ("prompt", "max_new", "future", "out", "emitted", "stream",
                 "streamed", "handouts", "kv", "ident", "parent", "t_mark",
                 "got_first")

    def __init__(self, prompt: List[int], max_new: int, ident: int):
        self.prompt = prompt
        self.max_new = max_new
        # for the request's three spans (engine.queue, .first_token,
        # .decode): its number, the trace plane's context of the call
        # that brought it, when the running span began, and whether the
        # first token has been handed out
        self.ident = ident
        self.parent = trace_plane.current_parent()
        self.t_mark = time.perf_counter()
        self.got_first = False
        self.future: Future = Future()
        self.out: List[int] = []   # tokens synced to host
        self.emitted = 0           # tokens produced on device (>= len(out))
        self.stream: Optional[TokenStream] = None
        self.streamed = 0          # tokens already handed out
        self.handouts = 0          # times _hand_out had tokens to give
        # disaggregated handoff: (k [L,S,KV,D], v, first_token) host
        # arrays from a prefill replica's export; admission imports the
        # pages instead of running the prompt pass
        self.kv: Optional[Tuple[Any, Any, int]] = None

    def end_span(self, name: str, now: float, **fields: Any) -> None:
        """The request's running span ends at ``now``, under ``name``
        and with what it covered, and the next begins."""
        spans.record(name, self.t_mark, now, ident=self.ident,
                     parent=self.parent, **fields)
        self.t_mark = now


class _Slot:
    __slots__ = ("req", "pages", "seq_len")

    def __init__(self):
        self.req: Optional[_Request] = None
        self.pages: List[int] = []
        self.seq_len = 0


class InferenceEngine:
    """Continuous-batching decode loop over a paged KV cache.

    ``mode`` disaggregates the engine for split-pool serving:

    - ``"both"`` (default): the monolithic engine — prompt passes and
      the continuous decode batch in one process.
    - ``"prefill"``: prompt passes only. No paged cache, no decode
      programs, no loop thread; ``prefill_export`` runs the bucketed
      prompt pass synchronously and hands the K/V pages + first token
      to the caller for shipping through the object plane.
    - ``"decode"``: the continuous batch only. Requests join via
      ``submit_stream_from_kv`` (imported pages); plain ``submit`` is
      rejected so a misrouted prompt fails loudly instead of silently
      paying an un-provisioned prefill.
    """

    def __init__(self, params: Dict[str, Any], model_cfg: Any,
                 cfg: InferenceConfig = InferenceConfig(),
                 mode: str = "both"):
        if mode not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown engine mode {mode!r}")
        if "params" in params and "embedding" not in params:
            params = params["params"]
        self.params = params
        # a TransformerConfig or a DecoderConfig; the engine reads the
        # layer-by-layer description either way
        self.mcfg = mcfg = describe(model_cfg)
        # what the layers keep that the KV handoff does not carry
        self._beside_kv = forward.kept_beside_kv(mcfg)
        if self._beside_kv and mode != "both":
            raise ValueError(
                f"engine mode {mode!r} hands whole sequences' keys and "
                f"values from a prefill replica to a decode replica; this "
                f"model keeps {self._beside_kv}, which the handoff does "
                f"not carry: serve it in mode 'both'")
        self.cfg = cfg
        self.mode = mode
        self._idents = itertools.count()
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._lock = threading.Lock()
        self._shutdown = False
        self.num_steps = 0
        self.max_concurrent = 0
        # cumulative counts behind stats(): decode steps dispatched,
        # decode tokens that stayed in a request's output, bursts, and
        # by prefill bucket [launches, rows, useful rows, prompt tokens]
        self._decode_steps = 0
        self._decode_pages_live = 0
        self._decode_tokens_kept = 0
        # the live slots' context when a burst began, whole and clipped
        # to the window a slot, times the burst's steps; a window
        # layer's ring of pages a slot (0: the model has no such layer)
        self._decode_ctx_tokens = 0
        self._decode_window_tokens = 0
        self._ring = (forward.window_ring(mcfg, cfg.page_size)
                      if mcfg.window_layers else 0)
        self._bursts = 0
        self._prefill_counts: Dict[int, List[int]] = {}
        # picks of routed experts by the tokens that counted (prompt
        # tokens, live slots' decode steps): all, and by held expert
        self._moe_picks_total = 0
        self._moe_load = np.zeros(mcfg.n_experts_held, np.int64)
        # held experts that got a pick, summed over the decode steps and
        # the expert layers
        self._moe_experts_touched = 0
        # single-prompt bucketed prompt pass for prefill_export;
        # compiles lazily per bucket on first use
        self._export_jits = {
            b: _program(f"engine_prefill_export_b{b}",
                        lambda p, t: prefill(p, mcfg, t))
            for b in cfg.prefill_buckets
        }
        if mode == "prefill":
            # everything decode-shaped is absent
            self._cache = ()
            self._slots = []
            self._free_pages = []
            self._thread = None
            return
        # what the model's layers keep between steps (page pools, a
        # state a slot: decoder_forward.init_cache), one pytree that
        # every program below takes donated and hands back, so each
        # step and each launch updates it in place on the device
        self._cache = forward.init_cache(mcfg, cfg)
        # the LAST physical page is the parking page for idle decode
        # slots (their dummy K/V appends land there), never allocated
        self._free_pages: List[int] = list(range(cfg.num_pages - 1))
        self._slots = [_Slot() for _ in range(cfg.batch_size)]
        self._wake = threading.Event()

        # params are ARGUMENTS of the jitted programs, never closed-over
        # constants (a closure would bake every weight into the HLO as a
        # literal — catastrophic compile times at real model sizes).
        # chunked decode programs (1, 2, 4, ... decode_chunk steps per
        # dispatch); the loop picks the largest chunk no active slot's
        # remaining budget forbids
        self._chunk_sizes = []
        n = 1
        while n <= max(1, cfg.decode_chunk):
            self._chunk_sizes.append(n)
            n *= 2
        self._decode_chunks = {
            steps: _program(
                f"engine_decode_n{steps}",
                lambda p, toks, cache, table, lens, live, _n=steps:
                forward.decode_chunk_cached(p, mcfg, toks, cache, table,
                                            lens, live, n_steps=_n),
                donate_argnums=(2,))
            for steps in self._chunk_sizes}
        # burst state rides ONE packed upload [B, 1 + max_pages]
        # (column 0 = seq_lens, rest = page table — one transfer
        # instead of two); lens then EVOLVES on device across the
        # burst's chained chunks while the table stays fixed. A slot is
        # live when its length is not 0: a prompt has at least one token
        self._split_packed = _program(
            "engine_split_packed",
            lambda packed: (packed[:, 1:], packed[:, 0], packed[:, 0] > 0))

        # BATCHED prefill: N admissions in one program behind ONE packed
        # upload. packed [N, 2 + bucket + n_prog] int32 rows of
        # [slot_idx, plen, tokens(bucket), pages(n_prog)]; dummy pad
        # rows carry slot_idx == batch_size, whose scatter is dropped
        # (out-of-bounds scatters drop) and whose pages point at the
        # parking page. N is _prefill_rows[bucket], so jit specializes
        # once per bucket. The first tokens go into the feedback
        # vector, and the launch's picks a held expert (of a model with
        # experts) ride behind them to the host.
        def prefill_write(p, packed, cache, toks_vec, bucket):
            n_prog = -(-bucket // cfg.page_size)
            slots = packed[:, 0]
            plens = packed[:, 1]
            toks = packed[:, 2:2 + bucket]
            pages = packed[:, 2 + bucket:2 + bucket + n_prog]
            logits, cache, counts = forward.prefill_cached(
                p, mcfg, cache, toks, plens, slots, pages,
                slots < cfg.batch_size)
            with jax.named_scope("head"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                toks_vec = toks_vec.at[slots].set(nxt)
            first = nxt if counts is None else jnp.concatenate([nxt, counts])
            return first, toks_vec, cache

        # every launch has one budget of positions: a single prompt in
        # the largest bucket, but no more than it takes to amortise a
        # launch's fixed cost (_LAUNCH_POSITIONS). A bucket b runs
        # budget // b rows (at most batch_size, at least 1), so a
        # bucket as long as the budget or longer runs its one row alone
        budget = min(max(cfg.prefill_buckets), _LAUNCH_POSITIONS)
        self._prefill_rows = {
            b: max(1, min(cfg.batch_size, budget // b))
            for b in cfg.prefill_buckets}
        self._prefill_many = ({} if mode == "decode" else {
            b: _program(
                f"engine_prefill_b{b}",
                functools.partial(prefill_write, bucket=b),
                donate_argnums=(2, 3))
            for b in cfg.prefill_buckets
        })

        # KV-page IMPORT: write a prefill replica's exported K/V
        # sequence into this engine's pages and scatter the already-
        # computed first token into the device feedback vector — the
        # decode-pool half of the disaggregated handoff. One request
        # per dispatch (handoffs arrive one at a time off the object
        # plane); jit specializes per bucket like prefill.
        def kv_import_one(cache, toks_vec, k_seq, v_seq, pages, slot_first):
            cache = forward.import_kv(mcfg, cache, k_seq, v_seq, pages)
            return toks_vec.at[slot_first[0]].set(slot_first[1]), cache

        # one jit, respecialized per padded bucket shape
        self._kv_import = _program("engine_kv_import", kv_import_one,
                                   donate_argnums=(0, 1))
        # persistent device-resident feedback state: admission scatters
        # the prefill's next-token in WITHOUT a host read (a sync
        # stalls the dispatch pipeline; a dispatch does not)
        self._dev_toks = jnp.zeros(cfg.batch_size, jnp.int32)
        # prefill next-tokens awaiting the next burst's combined fetch:
        # (device array [N], or [N + E_held] with the launch's picks a
        # held expert behind them; [(slot, row)]; the launch's span
        # fields, which the picks complete)
        self._pending_firsts: List[Tuple[Any, List[Tuple[_Slot, int]],
                                         dict]] = []
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="ray_tpu_llm_engine")
        self._thread.start()

    # -- API -----------------------------------------------------------
    def _validate(self, prompt: Sequence[int],
                  max_new_tokens: Optional[int]) -> int:
        if not prompt:
            raise ValueError("empty prompt")
        max_new = (self.cfg.max_new_tokens if max_new_tokens is None
                   else max_new_tokens)
        if max_new <= 0:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        if len(prompt) + max_new > self.cfg.max_context:
            raise ValueError(
                f"prompt({len(prompt)}) + max_new({max_new}) exceeds the "
                f"engine's max context {self.cfg.max_context}")
        if len(prompt) > max(self.cfg.prefill_buckets):
            raise ValueError(
                f"prompt longer than the largest prefill bucket "
                f"{max(self.cfg.prefill_buckets)}")
        return max_new

    def _check_mode(self, wants: str) -> None:
        if self.mode not in ("both", wants):
            raise RuntimeError(
                f"engine is in {self.mode!r} mode; this entry point "
                f"needs {wants!r}")
        if self._beside_kv:
            raise RuntimeError(
                f"the KV handoff carries whole sequences' keys and values "
                f"only; this model keeps {self._beside_kv} too and is "
                f"served whole (submit, submit_stream)")

    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None) -> Future:
        """Returns a Future resolving to the GENERATED token list."""
        if self.mode != "both":
            raise RuntimeError(
                f"engine is in {self.mode!r} mode; plain submit needs "
                f"the monolithic engine (prefill_export / "
                f"submit_stream_from_kv are the split-pool entry points)")
        max_new = self._validate(prompt, max_new_tokens)
        req = _Request(list(prompt), max_new, next(self._idents))
        self._queue.put(req)
        self._wake.set()
        return req.future

    def submit_stream(self, prompt: Sequence[int],
                      max_new_tokens: Optional[int] = None) -> TokenStream:
        """Streaming variant: tokens arrive on the returned iterator as
        each device sync lands (chunk granularity), ending at EOS /
        budget; .result() still yields the final list."""
        if self.mode != "both":
            raise RuntimeError(
                f"engine is in {self.mode!r} mode; plain submit_stream "
                f"needs the monolithic engine")
        max_new = self._validate(prompt, max_new_tokens)
        req = _Request(list(prompt), max_new, next(self._idents))
        stream = TokenStream(req.future, req.ident)
        req.stream = stream
        self._queue.put(req)
        self._wake.set()
        return stream

    # -- disaggregated prefill/decode handoff --------------------------
    def prefill_export(self, prompt: Sequence[int],
                       max_new_tokens: Optional[int] = None
                       ) -> Dict[str, Any]:
        """Run the prompt pass and export the session's KV pages as
        host arrays — the prefill-pool half of disaggregated serving.

        Returns ``{"prompt", "prompt_len", "first_token", "k", "v",
        "kv_bytes"}`` where k/v are numpy [L, prompt_len, KV, D] in the
        model dtype (page-layout-free: the importing engine writes them
        into ITS pages, so pools need not share page geometry). The
        first token is argmax of the last prompt position, i.e. the
        entire TTFT-critical work happens here; decode-side import adds
        one page write."""
        self._check_mode("prefill")
        max_new = self._validate(prompt, max_new_tokens)
        plen = len(prompt)
        bucket = next(b for b in sorted(self.cfg.prefill_buckets)
                      if b >= plen)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = list(prompt)
        logits, k_seq, v_seq = self._export_jits[bucket](
            self.params, jnp.asarray(toks))
        first = int(jnp.argmax(logits[plen - 1]))
        k = np.asarray(k_seq[:, :plen])
        v = np.asarray(v_seq[:, :plen])
        return {"prompt": list(prompt), "prompt_len": plen,
                "first_token": first, "k": k, "v": v,
                "kv_bytes": int(k.nbytes + v.nbytes),
                "max_new": max_new}

    def submit_stream_from_kv(self, kv: Dict[str, Any],
                              max_new_tokens: Optional[int] = None,
                              emit_first: bool = True) -> TokenStream:
        """Join the continuous batch from an exported KV handoff
        (``prefill_export`` dict) instead of a prompt pass. The first
        token is already known; with ``emit_first=False`` the stream
        treats it as already delivered (the ingress driver streamed it
        straight off the handoff) and yields only subsequent tokens."""
        self._check_mode("decode")
        prompt = list(kv["prompt"])
        max_new = self._validate(
            prompt, kv.get("max_new") if max_new_tokens is None
            else max_new_tokens)
        req = _Request(prompt, max_new, next(self._idents))
        req.kv = (kv["k"], kv["v"], int(kv["first_token"]))
        stream = TokenStream(req.future, req.ident)
        req.stream = stream
        if not emit_first:
            req.streamed = 1
        self._queue.put(req)
        self._wake.set()
        return stream

    def generate(self, prompt: Sequence[int],
                 max_new_tokens: Optional[int] = None,
                 timeout: float = 600.0) -> List[int]:
        return self.submit(prompt, max_new_tokens).result(timeout)

    def stats(self) -> Dict[str, Any]:
        """The engine's state now, and its work so far as cumulative
        counts. ``num_steps`` counts chunk DISPATCHES (a chunk runs 1 to
        ``decode_chunk`` steps); ``decode_steps`` counts the steps, each
        of which runs all ``batch_size`` slots (``decode_slot_steps``),
        of whose tokens ``decode_tokens_kept`` reached a request's
        output: the rest fell to idle slots and to steps past a
        request's ``max_new``. ``decode_pages_live`` sums, over the
        steps, the pages the live slots owned when their burst began,
        and ``decode_pages_tabled`` the page table's entries
        (``batch_size`` x ``max_pages_per_seq`` a step): the share of
        the table a step's attention has to read. A burst is one round
        of the loop that dispatched something and fetched once. A
        prefill launch of bucket ``b`` runs ``budget // b`` rows (at
        most ``batch_size``, at least 1), where the budget is the
        largest bucket or ``_LAUNCH_POSITIONS`` if that is less: short
        buckets share a launch of about that many positions, a bucket
        that long or longer runs its one row alone; summed over the
        launches these are ``prefill_rows`` and ``prefill_positions``
        (rows x bucket), run for the ``prefill_useful_rows`` requests
        admitted in them and their ``prefill_prompt_tokens``;
        ``prefill_by_bucket`` has the same five by bucket (useful rows
        over rows is a bucket's fill share). Of a model with experts,
        ``moe_picks_total`` counts the picks of routed experts by the
        tokens that counted (every prompt token, every decode step of a
        live slot; ``experts_per_token`` a token and expert layer),
        ``moe_picks_local`` those that fell on an expert held here and
        were computed, ``moe_load_by_expert`` the same by held expert,
        and ``moe_experts_touched`` sums, over the decode steps and the
        expert layers, the held experts that got at least one pick (each
        such expert's matrices are read once a step and layer).
        ``decode_ctx_tokens_live`` sums, over the steps, the live slots'
        context when their burst began, and ``decode_window_tokens_live``
        the same with each slot's context clipped to the window (what a
        window layer's read has to fetch; 0 for a model without such a
        layer); ``window_pool_tokens`` is what ONE window layer's pool
        holds for all slots (``batch_size`` rings of ``window /
        page_size + 1`` pages) and ``window_pages_live`` the pages of
        it that hold the live slots' tokens now.
        ``state_bytes`` is what the layers with a state of their own a
        slot (recurrent state, convolution tail) keep for all slots and
        ``pool_tokens`` the tokens the page pool can hold;
        ``latent_bytes_per_token`` is what the latent layers together
        hold for a token (their rows as they lie in memory, padded to
        whole lanes) and ``latent_pool_bytes`` their pools whole."""
        with self._lock:
            by_bucket = {
                b: {"launches": n, "rows": rows, "useful_rows": useful,
                    "positions": rows * b, "prompt_tokens": tokens}
                for b, (n, rows, useful, tokens) in sorted(
                    self._prefill_counts.items())}
            prefill = {
                "prefill_" + k: sum(c[k] for c in by_bucket.values())
                for k in ("launches", "rows", "useful_rows", "positions",
                          "prompt_tokens")}
            return {
                "mode": self.mode,
                "num_steps": self.num_steps,
                "max_concurrent": self.max_concurrent,
                "free_pages": len(self._free_pages),
                "active": sum(s.req is not None for s in self._slots),
                "queued": self._queue.qsize(),
                "bursts": self._bursts,
                "decode_steps": self._decode_steps,
                "decode_slot_steps": (self._decode_steps
                                      * self.cfg.batch_size),
                "decode_tokens_kept": self._decode_tokens_kept,
                "decode_pages_live": self._decode_pages_live,
                "decode_pages_tabled": (self._decode_steps
                                        * self.cfg.batch_size
                                        * self.cfg.max_pages_per_seq),
                "decode_ctx_tokens_live": self._decode_ctx_tokens,
                "decode_window_tokens_live": self._decode_window_tokens,
                "window_pool_tokens": (self.cfg.batch_size * self._ring
                                       * self.cfg.page_size),
                "window_pages_live": self._window_pages(
                    s.seq_len for s in self._slots if s.req is not None),
                **prefill,
                "prefill_by_bucket": by_bucket,
                "moe_picks_total": self._moe_picks_total,
                "moe_picks_local": int(self._moe_load.sum()),
                "moe_load_by_expert": self._moe_load.tolist(),
                "moe_experts_touched": self._moe_experts_touched,
                "state_bytes": sum(
                    a.nbytes for i in self.mcfg.state_layers
                    for a in jax.tree_util.tree_leaves(self._cache[i])),
                "latent_bytes_per_token": (
                    len(self.mcfg.latent_layers) * self.mcfg.latent_width
                    * jnp.dtype(self.mcfg.dtype).itemsize),
                "latent_pool_bytes": sum(
                    a.nbytes for i in self.mcfg.latent_layers
                    for a in self._cache[i]),
                "pool_tokens": (max(0, self.cfg.num_pages - 1)
                                * self.cfg.page_size
                                if self.mode != "prefill" else 0),
            }

    def shutdown(self) -> None:
        self._shutdown = True
        if self._thread is None:      # prefill-only engine: no loop
            return
        self._wake.set()
        self._thread.join(timeout=5.0)
        self._fail_outstanding(RuntimeError("engine shut down"))

    def _fail_outstanding(self, exc: BaseException) -> None:
        """Resolve every in-flight and queued Future exceptionally —
        a dead engine must never leave callers blocking to timeout."""
        def _fail(req: _Request) -> None:
            if not req.future.done():
                req.future.set_exception(exc)
            if req.stream is not None:
                req.stream._q.put(exc)

        self._pending_firsts = []
        for s in self._slots:
            req, s.req = s.req, None
            if req is not None:
                with self._lock:
                    self._free_pages.extend(s.pages)
                s.pages = []
                s.seq_len = 0
                _fail(req)
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            _fail(req)

    # -- internals ------------------------------------------------------
    def _pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.cfg.page_size)

    def _window_pages(self, contexts) -> int:
        """Pages of ONE window layer's rings that hold tokens of
        sequences as long as ``contexts``."""
        return sum(min(self._pages_needed(c), self._ring) for c in contexts)

    def _try_admit(self) -> None:
        """Admit every admissible queued request, then prefill them in
        BATCHED programs (grouped per prompt bucket): one packed upload
        + one dispatch per group, fully asynchronous — the next tokens
        scatter into the device feedback vector and sync with the next
        burst's combined fetch."""
        admits: List[Tuple[_Slot, _Request, List[int]]] = []
        imports: List[Tuple[_Slot, _Request, List[int]]] = []
        while True:
            free_slot = next((s for s in self._slots if s.req is None),
                             None)
            if free_slot is None or self._queue.empty():
                break
            req = self._queue.queue[0]
            total = len(req.prompt) + req.max_new
            need = self._pages_needed(total)
            with self._lock:
                if need > len(self._free_pages):
                    break  # head-of-line blocks until pages free
                self._queue.get_nowait()
                pages = [self._free_pages.pop() for _ in range(need)]
            plen = len(req.prompt)
            free_slot.req = req
            free_slot.pages = pages
            free_slot.seq_len = plen
            req.emitted = 1
            req.end_span("engine.queue", time.perf_counter(),
                         prompt_tokens=plen, max_new=req.max_new)
            (imports if req.kv is not None else admits).append(
                (free_slot, req, pages))
        for slot, req, pages in imports:
            self._import_group(slot, req, pages)
        if not admits:
            return
        by_bucket: Dict[int, List[Tuple[_Slot, _Request, List[int]]]] = {}
        for slot, req, pages in admits:
            bucket = next(b for b in sorted(self.cfg.prefill_buckets)
                          if b >= len(req.prompt))
            by_bucket.setdefault(bucket, []).append((slot, req, pages))
        for bucket, group in by_bucket.items():
            self._prefill_group(bucket, group)

    def _prefill_group(self, bucket: int, group: List[tuple]) -> None:
        """Prefill a bucket's admitted requests, ``_prefill_rows[bucket]``
        to a launch; only the last launch carries dummy rows."""
        n = self._prefill_rows[bucket]
        for i in range(0, len(group), n):
            self._prefill_launch(bucket, group[i:i + n])

    def _prefill_launch(self, bucket: int, group: List[tuple]) -> None:
        n_prog = -(-bucket // self.cfg.page_size)
        width = 2 + bucket + n_prog
        # ONE program shape per bucket, chosen by the bucket alone: a
        # shape that followed the size of the group would compile while
        # serving, whenever a size came up for the first time. The
        # dummy rows run the whole forward like any other, so a bucket
        # gets as many rows as fit a launch's budget of positions
        # (_prefill_rows: one row from _LAUNCH_POSITIONS up) and a
        # larger group takes more launches.
        n = self._prefill_rows[bucket]
        packed = np.zeros((n, width), np.int32)
        # dummy pad rows: scatter target out of bounds (dropped), pages
        # at the parking page, plen 1
        packed[:, 0] = self.cfg.batch_size
        packed[:, 1] = 1
        packed[:, 2 + bucket:] = self._parking_page
        rows: List[Tuple[_Slot, int]] = []
        for r, (slot, req, pages) in enumerate(group):
            plen = len(req.prompt)
            packed[r, 0] = self._slots.index(slot)
            packed[r, 1] = plen
            packed[r, 2:2 + plen] = req.prompt
            # the program writes n_prog pages: the sequence's own where
            # allocated (pad rows beyond the prompt are DON'T-CARE —
            # appends overwrite them, attention masks by seq_len), the
            # parking page past its allocation
            page_list = (pages + [self._parking_page] * n_prog)[:n_prog]
            packed[r, 2 + bucket:] = page_list
            rows.append((slot, r))
        prompt_lens = [len(req.prompt) for _, req, _ in group]
        prompt_tokens = sum(prompt_lens)
        with self._lock:
            counts = self._prefill_counts.setdefault(bucket, [0, 0, 0, 0])
            counts[0] += 1
            counts[1] += n
            counts[2] += len(group)
            counts[3] += prompt_tokens
        with spans.span("engine.prefill_launch", bucket=bucket, rows=n,
                        useful_rows=len(group),
                        prompt_tokens=prompt_tokens) as launch:
            launch.fields["prompt_lens"] = prompt_lens
            if self.mcfg.conv_layers:
                # slots whose convolution tails the launch replaced, in
                # each conv layer
                launch.fields["conv_tails_written"] = len(group)
            if self._ring:
                # positions a window layer's ring got: the pages that
                # hold a prompt's last ``window`` positions, whole
                page, window = self.cfg.page_size, self.mcfg.window
                launch.fields["window_tokens_kept"] = sum(
                    n - max(n - window, 0) // page * page
                    for n in prompt_lens)
            nxt, self._dev_toks, self._cache = self._prefill_many[bucket](
                self.params, jnp.asarray(packed), self._cache,
                self._dev_toks)
        self._pending_firsts.append((nxt, rows, launch.fields))

    def _import_group(self, slot: _Slot, req: _Request,
                      pages: List[int]) -> None:
        """Admit one KV handoff: pad the exported sequence to its
        bucket, write it into this engine's pages, scatter the known
        first token into the device feedback vector. The request joins
        the next burst exactly as if it had prefilled here."""
        k, v, first = req.kv
        req.kv = None  # drop the host copy as soon as it's uploaded
        plen = len(req.prompt)
        bucket = next(b for b in sorted(self.cfg.prefill_buckets)
                      if b >= plen)
        n_prog = -(-bucket // self.cfg.page_size)
        # k, v [L, plen, KV, D] over the layers that keep pages
        k_pad = np.zeros((k.shape[0], bucket) + k.shape[2:], k.dtype)
        v_pad = np.zeros((v.shape[0], bucket) + v.shape[2:], v.dtype)
        k_pad[:, :plen] = k
        v_pad[:, :plen] = v
        # pad rows past the prompt are DON'T-CARE (appends overwrite,
        # attention masks by seq_len); pages past the allocation park
        page_list = (pages + [self._parking_page] * n_prog)[:n_prog]
        slot_idx = self._slots.index(slot)
        self._dev_toks, self._cache = self._kv_import(
            self._cache, self._dev_toks,
            jnp.asarray(k_pad), jnp.asarray(v_pad),
            jnp.asarray(np.asarray(page_list, np.int32)),
            jnp.asarray(np.asarray([slot_idx, first], np.int32)))
        req.out = [first]
        self._maybe_finish(slot)  # max_new == 1 finishes at admission
        self._hand_out(req)

    def _hand_out(self, req: _Request) -> None:
        """Push what ``req.out`` has gained onto its stream, stamped
        with the time of this hand-out, and close the request's spans
        as it passes their ends: the clock is read once, BEFORE the
        put, so a span's end is its hand-out's stamp and no reader of
        the stream can find a token younger than nothing."""
        now = time.perf_counter()
        new = len(req.out) - req.streamed
        if new > 0:
            req.handouts += 1
            if req.stream is not None:
                req.stream._q.put((now, req.out[req.streamed:]))
            req.streamed += new
        if req.stream is not None and req.future.done():
            req.stream._q.put(_STREAM_END)
        if not req.got_first and req.out:
            req.got_first = True
            req.end_span("engine.first_token", now, tokens=len(req.out))
        if req.future.done():
            req.end_span("engine.decode", now, tokens=len(req.out),
                         handouts=req.handouts)

    def _maybe_finish(self, slot: _Slot) -> None:
        req = slot.req
        # budget first: covering-chunk overshoot may have produced
        # tokens past max_new, and an EOS in that overrun region must
        # not be honored (the caller asked for at most max_new)
        budget = req.out[:req.max_new]
        if self.cfg.eos_id is not None and self.cfg.eos_id in budget:
            # EOS may land mid-chunk: trim the overrun (its KV appends
            # stayed within the pages reserved for max_new)
            req.out = budget[:budget.index(self.cfg.eos_id) + 1]
            done = True
        else:
            done = len(req.out) >= req.max_new
            if done:
                req.out = budget
        if done:
            with self._lock:
                self._free_pages.extend(slot.pages)
            slot.req = None
            slot.pages = []
            slot.seq_len = 0
            req.future.set_result(req.out)

    def _loop(self) -> None:
        while not self._shutdown:
            try:
                self._loop_once()
            except Exception as e:  # noqa: BLE001
                # a dispatch/compile failure (OOM, bad config) must not
                # silently kill the engine thread with futures parked
                import logging

                logging.getLogger(__name__).exception(
                    "inference engine step failed")
                self._fail_outstanding(e)

    def _loop_once(self) -> None:
        """One burst: admit, dispatch, fetch, deliver. Each of the four
        is a span on this thread, so a burst that dispatched something
        leaves one set of them (and nothing is recorded while the
        engine idles)."""
        if not (self._queue.empty()
                and all(s.req is None for s in self._slots)):
            with spans.span("engine.admit"):
                self._try_admit()
        active = [s for s in self._slots if s.req is not None]
        if not active:
            self._wake.wait(timeout=0.05)
            self._wake.clear()
            return
        self.max_concurrent = max(self.max_concurrent, len(active))
        live_pages = sum(len(s.pages) for s in active)
        live_ctx = [s.seq_len for s in active]
        live_window = (sum(min(c, self.mcfg.window) for c in live_ctx)
                       if self._ring else 0)
        with spans.span("engine.dispatch", live_slots=len(active),
                        live_ctx_tokens=sum(live_ctx),
                        live_pages=live_pages) as burst:
            if self.mcfg.state_layers:
                burst.fields["state_slots_live"] = len(active)
            if self._ring:
                burst.fields.update(
                    live_window_tokens=live_window,
                    window_pages_live=self._window_pages(live_ctx))
            pending = self._dispatch_burst(active)
            steps = sum(chunk for _, chunk, _ in pending)
            burst.fields.update(steps=steps, chunks=len(pending))

        # ONE fetch per burst: chunk outputs + any pending prefill
        # first-tokens (+ the picks a held expert, of the launches and
        # of the chunks), concatenated on device, read together
        firsts, self._pending_firsts = self._pending_firsts, []
        parts = [outs.reshape(-1) for outs, _, _ in pending]
        parts.extend(arr for arr, _rows, _fields in firsts)
        parts.extend(counts for _, _, counts in pending
                     if counts is not None)
        if not parts:
            return
        with spans.span("engine.fetch"):
            flat = np.asarray(jnp.concatenate(parts)
                              if len(parts) > 1 else parts[0])
        with spans.span("engine.deliver") as deliver:
            kept = self._deliver(active, pending, firsts, flat)
            deliver.fields["kept_tokens"] = kept
            if self.mcfg.moe_layers:
                # each chunk's picks a held expert, then its experts
                # touched, close the fetch
                held = len(self._moe_load)
                rows = flat[len(flat) - len(pending) * (held + 1):].reshape(
                    len(pending), held + 1)
                deliver.fields.update(self._count_picks(
                    rows[:, :held], len(active) * steps))
                touched = int(rows[:, held].sum())
                burst.fields["moe_experts_touched"] = touched
        with self._lock:      # a burst's counts land together
            self._bursts += 1
            self._decode_steps += steps
            self._decode_pages_live += live_pages * steps
            self._decode_ctx_tokens += sum(live_ctx) * steps
            self._decode_window_tokens += live_window * steps
            self._decode_tokens_kept += kept
            self._moe_experts_touched += burst.fields.get(
                "moe_experts_touched", 0)

    def _count_picks(self, counts: np.ndarray, tokens: int
                     ) -> Dict[str, int]:
        """Add fetched picks a held expert (any number of [E_held]
        rows, flat) and the ``tokens`` they were counted over to the
        cumulative counters; returns the span fields that say the
        same of this launch or burst."""
        load = counts.reshape(-1, len(self._moe_load)).sum(axis=0)
        total = (tokens * self.mcfg.experts_per_token
                 * len(self.mcfg.moe_layers))
        with self._lock:
            self._moe_load += load
            self._moe_picks_total += total
        return {"moe_picks_total": total,
                "moe_picks_local": int(load.sum()),
                "moe_expert_load_max": int(load.max(initial=0)),
                "moe_load_by_expert": load.tolist()}

    def _dispatch_burst(self, active: List[_Slot]
                        ) -> List[Tuple[Any, int, Any]]:
        """Upload the burst's lens + page table and dispatch its decode
        chunks back-to-back; returns [(chunk's tokens on the device,
        its steps, its picks a held expert on the device or None)]."""
        # ONE packed upload per burst carries lens + page table
        # (host bookkeeping is authoritative for both); the TOKEN
        # feedback vector lives on device across bursts — prefill
        # results scatter in without ever being read to host first
        packed = np.zeros(
            (self.cfg.batch_size, 1 + self.cfg.max_pages_per_seq),
            np.int32)
        # idle slots decode dummy tokens whose K/V appends land in
        # the reserved parking page; their outputs are discarded.
        # UNALLOCATED table entries also point at the parking page:
        # budget-overrun appends (chunk overshoot, finished slots
        # decoding out a burst) land there instead of page 0.
        packed[:, 1:] = self._parking_page
        for i, s in enumerate(self._slots):
            if s.req is not None:
                packed[i, 0] = s.seq_len
                for j, p in enumerate(s.pages):
                    packed[i, 1 + j] = p
        dev_toks = self._dev_toks
        dev_table, dev_lens, dev_live = self._split_packed(
            jnp.asarray(packed))

        # async burst: dispatch chunks back-to-back WITHOUT reading
        # results (jax dispatch is async).
        # The host materializes tokens ONCE per burst in a single
        # combined fetch — or per-chunk when EOS detection is
        # configured (early exit needs the values).
        inflight = 0
        pending: List[Tuple[Any, int, Any]] = []
        while True:
            remaining = min(s.req.max_new - s.req.emitted
                            for s in active) - inflight
            if remaining <= 0 or len(pending) >= 4:
                break
            # smallest chunk COVERING the remaining budget when one
            # exists: a 63-token budget runs one 64-step program
            # (the 1-token overrun trims at finish; its KV appends
            # land in parking-paged table slots) instead of
            # 32+16+8+4+2+1 separate dispatches
            covering = [c for c in self._chunk_sizes
                        if c >= remaining]
            chunk = (min(covering) if covering
                     else self._chunk_sizes[-1])
            outs, dev_toks, dev_lens, self._cache, counts = \
                self._decode_chunks[chunk](
                    self.params, dev_toks, self._cache, dev_table, dev_lens,
                    dev_live)
            self.num_steps += 1
            pending.append((outs, chunk, counts))
            inflight += chunk
            for s in active:
                s.seq_len += chunk
            if self.cfg.eos_id is not None:
                break  # EOS needs the values: one chunk per burst
        self._dev_toks = dev_toks
        return pending

    def _deliver(self, active: List[_Slot],
                 pending: List[Tuple[Any, int, Any]],
                 firsts: List[Tuple[Any, List[Tuple[_Slot, int]], dict]],
                 flat: np.ndarray) -> int:
        """Distribute a burst's fetched tokens to its requests, finish
        those that are done and feed the streams. Returns how many of
        the burst's decode tokens stayed in a request's output."""
        # first-tokens sit after this burst's chunk rows
        off = sum(c * self.cfg.batch_size for _, c, _ in pending)
        held = len(self._moe_load) if self.mcfg.moe_layers else 0
        for arr, rows, launch in firsts:
            for slot, r in rows:
                if slot.req is not None:
                    slot.req.out.insert(0, int(flat[off + r]))
            off += len(arr)
            if held:      # the launch's picks ride behind its tokens
                launch.update(self._count_picks(
                    flat[off - held:off], launch["prompt_tokens"]))
        live = {id(s) for s in active}
        kept = -sum(len(s.req.out) for s in active if s.req is not None)
        pos = 0
        for _outs, chunk, _counts in pending:
            arr = flat[pos:pos + chunk * self.cfg.batch_size].reshape(
                chunk, self.cfg.batch_size)
            pos += chunk * self.cfg.batch_size
            for i, s in enumerate(self._slots):
                if s.req is None or id(s) not in live:
                    continue
                s.req.out.extend(int(t) for t in arr[:, i])
        for s in active:
            if s.req is not None:
                s.req.emitted = len(s.req.out)
        for s in active:
            req = s.req
            if req is None:
                continue
            self._maybe_finish(s)   # may trim EOS overrun + finish
            kept += len(req.out)
            self._hand_out(req)
        return kept

    @property
    def _parking_page(self) -> int:
        return self.cfg.num_pages - 1
