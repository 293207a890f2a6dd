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

Weights are the flagship Transformer's (models/transformer.py) taken
as-is — the same param tree a Train run produces serves directly; a
parity test pins this functional forward to the flax module's output.

    engine = InferenceEngine(params, model_cfg, InferenceConfig(...))
    fut = engine.submit([1, 2, 3], max_new_tokens=16)
    tokens = fut.result()
"""

from __future__ import annotations

import dataclasses
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
from ray_tpu.models.transformer import TransformerConfig, _rope
from ray_tpu.ops.paged_attention import (append_token_kv,
                                         paged_attention_auto,
                                         write_prefill_kv)


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


# ----------------------------------------------------------------------
# functional forward over the flax param tree
# ----------------------------------------------------------------------

def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
            * scale).astype(x.dtype)


def _mlp(p, x, dtype):
    h = (jax.nn.silu(x @ p["w_gate"].astype(dtype))
         * (x @ p["w_up"].astype(dtype)))
    return h @ p["w_down"].astype(dtype)


def _prefill_layer(p, cfg: TransformerConfig, x, positions):
    """Full-attention prefill for one layer over [N,S,Dm]; returns
    (x_out, k [N,S,KV,D], v [N,S,KV,D])."""
    a = p["Attention_0"]
    with jax.named_scope("attn"):
        h = _rms(x, p["RMSNorm_0"]["scale"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", h, a["wq"].astype(cfg.dtype))
        k = jnp.einsum("bsd,dhk->bshk", h, a["wk"].astype(cfg.dtype))
        v = jnp.einsum("bsd,dhk->bshk", h, a["wv"].astype(cfg.dtype))
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        rep = cfg.n_heads // cfg.n_kv_heads
        kr = jnp.repeat(k, rep, axis=2)
        vr = jnp.repeat(v, rep, axis=2)
        s = x.shape[1]
        mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
        scores = (jnp.einsum("bshk,bthk->bhst", q, kr)
                  / jnp.sqrt(cfg.head_dim))
        scores = jnp.where(mask, scores.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        attn = jnp.einsum("bhst,bthk->bshk", probs, vr)
        x = x + jnp.einsum("bshk,hkd->bsd", attn,
                           a["wo"].astype(cfg.dtype))
    with jax.named_scope("mlp"):
        x = x + _mlp(p["MLP_0"], _rms(x, p["RMSNorm_1"]["scale"],
                                      cfg.norm_eps), cfg.dtype)
    return x, k, v


def _decode_layer(p, cfg: TransformerConfig, x, positions, k_pages,
                  v_pages, page_table, seq_lens):
    """Single-token decode for one layer over [B,Dm] against the paged
    cache; appends this token's K/V. seq_lens = cache length BEFORE the
    token. Returns (x_out, k_pages, v_pages)."""
    a = p["Attention_0"]
    with jax.named_scope("attn"):
        h = _rms(x, p["RMSNorm_0"]["scale"], cfg.norm_eps)
        q = jnp.einsum("bd,dhk->bhk", h, a["wq"].astype(cfg.dtype))
        k = jnp.einsum("bd,dhk->bhk", h, a["wk"].astype(cfg.dtype))
        v = jnp.einsum("bd,dhk->bhk", h, a["wv"].astype(cfg.dtype))
        # rope over a length-1 "sequence" per slot
        q = _rope(q[:, None], positions[:, None], cfg.rope_theta)[:, 0]
        k = _rope(k[:, None], positions[:, None], cfg.rope_theta)[:, 0]
    with jax.named_scope("kv_append"):
        k_pages, v_pages = append_token_kv(k_pages, v_pages, k, v,
                                           page_table, seq_lens)
    with jax.named_scope("attn"):
        out = paged_attention_auto(q, k_pages, v_pages, page_table,
                                   seq_lens + 1)
        x = x + jnp.einsum("bhk,hkd->bd", out.astype(cfg.dtype),
                           a["wo"].astype(cfg.dtype))
    with jax.named_scope("mlp"):
        x = x + _mlp(p["MLP_0"], _rms(x, p["RMSNorm_1"]["scale"],
                                      cfg.norm_eps), cfg.dtype)
    return x, k_pages, v_pages


def prefill_batch(params: Dict[str, Any], cfg: TransformerConfig,
                  tokens: jnp.ndarray):
    """tokens [N,S] (padded to a bucket) -> (logits [N,S,V] f32,
    k_seq/v_seq [L,N,S,KV,D]) — N prompts prefill in one program."""
    embed = params["embedding"]
    with jax.named_scope("embed"):
        x = embed.astype(cfg.dtype)[tokens]
    s = tokens.shape[1]
    positions = jnp.arange(s)[None, :]
    ks, vs = [], []
    for i in range(cfg.n_layers):
        x, k, v = _prefill_layer(params[f"layer_{i}"], cfg, x, positions)
        ks.append(k)
        vs.append(v)
    with jax.named_scope("head"):
        x = _rms(x, params["final_norm"]["scale"], cfg.norm_eps)
        logits = jnp.einsum("bsd,vd->bsv", x, embed.astype(cfg.dtype))
        logits = logits.astype(jnp.float32)
    return (logits, jnp.stack(ks), jnp.stack(vs))


def prefill(params: Dict[str, Any], cfg: TransformerConfig,
            tokens: jnp.ndarray):
    """tokens [1,S] (padded to a bucket) -> (logits [S,V] f32,
    k_seq/v_seq [L,S,KV,D])."""
    logits, ks, vs = prefill_batch(params, cfg, tokens)
    return logits[0], ks[:, 0], vs[:, 0]


def decode_step(params: Dict[str, Any], cfg: TransformerConfig,
                tokens: jnp.ndarray, k_pages: jnp.ndarray,
                v_pages: jnp.ndarray, page_table: jnp.ndarray,
                seq_lens: jnp.ndarray):
    """One continuous-batching step: tokens [B] int32 (last emitted or
    last prompt token per slot), cache = per-layer TUPLES of
    [P,KV,page,D] arrays (a pytree, never re-stacked: each layer's
    scatter update aliases its own buffer in place under jit/scan —
    stacking into one [L,...] array would copy the whole cache every
    step). Returns (next_logits [B,V] f32, k_pages, v_pages)."""
    embed = params["embedding"]
    with jax.named_scope("embed"):
        x = embed.astype(cfg.dtype)[tokens]      # [B, Dm]
    positions = seq_lens                          # this token's position
    new_k, new_v = [], []
    for i in range(cfg.n_layers):
        x, kp, vp = _decode_layer(params[f"layer_{i}"], cfg, x, positions,
                                  k_pages[i], v_pages[i], page_table,
                                  seq_lens)
        new_k.append(kp)
        new_v.append(vp)
    with jax.named_scope("head"):
        x = _rms(x, params["final_norm"]["scale"], cfg.norm_eps)
        logits = jnp.einsum("bd,vd->bv", x, embed.astype(cfg.dtype))
        logits = logits.astype(jnp.float32)
    return (logits, tuple(new_k), tuple(new_v))


def decode_chunk(params: Dict[str, Any], cfg: TransformerConfig,
                 tokens: jnp.ndarray, k_pages: jnp.ndarray,
                 v_pages: jnp.ndarray, page_table: jnp.ndarray,
                 seq_lens: jnp.ndarray, *, n_steps: int):
    """n_steps greedy decode steps in ONE jitted program (lax.scan with
    argmax feedback). Returns (tokens [n_steps, B] int32, next_tokens
    [B], next_lens [B], k_pages, v_pages): the feedback state comes
    back as DEVICE arrays so the engine can chain chunks without a
    host round trip: chunks pipeline asynchronously and the host syncs
    only when a burst ends."""
    def body(carry, _):
        toks, kp, vp, lens = carry
        logits, kp, vp = decode_step(params, cfg, toks, kp, vp,
                                     page_table, lens)
        with jax.named_scope("head"):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return (nxt, kp, vp, lens + 1), nxt

    carry, outs = jax.lax.scan(body,
                               (tokens, k_pages, v_pages, seq_lens),
                               None, length=n_steps)
    toks, k_out, v_out, lens = carry
    return outs, toks, lens, k_out, v_out


# ----------------------------------------------------------------------
# the engine
# ----------------------------------------------------------------------

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


_STREAM_END = object()


class TokenStream:
    """Iterator over tokens as the engine produces them (per sync
    burst), plus the final-list future for callers that want both."""

    def __init__(self, future: Future):
        self._q: "queue.Queue" = queue.Queue()
        self.future = future

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is _STREAM_END:
                return
            if isinstance(item, BaseException):
                raise item
            yield from item  # one burst's new tokens

    def result(self, timeout: Optional[float] = None) -> List[int]:
        return self.future.result(timeout)


class _Request:
    __slots__ = ("prompt", "max_new", "future", "out", "emitted", "stream",
                 "streamed", "kv", "ident", "parent", "t_mark", "got_first")

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
        self.streamed = 0          # tokens already pushed to the stream
        # disaggregated handoff: (k [L,S,KV,D], v, first_token) host
        # arrays from a prefill replica's export; admission imports the
        # pages instead of running the prompt pass
        self.kv: Optional[Tuple[Any, Any, int]] = None

    def end_span(self, name: str) -> None:
        """The request's running span ends now, under ``name``, and the
        next begins."""
        now = time.perf_counter()
        spans.record(name, self.t_mark, now, self.ident, self.parent)
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

    def __init__(self, params: Dict[str, Any], model_cfg: TransformerConfig,
                 cfg: InferenceConfig = InferenceConfig(),
                 mode: str = "both"):
        if mode not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown engine mode {mode!r}")
        if "params" in params and "embedding" not in params:
            params = params["params"]
        self.params = params
        self.mcfg = model_cfg
        self.cfg = cfg
        self.mode = mode
        L = model_cfg.n_layers
        KV, D = model_cfg.n_kv_heads, model_cfg.head_dim
        self._idents = itertools.count()
        # cumulative counts behind stats(): decode steps dispatched,
        # decode tokens that stayed in a request's output, bursts, and
        # by prefill bucket [launches, rows, useful rows, prompt tokens]
        self._decode_steps = 0
        self._decode_tokens_kept = 0
        self._bursts = 0
        self._prefill_counts: Dict[int, List[int]] = {}
        # single-prompt bucketed prompt pass for prefill_export;
        # compiles lazily per bucket on first use
        mcfg = self.mcfg
        self._export_jits = {
            b: _program(f"engine_prefill_export_b{b}",
                        lambda p, t: prefill(p, mcfg, t))
            for b in cfg.prefill_buckets
        }
        if mode == "prefill":
            # everything decode-shaped is absent
            self._slots = []
            self._free_pages = []
            self._queue = queue.Queue()
            self._lock = threading.Lock()
            self._shutdown = False
            self._thread = None
            self.num_steps = 0
            self.max_concurrent = 0
            return
        # per-layer tuple (pytree), NOT a stacked [L,...] array: in-place
        # scatter updates per layer under the donated decode program
        self._k_pages = tuple(
            jnp.zeros((cfg.num_pages, KV, cfg.page_size, D),
                      model_cfg.dtype) for _ in range(L))
        self._v_pages = tuple(
            jnp.zeros((cfg.num_pages, KV, cfg.page_size, D),
                      model_cfg.dtype) for _ in range(L))
        # the LAST physical page is the parking page for idle decode
        # slots (their dummy K/V appends land there), never allocated
        self._free_pages: List[int] = list(range(cfg.num_pages - 1))
        self._slots = [_Slot() for _ in range(cfg.batch_size)]
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._shutdown = False
        self.num_steps = 0
        self.max_concurrent = 0

        # params are ARGUMENTS of the jitted programs, never closed-over
        # constants (a closure would bake every weight into the HLO as a
        # literal — catastrophic compile times at real model sizes).
        # The cache is donated: each step updates it in place on device.
        # chunked decode programs (1, 2, 4, ... decode_chunk steps per
        # dispatch); the loop picks the largest chunk no active slot's
        # remaining budget forbids
        self._chunk_sizes = []
        n = 1
        while n <= max(1, cfg.decode_chunk):
            self._chunk_sizes.append(n)
            n *= 2
        self._decode_chunks = {}
        for steps in self._chunk_sizes:
            self._decode_chunks[steps] = _program(
                f"engine_decode_n{steps}",
                lambda p, toks, kp, vp, table, lens, _n=steps:
                decode_chunk(p, mcfg, toks, kp, vp, table, lens,
                             n_steps=_n),
                donate_argnums=(2, 3))
        # burst state rides ONE packed upload [B, 1 + max_pages]
        # (column 0 = seq_lens, rest = page table — one transfer
        # instead of two); lens then EVOLVES
        # on device across the burst's chained chunks while the table
        # stays fixed
        self._split_packed = _program(
            "engine_split_packed",
            lambda packed: (packed[:, 1:], packed[:, 0]))

        # BATCHED prefill: N admissions in one program behind ONE packed
        # upload. packed [N, 2 + bucket + n_prog] int32 rows of
        # [slot_idx, plen, tokens(bucket), pages(n_prog)]; dummy pad
        # rows carry slot_idx == batch_size, whose scatter is dropped
        # (out-of-bounds scatters drop) and whose pages point at the
        # parking page. N is _prefill_rows[bucket], so jit specializes
        # once per bucket.
        def prefill_write_many(p, packed, kp, vp, toks_vec, bucket):
            n_prog = -(-bucket // cfg.page_size)
            slots = packed[:, 0]
            plens = packed[:, 1]
            toks = packed[:, 2:2 + bucket]
            pages = packed[:, 2 + bucket:2 + bucket + n_prog]
            logits, k_seq, v_seq = prefill_batch(p, mcfg, toks)
            new_k, new_v = list(kp), list(vp)
            n = packed.shape[0]
            with jax.named_scope("kv_append"):
                for i in range(mcfg.n_layers):
                    ki, vi = new_k[i], new_v[i]
                    for r in range(n):
                        ki, vi = write_prefill_kv(ki, vi, k_seq[i, r],
                                                  v_seq[i, r], pages[r])
                    new_k[i], new_v[i] = ki, vi
            with jax.named_scope("head"):
                row_logits = logits[jnp.arange(n), plens - 1]   # [N,V]
                nxt = jnp.argmax(row_logits, axis=-1).astype(jnp.int32)
                toks_vec = toks_vec.at[slots].set(nxt)
            return nxt, toks_vec, tuple(new_k), tuple(new_v)

        # every launch computes one budget of positions, that of a
        # single prompt in the largest bucket: a bucket b runs
        # largest // b rows (at most batch_size, at least 1)
        largest = max(cfg.prefill_buckets)
        self._prefill_rows = {
            b: max(1, min(cfg.batch_size, largest // b))
            for b in cfg.prefill_buckets}
        self._prefill_many = ({} if mode == "decode" else {
            b: _program(
                f"engine_prefill_b{b}",
                lambda p, packed, kp, vp, toks_vec, _b=b:
                prefill_write_many(p, packed, kp, vp, toks_vec, _b),
                donate_argnums=(2, 3, 4))
            for b in cfg.prefill_buckets
        })

        # KV-page IMPORT: write a prefill replica's exported K/V
        # sequence into this engine's pages and scatter the already-
        # computed first token into the device feedback vector — the
        # decode-pool half of the disaggregated handoff. One request
        # per dispatch (handoffs arrive one at a time off the object
        # plane); jit specializes per bucket like prefill.
        def kv_import_one(kp, vp, toks_vec, k_seq, v_seq, pages,
                          slot_first):
            new_k, new_v = list(kp), list(vp)
            with jax.named_scope("kv_append"):
                for i in range(mcfg.n_layers):
                    new_k[i], new_v[i] = write_prefill_kv(
                        new_k[i], new_v[i], k_seq[i], v_seq[i], pages)
            toks_vec = toks_vec.at[slot_first[0]].set(slot_first[1])
            return toks_vec, tuple(new_k), tuple(new_v)

        # one jit, respecialized per padded bucket shape
        self._kv_import = _program("engine_kv_import", kv_import_one,
                                   donate_argnums=(0, 1, 2))
        # persistent device-resident feedback state: admission scatters
        # the prefill's next-token in WITHOUT a host read (a sync
        # stalls the dispatch pipeline; a dispatch does not)
        self._dev_toks = jnp.zeros(cfg.batch_size, jnp.int32)
        # prefill next-tokens awaiting the next burst's combined fetch:
        # (device array [N], [(slot, row)])
        self._pending_firsts: List[Tuple[Any, List[Tuple[_Slot, int]]]] = []
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
        stream = TokenStream(req.future)
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
        stream = TokenStream(req.future)
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
        request's ``max_new``. A burst is one round of the loop that
        dispatched something and fetched once. A prefill launch of
        bucket ``b`` runs ``largest_bucket // b`` rows (at most
        ``batch_size``, at least 1), so every launch computes about the
        positions of one prompt in the largest bucket; summed over the
        launches these are ``prefill_rows`` and ``prefill_positions``
        (rows x bucket), run for the ``prefill_useful_rows`` requests
        admitted in them and their ``prefill_prompt_tokens``;
        ``prefill_by_bucket`` has the same five by bucket (useful rows
        over rows is a bucket's fill share)."""
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
                **prefill,
                "prefill_by_bucket": by_bucket,
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
            req.end_span("engine.queue")
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
        # (_prefill_rows) and a larger group takes more launches.
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
        prompt_tokens = sum(len(req.prompt) for _, req, _ in group)
        with self._lock:
            counts = self._prefill_counts.setdefault(bucket, [0, 0, 0, 0])
            counts[0] += 1
            counts[1] += n
            counts[2] += len(group)
            counts[3] += prompt_tokens
        with spans.span("engine.prefill_launch", bucket=bucket, rows=n,
                        useful_rows=len(group),
                        prompt_tokens=prompt_tokens):
            nxt, self._dev_toks, self._k_pages, self._v_pages = \
                self._prefill_many[bucket](
                    self.params, jnp.asarray(packed), self._k_pages,
                    self._v_pages, self._dev_toks)
        self._pending_firsts.append((nxt, rows))

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
        L = self.mcfg.n_layers
        KV, D = self.mcfg.n_kv_heads, self.mcfg.head_dim
        k_pad = np.zeros((L, bucket, KV, D), k.dtype)
        v_pad = np.zeros((L, bucket, KV, D), v.dtype)
        k_pad[:, :plen] = k
        v_pad[:, :plen] = v
        # pad rows past the prompt are DON'T-CARE (appends overwrite,
        # attention masks by seq_len); pages past the allocation park
        page_list = (pages + [self._parking_page] * n_prog)[:n_prog]
        slot_idx = self._slots.index(slot)
        self._dev_toks, self._k_pages, self._v_pages = self._kv_import(
            self._k_pages, self._v_pages, self._dev_toks,
            jnp.asarray(k_pad), jnp.asarray(v_pad),
            jnp.asarray(np.asarray(page_list, np.int32)),
            jnp.asarray(np.asarray([slot_idx, first], np.int32)))
        req.out = [first]
        self._maybe_finish(slot)  # max_new == 1 finishes at admission
        self._hand_out(req)

    def _hand_out(self, req: _Request) -> None:
        """Push what ``req.out`` has gained onto its stream, and close
        the request's spans as it passes their ends."""
        if req.stream is not None:
            new = req.out[req.streamed:]
            if new:
                req.stream._q.put(new)
            req.streamed += len(new)
            if req.future.done():
                req.stream._q.put(_STREAM_END)
        if not req.got_first and req.out:
            req.got_first = True
            req.end_span("engine.first_token")
        if req.future.done():
            req.end_span("engine.decode")

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
        with spans.span("engine.dispatch", live_slots=len(active),
                        live_ctx_tokens=sum(s.seq_len for s in active)
                        ) as burst:
            pending = self._dispatch_burst(active)
            steps = sum(chunk for _, chunk in pending)
            burst.fields.update(steps=steps, chunks=len(pending))

        # ONE fetch per burst: chunk outputs + any pending prefill
        # first-tokens, concatenated on device, read together
        firsts, self._pending_firsts = self._pending_firsts, []
        parts = [outs.reshape(-1) for outs, _ in pending]
        parts.extend(arr for arr, _rows in firsts)
        if not parts:
            return
        with spans.span("engine.fetch"):
            flat = np.asarray(jnp.concatenate(parts)
                              if len(parts) > 1 else parts[0])
        with spans.span("engine.deliver") as deliver:
            kept = self._deliver(active, pending, firsts, flat)
            deliver.fields["kept_tokens"] = kept
        with self._lock:      # a burst's counts land together
            self._bursts += 1
            self._decode_steps += steps
            self._decode_tokens_kept += kept

    def _dispatch_burst(self, active: List[_Slot]) -> List[Tuple[Any, int]]:
        """Upload the burst's lens + page table and dispatch its decode
        chunks back-to-back; returns [(chunk's tokens on the device,
        its steps)]."""
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
        dev_table, dev_lens = self._split_packed(jnp.asarray(packed))

        # async burst: dispatch chunks back-to-back WITHOUT reading
        # results (jax dispatch is async).
        # The host materializes tokens ONCE per burst in a single
        # combined fetch — or per-chunk when EOS detection is
        # configured (early exit needs the values).
        inflight = 0
        pending: List[Tuple[Any, int]] = []
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
            (outs, dev_toks, dev_lens, self._k_pages,
             self._v_pages) = self._decode_chunks[chunk](
                 self.params, dev_toks, self._k_pages, self._v_pages,
                 dev_table, dev_lens)
            self.num_steps += 1
            pending.append((outs, chunk))
            inflight += chunk
            for s in active:
                s.seq_len += chunk
            if self.cfg.eos_id is not None:
                break  # EOS needs the values: one chunk per burst
        self._dev_toks = dev_toks
        return pending

    def _deliver(self, active: List[_Slot], pending: List[Tuple[Any, int]],
                 firsts: List[Tuple[Any, List[Tuple[_Slot, int]]]],
                 flat: np.ndarray) -> int:
        """Distribute a burst's fetched tokens to its requests, finish
        those that are done and feed the streams. Returns how many of
        the burst's decode tokens stayed in a request's output."""
        # first-tokens sit after this burst's chunk rows
        off = sum(c * self.cfg.batch_size for _, c in pending)
        for arr, rows in firsts:
            for slot, r in rows:
                if slot.req is not None:
                    slot.req.out.insert(0, int(flat[off + r]))
            off += len(arr)
        live = {id(s) for s in active}
        kept = -sum(len(s.req.out) for s in active if s.req is not None)
        pos = 0
        for outs, chunk in pending:
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
