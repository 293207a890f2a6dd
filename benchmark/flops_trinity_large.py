"""Operations and bytes that serving the Trinity-Large share REQUIRES,
from shapes and from the engine's counts. Nothing here looks at what the
program executes: padded positions, idle slots, the pages of a ring
that lie outside the window and the sorted copies of picks do not
count.

The chip's share (configuration file): attention, routers, shared
experts, the dense layer and a slice of the vocabulary whole, the routed
experts ``experts_held``. A token's work on the routed experts is its
LOCAL picks: ``experts_per_token x held / router_width`` a layer in
expectation (1/2 here), or what the engine counted (``Counted``).

Attention's own products, two a head and pair of positions (scores,
values; 2 x 2 x 48 x 128 FLOPs a pair): a full layer's prompt over the
causal half, a window layer's over the BAND (row ``i`` sees ``min(i + 1,
window)`` keys), a decode step over the live context in a full layer
and over ``min(context, window)`` a slot in a window layer. The required
work of a window layer is the WINDOW's, whatever the program reads: a
program that read every cached token would read low, not high.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from benchmark.flops import roofline_seconds  # noqa: F401 (the readers')
from benchmark.flops_solar_open2 import experts_touched
from benchmark.weights_trinity_large import dims


def n_window_layers(config: Dict[str, Any]) -> int:
    return sum(k == "sliding_attention" for k in dims(config)["kinds"])


def mixer_params(config: Dict[str, Any]) -> int:
    """Matrix parameters of one layer's attention, window or full: the
    four projections and the output gate."""
    s = dims(config)
    return s["d"] * s["hd"] * (3 * s["h"] + 2 * s["kv"])


def expert_params(config: Dict[str, Any]) -> int:
    s = dims(config)
    return 3 * s["d"] * s["fe"]


def outside_experts_params(config: Dict[str, Any]) -> int:
    """Matrix parameters every token multiplies by, whatever it picks:
    the mixers, the dense layers' feed-forwards, routers, shared experts
    and the head slice (the embedding LOOKUP does no arithmetic)."""
    s = dims(config)
    moe_layers = s["layers"] - s["dense"]
    return (s["layers"] * mixer_params(config)
            + s["dense"] * 3 * s["d"] * s["f"]
            + moe_layers * (s["d"] * s["router"] + 3 * s["d"] * s["fs"])
            + s["v"] * s["d"])


def expected_local_picks(config: Dict[str, Any]) -> float:
    """Local picks a token and expert layer, if routing is uniform."""
    s = dims(config)
    lo, hi = s["held"]
    return s["top_k"] * (hi - lo) / s["router"]


def active_params(config: Dict[str, Any],
                  local_picks: Optional[float] = None) -> float:
    """Parameters a token multiplies by on this chip: everything
    outside the routed experts, and ``local_picks`` experts an expert
    layer."""
    s = dims(config)
    if local_picks is None:
        local_picks = expected_local_picks(config)
    return (outside_experts_params(config)
            + (s["layers"] - s["dense"]) * local_picks
            * expert_params(config))


def param_count(config: Dict[str, Any]) -> int:
    """Parameters held on this chip, to the last: both vocabulary
    slices, the held experts, every norm's scale (four a layer, two a
    head in every mixer, the final one) and the routers' biases."""
    s = dims(config)
    lo, hi = s["held"]
    moe_layers = s["layers"] - s["dense"]
    scales = s["layers"] * (4 * s["d"] + 2 * s["hd"]) + s["d"]
    return (outside_experts_params(config) + s["v"] * s["d"]
            + moe_layers * ((hi - lo) * expert_params(config) + s["router"])
            + scales)


def forward_flops(config: Dict[str, Any], tokens: int,
                  local_picks: Optional[float] = None) -> float:
    """2 x active parameters a token (``serve.model_mfu``'s numerator:
    attention's own products are left out, and a prompt token is
    charged the head like an output token, as the accepted metric
    charges it)."""
    return 2.0 * active_params(config, local_picks) * tokens


def _pair_flops(config: Dict[str, Any]) -> int:
    """FLOPs of one query position against one key position in one
    layer: every head's score and its weighted value."""
    s = dims(config)
    return 2 * 2 * s["h"] * s["hd"]


def band_pairs(n: int, window: int) -> float:
    """Pairs (i, j) with ``i - window < j <= i < n``: row ``i`` sees
    ``min(i + 1, window)`` keys; the whole causal half when the window
    is not shorter than the row."""
    w = min(n, window)
    return w * (w + 1) / 2 + (n - w) * w


def window_prefill_attention(config: Dict[str, Any],
                             prompt_lens: Sequence[int]) -> Dict[str, float]:
    """What attention over each prompt requires in the WINDOW layers:
    the band's two products a head; q, k, v read and the output written
    once, in 2 bytes."""
    s = dims(config)
    layers = n_window_layers(config)
    pairs = sum(band_pairs(n, s["window"]) for n in prompt_lens)
    per_token = (2 * s["h"] + 2 * s["kv"]) * s["hd"] * 2
    return {"flops": float(layers * _pair_flops(config) * pairs),
            "bytes": float(layers * per_token * sum(prompt_lens))}


def window_read(config: Dict[str, Any], live_window_tokens: int,
                live_slots: int, kv_bytes: int = 2) -> Dict[str, float]:
    """What ONE decode step's attention requires in the WINDOW layers:
    K and V of each live slot's ``min(context, window)`` tokens read
    once (``live_window_tokens`` is their sum), every head's two
    products over them; a live slot's queries in and result (float32)
    out beside them."""
    s = dims(config)
    layers = n_window_layers(config)
    row = 2 * s["kv"] * s["hd"] * kv_bytes
    io = live_slots * s["h"] * s["hd"] * (kv_bytes + 4)
    return {"flops": float(layers * _pair_flops(config)
                           * live_window_tokens),
            "bytes": float(layers * (row * live_window_tokens + io))}


def prefill_flops(config: Dict[str, Any], prompt_lens: Sequence[int],
                  local_picks: Optional[float] = None) -> float:
    """What a launch's prompts require: 2 x active parameters (without
    the head) a prompt token, the head once a prompt, causal attention
    over each prompt in the full layers and the band in the window
    layers."""
    s = dims(config)
    head = s["v"] * s["d"]
    per_token = 2.0 * (active_params(config, local_picks) - head)
    full = ((s["layers"] - n_window_layers(config)) * _pair_flops(config)
            * sum(n * (n + 1) / 2 for n in prompt_lens))
    return (per_token * sum(prompt_lens) + 2.0 * head * len(prompt_lens)
            + full + window_prefill_attention(config, prompt_lens)["flops"])


def _cached_rows(config: Dict[str, Any], live_ctx_tokens: float,
                 window_share: float) -> float:
    """Token-layers of keys and values a decode step has to read: the
    live context in every full layer, its window-clipped share in every
    window layer."""
    windows = n_window_layers(config)
    return live_ctx_tokens * (dims(config)["layers"] - windows
                              + windows * window_share)


def decode_step_flops(config: Dict[str, Any], live_slots: int,
                      live_ctx_tokens: int,
                      local_picks: Optional[float] = None,
                      window_share: float = 1.0) -> float:
    return (live_slots * 2.0 * active_params(config, local_picks)
            + _pair_flops(config) * _cached_rows(config, live_ctx_tokens,
                                                 window_share))


def decode_step_bytes(config: Dict[str, Any], live_slots: int,
                      live_ctx_tokens: int,
                      local_picks: Optional[float] = None,
                      weight_bytes: int = 2, kv_bytes: int = 2,
                      state_bytes: int = 4,
                      window_share: float = 1.0) -> float:
    """Least HBM traffic of ONE decode step: the weights outside the
    routed experts once, the held experts that the live slots' local
    picks touch once (uniform over the held: it reads a little high
    under uneven routing), K and V of the live context in the full
    layers and of ``window_share`` of it in the window layers (the
    share of the live context that lies inside its slot's window: 1
    counts every token, which no window layer has to read).
    ``state_bytes`` is the readers' and counts nothing: no layer keeps
    a recurrent state."""
    s = dims(config)
    lo, hi = s["held"]
    if local_picks is None:
        local_picks = expected_local_picks(config)
    touched = (s["layers"] - s["dense"]) * experts_touched(
        hi - lo, local_picks * live_slots)
    rows = (2 * s["kv"] * s["hd"] * kv_bytes
            * _cached_rows(config, float(live_ctx_tokens), window_share))
    return (weight_bytes * (outside_experts_params(config)
                            + touched * expert_params(config)) + rows)


class Counted:
    """This module's functions with the engine's COUNTS in place of
    expectations (``engine_stats``): the local picks a token and layer
    (``moe_picks_local``, ``moe_picks_total``) and, for a decode step,
    the share of the live context that lies inside its slot's window
    (``decode_window_tokens_live`` over ``decode_ctx_tokens_live``).
    That share is the engine's life-long AVERAGE, because
    ``serve.hybrid_decode_roofline`` hands ``decode_step_bytes`` a
    burst's whole ``live_ctx_tokens`` and nothing else; lead-in and
    window are one mix, so it is steady, and
    ``serve.window_read_roofline`` reads each burst's exact
    ``live_window_tokens`` instead. What a reader finds under
    ``ctx["flops"]``."""

    def __init__(self, config: Dict[str, Any],
                 engine_stats: Dict[str, Any]) -> None:
        total = engine_stats.get("moe_picks_total") or 0
        self.local_picks = (
            dims(config)["top_k"] * engine_stats["moe_picks_local"] / total
            if total else expected_local_picks(config))
        ctx = engine_stats.get("decode_ctx_tokens_live") or 0
        self.window_share = (
            engine_stats["decode_window_tokens_live"] / ctx if ctx else 1.0)

    def forward_flops(self, config, tokens):
        return forward_flops(config, tokens, self.local_picks)

    def prefill_flops(self, config, prompt_lens):
        return prefill_flops(config, prompt_lens, self.local_picks)

    def decode_step_flops(self, config, live_slots, live_ctx_tokens):
        return decode_step_flops(config, live_slots, live_ctx_tokens,
                                 self.local_picks, self.window_share)

    def decode_step_bytes(self, config, live_slots, live_ctx_tokens,
                          **sizes):
        return decode_step_bytes(config, live_slots, live_ctx_tokens,
                                 self.local_picks,
                                 window_share=self.window_share, **sizes)

    window_read = staticmethod(window_read)
    window_prefill_attention = staticmethod(window_prefill_attention)
    param_count = staticmethod(param_count)
    roofline_seconds = staticmethod(roofline_seconds)
