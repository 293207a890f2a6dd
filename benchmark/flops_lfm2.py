"""Operations and bytes that serving the LFM2 stage REQUIRES, from shapes
and from the engine's counts. Nothing here looks at what the program
executes: padded positions, idle slots, dummy rows and the sorted
copies of picks do not count.

The chip holds whole layers (configuration file): every expert of each
expert layer, the whole vocabulary as embedding and tied head. A
token's work on the routed experts is its ``experts_per_token`` picks a
layer, all of them local here, or what the engine counted
(``Counted``).

A conv layer's mixer is two products with weights (``w_in`` [d, 3d],
``w_out`` [d, d]) and a band of elementwise work (``B * x``, the three
taps, ``C * y``) that is counted as the taps' multiply-adds, 2 x K x d
a token; it keeps ``K - 1`` rows of d a slot in float32. Attention's own
products, two a head and pair of positions (scores, values; 2 x 2 x 32
x 64 FLOPs a pair), run in the full layers: a prompt over the causal
half, a decode step over the live context.

``grouped_decode`` is what ``serve.moe_grouped_decode_roofline`` reads:
the least work of the held experts' grouped products in decode steps,
from the engine's count of experts touched (each such expert's three
matrices read once a step and layer) and of local picks (a pick's rows
in and out, and its 6 x d x f FLOPs).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from benchmark.flops import roofline_seconds  # noqa: F401 (the readers')
from benchmark.flops_solar_open2 import experts_touched
from benchmark.weights_lfm2 import dims  # noqa: F401 (the readers')


def n_layers_of(config: Dict[str, Any], kind: str) -> int:
    return sum(k == kind for k in dims(config)["kinds"])


def conv_mixer_params(config: Dict[str, Any]) -> int:
    """A conv layer's mixer: the input projection to B, C and x, the
    output projection and the taps."""
    s = dims(config)
    return 4 * s["d"] * s["d"] + s["taps"] * s["d"]


def attention_mixer_params(config: Dict[str, Any]) -> int:
    """A full layer's mixer: the four projections (the head norms'
    scales are counted with the norms)."""
    s = dims(config)
    return s["d"] * s["hd"] * (2 * s["h"] + 2 * s["kv"])


def expert_params(config: Dict[str, Any]) -> int:
    s = dims(config)
    return 3 * s["d"] * s["fe"]


def moe_layers(config: Dict[str, Any]) -> int:
    s = dims(config)
    return s["layers"] - s["dense"]


def outside_experts_params(config: Dict[str, Any]) -> int:
    """Parameters every token multiplies by, whatever it picks: the
    mixers, the dense layers' feed-forwards, the routers and the head
    (the embedding LOOKUP does no arithmetic; the tied head does)."""
    s = dims(config)
    return (n_layers_of(config, "conv") * conv_mixer_params(config)
            + n_layers_of(config, "full_attention")
            * attention_mixer_params(config)
            + s["dense"] * 3 * s["d"] * s["f"]
            + moe_layers(config) * s["d"] * s["router"]
            + s["v"] * s["d"])


def expected_local_picks(config: Dict[str, Any]) -> float:
    """Local picks a token and expert layer, if routing is uniform."""
    s = dims(config)
    lo, hi = s["held"]
    return s["top_k"] * (hi - lo) / s["router"]


def active_params(config: Dict[str, Any],
                  local_picks: Optional[float] = None) -> float:
    """Parameters a token multiplies by on this chip: everything outside
    the routed experts, and ``local_picks`` experts an expert layer."""
    if local_picks is None:
        local_picks = expected_local_picks(config)
    return (outside_experts_params(config)
            + moe_layers(config) * local_picks * expert_params(config))


def param_count(config: Dict[str, Any]) -> int:
    """Parameters held on this chip, to the last: the held experts,
    the routers and their biases, every norm's scale (two a layer, two a
    full layer's heads, the final one) and the embedding once (it is
    the head too)."""
    s = dims(config)
    lo, hi = s["held"]
    scales = (s["layers"] * 2 * s["d"]
              + n_layers_of(config, "full_attention") * 2 * s["hd"] + s["d"])
    return (outside_experts_params(config)
            + moe_layers(config) * ((hi - lo) * expert_params(config)
                                    + s["router"])
            + scales)


def forward_flops(config: Dict[str, Any], tokens: int,
                  local_picks: Optional[float] = None) -> float:
    """2 x active parameters a token (``serve.model_mfu``'s numerator:
    attention's own products are left out, and a prompt token is
    charged the head like an output token, as the accepted metric
    charges it)."""
    return 2.0 * active_params(config, local_picks) * tokens


def _pair_flops(config: Dict[str, Any]) -> int:
    """FLOPs of one query position against one key position in one full
    layer: every head's score and its weighted value."""
    s = dims(config)
    return 2 * 2 * s["h"] * s["hd"]


def prefill_flops(config: Dict[str, Any], prompt_lens: Sequence[int],
                  local_picks: Optional[float] = None) -> float:
    """What a launch's prompts require: 2 x active parameters (without
    the head) a prompt token, the head once a prompt, causal attention
    over each prompt in the full layers."""
    s = dims(config)
    head = s["v"] * s["d"]
    per_token = 2.0 * (active_params(config, local_picks) - head)
    causal = (n_layers_of(config, "full_attention") * _pair_flops(config)
              * sum(n * (n + 1) / 2 for n in prompt_lens))
    return (per_token * sum(prompt_lens) + 2.0 * head * len(prompt_lens)
            + causal)


def decode_step_flops(config: Dict[str, Any], live_slots: int,
                      live_ctx_tokens: int,
                      local_picks: Optional[float] = None) -> float:
    return (live_slots * 2.0 * active_params(config, local_picks)
            + _pair_flops(config) * n_layers_of(config, "full_attention")
            * live_ctx_tokens)


def decode_step_bytes(config: Dict[str, Any], live_slots: int,
                      live_ctx_tokens: int,
                      local_picks: Optional[float] = None,
                      weight_bytes: int = 2, kv_bytes: int = 2,
                      state_bytes: int = 4) -> float:
    """Least HBM traffic of ONE decode step: the weights outside the
    routed experts once, the held experts that the live slots' local
    picks touch once (uniform over the held: it reads a little high
    under uneven routing), K and V of the live context in the full
    layers, and each live slot's convolution tails read and written
    (``state_bytes`` a number)."""
    s = dims(config)
    lo, hi = s["held"]
    if local_picks is None:
        local_picks = expected_local_picks(config)
    touched = moe_layers(config) * experts_touched(hi - lo,
                                                   local_picks * live_slots)
    rows = (2 * s["kv"] * s["hd"] * kv_bytes * live_ctx_tokens
            * n_layers_of(config, "full_attention"))
    tails = (2 * live_slots * n_layers_of(config, "conv")
             * (s["taps"] - 1) * s["d"] * state_bytes)
    return (weight_bytes * (outside_experts_params(config)
                            + touched * expert_params(config))
            + rows + tails)


def grouped_decode(config: Dict[str, Any], experts_touched_count: int,
                   local_picks: int, weight_bytes: int = 2,
                   act_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and bytes of the held experts' grouped products in decode
    steps: ``experts_touched_count`` (summed over steps and expert
    layers) experts' three matrices read once, and ``local_picks`` (the
    same sum) picks' rows in and out, 3 x (d + f) a pick (the gate and
    up products read a row of d and write one of f each, the down
    product reads f and writes d); 6 x d x f FLOPs a pick."""
    s = dims(config)
    d, f = s["d"], s["fe"]
    return {"flops": 6.0 * d * f * local_picks,
            "bytes": float(weight_bytes * 3 * d * f * experts_touched_count
                           + act_bytes * 3 * (d + f) * local_picks)}


class Counted:
    """This module's functions with the engine's COUNTS in place of
    expectations (``engine_stats``): the local picks a token and layer
    (``moe_picks_local``, ``moe_picks_total``). What a reader finds
    under ``ctx["flops"]``."""

    def __init__(self, config: Dict[str, Any],
                 engine_stats: Dict[str, Any]) -> None:
        total = engine_stats.get("moe_picks_total") or 0
        self.local_picks = (
            dims(config)["top_k"] * engine_stats["moe_picks_local"] / total
            if total else expected_local_picks(config))

    def forward_flops(self, config, tokens):
        return forward_flops(config, tokens, self.local_picks)

    def prefill_flops(self, config, prompt_lens):
        return prefill_flops(config, prompt_lens, self.local_picks)

    def decode_step_flops(self, config, live_slots, live_ctx_tokens):
        return decode_step_flops(config, live_slots, live_ctx_tokens,
                                 self.local_picks)

    def decode_step_bytes(self, config, live_slots, live_ctx_tokens,
                          **sizes):
        return decode_step_bytes(config, live_slots, live_ctx_tokens,
                                 self.local_picks, **sizes)

    grouped_decode = staticmethod(grouped_decode)
    param_count = staticmethod(param_count)
    roofline_seconds = staticmethod(roofline_seconds)
