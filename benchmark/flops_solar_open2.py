"""Operations and bytes that serving the Solar-Open2 share REQUIRES,
from shapes and from the engine's counted picks. Nothing here looks at
what the program executes: padded positions, idle slots, sorted copies
of picks and rewritten pools do not count.

The chip's share (configuration file): everything outside the routed
experts whole, the routed experts ``experts_held``, a slice of the
vocabulary. A token's work on the routed experts is its LOCAL picks
(those that fall on a held expert): ``experts_per_token x held /
router_width`` a layer in expectation (1 of 8 here), or what the
engine counted (``Counted``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from benchmark.flops import roofline_seconds  # noqa: F401 (the readers')
from benchmark.weights_solar_open2 import dims


def mixer_params(config: Dict[str, Any], index: int) -> int:
    """Matrix parameters of layer ``index``'s mixer."""
    s = dims(config)
    d = s["d"]
    if index in s["gqa"]:
        return d * s["hd"] * (3 * s["h"] + 2 * s["kv"])   # q, o, gate; k, v
    h, dd, r = s["dr_h"], s["dr_d"], s["rank"]
    return 4 * d * h * dd + 2 * (d * r + r * h * dd) + d * h


def expert_params(config: Dict[str, Any]) -> int:
    s = dims(config)
    return 3 * s["d"] * s["fe"]


def outside_experts_params(config: Dict[str, Any]) -> int:
    """Matrix parameters every token multiplies by, whatever it picks:
    the mixers, routers, shared experts and the head slice (the
    embedding LOOKUP does no arithmetic)."""
    s = dims(config)
    per_layer = s["d"] * s["router"] + 3 * s["d"] * s["fs"]
    return (sum(mixer_params(config, i) for i in range(s["layers"]))
            + s["layers"] * per_layer + s["v"] * s["d"])


def expected_local_picks(config: Dict[str, Any]) -> float:
    """Local picks a token and expert layer, if routing is uniform."""
    s = dims(config)
    lo, hi = s["held"]
    return s["top_k"] * (hi - lo) / s["router"]


def active_params(config: Dict[str, Any],
                  local_picks: Optional[float] = None) -> float:
    """Parameters a token multiplies by on this chip: everything
    outside the routed experts, and ``local_picks`` experts a layer."""
    s = dims(config)
    if local_picks is None:
        local_picks = expected_local_picks(config)
    return (outside_experts_params(config)
            + s["layers"] * local_picks * expert_params(config))


def param_count(config: Dict[str, Any]) -> int:
    """Parameters held on this chip (both vocabulary slices, norm
    scales and the convolutions' taps left out: under 0.01 %)."""
    s = dims(config)
    lo, hi = s["held"]
    return (outside_experts_params(config) + s["v"] * s["d"]
            + s["layers"] * (hi - lo) * expert_params(config))


def forward_flops(config: Dict[str, Any], tokens: int,
                  local_picks: Optional[float] = None) -> float:
    """2 x active parameters a token (``serve.model_mfu``'s numerator:
    attention's own products and the recurrence are left out, and a
    prompt token is charged the head like an output token, as the
    accepted metric charges it)."""
    return 2.0 * active_params(config, local_picks) * tokens


def recurrence_flops_per_token(config: Dict[str, Any]) -> float:
    """The delta rule's own products a token: decay, k^T S, the rank-1
    update and S^T q over a [dk, dv] state a head."""
    s = dims(config)
    n_dr = s["layers"] - len(s["gqa"])
    return n_dr * s["dr_h"] * 7.0 * s["dr_d"] * s["dr_d"]


def prefill_flops(config: Dict[str, Any], prompt_lens: Sequence[int],
                  local_picks: Optional[float] = None) -> float:
    """What a launch's prompts require: 2 x active parameters (without
    the head) a prompt token, the head once a prompt, causal attention
    over each prompt in the GQA layers, the recurrence a token."""
    s = dims(config)
    head = s["v"] * s["d"]
    per_token = (2.0 * (active_params(config, local_picks) - head)
                 + recurrence_flops_per_token(config))
    attn = sum(len(s["gqa"]) * 2 * 2 * s["h"] * s["hd"] * n * (n + 1) / 2
               for n in prompt_lens)
    return (per_token * sum(prompt_lens) + 2.0 * head * len(prompt_lens)
            + attn)


def decode_step_flops(config: Dict[str, Any], live_slots: int,
                      live_ctx_tokens: int,
                      local_picks: Optional[float] = None) -> float:
    s = dims(config)
    attn = len(s["gqa"]) * 2 * 2 * s["h"] * s["hd"] * live_ctx_tokens
    return (live_slots * (2.0 * active_params(config, local_picks)
                          + recurrence_flops_per_token(config)) + attn)


def experts_touched(held: int, picks: float) -> float:
    """Held experts with at least one of ``picks`` local picks of one
    layer and step, if those fall uniformly: each such expert's weights
    have to be read once, however many picks it has."""
    return held * (1.0 - (1.0 - 1.0 / held) ** picks)


def decode_step_bytes(config: Dict[str, Any], live_slots: int,
                      live_ctx_tokens: int,
                      local_picks: Optional[float] = None,
                      weight_bytes: int = 2, kv_bytes: int = 2,
                      state_bytes: int = 4) -> float:
    """Least HBM traffic of ONE decode step: the weights outside the
    routed experts once, the held experts that the live slots' local
    picks touch once, a live slot's recurrent state read and written,
    K and V of the live context in the GQA layers."""
    s = dims(config)
    lo, hi = s["held"]
    if local_picks is None:
        local_picks = expected_local_picks(config)
    touched = s["layers"] * experts_touched(hi - lo,
                                            local_picks * live_slots)
    n_dr = s["layers"] - len(s["gqa"])
    state = n_dr * s["dr_h"] * s["dr_d"] * s["dr_d"] * state_bytes * 2
    kv = len(s["gqa"]) * 2 * s["kv"] * s["hd"] * kv_bytes
    return (weight_bytes * (outside_experts_params(config)
                            + touched * expert_params(config))
            + state * float(live_slots) + kv * float(live_ctx_tokens))


class Counted:
    """This module's functions with the engine's COUNTED local picks a
    token and layer in place of the expected (``engine_stats``:
    ``moe_picks_local``, ``moe_picks_total``). What a reader finds
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

    param_count = staticmethod(param_count)
    roofline_seconds = staticmethod(roofline_seconds)
