"""Operations and bytes that serving the openPangu-Ultra-MoE share
REQUIRES, from shapes and from the engine's counted picks. Nothing here
looks at what the program executes: padded positions, idle slots, the
zeros that pad a latent row to whole lanes and the sorted copies of
picks do not count.

The chip's share (configuration file): latent attention, routers,
shared experts, the dense layer and a slice of the vocabulary whole,
the routed experts ``experts_held``. A token's work on the routed
experts is its LOCAL picks: ``experts_per_token x held / router_width``
a layer in expectation (1/2 here), or what the engine counted
(``Counted``).

Attention's own products are counted in the form each path has to
compute. A prompt (expanded, as published): every head scores keys of
``nope + rope`` and weighs values of ``v_head_dim`` over the causal
half. A decode step (absorbed): every head scores the cached row's
``kv_lora_rank + rope`` numbers and weighs its ``kv_lora_rank``, and
those ``(kv_lora_rank + rope)`` numbers a token and layer are all it
has to read (1,152 B in bfloat16).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from benchmark.flops import roofline_seconds  # noqa: F401 (the readers')
from benchmark.flops_solar_open2 import experts_touched
from benchmark.weights_openpangu_ultra import dims


def mixer_params(config: Dict[str, Any]) -> int:
    """Matrix parameters of one layer's latent attention."""
    s = dims(config)
    d, h = s["d"], s["h"]
    return (d * s["q_rank"] + s["q_rank"] * h * (s["nope"] + s["rope"])
            + d * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * h * (s["nope"] + s["vd"]) + h * s["vd"] * d)


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
    """Parameters held on this chip (both vocabulary slices; norm
    scales left out: under 0.01 %)."""
    s = dims(config)
    lo, hi = s["held"]
    return (outside_experts_params(config) + s["v"] * s["d"]
            + (s["layers"] - s["dense"]) * (hi - lo)
            * expert_params(config))


def forward_flops(config: Dict[str, Any], tokens: int,
                  local_picks: Optional[float] = None) -> float:
    """2 x active parameters a token (``serve.model_mfu``'s numerator:
    attention's own products are left out, and a prompt token is
    charged the head like an output token, as the accepted metric
    charges it)."""
    return 2.0 * active_params(config, local_picks) * tokens


def latent_prefill_attention(config: Dict[str, Any],
                             prompt_lens: Sequence[int]) -> Dict[str, float]:
    """What causal attention over each prompt requires in the expanded
    form, all layers: two products a head over the causal half, at the
    true widths; q, k, v read and the output written once, in 2
    bytes."""
    s = dims(config)
    pairs = sum(n * (n + 1) / 2 for n in prompt_lens)
    per_pair = 2 * s["h"] * (s["nope"] + s["rope"] + s["vd"])
    per_token = s["h"] * (2 * (s["nope"] + s["rope"]) + 2 * s["vd"]) * 2
    return {"flops": float(s["layers"] * per_pair * pairs),
            "bytes": float(s["layers"] * per_token * sum(prompt_lens))}


def latent_read(config: Dict[str, Any], live_ctx_tokens: int,
                live_slots: int, kv_bytes: int = 2) -> Dict[str, float]:
    """What ONE decode step's attention requires in the absorbed form,
    all layers: every head scores a cached row's ``kv_rank + rope``
    numbers and weighs its ``kv_rank``; each live token's row is read
    once, a live slot's queries in and mixed latents (float32) out."""
    s = dims(config)
    row = s["kv_rank"] + s["rope"]
    io = live_slots * s["h"] * (row * kv_bytes + s["kv_rank"] * 4)
    return {"flops": float(s["layers"] * 2 * s["h"] * (row + s["kv_rank"])
                           * live_ctx_tokens),
            "bytes": float(s["layers"] * (row * kv_bytes * live_ctx_tokens
                                          + io))}


def prefill_flops(config: Dict[str, Any], prompt_lens: Sequence[int],
                  local_picks: Optional[float] = None) -> float:
    """What a launch's prompts require: 2 x active parameters (without
    the head) a prompt token, the head once a prompt, expanded causal
    attention over each prompt."""
    s = dims(config)
    head = s["v"] * s["d"]
    per_token = 2.0 * (active_params(config, local_picks) - head)
    return (per_token * sum(prompt_lens) + 2.0 * head * len(prompt_lens)
            + latent_prefill_attention(config, prompt_lens)["flops"])


def decode_step_flops(config: Dict[str, Any], live_slots: int,
                      live_ctx_tokens: int,
                      local_picks: Optional[float] = None) -> float:
    return (live_slots * 2.0 * active_params(config, local_picks)
            + latent_read(config, live_ctx_tokens, live_slots)["flops"])


def decode_step_bytes(config: Dict[str, Any], live_slots: int,
                      live_ctx_tokens: int,
                      local_picks: Optional[float] = None,
                      weight_bytes: int = 2, kv_bytes: int = 2,
                      state_bytes: int = 4) -> float:
    """Least HBM traffic of ONE decode step: the weights outside the
    routed experts once, the held experts that the live slots' local
    picks touch once, the latent rows of the live context in every
    layer. ``state_bytes`` is the readers' and counts nothing: no layer
    keeps a recurrent state."""
    s = dims(config)
    lo, hi = s["held"]
    if local_picks is None:
        local_picks = expected_local_picks(config)
    touched = (s["layers"] - s["dense"]) * experts_touched(
        hi - lo, local_picks * live_slots)
    rows = (s["layers"] * (s["kv_rank"] + s["rope"]) * kv_bytes
            * float(live_ctx_tokens))
    return (weight_bytes * (outside_experts_params(config)
                            + touched * expert_params(config)) + rows)


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

    latent_read = staticmethod(latent_read)
    latent_prefill_attention = staticmethod(latent_prefill_attention)
    param_count = staticmethod(param_count)
    roofline_seconds = staticmethod(roofline_seconds)
