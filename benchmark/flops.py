"""Operations and bytes that the work REQUIRES, from shapes alone.

Nothing here looks at what the program executes: recomputation, padded
rows and rewritten pools do not count. These are the numerators of
every utilisation and roofline share the benchmark reports.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable

from benchmark.weights import dims


def layer_params(config: Dict[str, Any]) -> int:
    s = dims(config)
    attn = s["d"] * s["hd"] * (2 * s["h"] + 2 * s["kv"])
    mlp = 3 * s["d"] * s["ff"]
    return attn + mlp + 2 * s["d"]


def embedding_params(config: Dict[str, Any]) -> int:
    s = dims(config)
    return s["v"] * s["d"]


def param_count(config: Dict[str, Any]) -> int:
    """Parameters as the program holds them (one embedding, used as the
    head too), final norm included."""
    s = dims(config)
    return (s["layers"] * layer_params(config) + embedding_params(config)
            + s["d"])


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix product for every token:
    the blocks' matrices and the head. The embedding LOOKUP does no
    arithmetic, but the tied head multiplies by the same matrix, so N
    here equals param_count less the norm scales."""
    s = dims(config)
    return (s["layers"] * (layer_params(config) - 2 * s["d"])
            + embedding_params(config))


def causal_attention_flops(config: Dict[str, Any], seq: int) -> float:
    """Forward FLOPs of causal attention over one row of ``seq``
    positions, all layers: QK^T and PV, 2 FLOPs a multiply-add, half of
    the square (the causal triangle, diagonal included)."""
    s = dims(config)
    pairs = seq * (seq + 1) / 2
    return s["layers"] * 2 * 2 * s["h"] * s["hd"] * pairs


def train_step_flops(config: Dict[str, Any], batch: int, seq: int) -> float:
    """6*N*tokens for the matrices (forward 2, backward 4) plus three
    times the forward attention; no recomputation."""
    tokens = batch * seq
    return (6.0 * matmul_params(config) * tokens
            + 3.0 * batch * causal_attention_flops(config, seq))


def forward_flops(config: Dict[str, Any], tokens: int) -> float:
    """2*N a token: what serving a token requires, attention's own
    products left out (under 2 % at the contexts served here)."""
    return 2.0 * matmul_params(config) * tokens


def flash_fwd_bwd(config: Dict[str, Any], batch: int, seq: int
                  ) -> Dict[str, float]:
    """FLOPs and HBM bytes that causal attention needs in a train step
    (forward, then backward with the scores recomputed once, as any
    flash backward must): forward 2 products, backward 5 (recompute
    QK^T, dV, dP, dQ, dK) over the causal half. Bytes: q, k, v, o read
    or written once forward; q, k, v, o, do read and dq, dk, dv written
    backward, at the compute type's 2 bytes, heads repeated to the
    query count as the kernel sees them."""
    s = dims(config)
    pairs = seq * (seq + 1) / 2
    per_product = 2 * s["h"] * s["hd"] * pairs * batch * s["layers"]
    elems = batch * seq * s["h"] * s["hd"] * s["layers"]
    return {"flops": 7 * per_product, "bytes": (4 + 8) * elems * 2.0}


def decode_step_bytes(config: Dict[str, Any], live_tokens: Iterable[int],
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Least HBM traffic of ONE decode step: every weight once, plus K
    and V of every live token of every sequence in the step."""
    s = dims(config)
    kv = 2 * s["layers"] * s["kv"] * s["hd"] * kv_bytes
    return (param_count(config) * float(weight_bytes)
            + kv * float(sum(live_tokens)))


def roofline_seconds(flops: float, nbytes: float, peak: Dict[str, float]
                     ) -> Dict[str, Any]:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
