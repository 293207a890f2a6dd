"""The one general generator of inputs. A traffic mix or a training job
is a data file under ``benchmark/traffic/``; this reads its parameters
and makes the inputs from ``--seed``.

Steadiness: every seed gives the SAME multiset of lengths and of gaps
between arrivals (the quantiles of the stated distributions at n evenly
spaced probabilities), and a serving mix keeps them in ONE order, that
of its ``schedule_seed``: one fixed realisation of the arrival process,
replayed, while the token ids and the weights follow --seed. On the
chip the order alone moved a p90 by +-30 % between seeds while runs of
one order agreed within a few percent (PERF.md, PR 24).
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), *stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict[str, Any], n: int, rng: np.random.Generator
            ) -> np.ndarray:
    """n whole lengths: the quantiles of a log-normal distribution
    (``median``, ``sigma``), clipped to [min, max], shuffled."""
    z = np.array([statistics.NormalDist().inv_cdf(q)
                  for q in _quantiles(n)])
    vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    vals = np.clip(np.rint(vals), spec["min"], spec["max"])
    return rng.permutation(vals.astype(np.int64))


def arrivals(spec: Dict[str, Any], seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds) of an open loop of Poisson arrivals at
    ``rate_rps``: the quantiles of the exponential gap, shuffled."""
    n = max(1, round(float(spec["rate_rps"]) * seconds))
    gaps = rng.permutation(-np.log1p(-_quantiles(n)))
    # the last arrival falls half a mean gap before the window closes
    due = np.cumsum(gaps) * (seconds * (n - 0.5) / n / gaps.sum())
    return due - due[0] * 0.5


def serve_requests(mix: Dict[str, Any], vocab: int, seed: int,
                   seconds: float, stream: int = 0
                   ) -> List[Dict[str, Any]]:
    """[{"id", "due_s", "prompt": [ids], "max_new": int}, ...] in due
    order, every prompt its own uniform token ids. ``stream`` tells
    apart the schedules one run draws (the window's is 0, the lead-in
    before it 1)."""
    order = int(mix["schedule_seed"])
    due = arrivals(mix["arrivals"], seconds, _rng(order, 1, stream))
    n = len(due)
    p_len = lengths(mix["prompt_tokens"], n, _rng(order, 2, stream))
    o_len = lengths(mix["output_tokens"], n, _rng(order, 3, stream))
    ids = _rng(seed, 4, stream)
    return [{"id": i, "due_s": float(due[i]),
             "prompt": ids.integers(1, vocab, int(p_len[i])).tolist(),
             "max_new": int(o_len[i])} for i in range(n)]


def train_batch(job: Dict[str, Any], vocab: int, seed: int, step: int
                ) -> np.ndarray:
    """The batch of optimizer step ``step`` (1-based): [batch, seq_len +
    1] token ids, every row its own, a pure function of (seed, step)."""
    return _rng(seed, 7, step).integers(
        0, vocab, (int(job["batch"]), int(job["seq_len"]) + 1),
        dtype=np.int32)
