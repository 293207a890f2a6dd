"""What run.py, the drivers and the readers share."""

from __future__ import annotations

import json
import os
from typing import Any, Dict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def say(tag: str, **fields: Any) -> None:
    """An earlier line of stdout: everything worth seeing that is not
    the result."""
    print(f"[{tag}] " + " ".join(
        f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in fields.items()), flush=True)


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def deep_merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = (deep_merge(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def passes(checks) -> bool:
    """``correct``: there is something compared, and every number of
    [(name, value, limit)] is a number and within its limit."""
    return bool(checks) and all(
        value == value and value <= limit for _, value, limit in checks)
