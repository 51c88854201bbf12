"""Arithmetic the per-layer metric readers share.  Each takes the run's
data (``spec``, ``window``, ``trace``, ``peaks``, ``counts``) and returns
a number, or None where the run has nothing to read."""
from __future__ import annotations

from typing import Iterable, Optional, Tuple


def module_time(data: dict, prefixes: Iterable[str]) -> Tuple[int, float]:
    """(executions, device seconds) of the programs whose name starts with
    one of ``prefixes``, in the traced window."""
    n, t = 0, 0.0
    for name, (k, s) in (data["trace"] or {}).get("modules", {}).items():
        if any(name.startswith(p) for p in prefixes):
            n, t = n + k, t + s
    return n, t


def idle_share(data: dict) -> Optional[float]:
    tr = data["trace"]
    if not tr or tr["window_s"] <= 0 or tr["devices"] == 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mfu(data: dict, flops_per_item: int, items: int) -> Optional[float]:
    tr, peaks = data["trace"], data["peaks"]
    if not tr or not peaks or items == 0:
        return None
    rate = flops_per_item * items / tr["window_s"]
    return 100.0 * rate / (peaks["bf16_flops_per_s"] * tr["devices"])
