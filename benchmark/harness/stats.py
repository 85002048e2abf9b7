"""Percentiles and window arithmetic on a client-side request log.

A request record is a dict: ``due`` (when it was due to be sent: the
schedule in an open loop, the send time in a closed one), ``sent``,
``chunks`` (list of ``(arrival time, n tokens)``), ``done`` (time of the
final chunk or None), ``error`` (None or the exception's class name),
``engine_ttft_s`` (the engine's own figure from the final chunk or
None). All times are ``time.time()`` of one host.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default),
    ``q`` in [0, 100]. None for an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def first_token_time(rec: Dict[str, Any]) -> Optional[float]:
    for t, n in rec["chunks"]:
        if n > 0:
            return t
    return None


def tokens_in_window(records: List[Dict[str, Any]], t0: float,
                     t1: float) -> int:
    """Output tokens whose chunk reached the client in [t0, t1]."""
    return sum(n for r in records for t, n in r["chunks"] if t0 <= t <= t1)


def ttft_sample_ms(records: List[Dict[str, Any]], t0: float,
                   t1: float) -> List[float]:
    """Time to first token, from ``due`` to the first chunk that holds a
    token, of every request that was due inside the window. A request
    that failed, or had no token when the run stopped waiting, enters at
    the window's length: failing cannot improve the tail."""
    out = []
    for r in records:
        if not (t0 <= r["due"] <= t1):
            continue
        first = first_token_time(r)
        if r["error"] is not None or first is None:
            out.append((t1 - t0) * 1e3)
        else:
            out.append((first - r["due"]) * 1e3)
    return out


def itl_sample_ms(records: List[Dict[str, Any]], t0: float,
                  t1: float) -> List[float]:
    """Gaps between streamed tokens delivered inside the window: a
    chunk's gap to the chunk before it, divided over its tokens, once
    per token. The first chunk of a request has no gap."""
    out = []
    for r in records:
        prev = None
        for t, n in r["chunks"]:
            if n <= 0:
                continue
            if prev is not None and t0 <= t <= t1:
                out.extend([(t - prev) * 1e3 / n] * n)
            prev = t
    return out


def failed_in_window(records, t0, t1) -> int:
    return sum(1 for r in records
               if t0 <= r["due"] <= t1 and r["error"] is not None)


def attempted_in_window(records, t0, t1) -> int:
    return sum(1 for r in records if t0 <= r["due"] <= t1)


def client_overhead_sample_ms(records, t0, t1) -> List[float]:
    """Client TTFT minus the engine's own ttft of the same request."""
    out = []
    for r in records:
        first = first_token_time(r)
        if (t0 <= r["due"] <= t1 and first is not None
                and r.get("engine_ttft_s") is not None):
            out.append((first - r["sent"]) * 1e3
                       - r["engine_ttft_s"] * 1e3)
    return out
