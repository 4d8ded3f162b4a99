"""Percentile and rate arithmetic of the end-to-end metrics.

Every number is taken over all sessions and all gaps of the window: no
median of chunks, no trimming. Times are seconds on one monotonic clock.
"""

from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile by linear interpolation between order
    statistics (numpy's default); ``None`` of nothing."""
    if not values:
        return None
    ordered = sorted(values)
    rank = (len(ordered) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def window_metrics(sessions: List[Dict[str, Any]], t0: float,
                   seconds: float) -> Dict[str, Optional[float]]:
    """``sessions``: each with ``t_send`` and ``token_times`` (receipt of each
    output token). A token counts toward the rate if it was received inside
    ``[t0, t0 + seconds]``; a session's time to first token counts if it was
    begun inside the window; a gap counts if the token that closes it was
    received inside the window. A stall therefore shows as one long gap and
    as tokens missing from the rate, never as a shorter window."""
    t1 = t0 + seconds
    tokens, ttft, gaps = 0, [], []
    for s in sessions:
        times = s["token_times"]
        tokens += sum(1 for t in times if t0 <= t <= t1)
        if times and t0 <= s["t_send"] <= t1:
            ttft.append((times[0] - s["t_send"]) * 1e3)
        gaps.extend((b - a) * 1e3 for a, b in zip(times, times[1:])
                    if t0 <= b <= t1)
    return {
        "output_tokens_per_s": tokens / seconds,
        "ttft_p50_ms": percentile(ttft, 50),
        "ttft_p95_ms": percentile(ttft, 95),
        "token_gap_p50_ms": percentile(gaps, 50),
        "token_gap_p95_ms": percentile(gaps, 95),
        "sessions_timed": len(ttft),
        "gaps_timed": len(gaps),
    }


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"benchmark/peaks.json with its source")
    return table[device_kind]
