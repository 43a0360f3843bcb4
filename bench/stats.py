"""Arithmetic of the end-to-end metrics, kept apart from the harness so the
tests pin it: percentiles over all samples, time to first token censored
at the window's end, inter-token gaps and the open-loop generator's
lateness."""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile of every sample, linearly interpolated
    between order statistics (numpy's default rule); None when empty."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values: Iterable[float]) -> Optional[float]:
    """The mean of every sample; None when empty."""
    xs = [float(v) for v in values]
    return sum(xs) / len(xs) if xs else None


def ttft_samples(requests: Sequence[Dict], t_end: float) -> List[float]:
    """Time to first token, in seconds, of every request due in the
    window, timed from its DUE time.  A request with no first token by
    ``t_end`` counts at its age then (a lower bound on its TTFT), so a
    stall never hides a request from the tail."""
    out = []
    for r in requests:
        first = r.get("first_token")
        if first is not None and first <= t_end:
            out.append(first - r["due"])
        else:
            out.append(t_end - r["due"])
    return out


def inter_token_gaps(token_times: Dict[int, List[float]], t_start: float,
                     t_end: float) -> List[float]:
    """Every gap between consecutive output tokens of one request whose
    later token landed inside the window.  Time a preempted request spent
    parked falls inside its gap, so it counts."""
    gaps = []
    for times in token_times.values():
        for a, b in zip(times, times[1:]):
            if t_start < b <= t_end:
                gaps.append(b - a)
    return gaps


def time_per_output_token(token_times: Dict[int, List[float]],
                          t_start: float, t_end: float) -> List[float]:
    """Per request with at least two tokens in the window: the mean gap
    between its consecutive tokens there."""
    out = []
    for times in token_times.values():
        inside = [t for t in times if t_start < t <= t_end]
        if len(inside) >= 2:
            out.append((inside[-1] - inside[0]) / (len(inside) - 1))
    return out


def lateness(requests: Sequence[Dict]) -> List[float]:
    """Seconds each request was submitted after it was due: how far the
    open-loop generator fell behind its schedule."""
    return [r["submitted"] - r["due"] for r in requests]

