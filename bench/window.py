"""End-to-end arithmetic over one measured window.

The harness reads each request's raw record (scheduled arrival, admission,
the time of every emitted token, outcome) and does its own arithmetic here,
so no program change can alter how a metric is computed.

- A request cut at the end of the drain has an infinite latency: its
  missing first token counts as an infinite TTFT, and in an open-loop mix
  the token it still owed counts as one infinite gap. It is not a failure.
- Rates are all tokens emitted inside the window over the window's length.
- Tails are percentiles over all of the window's requests or gaps
  (nearest rank, so an infinite sample can be the percentile).
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

__all__ = ["Record", "percentile", "window_metrics"]

INF = float("inf")


@dataclasses.dataclass
class Record:
    arrival_s: float
    admitted_s: Optional[float]
    token_times: List[float]      # every emitted token, on the run's clock
    owed: int                     # tokens still due when the run ended
    outcome: str                  # ok | cut | rejected | timed_out | error


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p% of the
    samples at or below it."""
    xs = sorted(values)
    if not xs:
        return math.nan
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[k - 1]


def window_metrics(records: List[Record], seconds: float, *,
                   open_loop: bool) -> dict:
    """TTFT and gap tails, queue wait and the output rate of one window."""
    ttft, gaps, waits, emitted = [], [], [], 0
    for r in records:
        first = r.token_times[0] if r.token_times else None
        ttft.append(first - r.arrival_s if first is not None else INF)
        if r.admitted_s is not None:
            waits.append(r.admitted_s - r.arrival_s)
        elif r.outcome == "cut":
            waits.append(INF)
        ts = r.token_times
        if open_loop:
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
            if r.outcome == "cut" and ts and r.owed > 0:
                gaps.append(INF)
        else:
            inside = [t for t in ts if t <= seconds]
            gaps.extend(b - a for a, b in zip(inside, inside[1:]))
        emitted += sum(1 for t in ts if t <= seconds)
    return {
        "ttft_p50_s": percentile(ttft, 50),
        "ttft_p90_s": percentile(ttft, 90),
        "tpot_p95_s": percentile(gaps, 95),
        "queue_wait_p50_s": percentile(waits, 50),
        "output_tokens_per_s": emitted / seconds,
        "n_gaps": len(gaps),
        "n_tokens": emitted,
    }
