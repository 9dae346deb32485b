"""Arithmetic on request records: percentiles, first-token and per-token times.

A record is the dict `loadgen` fills for one request.  Times are seconds on
`time.monotonic()`; `due` is when the request was due to be sent, `sent` when
it actually was, `first` / `last` when its first / last token reached the
client.  A request that failed, was refused or hung has `ok` False and counts
as a miss: its time is `miss`, the length of the window (choosing-metrics
section 1: a failed request misses any latency limit).
"""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; None for no values."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    pos = (len(vals) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (pos - lo))


def ttft_ms(rec: dict, miss_s: float) -> float:
    """First-token time from the instant the request was DUE, not from when
    its thread got to send it: the wait a stalled generator or server imposes
    on later requests counts."""
    if not rec.get("ok") or rec.get("first") is None:
        return miss_s * 1000.0
    return (rec["first"] - rec["due"]) * 1000.0


def tpot_ms(rec: dict, miss_s: float) -> Optional[float]:
    """(last-token time - first-token time) / (output tokens - 1) of one
    request.  Per request and not per gap: the server streams tokens a decode
    chunk at a time, so single gaps read the chunk length.  None for a
    request of one token, which has no gap; a failed request is a miss."""
    if not rec.get("ok"):
        return miss_s * 1000.0
    n = rec.get("n_tokens", 0)
    if n < 2:
        return None
    return (rec["last"] - rec["first"]) * 1000.0 / (n - 1)


def ttfts(records: Iterable[dict], miss_s: float) -> List[float]:
    return [ttft_ms(r, miss_s) for r in records]


def tpots(records: Iterable[dict], miss_s: float) -> List[float]:
    out = [tpot_ms(r, miss_s) for r in records]
    return [v for v in out if v is not None]


def completed_tokens(records: Iterable[dict], t0: float, t1: float) -> int:
    """Output tokens of the requests that completed inside [t0, t1]."""
    return sum(
        r["n_tokens"] for r in records
        if r.get("ok") and r.get("last") is not None and t0 <= r["last"] <= t1
    )


def phase_counts(records: Sequence[dict]) -> dict:
    """Sent / succeeded / failed / refused / hung of one phase."""
    return {
        "sent": len(records),
        "succeeded": sum(1 for r in records if r.get("ok")),
        "refused": sum(1 for r in records if r.get("status") == 503),
        "hung": sum(1 for r in records if r.get("hung")),
        "failed": sum(
            1 for r in records
            if not r.get("ok") and r.get("status") != 503 and not r.get("hung")
        ),
    }
