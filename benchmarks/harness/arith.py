"""From what the client saw to the end-to-end numbers. Pure arithmetic: no
clock is read here, so the tests can feed it a synthetic chunk log.

A chunk log is one dict per request:
  {"due": s | None, "sent": s, "chunks": [(arrival_s, n_tokens), ...],
   "expected": max_new_tokens, "done": s | None, "error": str | None}
Times are seconds on one monotonic clock. A chunk may carry several tokens
(the proxy relays whatever accumulated since its last long-poll).
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

WORST_MS = 1.0e6   # what a failed or refused request counts as, in ms


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (q in 0..100); None when empty."""
    if not values:
        return None
    xs = sorted(values)
    at = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(at))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (at - lo)


def failed(row: Dict[str, Any]) -> bool:
    got = sum(n for _, n in row["chunks"])
    return bool(row.get("error")) or (
        row.get("done") is not None and got != row["expected"])


def tokens_in_window(rows: List[Dict[str, Any]], t0: float,
                     t1: float) -> int:
    """Output tokens that reached the client inside [t0, t1), of finished
    and unfinished requests alike."""
    return sum(n for row in rows for at, n in row["chunks"]
               if t0 <= at < t1)


def tpot_ms(row: Dict[str, Any]) -> Optional[float]:
    """Time per output token of one request: from the chunk with the first
    token to the chunk with the last, over the tokens after the first
    chunk. None where every token came in one chunk."""
    chunks = row["chunks"]
    if len(chunks) < 2:
        return None
    after_first = sum(n for _, n in chunks[1:])
    return (chunks[-1][0] - chunks[0][0]) / after_first * 1e3


def tpot_samples(rows: List[Dict[str, Any]], t0: float,
                 t1: float) -> List[float]:
    """One sample per request that ended in the window; a failed one
    counts as the worst."""
    out = []
    for row in rows:
        if row.get("done") is None or not t0 <= row["done"] < t1:
            continue
        sample = WORST_MS if failed(row) else tpot_ms(row)
        if sample is not None:
            out.append(sample)
    return out


def ttft_samples(rows: List[Dict[str, Any]], t0: float, t1: float,
                 now: Optional[float] = None) -> List[float]:
    """One sample per request DUE in the window (closed loop: sent in it):
    from due to the first token at the client. Failed, or still without a
    token when the books closed at `now`, counts as the worst."""
    out = []
    for row in rows:
        start = row["due"] if row.get("due") is not None else row["sent"]
        if not t0 <= start < t1:
            continue
        if failed(row) or not row["chunks"]:
            out.append(WORST_MS)
        else:
            out.append((row["chunks"][0][0] - start) * 1e3)
    return out


def gap_samples(rows: List[Dict[str, Any]], t0: float,
                t1: float) -> List[float]:
    """Pooled gaps between token-bearing chunks, per token of the later
    chunk, for chunks that arrived in the window (ms)."""
    out = []
    for row in rows:
        chunks = row["chunks"]
        for (a, _), (b, n) in zip(chunks, chunks[1:]):
            if t0 <= b < t1 and n:
                out.append((b - a) / n * 1e3)
    return out


def lateness_samples(rows: List[Dict[str, Any]], t0: float,
                     t1: float) -> List[float]:
    """How late the generator sent each request due in the window (ms)."""
    return [(row["sent"] - row["due"]) * 1e3 for row in rows
            if row.get("due") is not None and t0 <= row["due"] < t1]


def spread(values: Sequence[float]) -> float:
    """Inter-quartile distance over the median, as the contract reads it."""
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
