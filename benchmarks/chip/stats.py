"""Percentile and latency arithmetic of the benchmark.

The nearest-rank rule is copied from ``repro.launch.serve_dssfn``'s
``_percentile`` (index ``round(p/100 * (n-1))`` into the sorted values);
what differs is the handling of requests that never got an answer: they
are kept in the sample as ``+inf``, so a shed, expired or rejected
request can only push a tail up, and an empty sample is an error rather
than 0.
"""
from __future__ import annotations

import math
from typing import Iterable, Sequence

INF = math.inf


def percentile(values: Iterable[float], p: float) -> float:
    """Nearest-rank ``p``-th percentile (0 <= p <= 100) of ``values``;
    ``+inf`` entries (missing answers) sort last."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= p <= 100.0:
        raise ValueError(f"percentile p must be in [0, 100], got {p}")
    idx = min(len(vals) - 1, int(round(p / 100.0 * (len(vals) - 1))))
    return vals[idx]


def latencies_from_due(
    due: Sequence[float], done: Sequence[float | None]
) -> list[float]:
    """Open-loop latency of each request: from when it was due to be
    sent, not from when the generator got round to sending it, to when it
    was answered.  ``done[i] is None`` marks a request that was never
    answered (shed, expired, rejected or failed): its latency is ``+inf``.
    """
    if len(due) != len(done):
        raise ValueError(f"{len(due)} due times but {len(done)} completions")
    return [INF if d is None else d - t for t, d in zip(due, done)]

