"""Arithmetic on the loop's records: medians, the highest percentile a
sample can carry, the open loop's clock and the generator's lateness.
Pure Python/NumPy, no JAX: tested on the CPU as it is used on the chip.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import numpy as np

# a percentile is reported only when at least this many samples lie
# beyond it (choosing-metrics guide, section 1)
BEYOND = 10


def percentile(samples: Sequence[float], q: float,
               beyond: int = BEYOND) -> Optional[float]:
    """The ``q``-th percentile (0 < q < 100) of ``samples`` by the
    nearest-rank rule, or ``None`` when fewer than ``beyond`` samples
    lie above that rank. A missing answer is ``math.inf`` in
    ``samples``: it sorts last, so failures push the tail up and can
    make it infinite, never shorter."""
    s = sorted(samples)
    n = len(s)
    if n == 0:
        return None
    rank = max(1, math.ceil(q / 100.0 * n))       # 1-based nearest rank
    if n - rank < beyond:
        return None
    return float(s[rank - 1])


def median(samples: Sequence[float]) -> Optional[float]:
    """Median of the samples (mean of the middle two for an even
    count), ``None`` for no samples; unrounded."""
    if len(samples) == 0:
        return None
    return float(np.median(np.asarray(samples, dtype=np.float64)))


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start, sorted) of an open
    loop at ``rate`` requests a second over ``seconds``: a Poisson
    process conditioned on its count, so every seed offers the SAME
    amount of work — ``round(rate * seconds)`` requests at independent
    uniform instants — and only the instants change with the seed
    (a traffic mix fixes that seed: ``arrival_seed``)."""
    n = int(round(rate * seconds))
    rng = np.random.default_rng([int(seed), 0xA221])
    return np.sort(rng.uniform(0.0, float(seconds), size=n))


def open_loop_latencies(due, answered, ok) -> list:
    """Latency of each open-loop request on the DUE-time clock: from
    the instant it was due to be sent (not the instant the generator
    got round to sending it) to the answer in the client's hands.
    ``answered[i]`` is ``None`` and/or ``ok[i]`` false for a request
    that failed, was refused or answered wrongly: it counts as
    ``math.inf``."""
    out = []
    for d, a, good in zip(due, answered, ok):
        out.append(float(a - d) if (a is not None and good) else math.inf)
    return out


def lateness(due, sent) -> list:
    """How late the generator sent each request against its schedule
    (seconds, never negative)."""
    return [max(0.0, float(s - d)) for d, s in zip(due, sent)]


def rel_err(got, ref) -> float:
    """Relative 2-norm error of ``got`` against ``ref`` (float64 on the
    host); non-finite input gives ``inf``/``nan``, which no tolerance
    admits."""
    got = np.asarray(got, dtype=np.float64)
    ref = np.asarray(ref, dtype=np.float64)
    return float(np.linalg.norm(got - ref)
                 / max(float(np.linalg.norm(ref)), 1e-30))
