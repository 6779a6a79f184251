"""Open-loop arrival schedules for the served traffic mixes.

Every slot of the server carries one stream at a time; frames arrive at a
slot as a Poisson process whose rate follows a Zipf law over the slots.
The schedule is a list of due times per slot, in seconds after the window
opens, drawn in full before the window from ``arrival_seed``: the
generator sends each frame at ``window start + due`` whatever happened to
the frames before it, and every frame's latency is counted from that due
time, so a stall shows as latency of every frame it delays.
"""
from __future__ import annotations

import numpy as np


def zipf_rates(total: float, slots: int, s: float) -> np.ndarray:
    """Per-slot rates ``total * i**-s / sum_j j**-s`` for ranks
    ``i = 1..slots``, hottest first; they sum to ``total``."""
    if total <= 0 or slots < 1:
        raise ValueError(f"need a positive rate and slots, got {total}, "
                         f"{slots}")
    w = np.arange(1, slots + 1, dtype=np.float64) ** -float(s)
    return total * w / w.sum()


def counts(rates: np.ndarray, seconds: float) -> np.ndarray:
    """Arrivals per slot in ``seconds``: each slot's expected count
    rounded, the remainders handed to the largest fractions, so that
    they sum to ``round(sum(rates) * seconds)``."""
    want = np.asarray(rates, np.float64) * seconds
    out = np.floor(want).astype(int)
    short = int(round(want.sum())) - int(out.sum())
    out[np.argsort(out - want)[:short]] += 1
    return out


def schedule(total: float, slots: int, s: float, seconds: float,
             arrival_seed: int, burst: int = 1) -> list[np.ndarray]:
    """Due times per Zipf rank (hottest first), from ``arrival_seed``
    alone: every run of a mix gets the same arrivals.  Each slot's
    arrivals are its expected count (``counts``) placed uniformly at
    random in ``[0, seconds)`` — a Poisson process given its count — so
    the offered load is the rate asked for, not a draw around it.  With
    ``burst`` > 1 each arrival brings ``burst`` frames due at once, at the
    same mean rate of frames."""
    if burst < 1:
        raise ValueError(f"burst must be >= 1, got {burst}")
    rng = np.random.default_rng(arrival_seed)
    rates = zipf_rates(total, slots, s) / burst
    return [np.repeat(np.sort(rng.uniform(0.0, seconds, n)), burst)
            for n in counts(rates, seconds)]


def streams_needed(dues: list[np.ndarray], frames_per_stream: int) -> int:
    """How many streams the schedule opens: each rank's arrivals fill
    streams of ``frames_per_stream`` frames one after another."""
    return int(sum(-(-len(d) // frames_per_stream) for d in dues))
