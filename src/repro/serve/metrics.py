"""Lightweight serving metrics: counters, windowed timers and spans.

The request plane (``repro.serve.frontend``) needs a handful of
operational numbers — coalesce factor, step time, park/resume events,
per-frame latency, time spent waiting in the queue — without dragging in
a metrics dependency.  ``Metrics`` keeps monotonic counters plus bounded
sample windows and renders everything as one plain ``snapshot()`` dict
(JSON-ready; ``repro.launch.serve`` prints its plane report from it).

``span`` times one phase of the plane twice over: as a
``jax.profiler.TraceAnnotation`` named ``ppf.<name>``, which lands on the
device trace's clock when a profiler runs, and as the counter of the same
name, which sums the phase's seconds whether or not one runs.

Quantiles are computed over the most recent ``window`` samples per
series (a ring buffer, so a long-lived server's memory stays bounded);
``count``/``sum``/``min``/``max`` are exact over the full lifetime.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time

import jax
import numpy as np


@dataclasses.dataclass
class _Series:
    """One observed series: exact lifetime aggregates + a quantile ring."""

    window: int
    count: int = 0
    total: float = 0.0
    vmin: float = float("inf")
    vmax: float = float("-inf")
    ring: collections.deque = None  # type: ignore[assignment]

    def __post_init__(self):
        self.ring = collections.deque(maxlen=self.window)

    def add(self, value: float) -> None:
        """Fold one sample into the aggregates and the quantile window."""
        v = float(value)
        self.count += 1
        self.total += v
        self.vmin = min(self.vmin, v)
        self.vmax = max(self.vmax, v)
        self.ring.append(v)

    def summary(self) -> dict:
        """count/mean/min/max (lifetime) + p50/p90/p99 (recent window)."""
        q = np.percentile(np.fromiter(self.ring, float),
                          [50, 90, 99]) if self.ring else [0.0] * 3
        return {
            "count": self.count,
            "mean": self.total / self.count if self.count else 0.0,
            "min": self.vmin if self.count else 0.0,
            "max": self.vmax if self.count else 0.0,
            "p50": float(q[0]), "p90": float(q[1]), "p99": float(q[2]),
        }


class Metrics:
    """A named bag of counters and sample series.

    ``inc`` bumps a monotonic counter; ``observe`` records one sample of
    a distribution (latency seconds, batch sizes, ...); ``span`` times a
    block into a counter and the profiler's trace.  ``snapshot`` renders
    counters and series as a nested plain dict.  No locks: every counter
    and series has one writing thread (the request plane's event loop,
    or for the ``ppf.bank.*`` spans its step executor), and ``snapshot``
    copies in one call under the interpreter lock.
    """

    def __init__(self, window: int = 4096):
        self._window = window
        self._counters: dict[str, float] = collections.defaultdict(float)
        self._series: dict[str, _Series] = {}

    def inc(self, name: str, value: float = 1) -> None:
        """Add ``value`` (default 1) to counter ``name``."""
        self._counters[name] += value

    def observe(self, name: str, value: float) -> None:
        """Record one sample of series ``name``."""
        series = self._series.get(name)
        if series is None:
            series = self._series[name] = _Series(self._window)
        series.add(value)

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Time the block as the trace event ``ppf.<name>`` with ``args``
        as its stats, and add its seconds to the counter ``ppf.<name>``.

        Yields the ``TraceAnnotation``, whose ``set_metadata`` adds stats
        known only inside the block.  The counter is added to also when
        the block raises.
        """
        full = "ppf." + name
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(full, **args) as annotation:
                yield annotation
        finally:
            self.inc(full, time.perf_counter() - t0)

    def counter(self, name: str) -> float:
        """Current value of counter ``name`` (0 if never incremented)."""
        return self._counters.get(name, 0)

    def snapshot(self) -> dict:
        """``{"counters": {...}, "series": {name: summary, ...}}`` —
        plain floats/ints throughout, safe to ``json.dump``."""
        return {
            "counters": dict(self._counters),
            "series": {k: s.summary() for k, s in self._series.items()},
        }
