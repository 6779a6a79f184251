"""Reduction of a profiler trace to the numbers the benchmark reports.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  On a TPU every chip is a plane
``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event per operation
that ran; the benchmark's own host spans (``jax.profiler.TraceAnnotation``
named ``bench.*``) sit on the host plane's thread lines.  Both carry
nanosecond times on one clock.

Everything below ``load`` works on plain lists of ``(start_ns, end_ns,
name)`` so that it can be checked on synthetic events.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Iterable

Interval = tuple[float, float, str]

SPAN_PREFIX = "bench."
MARK_START = SPAN_PREFIX + "mark.start"
MARK_STOP = SPAN_PREFIX + "mark.stop"
NAME_CHARS = 160


def load(trace_dir: str, device_prefix: str = "/device:TPU",
         op_line: str = "XLA Ops",
         module_line: str = "XLA Modules") -> tuple[dict, dict, list, list]:
    """``(ops, modules, spans, planes)`` of the newest trace under
    ``trace_dir``.

    ``ops`` maps each device plane (name starting with ``device_prefix``)
    to the events of its lines whose name starts with ``op_line``, and
    ``modules`` to those of ``module_line`` (one event per program run);
    ``spans`` are the ``bench.*`` events of every line; ``planes`` lists
    ``(plane, line, events)`` for a look by hand.
    """
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops, modules, spans, planes = {}, {}, [], []
    for plane in data.planes:
        device = plane.name.startswith(device_prefix)
        for line in plane.lines:
            events = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                       e.name) for e in line.events]
            planes.append((plane.name, line.name, len(events)))
            if device and line.name.startswith(op_line):
                ops.setdefault(plane.name, []).extend(events)
            elif device and line.name.startswith(module_line):
                modules.setdefault(plane.name, []).extend(events)
            spans.extend(e for e in events if e[2].startswith(SPAN_PREFIX))
    return ops, modules, spans, planes


def busy_events(ops: dict, modules: dict) -> dict:
    """Per device, every event during which the device ran something: its
    operations and its program runs (an operation inside a loop may be
    traced as many short events with the loop's control between them;
    the program run covers it whole)."""
    return {d: ops.get(d, []) + modules.get(d, [])
            for d in sorted(set(ops) | set(modules))}


def union(intervals: Iterable[Interval]) -> list[tuple[float, float]]:
    """The union of ``intervals`` as sorted, disjoint ``(start, end)``."""
    merged: list[list[float]] = []
    for start, end, _ in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(merged: list, lo: float, hi: float) -> list[tuple[float, float]]:
    """``merged`` cut to the window ``[lo, hi]``."""
    out = []
    for a, b in merged:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def total(merged: list) -> float:
    """Summed length of disjoint intervals."""
    return sum(b - a for a, b in merged)


def busy_ns(events: list, lo: float, hi: float) -> float:
    """Time in ``[lo, hi]`` in which at least one event ran."""
    return total(clip(union(events), lo, hi))


def idle_share(ops: dict, lo: float, hi: float) -> float:
    """1 - busy / window, busy averaged over the devices in ``ops``."""
    if not ops or hi <= lo:
        raise ValueError("no device events or an empty window")
    busy = sum(busy_ns(ev, lo, hi) for ev in ops.values()) / len(ops)
    return 1.0 - busy / (hi - lo)


def gaps(merged: list, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle stretches of ``[lo, hi]`` between busy intervals."""
    out, t = [], lo
    for a, b in clip(merged, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def open_span(spans: list, t: float) -> str:
    """The innermost (latest started) host span open at time ``t``, or
    ``"no span"``."""
    best = None
    for start, end, name in spans:
        if start <= t < end and (best is None or start > best[0]):
            best = (start, name)
    return best[1] if best else "no span"


def idle_by_span(ops: dict, spans: list, lo: float, hi: float,
                 top: int = 10) -> list[list]:
    """Idle time of the devices in ``[lo, hi]``, attributed to the host
    span open at each gap's midpoint: ``[[span, seconds], ...]``, the
    largest first, averaged over the devices."""
    by = collections.Counter()
    inner = [s for s in spans if not s[2].startswith(SPAN_PREFIX + "mark")]
    for events in ops.values():
        for a, b in gaps(union(events), lo, hi):
            by[open_span(inner, 0.5 * (a + b))] += (b - a) / len(ops)
    return [[name, ns * 1e-9] for name, ns in by.most_common(top)]


def top_ops(ops: dict, lo: float, hi: float, top: int = 10) -> list[list]:
    """The operations that took most device time in ``[lo, hi]``, by the
    name the trace prints (an HLO instruction's text is cut to its first
    ``NAME_CHARS`` characters): ``[[name, seconds], ...]`` averaged over
    the devices.  An operation traced inside a loop counts apart from
    the loop that holds it."""
    by = collections.Counter()
    for events in ops.values():
        for start, end, name in events:
            a, b = max(start, lo), min(end, hi)
            if b > a:
                by[name[:NAME_CHARS]] += (b - a) / len(ops)
    return [[name, ns * 1e-9] for name, ns in by.most_common(top)]


def window(spans: list) -> tuple[float, float]:
    """The traced window: from the ``bench.mark.start`` host mark to the
    ``bench.mark.stop`` one."""
    marks = {name: start for start, _, name in spans
             if name in (MARK_START, MARK_STOP)}
    if len(marks) != 2:
        raise ValueError(f"the trace holds marks {sorted(marks)}, not "
                         f"{MARK_START} and {MARK_STOP}")
    return marks[MARK_START], marks[MARK_STOP]
