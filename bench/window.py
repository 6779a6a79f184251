"""What every traffic driver shares: the host span it opens around each
call into the program, the compile counter that shows nothing compiles in
the window, and the ``Window`` a measured window hands back.

A driver is a file ``bench/drivers/<driver>.py`` that the traffic mix's
``driver`` key names; it defines ``Driver(cell, seed, seconds)`` with four
phases: ``setup`` (inputs from the seed, the program built and every shape
it will run warmed), ``window`` (measure), ``release`` (free the program's
device state) and ``compare`` (run the configuration's plain reference and
return the numbers compared).  ``CONTROL_WINDOW`` says whether the control
comparison needs a window run first.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

Span = jax.profiler.TraceAnnotation


class CompileCounter:
    """Counts the programs JAX traces or compiles while ``active``."""

    def __init__(self):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_) -> None:
        if self.active and event.startswith("/jax/core/compile"):
            self.count += 1


@dataclasses.dataclass
class Window:
    """What a measured window gives the result line and the readers."""

    metrics: dict          # end-to-end values by name (setup_s aside)
    attempted: int
    failed: int
    frames: int            # frames (or steps) completed in the window
    seconds: float         # host-clock length of the window
    counters: dict = dataclasses.field(default_factory=dict)
    resample_share: float = 1.0


def host(tree):
    """Every leaf of ``tree`` as a float64 host array."""
    return jax.tree_util.tree_map(lambda x: np.asarray(x, np.float64), tree)


def finite(tree) -> bool:
    """Whether every leaf of ``tree`` is finite."""
    return all(np.isfinite(x).all() for x in jax.tree_util.tree_leaves(tree))
