"""Frames the request plane put into one bank step: the frontend's
``frames`` counter over its ``steps`` counter, across the window."""


def read(ctx):
    """Frames per step, or ``None`` when no step ran."""
    c = ctx["window"].counters
    return c["frames"] / c["steps"] if c.get("steps") else None
