"""Host-clock time of one bank step as the frontend measures it (from
firing the step to its outputs read back): the total of its ``step_ms``
observations over its ``steps`` counter, across the window."""


def read(ctx):
    """Milliseconds per step, or ``None`` when no step ran."""
    c = ctx["window"].counters
    return c["step_ms_total"] / c["steps"] if c.get("steps") else None
