"""Roofline share of the decode step's expert layers (``ppf.lm.moe``: the
held and shared experts' and the router's weights, the routed pairs at
their expected share): the least time of its counted work at the chip's
peaks (the configuration's ``moe_work``) over its operations' traced
device time per step (``bench/scopes.py``)."""
import sys

from bench import scopes


def read(ctx):
    """The share in %, or ``None`` where the trace holds no step of the
    scoped program."""
    cell, peak = ctx["cell"], ctx["peaks"]
    step_s = scopes.per_step(ctx, "ppf.lm.moe", "ppf.lm.head")
    if not step_s:
        return None
    rows = cell.traffic["prompts"] * cell.traffic["particles"]
    flops, nbytes = cell.model.moe_work(cell.config, cell.traffic, rows)
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "HBM bytes" if t_bytes >= t_flops else "FLOPs"
    print(f"moe_roofline.decode: {flops!r} FLOP and {nbytes!r} B per step, "
          f"bound by {bound}; least time {max(t_flops, t_bytes)!r} s of "
          f"{step_s!r} s traced per step", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / step_s
