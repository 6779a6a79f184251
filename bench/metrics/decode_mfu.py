"""The whole decode step's share of the chip's peak: the least time the
step's work could take (the larger of its FLOPs over peak FLOP/s and its
bytes over peak HBM bandwidth, counted from shapes by the configuration's
``work``) over the measured time per step (window wall time over the
steps completed)."""
import sys


def read(ctx):
    """The share in %; an earlier line of standard error says which of
    the two bounds it."""
    cell, win, peak = ctx["cell"], ctx["window"], ctx["peaks"]
    rows = cell.traffic["prompts"] * cell.traffic["particles"]
    flops, nbytes = cell.model.work(cell.config, cell.traffic, rows,
                                    win.resample_share)
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    step_s = win.seconds / win.frames
    bound = "HBM bytes" if t_bytes >= t_flops else "FLOPs"
    print(f"decode_mfu: {flops!r} FLOP and {nbytes!r} B per step, bound by "
          f"{bound}; least time {max(t_flops, t_bytes)!r} s of "
          f"{step_s!r} s per step", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) / step_s
