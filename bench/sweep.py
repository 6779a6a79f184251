"""Rate sweep of a served cell, to find the highest rate its system
sustains (the knee); the cell then offers a fixed share of it.

    python3 bench/sweep.py --workload <served cell> --rates 10,20,30 \\
        --seconds 20 [--seed 1]

One process: the cell is set up once, then each rate runs one window of
``--seconds`` with the mix's arrivals scaled to that total rate.  One JSON
line per rate: the rate offered, frames answered per second, p50 and p95
latency, and the median latency of the first and the last quarter of the
window's frames by due time (a backlog that grows all through the window
shows as a last quarter far slower than the first).  Runs only on the
chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def quarters(driver) -> tuple[float, float]:
    """Median latency (s) of the first and the last quarter of the
    window's frames, by due time."""
    due = np.concatenate([r["due"] for r in driver.records])
    done = np.concatenate([r["done"] for r in driver.records])
    lat = np.where(np.isfinite(done), done - due, driver.deadline - due)
    lat = lat[np.argsort(due)]
    q = max(1, len(lat) // 4)
    return float(np.median(lat[:q])), float(np.median(lat[-q:]))


def main(argv=None) -> int:
    """Sweep the rates on the chip."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if "total_rate" not in cell.traffic:
        print("sweep: the cell's traffic mix has no rate", file=sys.stderr)
        return 2
    try:
        harness.check_devices(cell.chips)
    except harness.Refusal as err:
        print(f"sweep: refused: {err}", file=sys.stderr)
        return 2
    from repro.core import runtime
    runtime.enable_compile_cache()
    driver = harness.make_driver(cell, args.seed, args.seconds)
    driver.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        driver.prepare(args.seed, total_rate=rate)
        win = driver.window(args.seconds)
        first, last = quarters(driver)
        print(json.dumps({
            "workload": cell.name, "rate": rate,
            "answered_per_s": win.frames / win.seconds,
            "attempted": win.attempted, "failed": win.failed,
            "p50_ms": win.metrics["frame_p50_ms"],
            "p95_ms": win.metrics["frame_p95_ms"],
            "first_quarter_p50_s": first, "last_quarter_p50_s": last,
            "frames_per_step": (win.counters["frames"]
                                / max(1, win.counters["steps"])),
            "window_s": win.seconds}), flush=True)
    driver.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
