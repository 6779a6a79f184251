"""Readings that the limits of ``correct`` are set from, all in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1-12 \\
        --control-seeds 1-3 --seconds <s> [--out <file>]

For each seed of ``--seeds`` the cell's program runs as a benchmark run
does (the seed's inputs, a window of ``--seconds`` at the cell's own load)
and its outputs are compared with the plain reference; for each seed of
``--control-seeds`` the reference computed in its control precision takes
the program's place in the same comparison.  One JSON line per seed, then
a summary: per compared number the largest program reading (the lower
reading), the smallest control reading (the upper one) and their ratio.
The benchmark's own runs never run the control.  Like ``run.py`` it runs
only on the chip.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def seed_list(text: str) -> list[int]:
    """``"1-12"`` or ``"4,9,30"`` as a list of seeds."""
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def readings(driver, seeds, control_seeds, seconds, emit) -> dict:
    """Program readings on ``seeds`` and control readings on
    ``control_seeds`` through ``driver`` (already set up); returns the
    summary.  A control seed that is also a program seed is read on the
    same window."""
    low: dict = {}
    high: dict = {}

    def control(seed):
        values = driver.compare(control=True)
        emit({"seed": seed, "kind": "control", "values": values})
        for k, v in values.items():
            high[k] = min(high.get(k, float("inf")), v)

    for seed in seeds:
        driver.prepare(seed)
        win = driver.window(seconds)
        values = driver.compare()
        emit({"seed": seed, "kind": "program", "failed": win.failed,
              "values": values})
        for k, v in values.items():
            low[k] = max(low.get(k, float("-inf")), v)
        if seed in control_seeds:
            control(seed)
    for seed in control_seeds:
        if seed in seeds:
            continue
        driver.prepare(seed)
        if driver.CONTROL_WINDOW:
            driver.window(seconds)
        control(seed)
    return {k: {"lower": low[k], "upper": high.get(k),
                "ratio": (high[k] / low[k]) if k in high and low[k] > 0
                else None} for k in low}


def main(argv=None) -> int:
    """Calibrate one cell on the chip."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.check_devices(cell.chips)
    except harness.Refusal as err:
        print(f"calibrate: refused: {err}", file=sys.stderr)
        return 2
    from repro.core import runtime
    runtime.enable_compile_cache()
    seeds = seed_list(args.seeds)
    out = open(args.out, "a") if args.out else None

    def emit(record):
        line = json.dumps({"workload": cell.name, **record})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    driver = harness.make_driver(cell, seeds[0], args.seconds)
    driver.setup()
    summary = readings(driver, seeds, seed_list(args.control_seeds),
                       args.seconds, emit)
    emit({"summary": summary})
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
