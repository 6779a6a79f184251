"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix and metric readers are found by name
(``bench/harness.py``).  One run: refuse anything but a TPU with the
cell's chip count, make the inputs from ``--seed``, build the program and
warm every shape it will run (``setup_s``), measure for ``--seconds``
(with ``--trace 1`` under the profiler, reporting the per-layer metrics
instead of the end-to-end ones), read the peak device memory, free the
program, and compare what the window produced with the configuration's
plain reference.  The compared numbers and their limits are the last lines
of standard error; the result is the last line of standard output.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
# the traced part of a --trace 1 window, unless the traffic mix sets its
# own ``trace_seconds``: reading the trace takes time in proportion to its
# events, and a loop over particles is traced step by step
TRACE_SECONDS = 2.0
# where the device's operations and program runs sit in a TPU trace:
# plane-name prefix, operation line, program-run line
TRACE_LINES = ("/device:TPU", "XLA Ops", "XLA Modules")


def parse(argv=None) -> argparse.Namespace:
    """The command line the benchmark's contract fixes."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def read_layer_metrics(cell: harness.Cell, ctx: dict) -> dict:
    """Each per-layer metric of the cell from its reader
    ``bench/metrics/<name>.py``; a reader that finds nothing to read
    returns ``None`` and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        reader = harness.load_module(
            harness.BENCH / "metrics" / (m["name"] + ".py"),
            "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class WindowTrace:
    """Profiles the first ``seconds`` of the window: the device trace of
    a whole window of this program can hold millions of operations (a
    loop over particles is traced step by step), more than a run has time
    to read.  Host marks ``bench.mark.start`` / ``bench.mark.stop`` bound
    the traced part."""

    def __init__(self, directory: pathlib.Path, seconds: float):
        self.directory, self.seconds = directory, seconds
        self.lock = threading.Lock()
        self.running = False

    def __enter__(self):
        import jax
        from bench import tracereduce as tr
        shutil.rmtree(self.directory, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.directory),
                                 profiler_options=opts)
        self.running = True
        with jax.profiler.TraceAnnotation(tr.MARK_START):
            pass
        self.timer = threading.Timer(self.seconds, self.stop)
        self.timer.start()
        return self

    def stop(self) -> None:
        """End the trace (once), marking where it ends."""
        import jax
        from bench import tracereduce as tr
        with self.lock:
            if not self.running:
                return
            with jax.profiler.TraceAnnotation(tr.MARK_STOP):
                pass
            jax.profiler.stop_trace()
            self.running = False

    def __exit__(self, *exc):
        self.timer.cancel()
        self.stop()


def traced_context(cell, win, devices, peak) -> tuple[dict, dict, dict]:
    """Reduce the window's trace: the readers' context, ``busy_s`` /
    ``window_s`` for the device entry, and the breakdown."""
    from bench import tracereduce as tr
    ops, modules, spans, planes = tr.load(str(TRACE_DIR), *TRACE_LINES)
    for plane, line, count in planes:
        if count:
            print(f"trace plane {plane!r} line {line!r} events {count}",
                  file=sys.stderr)
    lo, hi = tr.window(spans)
    busy_ev = tr.busy_events(ops, modules)
    busy_ev = dict(list(busy_ev.items())[:len(devices)])
    ops = {d: ops.get(d, []) for d in busy_ev}
    if not any(busy_ev.values()):
        raise RuntimeError("the trace holds no device operations")
    busy = sum(tr.busy_ns(ev, lo, hi) for ev in busy_ev.values()) \
        / len(busy_ev)
    ctx = {"busy": busy_ev, "lo": lo, "hi": hi, "window": win,
           "peaks": peak, "cell": cell}
    device = {"busy_s": busy * 1e-9, "window_s": (hi - lo) * 1e-9}
    breakdown = {"device_ops": tr.top_ops(ops, lo, hi),
                 "idle_gaps": tr.idle_by_span(busy_ev, spans, lo, hi)}
    return ctx, device, breakdown


def main(argv=None) -> int:
    """One run of one cell on the chip; returns the exit code."""
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.check_devices(cell.chips)
        peak = harness.peaks(devices[0].device_kind)
    except harness.Refusal as err:
        print(f"bench: refused: {err}", file=sys.stderr)
        return 2
    from repro.core import runtime
    cache = runtime.enable_compile_cache()
    print(f"bench: {cell.name} on {len(devices)} x "
          f"{devices[0].device_kind}, compile cache {cache}",
          file=sys.stderr, flush=True)
    result, checks = measure(cell, args, devices, peak)
    harness.emit(result, checks)
    return 0


def measure(cell: harness.Cell, args: argparse.Namespace, devices,
            peak: dict) -> tuple[dict, dict]:
    """Everything of a run after the device check: set-up, window,
    memory, metrics, and the comparison; returns the result line's
    fields and the compared numbers with their limits."""
    from bench.window import CompileCounter
    compiles = CompileCounter()
    driver = harness.make_driver(cell, args.seed, args.seconds)
    driver.setup()
    setup_s = time.perf_counter() - T0

    compiles.active = True
    if args.trace:
        traced = cell.traffic.get("trace_seconds", TRACE_SECONDS)
        with WindowTrace(TRACE_DIR, min(traced, args.seconds)):
            win = driver.window(args.seconds)
    else:
        win = driver.window(args.seconds)
    compiles.active = False
    print(f"bench: window {win.seconds!r} s, {win.frames} frames, "
          f"{win.attempted} attempted, {win.failed} failed, "
          f"{compiles.count} compiles in the window",
          file=sys.stderr, flush=True)
    memory = harness.memory_peak(devices)

    result = {"attempted": win.attempted, "failed": win.failed}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    if args.trace:
        ctx, busy, breakdown = traced_context(cell, win, devices, peak)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        metrics = read_layer_metrics(cell, ctx)
        device.update(busy)
        result["breakdown"] = breakdown
    else:
        metrics = {m["name"]: {"value": win.metrics[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    driver.release()
    t_ref = time.perf_counter()
    values = driver.compare()
    print(f"bench: set-up {setup_s!r} s, reference and comparison "
          f"{time.perf_counter() - t_ref!r} s", file=sys.stderr, flush=True)
    correct, checks = harness.limits_check(values, cell.traffic["limits"])
    correct = correct and win.failed == 0
    return ({"correct": correct, **result, "metrics": metrics,
             "device": device}, checks)


if __name__ == "__main__":
    sys.exit(main())
