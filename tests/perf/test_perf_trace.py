"""The benchmark's trace reduction (bench/tracereduce.py): on synthetic
event lists, where every number can be worked out by hand, and on one small
profiler trace recorded on the CPU."""
import pathlib
import sys
import time

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import tracereduce as tr  # noqa: E402


def ev(a, b, name="op"):
    return (float(a), float(b), name)


def test_union_merges_overlaps_and_keeps_gaps():
    merged = tr.union([ev(5, 7), ev(0, 2), ev(1, 3), ev(7, 8), ev(10, 11)])
    assert merged == [(0, 3), (5, 8), (10, 11)]
    assert tr.total(merged) == 3 + 3 + 1


def test_busy_and_idle_share_clip_to_the_window():
    ops = {"d0": [ev(0, 4), ev(2, 6), ev(8, 12)]}
    # window [2, 10]: busy 2..6 and 8..10 -> 6 of 8
    assert tr.busy_ns(ops["d0"], 2, 10) == 6
    assert tr.idle_share(ops, 2, 10) == pytest.approx(0.25)


def test_idle_share_averages_over_devices():
    ops = {"d0": [ev(0, 10)], "d1": [ev(0, 5)]}
    assert tr.idle_share(ops, 0, 10) == pytest.approx(0.25)


def test_idle_gaps_go_to_the_host_span_open_in_them():
    ops = {"d0": [ev(0, 10), ev(20, 30), ev(34, 40)]}
    spans = [ev(0, 40, "bench.outer"), ev(10, 20, "bench.send"),
             ev(12, 18, "bench.open"), ev(30, 34, "bench.run")]
    assert tr.gaps(tr.union(ops["d0"]), 0, 40) == [(10, 20), (30, 34)]
    by = dict(tr.idle_by_span(ops, spans, 0, 40))
    # the gap 10..20 has its midpoint in the innermost span bench.open
    assert by == {"bench.open": pytest.approx(10e-9),
                  "bench.run": pytest.approx(4e-9)}


def test_a_gap_with_no_span_open_is_named_so():
    ops = {"d0": [ev(0, 1), ev(5, 6)]}
    assert tr.idle_by_span(ops, [], 0, 6) == [["no span", pytest.approx(4e-9)]]


def test_top_ops_sum_by_name_largest_first():
    ops = {"d0": [ev(0, 3, "fusion.1"), ev(3, 4, "sort"),
                  ev(4, 8, "fusion.1")]}
    assert tr.top_ops(ops, 0, 8) == [["fusion.1", pytest.approx(7e-9)],
                                     ["sort", pytest.approx(1e-9)]]


def test_window_runs_from_mark_to_mark():
    spans = [ev(1, 2, "bench.run"), ev(0, 0.1, tr.MARK_START),
             ev(9, 9.1, tr.MARK_STOP)]
    assert tr.window(spans) == (0, 9)
    with pytest.raises(ValueError):
        tr.window(spans[:2])


def test_busy_counts_a_program_run_whose_ops_leave_gaps():
    """A loop traced as many short operations with its control between
    them is busy all through its program run."""
    ops = {"d0": [ev(0, 1), ev(2, 3), ev(4, 5)]}
    modules = {"d0": [ev(0, 5, "jit_run")]}
    busy = tr.busy_events(ops, modules)
    assert tr.idle_share(busy, 0, 10) == pytest.approx(0.5)
    assert tr.idle_share(ops, 0, 10) == pytest.approx(0.7)


def test_reduction_of_a_profiler_trace_recorded_on_the_cpu(tmp_path):
    """On the CPU the operations run on the host plane's XLA client
    thread; the same reduction reads them beside the bench spans."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation(tr.MARK_START):
            pass
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.run"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.02)
        with jax.profiler.TraceAnnotation(tr.MARK_STOP):
            pass
    ops, modules, spans, planes = tr.load(
        str(tmp_path), device_prefix="/host:CPU",
        op_line="tf_XLAPjRtCpuClient")
    assert planes and ops and not modules, planes
    names = [s[2] for s in spans]
    assert names.count("bench.run") == 3
    lo, hi = tr.window(spans)
    assert hi - lo >= 0.06e9
    share = tr.idle_share(ops, lo, hi)
    assert 0.5 < share < 1.0          # three 20 ms sleeps, short matmuls
    by = dict(tr.idle_by_span(ops, spans, lo, hi))
    assert by["bench.sleep"] >= 0.05
    assert tr.top_ops(ops, lo, hi)


def test_a_traced_run_reports_busy_time_breakdown_and_layers(monkeypatch):
    """A ``--trace 1`` run here, past the look for a chip: the first
    seconds of the window are traced (the CPU's operations sit on the
    host plane), and the result carries busy and window seconds, the
    breakdown and the per-layer metrics the trace and counters give."""
    import argparse
    import jax
    from bench import harness
    from bench import run as bench_run

    monkeypatch.setattr(bench_run, "TRACE_LINES",
                        ("/host:CPU", "tf_XLAPjRtCpuClient", "XLA Modules"))
    cell = harness.load_cell(
        "track-512-n1m", {"img": 32, "frames": 4, "warmup_frames": 1,
                          "trace_seconds": 0.5,
                          "limits": {"pos_gap_px": 1e9, "logz_gap": 1e9,
                                     "ess_gap": 1e9}},
        {"n_particles_per_chip": 1024})
    args = argparse.Namespace(workload=cell.name, seed=3, seconds=1.0,
                              trace=1)
    result, _ = bench_run.measure(cell, args, jax.devices()[:1],
                                  {"flops_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9})
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert 0.4 < dev["window_s"] < 1.5
    assert set(result["metrics"]) == {"idle_share.offline", "step_mfu"}
    assert 0 <= result["metrics"]["idle_share.offline"]["value"] <= 100
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert result["breakdown"]["idle_gaps"]
