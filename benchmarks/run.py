"""Benchmark harness entry point — one suite per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Scaling suites (Figs 5–8) spawn
subprocess workers with their own device counts; this process keeps a
single CPU device.

  PYTHONPATH=src python -m benchmarks.run             # all suites
  PYTHONPATH=src python -m benchmarks.run fig5 rmse   # subset
"""
from __future__ import annotations

import sys
import traceback

SUITES = {
    "fig5": ("benchmarks.fig5_rna_strong", "Figs 5-6: RNA/ARNA strong scaling"),
    "fig7": ("benchmarks.fig7_rpa_weak", "Fig 7: RPA weak scaling GS/SGS/LGS"),
    "fig8": ("benchmarks.fig8_rpa_strong", "Fig 8: RPA strong-scaling efficiency"),
    "rmse": ("benchmarks.rmse_parity", "§VII.E tracking RMSE parity"),
    "asir": ("benchmarks.asir_speedup", "§VI.F ASIR speedup"),
    "kernels": ("benchmarks.kernel_bench", "§V.E kernel microbench"),
    "roofline": ("benchmarks.roofline_table", "dry-run roofline table"),
    "bank": ("benchmarks.bank_bench",
             "FilterBank/DRA throughput baseline (BENCH_bank.json)"),
    "domain": ("benchmarks.bench_domain",
               "domain decomposition vs replicated frames "
               "(BENCH_domain.json)"),
    "serve": ("benchmarks.bench_serve",
              "resident-session serving: occupancy/churn sweeps vs naive "
              "recompile baseline (BENCH_serve.json)"),
    "fleet": ("benchmarks.bench_fleet",
              "multi-bank fleet: 1-bank vs 2-bank-with-rebalancing under "
              "skewed Poisson load + migration cost (BENCH_fleet.json)"),
    "decode": ("benchmarks.bench_decode",
               "SMC decoding: tokens/s vs K and B, session-hosted vs "
               "standalone, resample/gather share (BENCH_decode.json)"),
    "ssm": ("benchmarks.bench_ssm",
            "generic-SSM model families: single filter vs FilterBank B=8 "
            "(BENCH_ssm.json)"),
}


def main() -> None:
    args = [a for a in sys.argv[1:] if not a.startswith("-")]
    chosen = args or list(SUITES)
    print("name,us_per_call,derived")
    failed = []
    for key in chosen:
        mod_name, desc = SUITES[key]
        try:
            mod = __import__(mod_name, fromlist=["run"])
            for row in mod.run():
                print(f"{row['name']},{row['us_per_call']:.1f},"
                      f"\"{row['derived']}\"", flush=True)
        except Exception:   # noqa: BLE001
            failed.append(key)
            print(f"{key},-1,\"FAILED\"", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"suites failed: {failed}")


if __name__ == "__main__":
    main()
