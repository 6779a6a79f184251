"""Shared machinery for the paper-figure scaling benchmarks (Figs 5–8).

Each figure harness spawns ``pf_worker.py`` subprocesses with their own
``--xla_force_host_platform_device_count`` so this process (and everything
else in ``benchmarks.run``) keeps its single CPU device.

The paper's 38.4M-particle / 192-core runs are scaled to container size
(CPU cores, not TPU pods) — the *shape* of the scaling curves and the
relative ordering of the DRA/DLB variants is the reproduced object, and
the same harness runs unchanged at full scale on a real mesh.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_worker(devices: int, dra: str, particles: int, *, scheduler="lgs",
               exchange_ratio=0.10, frames=10, img=128, repeats=2,
               domain=False, k_cap=0, butterfly_cap=32, warmup=None,
               timeout=1200) -> dict:
    """Run one ``pf_worker.py`` configuration in a child process on
    simulated CPU devices; returns its JSON result line.

    CPU only: on an accelerator this process holds the chip, so a child
    that needs it would fail or hang — refuse instead.
    """
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"run_worker simulates devices in a child process and runs on "
            f"the CPU only; this process holds the "
            f"{jax.default_backend()} device the child would need")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, os.path.join(REPO, "benchmarks", "pf_worker.py"),
           "--devices", str(devices), "--dra", dra,
           "--scheduler", scheduler,
           "--exchange-ratio", str(exchange_ratio),
           "--butterfly-cap", str(butterfly_cap),
           "--particles", str(particles), "--frames", str(frames),
           "--img", str(img), "--repeats", str(repeats)]
    if warmup is not None:
        cmd += ["--warmup", str(warmup)]
    if domain:
        cmd += ["--domain", "--k-cap", str(k_cap)]
    out = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"worker failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def device_counts(limit: int = 8) -> list[int]:
    """Virtual host-device counts for the scaling sweeps.

    NOTE: this container exposes a SINGLE physical core, so the P virtual
    devices timeshare it and wall-clock parallel efficiency cannot be
    measured directly.  The suites therefore report the *serialized
    work-ratio* tP/t1 (ideal = 1.0; distributed-resampling communication
    and imbalance overhead shows as the excess) — the paper's relative
    ordering claims (RNA10 < RNA50 overhead, LGS < GS/SGS) are the
    reproduced object.  On a real multi-core/multi-chip mesh the same
    harness measures true efficiency unchanged.
    """
    return [1, 2, 4, 8][: max(1, limit.bit_length())]


ALL_DRAS = ["mpf", "rna", "arna", "rpa", "butterfly"]


def smoke() -> list[dict]:
    """CI-sized sweep over the simulated-device harness: one local baseline
    plus ALL FIVE DRA families on a 2-device mesh, minutes not hours.
    Exercises the same worker/runtime path as the full figure harnesses and
    writes a gitignored ``BENCH_scale38m.smoke.json`` mirroring the
    committed full-sweep schema."""
    results = [run_worker(1, "rna", particles=2048, frames=8, img=48,
                          repeats=1)]
    print(json.dumps(results[0]), flush=True)
    for dra in ALL_DRAS:
        r = run_worker(2, dra, particles=2048, frames=8, img=48, repeats=1)
        results.append(r)
        print(json.dumps(r), flush=True)
    by_dra = {r["dra"]: r for r in results[1:]}
    # bounded slabs must undercut RPA's all-to-all even at P=2 (one stage);
    # the >=4x headline separation only opens up at P=8 (full sweep)
    assert by_dra["butterfly"]["bytes_per_frame"] < \
        by_dra["rpa"]["bytes_per_frame"], by_dra
    payload = {
        "smoke": True,
        "weak": results,
        "strong": [],
        "headline": {
            "devices": 2,
            "butterfly_bytes_per_frame":
                by_dra["butterfly"]["bytes_per_frame"],
            "rpa_bytes_per_frame": by_dra["rpa"]["bytes_per_frame"],
        },
    }
    with open(os.path.join(REPO, "BENCH_scale38m.smoke.json"), "w") as fh:
        json.dump(payload, fh, indent=1)
    return results


def sweep38m() -> dict:
    """Weak + strong scaling across all five DRAs up to the paper's
    38.4M-particle configuration (Figs 5-8 regime, container-scaled).

    Weak scaling fixes the per-shard load at 4.8M particles (the paper's
    ~200k/core scaled to this container's memory) and grows the mesh
    P = 1, 2, 4, 8, ending at the 38.4M-particle headline point.  Strong
    scaling fixes the global cloud at 4.8M and grows P.  The P = 1 local
    baseline is shared by both sweeps.  ``seconds`` is the serialized
    work-ratio numerator (see ``device_counts``); ``bytes_per_frame`` /
    ``collective_stages`` are the exact static comm-volume figures from
    DESIGN.md §14.3 and are hardware-independent.
    """
    per_shard = 4_800_000
    frames, img, warmup = 4, 64, 1
    kw = dict(frames=frames, img=img, repeats=1, warmup=warmup,
              timeout=3600)
    # devices=1 bypasses the mesh entirely, so the dra flag is inert here
    base = run_worker(1, "rna", particles=per_shard, **kw)
    base["sweep"] = "baseline"
    print(json.dumps(base), flush=True)
    weak, strong = [base], [base]
    for p in [2, 4, 8]:
        for dra in ALL_DRAS:
            r = run_worker(p, dra, particles=per_shard * p, **kw)
            r["sweep"] = "weak"
            weak.append(r)
            print(json.dumps(r), flush=True)
            r = run_worker(p, dra, particles=per_shard, **kw)
            r["sweep"] = "strong"
            strong.append(r)
            print(json.dumps(r), flush=True)
    at8 = {r["dra"]: r for r in weak if r["devices"] == 8}
    reduction = at8["rpa"]["bytes_per_frame"] / \
        at8["butterfly"]["bytes_per_frame"]
    assert reduction >= 4.0, (reduction, at8)
    payload = {
        "smoke": False,
        "note": "seconds is the serialized work-ratio numerator (single "
                "physical core timeshared by the P virtual shards — see "
                "benchmarks/scaling.py:device_counts); bytes_per_frame and "
                "collective_stages are exact static per-shard comm figures "
                "(DESIGN.md §14.3) and hold on any hardware",
        "weak": weak,
        "strong": strong,
        "headline": {
            "particles": per_shard * 8,
            "devices": 8,
            "butterfly_bytes_per_frame": at8["butterfly"]["bytes_per_frame"],
            "rpa_bytes_per_frame": at8["rpa"]["bytes_per_frame"],
            "bytes_reduction_vs_rpa": reduction,
            "rmse": {k: v["rmse"] for k, v in at8.items()},
            "ess_min": {k: v["ess_min"] for k, v in at8.items()},
        },
    }
    with open(os.path.join(REPO, "BENCH_scale38m.json"), "w") as fh:
        json.dump(payload, fh, indent=1)
    return payload


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny five-DRA sweep for CI (simulated 1/2-device "
                         "meshes); writes BENCH_scale38m.smoke.json")
    ap.add_argument("--full", action="store_true",
                    help="full weak+strong 38.4M-particle sweep; writes "
                         "BENCH_scale38m.json (hours on one core)")
    args = ap.parse_args()
    if args.smoke:
        res = smoke()
        assert all(r["rmse"] < 50.0 for r in res), res
        print(f"scaling smoke OK: {len(res)} configurations")
    elif args.full:
        payload = sweep38m()
        print(f"scale38m sweep OK: butterfly bytes/frame is "
              f"{payload['headline']['bytes_reduction_vs_rpa']:.2f}x below "
              f"RPA at P=8")
    else:
        ap.error("pass --smoke or --full; run benchmarks/run.py or the "
                 "fig5/7/8 harnesses for the other sweeps")
