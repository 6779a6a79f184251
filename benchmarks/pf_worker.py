"""Subprocess worker for the PF scaling benchmarks.

Runs one (DRA × device-count × particle-count) configuration on a
simulated CPU device mesh and prints a JSON result line (on an
accelerator it runs on the first ``--devices`` chips, and fails if fewer
exist).  Invoked by the fig5/7/8 harnesses with
XLA_FLAGS=--xla_force_host_platform_device_count=<P> so the parent
process (and every other benchmark) keeps seeing one device.
"""
import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, required=True)
    ap.add_argument("--dra", default="rna")
    ap.add_argument("--scheduler", default="lgs")
    ap.add_argument("--exchange-ratio", type=float, default=0.10)
    ap.add_argument("--butterfly-cap", type=int, default=32,
                    help="slab slots per butterfly mix stage")
    ap.add_argument("--particles", type=int, required=True)
    ap.add_argument("--frames", type=int, default=15)
    ap.add_argument("--img", type=int, default=256)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--warmup", type=int, default=5,
                    help="frames excluded from the tracking-rmse report "
                         "(short scaling runs pass a small value)")
    ap.add_argument("--domain", action="store_true",
                    help="input-space domain decomposition (DESIGN.md §10): "
                         "tile-sharded halo slabs instead of replicated "
                         "frames")
    ap.add_argument("--k-cap", type=int, default=0,
                    help="migration window per destination shard "
                         "(0 = ensemble capacity: exact, never overflows)")
    args = ap.parse_args()

    from repro.core import runtime
    runtime.simulate_host_devices(args.devices)
    import jax
    import jax.numpy as jnp
    from repro.core import SIRConfig, ParallelParticleFilter
    from repro.core.distributed import DRAConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models.tracking import (TrackingConfig, make_domain_spec,
                                       make_tracking_model)
    from repro.data.synthetic_movie import generate_movie, tracking_rmse

    cfg = TrackingConfig(img_size=(args.img, args.img), v_init=1.5)
    model = make_tracking_model(cfg)
    movie = generate_movie(jax.random.key(0), cfg, n_frames=args.frames)
    mesh = make_host_mesh(args.devices)
    dra = DRAConfig(kind=args.dra, scheduler=args.scheduler,
                    exchange_ratio=args.exchange_ratio,
                    butterfly_cap=args.butterfly_cap)
    spec = None
    if args.domain:
        spec = make_domain_spec(cfg, args.devices,
                                k_cap=args.k_cap or None)
    pf = ParallelParticleFilter(
        model=model, sir=SIRConfig(n_particles=args.particles, ess_frac=0.5),
        dra=dra, mesh=mesh if (args.devices > 1 or args.domain) else None,
        domain=spec)

    def once():
        res = pf.run(jax.random.key(1), movie.frames)
        jax.block_until_ready(res.estimates)
        return res

    res = once()                      # compile + warm
    t0 = time.time()
    for _ in range(args.repeats):
        res = once()
    dt = (time.time() - t0) / args.repeats

    import numpy as np
    rmse = float(tracking_rmse(res.estimates, movie.trajectories[:, 0],
                               warmup=min(args.warmup, args.frames - 1)))
    out = {
        "devices": args.devices, "dra": args.dra,
        "scheduler": args.scheduler,
        "exchange_ratio": args.exchange_ratio,
        "particles": args.particles, "frames": args.frames,
        "seconds": dt, "rmse": rmse, "domain": bool(args.domain),
        "ess_min": float(np.asarray(res.ess).min()),
        "obs_bytes_per_shard": args.img * args.img * 4,
    }
    # comm-volume accounting (DESIGN.md §14.3): static per frame, so one
    # sample carries the whole run; absent on the single-device path
    if "comm_bytes" in res.diag:
        out["bytes_per_frame"] = int(np.asarray(res.diag["comm_bytes"])[0])
        out["collective_stages"] = int(
            np.asarray(res.diag["comm_stages"])[0])
    if spec is not None:
        out.update({
            "grid": list(spec.grid),
            "obs_bytes_per_shard": spec.slab_bytes(),
            "mig_moved_total": int(np.asarray(res.diag["mig_moved"]).sum()),
            "mig_overflow_total": int(
                np.asarray(res.diag["mig_overflow"]).sum()),
        })
    print(json.dumps(out))


if __name__ == "__main__":
    main()
