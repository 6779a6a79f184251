"""Smoke run of the PPF main paths on a TPU, all in one process.

    python chip_smoke.py [--seed S] [--chips 4]

With one chip (the default) three phases run through the library's user
entry points at full width, on data made from ``--seed``:

* ``filter`` — ``ParallelParticleFilter`` on the tracking model (512x512
  frames, patch radius 4), N = 2^20, 20 frames: tracking RMSE against
  the synthetic ground truth < 1 px after acquisition, finite ESS and
  log-marginal.
* ``oracle`` — the ``cv2d`` linear-Gaussian model at N = 2^20 through the
  composed and the fused SIR step, against the float64 Kalman filter
  within the CLT bounds of ``tests/test_ssm_oracle.py``.
* ``serve`` — ``ParticleFrontend`` over a ``ParticleSessionServer``: 8
  tracking sessions of N = 16384 (capacity 8), 10 frames each submitted
  through the client API; each stream is compared with a standalone
  filter on the same key and frames.

``--chips 4`` runs only the sharded phases: RNA and RPA at 2^20
particles per chip over a 4-chip mesh, and the domain-decomposed RNA
filter against the replicated-frame RNA run on the same mesh at 2^18
per chip (``Sizes.n_domain_per_chip`` says why), each checked against
ground-truth RMSE.

Every phase prints one ``PHASE {...}`` line: outcome, compile seconds,
steady ms/frame (informational, not a benchmark), and the backend each
kernel slot is configured to with the number of ``tpu_custom_call``
kernels in the compiled program; a slot configured for a Pallas kernel
whose program compiled without one fails the phase.  Without a TPU, or
with fewer chips than asked for, the script exits non-zero before any
phase.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "tests")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import stats  # noqa: E402  (tests/stats.py: the oracle bounds)
from repro.core import ParallelParticleFilter, SIRConfig, runtime  # noqa: E402
from repro.core import distributed as dist  # noqa: E402
from repro.data.synthetic_movie import generate_movie, tracking_rmse  # noqa: E402
from repro.kernels import sir_fused  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import ssm  # noqa: E402
from repro.models.tracking import (TrackingConfig, make_domain_spec,  # noqa: E402
                                   make_tracking_model)
from repro.serve import ParticleFrontend, ParticleSessionServer  # noqa: E402
from test_ssm_oracle import N_STEPS, SEEDS, SLACKS  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Phase sizes; ``FULL`` is what the script runs.

    The tracking movies move at ``v_init = 1`` px/frame (the examples'
    setting).  A uniform prior over the frame needs several frames to
    acquire the SNR-2 spot, so each RMSE counts only the frames after
    ``*_warmup``.
    """

    img: int = 512
    n_filter: int = 1 << 20
    frames: int = 20
    filter_warmup: int = 12
    n_oracle: int = 1 << 20
    # serving: 64x64 frames keep 4 particles per pixel at N = 16384, the
    # filter phase's density, so a 10-frame session can acquire its spot
    serve_img: int = 64
    sessions: int = 8
    n_session: int = 16384
    capacity: int = 8
    session_frames: int = 10
    serve_warmup: int = 7
    n_per_chip: int = 1 << 20
    # The domain path evaluates the tile likelihood over its merged
    # ensemble: own C slots plus up to C migrants from each of the P
    # shards (5C at P = 4).  Its [5C, 9, 9] patch tensor takes 8 KiB per
    # particle under the (8, 128) tiling: 42.9 GB at C = 2^20, while the
    # whole program needs 11.8 GB per chip at C = 2^18 (v5e compile).
    n_domain_per_chip: int = 1 << 18


FULL = Sizes()


@dataclasses.dataclass
class Phase:
    """One phase's outcome and the numbers its line prints."""

    name: str
    ok: bool
    compile_s: float
    steady_ms_per_frame: float
    kernels: dict
    checks: dict

    def line(self) -> str:
        """The ``PHASE {...}`` line (numbers unrounded)."""
        return "PHASE " + json.dumps({
            "phase": self.name, "ok": self.ok, "compile_s": self.compile_s,
            "steady_ms_per_frame": self.steady_ms_per_frame,
            "steady_note": "informational, not a benchmark",
            "kernels": self.kernels, **self.checks})


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def compile_entry(fn, *args):
    """``jax.jit(fn)`` compiled for ``args``; returns (executable, seconds)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def run_timed(compiled, n_frames: int, *args):
    """First result of ``compiled(*args)`` and the steady ms/frame of a
    second, identical call."""
    out = jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(*args))
    return out, (time.perf_counter() - t0) * 1e3 / n_frames


def kernel_census(compiled, slots: dict) -> tuple[dict, bool]:
    """Configured backend per kernel slot plus the Pallas kernels the
    compiled program holds; not ok when a slot configured for Pallas
    compiled without any."""
    n = compiled.as_text().count("tpu_custom_call")
    ok = n > 0 or "pallas" not in slots.values()
    return {"slots": slots, "tpu_custom_call": n}, ok


def sir_slots(sir: SIRConfig, dra: dist.DRAConfig | None = None,
              shards: int = 1) -> dict:
    """Backend each kernel slot of a filter takes under its config: the
    weight phase (``sir_fused`` rule), the per-shard local resample
    (``distributed`` rule), and the likelihood, which the models
    evaluate in jnp."""
    if dra is not None:
        pallas = dist.use_pallas_resample(dra, sir.n_particles // shards)
        return {"local_resample": "pallas" if pallas else "xla",
                "likelihood": "xla"}
    fused = (sir.step_backend == "fused"
             and sir_fused.fused_applicable(sir.resampler))
    weight = (sir.fused_backend or sir_fused.default_backend()) if fused \
        else "xla"
    return {"weight_phase": weight, "likelihood": "xla"}


def finite(*xs) -> bool:
    """Every value of every array is finite."""
    return all(bool(np.isfinite(np.asarray(x)).all()) for x in xs)


def deviation(got, ref) -> tuple[float, int]:
    """(max |got - ref|, first frame where they differ or -1) over two
    ``(estimates, log_marginal)`` trajectories."""
    a, b = (np.concatenate([np.asarray(est, np.float64),
                            np.asarray(lm, np.float64)[:, None]], axis=1)
            for est, lm in (got, ref))
    diff = np.abs(a - b).max(axis=1)
    differs = np.flatnonzero(diff > 0)
    return float(diff.max()), int(differs[0]) if differs.size else -1


def tracking_setup(img: int, n_frames: int, seed: int):
    """Tracking model and a seeded synthetic movie at ``img`` x ``img``."""
    cfg = TrackingConfig(img_size=(img, img), v_init=1.0)
    movie = generate_movie(jax.random.key(seed), cfg, n_frames=n_frames)
    return cfg, make_tracking_model(cfg), movie


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_filter(sz: Sizes, seed: int) -> Phase:
    """Single-chip tracking filter at full width."""
    _, model, movie = tracking_setup(sz.img, sz.frames, seed)
    sir = SIRConfig(n_particles=sz.n_filter, ess_frac=0.5)
    pf = ParallelParticleFilter(model=model, sir=sir)
    key = jax.random.key(seed + 1)
    compiled, compile_s = compile_entry(pf.run, key, movie.frames)
    kernels, k_ok = kernel_census(compiled, sir_slots(sir))
    res, ms = run_timed(compiled, sz.frames, key, movie.frames)
    rmse = float(tracking_rmse(res.estimates, movie.trajectories[:, 0],
                               warmup=sz.filter_warmup))
    fin = finite(res.ess, res.log_marginal, res.estimates)
    return Phase("filter", k_ok and fin and rmse < 1.0, compile_s, ms,
                 kernels, {"n_particles": sz.n_filter, "frames": sz.frames,
                           "img": sz.img, "rmse_px": rmse,
                           "rmse_after_frame": sz.filter_warmup,
                           "ess_min": float(np.min(res.ess)),
                           "finite": fin})


def phase_oracle(sz: Sizes, seed: int) -> list[Phase]:
    """``cv2d`` LGSSM, composed and fused step, against the Kalman
    filter (the bounds of tests/test_ssm_oracle.py)."""
    model = ssm.oracle_configs()["cv2d"]
    k_sim, k_run = jax.random.split(
        jax.random.fold_in(jax.random.key(seed), SEEDS["cv2d"]))
    _, zs = ssm.simulate(k_sim, model, N_STEPS)
    zs = np.asarray(zs)
    oracle = ssm.kalman_filter(model, zs)
    mean_slack, lz_slack = SLACKS["cv2d"]
    bound = stats.pf_mean_bound(oracle.covs, sz.n_oracle, slack=mean_slack)
    lz_bound = stats.log_marginal_bound(N_STEPS, sz.n_oracle, slack=lz_slack)
    spread = float(np.sqrt(np.trace(oracle.covs, axis1=-2, axis2=-1).mean()))
    phases = []
    for backend in ("composed", "fused"):
        sir = SIRConfig(n_particles=sz.n_oracle, step_backend=backend)
        pf = ParallelParticleFilter(model=model, sir=sir)
        compiled, compile_s = compile_entry(pf.run, k_run, zs)
        kernels, k_ok = kernel_census(compiled, sir_slots(sir))
        res, ms = run_timed(compiled, N_STEPS, k_run, zs)
        err = stats.rmse(res.estimates, oracle.means)
        lz_err = abs(float(np.asarray(res.log_marginal, np.float64).sum())
                     - float(oracle.log_marginals.sum()))
        fin = finite(res.ess, res.log_marginal, res.estimates)
        ok = (k_ok and fin and bound < spread and err <= bound
              and lz_err <= lz_bound)
        phases.append(Phase(
            f"oracle_{backend}", ok, compile_s, ms, kernels,
            {"n_particles": sz.n_oracle, "steps": N_STEPS,
             "mean_rmse": err, "mean_bound": bound,
             "log_marginal_err": lz_err, "log_marginal_bound": lz_bound,
             "finite": fin}))
    return phases


async def _serve_stream(fe: ParticleFrontend, key, frames) -> list:
    """One client: open a stream, submit every frame, await the results."""
    stream = await fe.open(key)
    futs = [await fe.submit(stream, f) for f in frames]
    results = await asyncio.gather(*futs)
    await fe.close(stream)
    return results


async def _serve(server, keys, movies) -> tuple[list, float, float]:
    async with ParticleFrontend(server) as fe:
        t0 = time.perf_counter()
        await fe.warmup(np.asarray(movies[0].frames[0]))
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        results = await asyncio.gather(*(
            _serve_stream(fe, k, np.asarray(m.frames))
            for k, m in zip(keys, movies)))
        return results, compile_s, time.perf_counter() - t0


def phase_serve(sz: Sizes, seed: int) -> Phase:
    """Tracking sessions through the asyncio request plane, each stream
    against the standalone filter on the same key and frames."""
    cfg = TrackingConfig(img_size=(sz.serve_img, sz.serve_img), v_init=1.0)
    model = make_tracking_model(cfg)
    movies = [generate_movie(jax.random.key(seed * 1000 + 10 + i), cfg,
                             n_frames=sz.session_frames)
              for i in range(sz.sessions)]
    keys = [jax.random.key(seed * 1000 + 100 + i) for i in range(sz.sessions)]
    sir = SIRConfig(n_particles=sz.n_session, ess_frac=0.5)
    server = ParticleSessionServer(model=model, sir=sir,
                                   capacity=sz.capacity)
    results, compile_s, wall = asyncio.run(_serve(server, keys, movies))

    standalone = ParallelParticleFilter(model=model, sir=sir).run
    compiled, _ = compile_entry(standalone, keys[0], movies[0].frames)
    kernels, k_ok = kernel_census(compiled, sir_slots(sir))
    max_dev, first_diff, rmses, fin = 0.0, -1, [], True
    for key, movie, res in zip(keys, movies, results):
        est = np.stack([r.estimate for r in res])
        lm = np.asarray([r.log_marginal for r in res])
        ref = compiled(key, movie.frames)
        dev, first = deviation((est, lm), (ref.estimates, ref.log_marginal))
        max_dev = max(max_dev, dev)
        if first >= 0:
            first_diff = first if first_diff < 0 else min(first_diff, first)
        fin = fin and finite(est, lm, [r.ess for r in res])
        rmses.append(float(tracking_rmse(est, movie.trajectories[:, 0],
                                         warmup=sz.serve_warmup)))
    ok = k_ok and fin and max(rmses) < 1.0
    return Phase("serve", ok, compile_s,
                 wall * 1e3 / sz.session_frames, kernels,
                 {"sessions": sz.sessions, "n_particles": sz.n_session,
                  "capacity": sz.capacity, "frames": sz.session_frames,
                  "img": sz.serve_img, "rmse_px_max": max(rmses),
                  "rmse_after_frame": sz.serve_warmup,
                  "max_dev_vs_standalone": max_dev,
                  "first_differing_frame": first_diff, "finite": fin})


def phase_sharded(sz: Sizes, seed: int, chips: int) -> list[Phase]:
    """RNA and RPA over a ``chips``-device mesh, and domain-decomposed
    RNA against the replicated-frame RNA run at the same size."""
    cfg, model, movie = tracking_setup(sz.img, sz.frames, seed)
    mesh = make_host_mesh(chips)
    rna, rpa = dist.DRAConfig(kind="rna"), dist.DRAConfig(kind="rpa")
    spec = make_domain_spec(cfg, chips)
    key = jax.random.key(seed + 1)
    phases, runs = [], {}
    for name, dra, per_chip, domain in (
            ("sharded_rna", rna, sz.n_per_chip, None),
            ("sharded_rpa", rpa, sz.n_per_chip, None),
            ("sharded_rna_domain_ref", rna, sz.n_domain_per_chip, None),
            ("sharded_domain_rna", rna, sz.n_domain_per_chip, spec)):
        sir = SIRConfig(n_particles=per_chip * chips, ess_frac=0.5)
        pf = ParallelParticleFilter(model=model, sir=sir, dra=dra,
                                    mesh=mesh, domain=domain)
        compiled, compile_s = compile_entry(pf.run, key, movie.frames)
        kernels, k_ok = kernel_census(compiled, sir_slots(sir, dra, chips))
        res, ms = run_timed(compiled, sz.frames, key, movie.frames)
        runs[name] = res
        rmse = float(tracking_rmse(res.estimates, movie.trajectories[:, 0],
                                   warmup=sz.filter_warmup))
        fin = finite(res.ess, res.log_marginal, res.estimates)
        checks = {"chips": chips, "n_particles": sir.n_particles,
                  "frames": sz.frames, "img": sz.img, "rmse_px": rmse,
                  "rmse_after_frame": sz.filter_warmup, "finite": fin}
        if domain is not None:
            ref = runs["sharded_rna_domain_ref"]
            dev, first = deviation((res.estimates, res.log_marginal),
                                   (ref.estimates, ref.log_marginal))
            checks.update(max_dev_vs_replicated=dev,
                          first_differing_frame=first)
        phases.append(Phase(name, k_ok and fin and rmse < 1.0, compile_s,
                            ms, kernels, checks))
    return phases


def run_phases(sz: Sizes, seed: int, chips: int = 1) -> list[Phase]:
    """The phases for ``chips`` (1: filter, oracle, serve; more: the
    sharded phases only), each line printed as it completes."""
    if chips > 1:
        steps = [lambda: phase_sharded(sz, seed, chips)]
    else:
        steps = [lambda: [phase_filter(sz, seed)],
                 lambda: phase_oracle(sz, seed),
                 lambda: [phase_serve(sz, seed)]]
    phases = []
    for step in steps:
        for phase in step():
            print(phase.line(), flush=True)
            phases.append(phase)
    return phases


def main() -> int:
    """Check the device, run the phases, print the verdict line."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    cache = runtime.enable_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked for, "
              f"{len(devices)} present", file=sys.stderr)
        return 1
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
          f"compile cache {cache}", flush=True)

    phases = run_phases(FULL, args.seed, args.chips)
    failed = [p.name for p in phases if not p.ok]
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
