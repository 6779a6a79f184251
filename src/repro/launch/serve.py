"""Serving launchers: the LM decode path and the particle request plane.

Two front ends share this entry point:

* ``--mode greedy|sample|smc`` — batched LM decoding (prefill + jitted
  decode scan, or SMC particle decoding); ``--devices N`` exposes N
  simulated devices on the CPU and is inert on an accelerator.  Timing
  separates one-off compile from steady state: ``--warmup`` runs
  (default 1, the ``benchmarks/pf_worker.py`` convention) execute
  before the measured window, and the reported
  tok/s is pure steady-state — the compile seconds are printed on their
  own line instead of silently inflating the first measurement.
* ``--mode sessions`` — the asyncio request plane (DESIGN.md §15): a
  ``ParticleFrontend`` over a resident ``ParticleSessionServer`` bank,
  driven by a synthetic Poisson client fleet, reporting p50/p99
  per-frame latency, the scheduler's operational counters, and the
  seconds its loop and its step executor spent in each ``ppf.*`` span.
  The whole report reads from the frontend's ``Metrics`` snapshot, so
  there is exactly one accounting path to trust.
* ``--mode fleet`` — the multi-bank controller (DESIGN.md §16): two
  active banks plus a standby, skewed Poisson clients with mid-run
  churn, printing the migration/scale counters and the per-bank
  placement map.  The committed benchmark is
  ``benchmarks/bench_fleet.py``; this is the watch-it-run demo.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --smoke \
        --batch 4 --prompt-len 32 --steps 32 --mode greedy
    PYTHONPATH=src python -m repro.launch.serve --mode sessions \
        --sessions 12 --capacity 8 --duration 3
    PYTHONPATH=src python -m repro.launch.serve --mode fleet \
        --sessions 8 --capacity 8 --duration 4
"""
import argparse
import time


def main() -> None:
    """Parse args and dispatch to the LM or sessions front end."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--mode", default="greedy",
                    choices=["greedy", "sample", "smc", "sessions", "fleet"])
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--particles", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=1,
                    help="untimed compile/warmup runs before the "
                         "measured window (LM modes)")
    # sessions/fleet-mode knobs
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=8,
                    help="total slot budget (fleet mode splits it "
                         "across two banks + a standby)")
    ap.add_argument("--duration", type=float, default=3.0,
                    help="seconds of synthetic Poisson load")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="per-session mean frames/s")
    ap.add_argument("--max-delay", type=float, default=0.005,
                    help="scheduler deadline trigger in seconds")
    args = ap.parse_args()

    from repro.core import runtime
    if args.devices > 1:
        runtime.simulate_host_devices(args.devices)
    runtime.enable_compile_cache()

    if args.mode == "sessions":
        _serve_sessions(args)
    elif args.mode == "fleet":
        _serve_fleet(args)
    else:
        _serve_lm(args)


def _serve_lm(args) -> None:
    """LM decode modes with compile/steady-state separated timing."""
    import jax

    from repro.configs import get_config
    from repro.models.lm import model as M
    from repro.serve import SMCDecodeConfig, generate, smc_decode

    cfg = get_config(args.arch, smoke=args.smoke)
    params = M.init_params(jax.random.key(0), cfg)
    if cfg.n_codebooks > 1:
        prompt = jax.random.randint(
            jax.random.key(1), (args.batch, args.prompt_len, cfg.n_codebooks),
            0, cfg.vocab_size)
    else:
        prompt = jax.random.randint(
            jax.random.key(1), (args.batch, args.prompt_len), 0,
            cfg.vocab_size)

    if args.mode == "smc":
        smc = SMCDecodeConfig(n_particles=args.particles, steps=args.steps)

        def run(key):
            out = smc_decode(params, cfg, prompt, smc, key=key)
            jax.block_until_ready(out[0])
            return out
    else:
        temp = 0.0 if args.mode == "greedy" else args.temperature

        def run(key):
            out = generate(params, cfg, prompt, steps=args.steps,
                           temperature=temp, key=key)
            jax.block_until_ready(out)
            return out

    # warmup runs eat the compile; the measured window is steady state
    # (the old single-window measurement reported compile+prefill+decode
    # as one conflated "tok/s" — useless for comparing runs)
    t0 = time.perf_counter()
    for i in range(max(args.warmup, 0)):
        run(jax.random.key(100 + i))
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = run(jax.random.key(2))
    steady_s = time.perf_counter() - t0

    if args.mode == "smc":
        seqs, lw, log_z, ess = out
        print(f"compile+warmup ({args.warmup} runs): {compile_s:.2f}s")
        print(f"SMC decode {seqs.shape}: {steady_s:.2f}s steady "
              f"({steady_s / args.steps * 1e3:.1f} ms/token-step), "
              f"logZ={[round(float(z), 3) for z in log_z]}")
    else:
        tput = args.batch * args.steps / steady_s
        print(f"compile+warmup ({args.warmup} runs): {compile_s:.2f}s")
        print(f"{args.mode} decode {out.shape}: {steady_s:.2f}s steady "
              f"({tput:.1f} tok/s batch throughput)")


def _lg_demo_model():
    """The 1-D linear-Gaussian demo model both serving modes drive."""
    import jax
    import jax.numpy as jnp

    from repro.core.smc import StateSpaceModel

    a, q, h, r0 = 0.9, 0.5, 1.0, 0.4

    def init_sampler(key, n):
        return jax.random.normal(key, (n, 1)) * 2.0

    def dynamics_sample(key, s):
        return a * s + jnp.sqrt(q) * jax.random.normal(key, s.shape)

    def log_likelihood(s, z):
        return -0.5 * (z - h * s[:, 0]) ** 2 / r0

    return StateSpaceModel(init_sampler, dynamics_sample,
                           log_likelihood, state_dim=1)


def _print_plane_report(snap: dict, label: str) -> None:
    """Render one request plane's report from its ``Metrics`` snapshot —
    frames, latency percentiles, park/resume counts, the mean queue
    wait and the seconds in each ``ppf.*`` span all come from the same
    snapshot the scheduler maintains (no shadow accounting)."""
    c = snap["counters"]
    lat = snap["series"].get("latency", {})
    coalesce = snap["series"].get("coalesce", {})
    frames = c.get("frames", 0)
    print(f"{label} frames={frames:.0f} "
          f"p50={lat.get('p50', 0.0) * 1e3:.1f}ms "
          f"p99={lat.get('p99', 0.0) * 1e3:.1f}ms "
          f"steps={c.get('steps', 0):.0f} "
          f"coalesce_mean={coalesce.get('mean', 0.0):.2f} "
          f"parks={c.get('park_events', 0):.0f} "
          f"resumes={c.get('resume_events', 0):.0f}")
    wait = c.get("queue_wait_s", 0.0) / frames if frames else 0.0
    spans = " ".join(f"{name[4:]}={c[name]:.3f}s" for name in sorted(c)
                     if name.startswith("ppf."))
    print(f"{label} queue_wait_mean={wait * 1e3:.1f}ms {spans}")


def _serve_sessions(args) -> None:
    """Drive the asyncio request plane with a synthetic Poisson fleet."""
    import asyncio

    import jax
    import numpy as np

    from repro.core import SIRConfig
    from repro.serve import (FrontendConfig, ParticleFrontend,
                             ParticleSessionServer)

    async def client(fe, sid, rng, until):
        stream = await fe.open(jax.random.key(sid))
        futs = []
        loop = asyncio.get_running_loop()
        while loop.time() < until:
            await asyncio.sleep(rng.exponential(1.0 / args.rate))
            futs.append(await fe.submit(stream, np.float32(rng.normal())))
        await asyncio.gather(*futs)
        await fe.close(stream)

    async def run():
        server = ParticleSessionServer(
            model=_lg_demo_model(),
            sir=SIRConfig(n_particles=1024, ess_frac=0.5),
            capacity=args.capacity)
        async with ParticleFrontend(
                server, FrontendConfig(max_delay=args.max_delay)) as fe:
            t0 = time.perf_counter()         # compile before traffic, and
            await fe.warmup(np.float32(0.0))  # report it separately
            print(f"compile+warmup ({len(server.tiers)} tiers): "
                  f"{time.perf_counter() - t0:.2f}s")
            until = asyncio.get_running_loop().time() + args.duration
            await asyncio.gather(*(
                client(fe, i, np.random.default_rng(i), until)
                for i in range(args.sessions)))
            snap = fe.snapshot()
        _print_plane_report(
            snap, f"sessions={args.sessions} capacity={args.capacity}")
        print(f"tier_hits={snap['tier_hits']} "
              f"step_traces={snap['step_traces']}")

    asyncio.run(run())


def _serve_fleet(args) -> None:
    """Multi-bank demo: two active banks + a standby under skewed
    Poisson load with mid-run churn, so the rebalancer has work to do."""
    import asyncio

    import jax
    import numpy as np

    from repro.core import SIRConfig
    from repro.launch.registry import BankSpec, FleetRegistry
    from repro.serve import (FleetConfig, FleetController, FrontendConfig,
                             ParticleSessionServer)

    per_bank = max(args.capacity // 2, 1)
    registry = FleetRegistry([
        BankSpec("a", per_bank),
        BankSpec("b", per_bank),
        BankSpec("spare", per_bank, standby=True),
    ])

    def make_server(spec):
        return ParticleSessionServer(
            model=_lg_demo_model(),
            sir=SIRConfig(n_particles=1024, ess_frac=0.5),
            capacity=spec.capacity)

    async def client(fleet, sid, rng, until):
        # every 4th stream is hot (4x rate); the even-indexed half is
        # short-lived — its departure skews residency and forces the
        # rebalancer to migrate survivors (same shape as bench_fleet)
        rate = args.rate * (4.0 if sid % 4 == 0 else 1.0)
        fs = await fleet.open(jax.random.key(sid))
        futs = []
        loop = asyncio.get_running_loop()
        while loop.time() < until:
            await asyncio.sleep(rng.exponential(1.0 / rate))
            futs.append(await fleet.submit(fs, np.float32(rng.normal())))
        await asyncio.gather(*futs)
        await fleet.close(fs)

    async def run():
        cfg = FleetConfig(
            rebalance_interval=0.05,
            frontend=FrontendConfig(max_delay=args.max_delay))
        async with FleetController(make_server, registry, cfg) as fleet:
            t0 = time.perf_counter()
            await fleet.warmup(np.float32(0.0))
            print(f"compile+warmup (2 banks): "
                  f"{time.perf_counter() - t0:.2f}s")
            now = asyncio.get_running_loop().time()
            await asyncio.gather(*(
                client(fleet, i, np.random.default_rng(i),
                       now + args.duration * (0.4 if i % 2 == 0 else 1.0))
                for i in range(args.sessions)))
            snap = fleet.snapshot()
        c = snap["counters"]
        stall = snap["series"].get("migration_stall_frames", {})
        print(f"sessions={args.sessions} total_capacity={args.capacity} "
              f"migrations={c.get('migrations', 0):.0f} "
              f"stall_frames_mean={stall.get('mean', 0.0):.2f} "
              f"scale_out={c.get('scale_out_events', 0):.0f} "
              f"scale_in={c.get('scale_in_events', 0):.0f} "
              f"bank_failures={c.get('bank_failures', 0):.0f}")
        for name, bank in sorted(snap["banks"].items()):
            _print_plane_report(
                bank["frontend"],
                f"bank {name} cap={bank['capacity']} "
                f"streams={bank['live_streams']} dead={bank['dead']}")

    asyncio.run(run())


if __name__ == "__main__":
    main()
