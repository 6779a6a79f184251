"""Production training launcher: mesh + sharded params/opt + data +
checkpoint/restart + straggler-aware step loop.

On real TPU pods this binary runs per-host under the usual multi-host
runtime (jax.distributed.initialize); in this container it runs the same
code on the host-device mesh.  Fault-tolerance contract:

* checkpoints are atomic and every k steps (``--ckpt-every``);
* the data pipeline is (seed, step)-indexed — restart needs NO data state;
* ``--devices N`` trains on N devices — N simulated host devices on the
  CPU, the first N chips on an accelerator (testing elastic restore:
  train on 4, resume on 8 — shardings are rebuilt at load).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-32b \
        --smoke --devices 4 --steps 50 --batch 8 --seq 128
"""
import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.core import runtime
    if args.devices > 1:
        runtime.simulate_host_devices(args.devices)
    runtime.enable_compile_cache()

    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
    from repro.configs import get_config
    from repro.data.tokens import make_batch
    from repro.launch.sharding import make_param_shardings, mesh_context
    from repro.models.lm import model as M
    from repro.optim import OptConfig, init_opt_state
    from repro.train import TrainConfig, make_train_step

    cfg = get_config(args.arch, smoke=args.smoke)
    devs = list(runtime.host_mesh(args.devices).devices)
    n = len(devs)
    # 2-D mesh when we have ≥4 devices: (data, model); else 1-D data
    if n >= 4:
        model_par = 2
        mesh = Mesh(np.array(devs).reshape(n // model_par, model_par),
                    ("data", "model"))
    else:
        mesh = Mesh(np.array(devs), ("data",))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    opt = OptConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps)
    tc = TrainConfig(num_microbatches=args.microbatches,
                     xent_chunk=min(64, args.seq))

    with mesh_context(mesh):
        params = M.init_params(jax.random.key(args.seed), cfg)
        p_sh = make_param_shardings(mesh, params)
        params = jax.device_put(params, p_sh)
        opt_state = init_opt_state(params)
        opt_state = jax.device_put(
            opt_state, {"m": p_sh, "v": p_sh,
                        "step": NamedSharding(mesh, P())})
        step_fn = jax.jit(make_train_step(cfg, opt, tc),
                          donate_argnums=(0, 1))

        start = 0
        if args.ckpt_dir:
            resume = latest_step(args.ckpt_dir)
            if resume is not None:
                tree = load_checkpoint(
                    args.ckpt_dir, resume,
                    {"params": params, "opt": opt_state},
                    shardings={"params": p_sh,
                               "opt": {"m": p_sh, "v": p_sh,
                                       "step": NamedSharding(mesh, P())}})
                params, opt_state = tree["params"], tree["opt"]
                start = resume
                print(f"resumed step {resume} onto {n} devices (elastic)")

        slow_steps = 0
        t_hist = []
        for s in range(start, args.steps):
            batch = make_batch(args.seed, s, cfg, args.batch, args.seq)
            t0 = time.time()
            params, opt_state, m = step_fn(params, opt_state, batch)
            jax.block_until_ready(m["loss"])
            dt = time.time() - t0
            t_hist.append(dt)
            # straggler detection: flag steps ≥3× trailing median (on a real
            # cluster this triggers the launcher's requeue path)
            if len(t_hist) > 5:
                med = sorted(t_hist[-20:])[len(t_hist[-20:]) // 2]
                if dt > 3 * med:
                    slow_steps += 1
                    print(f"[straggler] step {s} took {dt:.2f}s "
                          f"(median {med:.2f}s)")
            if (s + 1) % 10 == 0:
                print(f"step {s + 1:4d}  loss {float(m['loss']):.4f}  "
                      f"gnorm {float(m['grad_norm']):.3f}  {dt * 1e3:.0f} ms",
                      flush=True)
            if args.ckpt_dir and (s + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, s + 1,
                                {"params": params, "opt": opt_state})
        print(f"finished {args.steps - start} steps; "
              f"{slow_steps} straggler events")


if __name__ == "__main__":
    main()
