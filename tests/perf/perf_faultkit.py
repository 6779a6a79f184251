"""Shared pieces of the fault tests: the cells at a size a test run
holds, a run of the harness past its look for a chip, and the faults
planted under the timed path.

The limits are the cells' own, from their traffic mixes: a sound run at
the test size stays under them, and each fault goes over one of them.
The offline cell keeps its 512x512 frames (where bfloat16 positions lie
2 px apart above 256, so the control loses the spot as it does on the
chip) with 2^18 particles over 20 frames; the served cell keeps its 64x64
frames and 16384 particles a session on 4 slots.
"""
import argparse
import pathlib
import sys

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
OFFLINE = ("track-512-n1m",
           {"frames": 20, "warmup_frames": 12},
           {"n_particles_per_chip": 1 << 18}, 0.5)
SERVED = ("track-serve-roi64",
          {"frames": 16, "capacity": 4, "slots": 4, "total_rate": 40.0,
           "min_frames": 14, "wait_s": 30.0},
          {}, 3.0)


def control_checks(spec, seed=5):
    """The compared numbers against the cell's limits with the reference
    in its control precision in the program's place."""
    name, traffic, config, seconds = spec
    cell = harness.load_cell(name, traffic, config)
    d = harness.make_driver(cell, seed, seconds)
    d.setup()
    if d.CONTROL_WINDOW:
        d.window(seconds)
    d.release()
    return harness.limits_check(d.compare(control=True),
                                cell.traffic["limits"])


def run_cell(spec, seed=5):
    name, traffic, config, seconds = spec
    cell = harness.load_cell(name, traffic, config)
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=0)
    result, checks = bench_run.measure(cell, args, jax.devices()[:1], PEAK)
    return result["correct"], checks


def over(checks, name):
    """Whether the compared number ``name`` went over its limit."""
    return checks[name]["value"] > checks[name]["limit"]


def frozen_state(make_step):
    """A step that returns the state it was given."""
    def build(*a, **k):
        step = make_step(*a, **k)

        def frozen(carry, obs):
            _, out = step(carry, obs)
            return carry, out
        return frozen
    return build


def half_the_particles(original):
    """Half of the particles left out: their likelihood is -inf, so the
    weights and the mean are taken over the rest."""
    def ll(self, state, frame):
        out = original(self, state, frame)
        half = out.shape[0] // 2
        return out.at[half:].set(-jnp.inf)
    return ll
