"""The offline driver: the program's offline entry
(``ParallelParticleFilter.run``, built by the configuration's
``build_filter`` on the cell's chips) called again and again over one
seeded observation sequence, each call with its own PRNG key and ended
with ``block_until_ready``; the window holds only whole calls.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import harness
from bench.window import Span, Window, finite, host


class Driver:
    """Repeated whole-sequence filter calls over one seeded sequence."""

    KEYS = 1024
    CONTROL_WINDOW = False   # the control needs only the inputs

    def __init__(self, cell: harness.Cell, seed: int, seconds: float):
        self.cell = cell
        self.t = cell.traffic
        self.seed = seed
        self.n = cell.config["n_particles_per_chip"] * cell.chips
        self.outs: list = []

    def setup(self) -> None:
        """The program, compiled for the seed's inputs (from the
        persistent cache after a cell's first run), and those inputs."""
        run, self.place = self.cell.model.build_filter(
            self.cell.config, self.t, self.n, self.cell.chips)
        self.prepare(self.seed)
        self.run = run.lower(self.keys[0], self.obs).compile()

    def prepare(self, seed: int) -> None:
        """The observation sequence and the calls' PRNG keys of ``seed``."""
        (self.s_inputs, self.s_prog, self.s_ref, self.s_ctrl,
         self.s_sample) = harness.sub_seeds(seed, 5)
        obs, _ = self.cell.model.make_inputs(
            self.cell.config, self.t, jax.random.key(self.s_inputs), 1)
        self.obs = self.place(obs[0])
        self.obs_host = np.asarray(self.obs)
        self.keys = list(jax.random.split(jax.random.key(self.s_prog),
                                          self.KEYS))

    def _call(self, i: int):
        res = self.run(self.keys[i % self.KEYS], self.obs)
        leaves = (res.estimates, res.ess, res.log_marginal, res.resampled)
        jax.block_until_ready(res)
        return leaves

    def window(self, seconds: float) -> Window:
        """Whole calls until ``seconds`` have passed."""
        self.outs = []
        t0 = time.perf_counter()
        while True:
            with Span("bench.run"):
                self.outs.append(self._call(len(self.outs)))
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        self.outs = [host(o) for o in self.outs]
        steps = self.obs_host.shape[0]
        frames = steps * len(self.outs)
        failed = sum(not finite(o[:3]) for o in self.outs)
        share = float(np.mean([o[3].mean() for o in self.outs]))
        return Window({"frame_ms": wall * 1e3 / frames},
                      attempted=len(self.outs), failed=failed,
                      frames=frames, seconds=wall, resample_share=share)

    def release(self) -> None:
        """Drop the program and its device buffers."""
        self.run = self.obs = self.keys = None

    def compare(self, control: bool = False) -> dict:
        """The compared numbers of one call of the window, drawn from the
        seed, against a reference run over the same inputs (one call, so
        that the numbers mean the same however many calls a window
        holds).  With ``control`` the reference in its control precision
        takes the place of the program's call."""
        ref, cfg = self.cell.ref, self.cell.config
        if control:
            got = ref.reference(cfg, self.t, self.obs_host, self.n,
                                jax.random.key(self.s_ctrl),
                                dtype=ref.CONTROL)
        else:
            rng = np.random.default_rng(self.s_sample)
            got = self.outs[int(rng.integers(len(self.outs)))][:3]
        r = ref.reference(cfg, self.t, self.obs_host, self.n,
                          jax.random.key(self.s_ref))
        return ref.compare(cfg, self.t, got, r)
