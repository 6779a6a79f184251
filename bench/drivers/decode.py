"""The decode driver: SMC decoding of a batch of prompts through the
program's decode entry (``serve.smc_decode._decode_scan``: the shared bank
step, ``filters.make_bank_step`` over ``LMDecodeSSM``, scanned over the
tokens), called again and again from one prefilled carry, each call with
its own PRNG keys and ended with ``block_until_ready``; the window holds
only whole calls, each one token for every row per step.

Set-up makes the configuration's weights and the mix's prompts from the
seed, prefills each prompt through ``decode_ssm.decode_carry`` (jitted,
which also draws each row's first token), compiles the decode scan and
runs it once.  It maps the compiled scan's instructions to their ``ppf.*``
scopes (``bench/scopes.py``) for the trace readers, with the compile
cache keyed on metadata so that a cached executable carries the scopes.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, scopes
from bench.window import Span, Window


class Driver:
    """Repeated whole decode calls over one seeded, prefilled batch."""

    KEYS = 64
    CONTROL_WINDOW = True    # the control reads the window's sequences

    def __init__(self, cell: harness.Cell, seed: int, seconds: float):
        self.cell = cell
        self.t = cell.traffic
        self.seed = seed
        self.arch = cell.model.arch_config(cell.config)
        self.outs: list = []
        self.params = self.carry = self.run = None

    def setup(self) -> None:
        """Weights, prompts and prefilled carry of the seed, the decode
        scan compiled for them and warmed by one call."""
        from repro.models.lm.decode_ssm import SMCDecodeConfig
        from repro.serve.smc_decode import _decode_scan
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        t = self.t
        self.dcfg = SMCDecodeConfig(
            n_particles=t["particles"], steps=t["steps"],
            proposal_temperature=t["proposal_temperature"],
            ess_frac=t["ess_frac"], resampler=t["resampler"])
        self._prefill = jax.jit(self._prefill)
        self.prepare(self.seed)
        self.run = _decode_scan.lower(self.params, self.arch, self.dcfg,
                                      t["prompt_len"], None,
                                      self.carry).compile()
        text = self.run.as_text()
        scope_of, loops = scopes.scope_map(text)
        self.counters = {"scope_of": scope_of, "loops": loops,
                         "module": scopes.module_name(text)}
        self._call(0)

    def _prefill(self, params, key, prompt):
        """One prompt's carry: its K rows prefilled, each row's first
        token drawn (jitted in ``setup``)."""
        from repro.models.lm import decode_ssm
        model = decode_ssm.LMDecodeSSM(params=params, cfg=self.arch,
                                       decode=self.dcfg,
                                       prompt_len=self.t["prompt_len"])
        return decode_ssm.decode_carry(model, key, prompt)[0]

    def prepare(self, seed: int) -> None:
        """The weights, prompts, prefilled carry and call keys of
        ``seed``."""
        self.params = self.carry = None
        cfg, t = self.cell.config, self.t
        (self.s_weights, s_inputs, s_prefill, s_calls,
         self.s_pick) = harness.sub_seeds(seed, 5)
        self.params = self.cell.model.make_weights(cfg, self.s_weights)
        b = t["prompts"]
        prompts = self.cell.model.make_inputs(
            cfg, t, jax.random.key(s_inputs), b)
        self.prompts = np.asarray(prompts)
        keys = jax.random.split(jax.random.key(s_prefill), b)
        parts = [self._prefill(self.params, keys[i], prompts[i])
                 for i in range(b)]
        self.carry = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                            *parts)
        del parts
        self.keys = list(jax.random.split(jax.random.key(s_calls),
                                          (self.KEYS, b)))
        jax.block_until_ready((self.carry, self.keys))

    def _call(self, i: int) -> dict:
        carry = self.carry._replace(key=self.keys[i % self.KEYS])
        end, outs = self.run(self.params, carry)
        ens = end.ensemble
        small = {"tokens": ens.state["tokens"], "logp": ens.state["logp"],
                 "log_weights": ens.log_weights, "resampled": outs.resampled}
        jax.block_until_ready(small)
        return small

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
        self.outs = [{k: np.asarray(v) for k, v in o.items()}
                     for o in self.outs]
        steps = self.t["steps"] - 1
        frames = steps * len(self.outs)
        failed = sum(not (np.isfinite(o["logp"]).all()
                          and np.isfinite(o["log_weights"]).all())
                     for o in self.outs)
        share = float(np.mean([o["resampled"].mean() for o in self.outs]))
        return Window({"frame_ms": wall * 1e3 / frames},
                      attempted=len(self.outs), failed=failed,
                      frames=frames, seconds=wall,
                      counters=dict(self.counters), resample_share=share)

    def release(self) -> None:
        """Drop the compiled scan, the prefilled carry, the keys and the
        program's weights: the reference draws its own by name."""
        self.run = self.carry = self.keys = self.params = None

    def compare(self, control: bool = False) -> dict:
        """The compared numbers of one call of the window and
        ``compared_prompts`` of its prompts, drawn from the seed, against
        the reference over the same sequences.  With ``control`` the
        reference in its control precision takes the program's place."""
        ref, cfg, t = self.cell.ref, self.cell.config, self.t
        weights = self.cell.model.weights(cfg, self.s_weights)
        rng = np.random.default_rng(self.s_pick)
        out = self.outs[int(rng.integers(len(self.outs)))]
        chosen = rng.choice(t["prompts"], t["compared_prompts"],
                            replace=False)
        got, want = [], []
        for b in chosen:
            # step 0 is the prefill's draw, which never resamples
            resampled = np.concatenate([[False], out["resampled"][:, b]])
            args = (cfg, t, weights, self.prompts[b], out["tokens"][b],
                    resampled)
            want.append(ref.reference(*args))
            got.append(ref.reference(*args, dtype=ref.CONTROL) if control
                       else {"logp": out["logp"][b],
                             "log_weights": out["log_weights"][b]})
        def join(rows):
            return {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}
        return ref.compare(cfg, t, join(got), join(want))
