"""SMC (particle-filter) decoding — the paper's technique as a first-class
serving feature (DESIGN.md §17).

Each prompt carries K particles = decode hypotheses.  The model side
lives in ``repro.models.lm.decode_ssm.LMDecodeSSM`` (state = KV caches +
last token + position, proposal = model at temperature τ, importance
increment = ``log p − log q`` plus an optional reward); this module is
the *driver*: ``smc_decode`` is a thin wrapper over the shared
``filters.make_bank_step`` / ``smc.make_sir_step`` path — the very same
step the tracking filter, the FilterBank, and the resident session
server run — vmapped over the prompt batch, with ancestry recording on.

Weight/normalizer conventions are therefore the shared SIR ones
(DESIGN.md §13.1): ``logsumexp(lw) == 0`` entering every step, each
step's ``log_z`` is the marginal-likelihood increment, and the total
``log_z`` is the sum of all increments *including the prefill draw's* —
no resample-event-only accounting, no dropped residual tail.  The
per-prompt ``log_z`` is the SMC estimate of log E_q[w] (≡ 0-unbiased in
expectation: E[exp(log_z)] = 1 without a reward), which is what makes
best-of-K reranking scores meaningful.

Cache shuffles are the *compressed particles* idea of paper §V: only
ancestor indices are exchanged; replica "creation" is a local cache
gather (``LMDecodeSSM.gather_state``).

``suspended_decode_session`` packages a prefilled prompt as a
``SuspendedSession``, so per-prompt decoding runs as resident sessions
on ``ParticleSessionServer`` (and, via ``Handoff``/``adopt``, on
``ParticleFrontend``) with per-slot prompts — bitwise the standalone
``smc_decode`` loop for the same keys.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import filters, particles
from repro.models.lm import decode_ssm
from repro.models.lm.decode_ssm import (  # noqa: F401  (re-exports)
    LMDecodeSSM, SMCDecodeConfig,
)
from repro.serve.sessions import SuspendedSession

Array = jax.Array


class SMCDecodeResult(NamedTuple):
    """Everything one SMC decode run produces, per prompt.

    ``steps`` rows below include the prefill-sampled first token as row
    0 (identity ancestors, ``resampled=False``, the ``p₀ − q₀``
    log-normalizer increment).
    """

    sequences: Array     # (B, K, steps) lineage-coherent token rows
    log_weights: Array   # (B, K) final normalized log-weights
    log_z: Array         # (B,) total log-normalizer estimate
    ess: Array           # (steps, B) effective sample size per step
    log_marginal: Array  # (steps, B) per-step log-normalizer increments
    resampled: Array     # (steps, B) ESS-trigger trace
    ancestors: Array     # (steps, B, K) recorded ancestor indices
    emissions: Array     # (steps, B, K) pre-gather token draws
    logp: Array          # (B, K) each row's summed target log p


@functools.partial(jax.jit, static_argnames=("cfg", "dcfg", "t0", "reward"))
def _decode_scan(params, cfg: ArchConfig, dcfg: SMCDecodeConfig, t0: int,
                 reward, carry):
    """The jitted decode loop: scan the shared bank step (B prompts ×
    K particles) over the remaining ``steps − 1`` frames."""
    model = LMDecodeSSM(params=params, cfg=cfg, decode=dcfg, prompt_len=t0,
                        reward=reward)
    step = filters.make_bank_step(model, dcfg.sir())
    b = carry.ensemble.log_weights.shape[0]
    n_dec = dcfg.steps - 1
    # the "observation" of decode step t is the step index — the reward
    # hook's clock; the importance increment itself rides in the state
    obs = jnp.broadcast_to(
        jnp.arange(1, dcfg.steps, dtype=jnp.float32)[:, None], (n_dec, b))
    active = jnp.ones((n_dec, b), bool)
    return jax.lax.scan(step, carry, (obs, active))


def smc_decode(params, cfg: ArchConfig, prompt: Array,
               smc: SMCDecodeConfig = SMCDecodeConfig(), *,
               key: Array | None = None,
               reward=None) -> SMCDecodeResult:
    """Decode ``prompt`` (B, T0) with K SMC hypotheses per prompt.

    Prompt ``i`` consumes PRNG stream ``jax.random.split(key, B)[i]``
    through ``decode_ssm.decode_carry`` — the same contract
    ``suspended_decode_session`` uses, which is what makes
    session-hosted decoding bitwise this function.  Prefill runs
    per-prompt on the host (eagerly, like the serving path); the decode
    loop is one jitted scan.
    """
    key = key if key is not None else jax.random.key(0)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, t0 = prompt.shape
    k_part = smc.n_particles
    model = LMDecodeSSM(params=params, cfg=cfg, decode=smc, prompt_len=t0,
                        reward=reward)
    keys = jax.random.split(key, b)
    parts = [decode_ssm.decode_carry(model, keys[i], prompt[i])
             for i in range(b)]
    carry = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                   *[p[0] for p in parts])
    log_z0 = jnp.stack([p[1] for p in parts])
    ess0 = jnp.stack([p[2] for p in parts])
    first = carry.ensemble.state["tokens"][:, :, 0]          # (B, K)

    carry, outs = _decode_scan(params, cfg, smc, t0, reward, carry)

    ident = jnp.broadcast_to(jnp.arange(k_part, dtype=jnp.int32),
                             (1, b, k_part))
    log_marginal = jnp.concatenate([log_z0[None], outs.log_marginal], 0)
    return SMCDecodeResult(
        sequences=carry.ensemble.state["tokens"],
        log_weights=carry.ensemble.log_weights,
        log_z=jnp.sum(log_marginal, axis=0),
        ess=jnp.concatenate([ess0[None], outs.ess], 0),
        log_marginal=log_marginal,
        resampled=jnp.concatenate(
            [jnp.zeros((1, b), outs.resampled.dtype), outs.resampled], 0),
        ancestors=jnp.concatenate([ident, outs.ancestors], 0),
        emissions=jnp.concatenate([first[None], outs.diag["emission"]], 0),
        logp=carry.ensemble.state["logp"],
    )


def suspended_decode_session(model: LMDecodeSSM, key: Array,
                             prompt: Array) -> SuspendedSession:
    """Package a freshly prefilled prompt as a ``SuspendedSession``.

    ``ParticleSessionServer.resume`` on the result attaches the prompt
    as a resident decode session: frame ``t`` (a float32 step index,
    ``t = 1, 2, ...``) advances it one token, exactly like the
    standalone loop — with the same per-prompt key, bitwise so.  The
    snapshot's history holds the prefill draw as frame 0 (its
    ``log_z0``/``ess0``/identity-ancestors row), so ``result()`` after
    ``steps − 1`` served frames spans the whole decode.

    All sessions on one server share the state *shapes*: equal
    ``prompt_len`` (pad prompts to a bucket) and one ``SMCDecodeConfig``.
    """
    carry, log_z0, ess0 = decode_ssm.decode_carry(model, key, prompt)
    ens = carry.ensemble
    k_part = model.decode.n_particles
    est0 = particles.weighted_mean(
        ens.replace(state=model.estimate_state(ens.state)))
    return SuspendedSession(
        key_data=np.asarray(jax.random.key_data(carry.key)),
        state=jax.tree_util.tree_map(np.asarray, ens.state),
        log_weights=np.asarray(ens.log_weights),
        counts=np.asarray(ens.counts),
        frames_done=1,
        estimates=jax.tree_util.tree_map(
            lambda x: np.asarray(x)[None], est0),
        ess=np.asarray(ess0)[None],
        log_marginal=np.asarray(log_z0)[None],
        resampled=np.zeros((1,), bool),
        ancestors=np.arange(k_part, dtype=np.int32)[None],
    )
