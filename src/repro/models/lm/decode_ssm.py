"""LM decoding as a ``StateSpaceModel`` — the adapter that puts SMC
decoding on the shared filter substrate (DESIGN.md §17).

Decoding K hypotheses for one prompt IS a K-particle SIR filter over
token sequences: the particle state is the decode state (KV caches, last
sampled token, position), ``transition_sample`` is one ``forward_decode``
call plus a proposal draw from the τ-flattened logits, and
``observation_log_prob`` returns the target-vs-proposal importance
increment ``log p(tok) − log q(tok)`` (plus an optional reward score).
With that mapping, ``repro.serve.smc_decode`` is a thin wrapper over
``smc.make_sir_step`` / ``filters.make_bank_step`` — the same code path
the tracking filter, the FilterBank, and the resident session server
run — and a prefilled prompt becomes a resumable
``ParticleSessionServer`` session.

Conventions the adapter pins down:

* The first token is sampled during prefill (``prefill_state``) and
  its importance increment ``p₀ − q₀`` is folded into the *initial*
  log-weights, so ``decode_carry`` returns the step-0 log-normalizer
  increment alongside the carry — the first token is a full SMC step,
  not a freebie (the historical ``smc_decode`` dropped both the token
  and its weight).
* The emitted-token history rides *inside the particle state*
  (``state["tokens"]``), so the resampling gather re-indexes the whole
  history with the caches — returned sequences are root-to-leaf paths
  of the recorded ancestry by construction
  (``repro.core.genealogy.reconstruct_trajectories`` is the oracle).
* Scan-stacked KV cache groups carry the particle axis at dim 1, so the
  adapter implements the ``gather_state`` hook instead of relying on
  the core's leading-axis gather.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import smc
from repro.core.particles import ParticleEnsemble, effective_sample_size
from repro.models.lm import model as M

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class SMCDecodeConfig:
    """SMC decoding knobs: K particles per prompt, proposal temperature
    τ (τ=1 ⇒ proposal == target ⇒ uniform weights), and the shared
    ESS-triggered resampling decision (``smc.ess_resample``)."""

    n_particles: int = 8         # K hypotheses per prompt
    steps: int = 32
    proposal_temperature: float = 1.5
    ess_frac: float = 0.5
    resampler: str = "systematic"

    def sir(self) -> smc.SIRConfig:
        """The ``SIRConfig`` a decode filter runs under — ancestry
        recording on, so sequences/lineage invariants are checkable."""
        return smc.SIRConfig(
            n_particles=self.n_particles, resampler=self.resampler,
            ess_frac=self.ess_frac, record_ancestry=True)


@dataclasses.dataclass(frozen=True, eq=False)
class LMDecodeSSM:
    """The LM-as-``StateSpaceModel`` adapter (one prompt, K particles).

    The particle state pytree:

    * ``caches`` — per-particle KV caches (``M.init_caches`` layout).
    * ``token`` — ``(K,)`` the last sampled token per particle.
    * ``pos`` — ``(K,)`` absolute decode position (identical across
      particles; kept per-particle so every leaf has the particle axis).
    * ``emitted`` — ``(K,)`` number of tokens emitted so far.
    * ``inc`` — ``(K,)`` the pending importance increment
      ``log p − log q`` of the token just drawn; consumed by
      ``observation_log_prob``.
    * ``logp`` — ``(K,)`` cumulative target log-probability of the
      particle's sequence (the ``estimate_state`` summary).
    * ``tokens`` — ``(K, steps)`` the emitted-token history buffer;
      resample-gathered with everything else, which is what keeps
      returned sequences lineage-coherent.

    ``reward`` optionally scores ``(state, observation) -> (K,)`` extra
    log-weight per step — constraint/reward-guided decoding rides the
    same importance weights.

    The dataclass is a closure over traced ``params`` — pass it INTO
    jitted code, never as a static argument.
    """

    params: Any
    cfg: ArchConfig
    decode: SMCDecodeConfig
    prompt_len: int
    reward: Optional[Callable[[Any, Any], Array]] = None
    state_dim: int = 1

    @property
    def max_len(self) -> int:
        """KV-cache capacity: prompt + decode steps + 1 slack slot."""
        return self.prompt_len + self.decode.steps + 1

    def init(self, key: Array, n: int) -> Any:
        """A blank (all-zeros) decode state — the shape/dtype template
        servers and ``eval_shape`` callers need; real decoding starts
        from ``prefill_state``."""
        del key
        return {
            "caches": M.init_caches(self.cfg, n, self.max_len),
            "token": jnp.zeros((n,), jnp.int32),
            "pos": jnp.full((n,), self.prompt_len, jnp.int32),
            "emitted": jnp.zeros((n,), jnp.int32),
            "inc": jnp.zeros((n,), jnp.float32),
            "logp": jnp.zeros((n,), jnp.float32),
            "tokens": jnp.zeros((n, self.decode.steps), jnp.int32),
        }

    def transition_sample(self, key: Array, state: Any) -> Any:
        """One decode step: ``forward_decode`` on every particle's last
        token, then a proposal draw from the τ-flattened logits.  The
        importance increment is stashed in ``state["inc"]`` for
        ``observation_log_prob`` to report."""
        dec = self.decode
        logits, caches = M.forward_decode(
            self.params, self.cfg, state["token"][:, None],
            state["pos"][0], state["caches"])
        with jax.named_scope("ppf.lm.head"):
            logits = logits[:, 0].astype(jnp.float32)        # (K, V)
            p_log = jax.nn.log_softmax(logits, axis=-1)
            q_log = jax.nn.log_softmax(logits / dec.proposal_temperature,
                                       -1)
        tok = jax.random.categorical(key, q_log, axis=-1).astype(jnp.int32)
        pick = lambda lp: jnp.take_along_axis(      # noqa: E731
            lp, tok[:, None], -1)[:, 0]
        tokens = jax.lax.dynamic_update_slice(
            state["tokens"], tok[:, None],
            (jnp.zeros((), jnp.int32), state["emitted"][0]))
        return {"caches": caches, "token": tok, "pos": state["pos"] + 1,
                "emitted": state["emitted"] + 1,
                "inc": pick(p_log) - pick(q_log),
                "logp": state["logp"] + pick(p_log), "tokens": tokens}

    def observation_log_prob(self, state: Any, observation: Any) -> Array:
        """The importance increment of the token just drawn (target
        minus proposal), plus the pluggable reward score.  The
        ``observation`` is the frame index the serving plane submits —
        the reward hook may use it as a decode-step clock."""
        inc = state["inc"]
        if self.reward is not None:
            inc = inc + self.reward(state, observation)
        return inc

    # -- optional protocol hooks (DESIGN.md §17) ---------------------------
    def emission(self, state: Any) -> Array:
        """Genealogy emission: the token sampled this step."""
        return state["token"]

    def estimate_state(self, state: Any) -> Any:
        """Per-frame estimate: the cumulative target log-probability
        (posterior-weighted mean sequence score); token ids and caches
        have no meaningful mean."""
        return {"logp": state["logp"]}

    def gather_state(self, state: Any, ancestors: Array) -> Any:
        """Resampling gather aware of the cache layout: scan-stacked
        ``blocks`` groups carry the particle axis at dim 1, everything
        else leads with it — the §V compressed-particles exchange
        (ancestor indices only, replica creation is a local gather)."""
        caches = dict(state["caches"])
        if "blocks" in caches:
            blocks = jax.tree_util.tree_map(
                lambda x: jnp.take(x, ancestors, axis=1), caches["blocks"])
        lead = {k: jax.tree_util.tree_map(
                    lambda x: jnp.take(x, ancestors, axis=0), v)
                for k, v in caches.items() if k != "blocks"}
        if "blocks" in caches:
            lead["blocks"] = blocks
        rest = {k: jnp.take(v, ancestors, axis=0)
                for k, v in state.items() if k != "caches"}
        return {"caches": lead, **rest}


def prefill_state(model: LMDecodeSSM, key: Array, prompt: Array):
    """Prefill one prompt for K particles and draw the FIRST token.

    The prompt is replicated across the K particle rows, prefilled once,
    and the first token is drawn from the τ-flattened next-token
    distribution — with its importance increment ``p₀ − q₀`` folded
    into the returned weights, the prefill draw is a complete SMC step.

    Returns ``(state, log_weights, log_z0)``: the decode state after
    emitting token 0, the normalized ``(K,)`` initial log-weights, and
    the step-0 log-normalizer increment
    ``logsumexp(inc₀ − log K)``.
    """
    cfg, dec = model.cfg, model.decode
    k_part = dec.n_particles
    prompt = jnp.asarray(prompt, jnp.int32).reshape(1, -1)
    rep = jnp.broadcast_to(prompt, (k_part, prompt.shape[1]))
    h_last, caches, _ = M.forward_prefill(model.params, cfg, rep,
                                          max_len=model.max_len)
    logits = M.unembed(M.cast_params(model.params, cfg), cfg,
                       h_last)[:, 0].astype(jnp.float32)
    p_log = jax.nn.log_softmax(logits, axis=-1)
    q_log = jax.nn.log_softmax(logits / dec.proposal_temperature, -1)
    first = jax.random.categorical(key, q_log, axis=-1).astype(jnp.int32)
    pick = lambda lp: jnp.take_along_axis(      # noqa: E731
        lp, first[:, None], -1)[:, 0]
    inc0 = pick(p_log) - pick(q_log)
    lw_unnorm = inc0 - jnp.log(float(k_part))
    log_z0 = jax.scipy.special.logsumexp(lw_unnorm)
    t0 = prompt.shape[1]
    state = {
        "caches": caches,
        "token": first,
        "pos": jnp.full((k_part,), t0, jnp.int32),
        "emitted": jnp.ones((k_part,), jnp.int32),
        "inc": inc0,
        "logp": pick(p_log),
        "tokens": jnp.zeros((k_part, dec.steps),
                            jnp.int32).at[:, 0].set(first),
    }
    return state, lw_unnorm - log_z0, log_z0


def decode_carry(model: LMDecodeSSM, key: Array, prompt: Array):
    """A filter carry ready for the shared SIR step.

    Mirrors ``filters.member_carry``'s key discipline (split into
    init + run streams) with the init stream consumed by the prefill
    draw.  Returns ``(SIRCarry, log_z0, ess0)`` — the step-0
    log-normalizer increment and effective sample size belong to the
    prefill-sampled first token and prepend the scanned outputs.
    """
    k_init, k_run = jax.random.split(key)
    state, lw0, log_z0 = prefill_state(model, k_init, prompt)
    ens = ParticleEnsemble(
        state=state, log_weights=lw0,
        counts=jnp.ones((model.decode.n_particles,), jnp.int32))
    return smc.SIRCarry(k_run, ens), log_z0, effective_sample_size(lw0)
