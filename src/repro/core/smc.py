"""Sequential importance resampling (paper Alg. 1) — single-device and
per-shard SPMD step builders.

The step builders return functions suitable for ``jax.lax.scan`` over a
sequence of observations (frames).  Both carry a ``SIRCarry(key,
ensemble)`` — ``ParticleEnsemble`` is the currency of the whole stack
(DESIGN.md §9).  The distributed builder is a *per-shard* program
(collectives by ``axis_name``) to be wrapped in ``shard_map`` by
``repro.core.filters``.

Every builder is parameterized by ANY implementation of the
``repro.models.ssm.StateSpaceModel`` protocol (DESIGN.md §12): the core
only calls ``init`` / ``transition_sample`` / ``observation_log_prob``
(plus the optional spatial hooks for domain decomposition) and knows
nothing about the observation modality.  The ``StateSpaceModel``
dataclass below is the closure-style callable-bundle adapter for that
protocol — the historical constructor, kept because closures are the
lightest way to write a throwaway model.

``ess_resample`` is the one SIR resampling decision (Alg. 1 lines 15–18)
shared by the single-device step, the ``FilterBank``, and SMC decoding
(``repro.serve.smc_decode``): ESS check, conditional resample, identity
ancestors when the threshold is not hit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import distributed as dist
from repro.core import domain as domain_mod
from repro.core import particles
from repro.core import resampling
from repro.core import runtime
from repro.core.particles import ParticleEnsemble, effective_sample_size
from repro.kernels import sir_fused
from repro.models.ssm import base as ssm_base

Array = jax.Array

# re-exported so `smc.domain_hooks` reads naturally at the call sites
domain_hooks = ssm_base.domain_hooks


@dataclasses.dataclass(frozen=True)
class StateSpaceModel:
    """Closure-style adapter for the ``repro.models.ssm.StateSpaceModel``
    protocol (paper §II bootstrap-proposal models).

    Bundle three callables and this class exposes them under the
    protocol method names (``init`` / ``transition_sample`` /
    ``observation_log_prob``) every filter driver consumes — the
    lightest way to define a throwaway model; class-based models
    (``repro.models.ssm`` families, ``repro.models.tracking.TrackingSSM``)
    implement the protocol directly instead.

    All callables are batched over the leading particle axis.

    init_sampler:    (key, n) -> state pytree with leading dim n
    dynamics_sample: (key, state) -> state            (the proposal π = prior)
    log_likelihood:  (state, observation) -> (n,)     log p(z|x)

    Models with spatial (image) observations may additionally provide the
    domain-decomposition hooks (DESIGN.md §10; both required for
    ``ParallelParticleFilter(domain=...)``):

    positions:           (state) -> (n, 2) frame-coordinate (y, x)
    tile_log_likelihood: (state, slab, (oy, ox)) -> (n,)  log p(z|x)
        against one halo slab whose [0, 0] pixel sits at frame
        coordinates (oy, ox); must agree exactly with ``log_likelihood``
        for particles owned by the slab's tile.
    """

    init_sampler: Callable[..., Any]
    dynamics_sample: Callable[..., Any]
    log_likelihood: Callable[..., Array]
    state_dim: int = 5
    positions: Callable[..., Array] | None = None
    tile_log_likelihood: Callable[..., Array] | None = None

    def init(self, key: Array, n: int) -> Any:
        """Protocol ``init`` — delegates to ``init_sampler``."""
        return self.init_sampler(key, n)

    def transition_sample(self, key: Array, state: Any) -> Any:
        """Protocol ``transition_sample`` — delegates to
        ``dynamics_sample``."""
        return self.dynamics_sample(key, state)

    def observation_log_prob(self, state: Any, observation: Any) -> Array:
        """Protocol ``observation_log_prob`` — delegates to
        ``log_likelihood``."""
        return self.log_likelihood(state, observation)


@dataclasses.dataclass(frozen=True)
class SIRConfig:
    """SIR filter knobs (paper Alg. 1).

    Attributes:
      n_particles: global particle count ``N`` (distributed runs split it
        into ``N / P`` slots per shard).
      resampler: key into ``repro.core.resampling.RESAMPLERS``
        (``systematic`` / ``stratified`` / ``multinomial`` / ``residual``
        / the collective-free ``metropolis`` / ``rejection``).
      ess_frac: resample when ``N_eff < ess_frac * N`` (Alg. 1 line 15).
      always_resample: resample every frame regardless of ESS.
      step_backend: ``"composed"`` runs reweight → estimate → ESS →
        resample as separate ops (the historical, golden-pinned path);
        ``"fused"`` runs the whole weight phase through
        ``repro.kernels.sir_fused`` — one normalization shared by every
        statistic, ancestors without the counts round-trip, as one
        XLA program on every platform (DESIGN.md §13; its Pallas
        megakernel does not lower for TPU yet).  Configs a fused step
        cannot honor (a comb-only resampler, the per-shard DRA step,
        ancestry recording, an ``estimate_state`` model hook) fall back
        to the composed path automatically.
      fused_backend: optional override of the fused execution backend
        (``"interpret"`` / ``"xla"``; ``"pallas"`` raises, as does
        ``"interpret"`` on a TPU); ``None`` is ``"xla"``.
      record_ancestry: emit the per-step ancestor indices in
        ``StepOutput.ancestors`` plus the genealogy diagnostics
        (``diag["emission"]`` — the model's per-particle emission before
        the resampling gather — and ``diag["log_weights"]`` — the
        normalized post-reweight weights) that
        ``repro.core.genealogy`` consumes for trajectory reconstruction
        and smoothing (DESIGN.md §17).  Off by default: recording costs
        O(N) per frame in the scanned outputs.
    """

    n_particles: int = 4096
    resampler: str = "systematic"
    ess_frac: float = 0.5           # resample when N_eff < ess_frac * N
    always_resample: bool = False
    step_backend: str = "composed"  # "composed" | "fused" (DESIGN.md §13.1)
    fused_backend: str | None = None
    record_ancestry: bool = False   # genealogy layer (DESIGN.md §17)


class SIRCarry(NamedTuple):
    """Scan carry of every SIR step: PRNG key + the particle ensemble."""

    key: Array
    ensemble: ParticleEnsemble


class StepOutput(NamedTuple):
    """Per-frame outputs of one SIR step (leading dims follow the caller:
    ``(...)`` single filter, ``(B, ...)`` bank, ``(K, ...)`` after scan).
    """

    estimate: Any        # MMSE state estimate (paper §II)
    ess: Array           # global effective sample size
    log_marginal: Array  # running log p(Z^k) increment
    resampled: Array     # bool
    ancestors: Array     # (N,) ancestor indices when recording, else (0,)
    diag: dict           # DRA diagnostics (links, overflow, q, ...)


class ResampleDecision(NamedTuple):
    """Outcome of ``ess_resample`` — Alg. 1 lines 15–18 in one record."""

    ancestors: Array     # (N,) — identity permutation when not resampled
    ess: Array           # N_eff before resampling
    log_z: Array         # logsumexp of the incoming weights
    resampled: Array     # bool


def no_ancestors() -> Array:
    """The ``StepOutput.ancestors`` placeholder when ancestry recording
    is off: a width-0 int32 vector, so the field stacks/vmaps/masks like
    any other leaf without reserving O(N) per frame."""
    return jnp.zeros((0,), jnp.int32)


def ess_resample(key: Array, log_weights: Array, *, ess_frac: float,
                 resampler: str = "systematic",
                 always: bool = False) -> ResampleDecision:
    """Alg. 1 lines 15–18 as one shared op: ESS check + conditional
    resample.  Gathering ``state[ancestors]`` commits the decision — the
    ancestors are the identity when the threshold is not hit, so callers
    need no extra select (the resample itself still runs unconditionally,
    keeping the SPMD schedule static, DESIGN.md §2.3).

    Weight-reset conventions differ per caller (tracking normalizes every
    step, decoding only on resample) and stay at the call site.
    """
    n = log_weights.shape[0]
    ess = effective_sample_size(log_weights)
    log_z = jax.scipy.special.logsumexp(log_weights)
    resampled = jnp.logical_or(ess < ess_frac * n, jnp.asarray(always))
    counts = resampling.RESAMPLERS[resampler](key, log_weights, n, capacity=n)
    ancestors = resampling.counts_to_ancestors(counts, n)
    ancestors = jnp.where(resampled, ancestors,
                          jnp.arange(n, dtype=ancestors.dtype))
    return ResampleDecision(ancestors, ess, log_z, resampled)


# ---------------------------------------------------------------------------
# Single-device SIR (reference semantics for everything else)
# ---------------------------------------------------------------------------

def make_sir_step(model: ssm_base.StateSpaceModel, cfg: SIRConfig):
    """Build the single-device SIR step (Alg. 1 lines 5–18).

    ``model`` is any ``repro.models.ssm.StateSpaceModel`` implementation.
    Returns ``step(carry: SIRCarry, observation) -> (SIRCarry, StepOutput)``
    suitable for ``jax.lax.scan`` over an observation stack; the reference
    semantics every other execution path (bank, distributed, resident
    sessions) is pinned against.

    With ``cfg.step_backend == "fused"`` the weight phase (reweight /
    estimate / ESS / resampling commit) runs through
    ``repro.kernels.sir_fused`` instead of the composed ops — same PRNG
    stream split, same decision rule, ulp-level numerics (DESIGN.md §13);
    unsupported configs fall back to the composed step here rather than
    erroring, so drivers never branch on backend.

    Two optional model hooks extend the protocol (DESIGN.md §17):
    ``estimate_state(state) -> pytree`` maps the particle state to the
    quantity whose weighted mean is reported as ``StepOutput.estimate``
    (needed when the raw state is non-averageable, e.g. token ids plus
    KV caches), and ``emission(state) -> pytree`` selects the
    per-particle slice recorded in ``diag["emission"]`` for genealogy
    reconstruction when ``cfg.record_ancestry`` is set.  Both force the
    composed path.  A third hook, ``gather_state(state, ancestors) ->
    state``, overrides the resampling gather for states whose particle
    axis is not uniformly leading (the LM adapter's scan-stacked KV
    caches carry it at dim 1).
    """
    est_fn = getattr(model, "estimate_state", None)
    emit_fn = getattr(model, "emission", None)
    gather_fn = getattr(model, "gather_state", None)
    if (cfg.step_backend == "fused" and sir_fused.fused_applicable(
            cfg.resampler) and not cfg.record_ancestry
            and est_fn is None and gather_fn is None):
        return _make_fused_sir_step(model, cfg)
    n = cfg.n_particles

    def step(carry: SIRCarry, observation):
        key, ens = carry
        key, k_dyn, k_res = jax.random.split(key, 3)
        ens = particles.advance(ens, k_dyn, model.transition_sample)
        ens = particles.reweight(ens, model.observation_log_prob(ens.state,
                                                                 observation))
        est_ens = ens if est_fn is None else ens.replace(
            state=est_fn(ens.state))
        estimate = particles.weighted_mean(est_ens)

        dec = ess_resample(k_res, ens.log_weights, ess_frac=cfg.ess_frac,
                           resampler=cfg.resampler,
                           always=cfg.always_resample)
        state = (jax.tree_util.tree_map(lambda x: x[dec.ancestors], ens.state)
                 if gather_fn is None else gather_fn(ens.state, dec.ancestors))
        # N·max(w): the weight-skew diagnostic the chain-resampler bias
        # gates consume (tests/stats.py ``chain_tv_profile``) — 1 at
        # uniform weights, N at full collapse.
        skew = n * jnp.exp(jnp.max(ens.log_weights) - dec.log_z)
        diag = {"weight_skew": skew}
        if cfg.record_ancestry:
            # pre-gather snapshot: ``ancestors[t]`` maps post-step slots
            # to the pre-resample particles that produced these leaves
            # (repro.core.genealogy index convention).
            diag["emission"] = (ens.state if emit_fn is None
                                else emit_fn(ens.state))
            diag["log_weights"] = ens.log_weights - dec.log_z
        ancestors = dec.ancestors if cfg.record_ancestry else no_ancestors()
        # invariant: logsumexp(lw) == 0 entering every step, so ``log_z`` IS
        # the marginal-likelihood increment log p(z_k | Z^{k-1}).
        lw = jnp.where(dec.resampled,
                       jnp.full_like(ens.log_weights, -jnp.log(n)),
                       ens.log_weights - dec.log_z)
        ens = ens.replace(state=state, log_weights=lw)
        out = StepOutput(estimate, dec.ess, dec.log_z, dec.resampled,
                         ancestors, diag)
        return SIRCarry(key, ens), out

    return step


def _make_fused_sir_step(model: ssm_base.StateSpaceModel, cfg: SIRConfig):
    """The fused-backend SIR step (DESIGN.md §13.1).

    Identical control flow and PRNG stream to the composed step — split
    into (carry, dynamics, resample) keys, advance, one likelihood call —
    with the entire weight phase delegated to
    ``repro.kernels.sir_fused.fused_weight_step`` and the resampling
    gather applied to the decision it returns.
    """

    def step(carry: SIRCarry, observation):
        key, ens = carry
        key, k_dyn, k_res = jax.random.split(key, 3)
        ens = particles.advance(ens, k_dyn, model.transition_sample)
        ll = model.observation_log_prob(ens.state, observation)
        dec = sir_fused.fused_weight_step(
            ens.log_weights, ll, ens.state, k_res,
            resampler=cfg.resampler, ess_frac=cfg.ess_frac,
            always=cfg.always_resample, backend=cfg.fused_backend)
        state = jax.tree_util.tree_map(lambda x: x[dec.ancestors], ens.state)
        ens = ens.replace(state=state, log_weights=dec.new_log_weights)
        out = StepOutput(dec.estimate, dec.ess, dec.log_z, dec.resampled,
                         no_ancestors(), {"weight_skew": dec.weight_skew})
        return SIRCarry(key, ens), out

    return step


def run_sir(key: Array, model: ssm_base.StateSpaceModel, cfg: SIRConfig,
            observations: Any) -> tuple[SIRCarry, StepOutput]:
    """Run the filter over a stacked observation sequence."""
    k_init, k_run = jax.random.split(key)
    ens = particles.init_ensemble(k_init, model.init, cfg.n_particles)
    step = make_sir_step(model, cfg)
    carry, outs = jax.lax.scan(step, SIRCarry(k_run, ens), observations)
    return carry, outs


# ---------------------------------------------------------------------------
# Distributed (per-shard) SIR step
# ---------------------------------------------------------------------------

def make_distributed_sir_step(model: ssm_base.StateSpaceModel, cfg: SIRConfig,
                              dra: dist.DRAConfig, axis_name: str = "data",
                              domain: "domain_mod.DomainSpec | None" = None):
    """Per-shard SIR step for any ``repro.models.ssm.StateSpaceModel``.
    ``cfg.n_particles`` is the GLOBAL count; each of the P shards
    carries an ensemble of C = n_particles / P slots.

    With ``domain`` set, the observation fed to the step is this shard's
    halo slab (not the full frame) and the reweight runs through the
    migrate-after-advance hook (DESIGN.md §10.3): particles travel to
    their tile owners, are reweighted tile-locally, and the
    log-likelihoods travel back to their home slots — everything after
    the reweight (estimate, ESS, DRA resampling) is untouched, which is
    what keeps the domain-decomposed filter on the replicated filter's
    exact trajectory.  Tiling requires the model's optional spatial
    hooks (``positions`` + ``tile_observation_log_prob``, resolved by
    ``domain_hooks``).
    """
    positions_fn, tile_fn = domain_hooks(model)
    if domain is not None and tile_fn is None:
        raise ValueError("domain decomposition needs a model with "
                         "tile_observation_log_prob and positions hooks")

    def step(carry: SIRCarry, observation):
        key, ens = carry
        c = ens.capacity
        p = runtime.axis_size(axis_name)
        n_total = c * p
        key, k_dyn, k_res = jax.random.split(key, 3)

        ens = particles.advance(ens, k_dyn, model.transition_sample)
        if domain is None:
            ll = model.observation_log_prob(ens.state, observation)
            mig_diag = {}
        else:
            origin = domain.slab_origin(runtime.axis_index(axis_name))

            def tile_ll(state):
                return tile_fn(state, observation, origin)

            ll, mig_diag = domain_mod.exchange_log_likelihood(
                domain, ens, positions_fn(ens.state), tile_ll,
                axis_name=axis_name)
        ens = particles.reweight(ens, ll)
        lw = ens.log_weights
        max_ll = jnp.max(jnp.where(jnp.isfinite(lw), ll, -jnp.inf))

        glz = dist.global_log_z(lw, axis_name)
        ess = dist.global_ess(lw, axis_name)

        # MMSE estimate with globally normalized weights (one psum)
        w = jnp.exp(jnp.where(jnp.isfinite(lw), lw - glz, -jnp.inf))
        est_fn = getattr(model, "estimate_state", None)
        est_state = ens.state if est_fn is None else est_fn(ens.state)
        estimate = jax.tree_util.tree_map(
            lambda x: runtime.psum(jnp.tensordot(w.astype(x.dtype), x, axes=1),
                                   axis_name), est_state)

        do_resample = jnp.logical_or(ess < cfg.ess_frac * n_total,
                                     jnp.asarray(cfg.always_resample))

        if dra.kind == "mpf":
            r_ens, diag = dist.mpf_resample(k_res, ens, dra, axis_name)
        elif dra.kind == "rna":
            r_ens, diag = dist.rna_resample(k_res, ens, dra, axis_name)
        elif dra.kind == "arna":
            r_ens, diag = dist.arna_resample(k_res, ens, dra, axis_name,
                                             max_ll)
        elif dra.kind == "rpa":
            r_ens, diag = dist.rpa_resample(k_res, ens, dra, axis_name)
        elif dra.kind == "butterfly":
            r_ens, diag = dist.butterfly_resample(k_res, ens, dra, axis_name)
        else:
            raise ValueError(dra.kind)

        # fold the weight-phase collectives into the DRA's comm accounting
        # (DESIGN.md §14.3): logZ gather + ESS gather/psum + estimate psum.
        # Domain-migration traffic is reported separately in mig_diag.
        step_bytes = 12 + runtime.tree_bytes(estimate)
        diag = {**diag,
                "comm_bytes": diag["comm_bytes"] + step_bytes,
                "comm_stages": diag["comm_stages"] + 4}

        # select keeps SPMD collective schedule static (DESIGN.md §2.3)
        kept = ens.replace(log_weights=lw - glz)
        ens = jax.tree_util.tree_map(
            lambda a, b: jnp.where(do_resample, a, b), r_ens, kept)

        # the DRA paths exchange (state, multiplicity) pairs, not ancestor
        # indices — genealogy recording is a single-device/bank feature.
        out = StepOutput(estimate, ess, glz, do_resample, no_ancestors(),
                         {**diag, **mig_diag})
        return SIRCarry(key, ens), out

    return step


# ---------------------------------------------------------------------------
# Per-slot masking (resident banks, DESIGN.md §11)
# ---------------------------------------------------------------------------

def neutral_output(out: StepOutput, active: Array) -> StepOutput:
    """Zero a step's outputs wherever ``active`` is False.

    Masked slots contribute *nothing* to estimates / ESS / log-marginal /
    diagnostics: every leaf is ``where(active, leaf, 0)`` (``resampled``
    becomes False).  ``active`` broadcasts against scalar-per-slot leaves.
    """
    return jax.tree_util.tree_map(
        lambda x: jnp.where(active, x, jnp.zeros_like(x)), out)


def make_masked_step(step):
    """Wrap a SIR step with a per-slot activity gate (DESIGN.md §11.1).

    ``masked(carry, (observation, active))`` runs ``step`` unconditionally
    — identical ops, identical shapes, so the SPMD/compiled schedule never
    depends on membership — then *selects*: an active slot takes the new
    carry and real outputs, an inactive slot keeps its carry (key AND
    ensemble) bit-for-bit frozen and emits ``neutral_output`` zeros.
    This is what lets a resident ``FilterBank`` keep one jitted program
    while members attach and detach (zero retraces under churn): only the
    *values* of the ``active`` vector change, never a shape.

    ``active`` is a scalar bool per slot; vmap over the slot axis to gate
    a whole bank.  The frozen-carry select means a slot stepped only on
    its own frames reproduces the standalone filter bitwise (the
    estimate's reduction order is vmap-stable by construction, see
    ``particles.weighted_mean``).
    """

    def masked(carry: SIRCarry, xs):
        observation, active = xs
        new_carry, out = step(carry, observation)
        keep = lambda new, old: jnp.where(active, new, old)  # noqa: E731
        carry = jax.tree_util.tree_map(keep, new_carry, carry)
        return carry, neutral_output(out, active)

    return masked
