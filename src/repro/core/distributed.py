"""Distributed resampling algorithms (paper §III) as SPMD shard programs.

Five DRA families — the paper's taxonomy plus the butterfly topology of
Heine–Whiteley–Cemgil (arXiv:1812.01502):

* **MPF**  — bank of independent PFs; zero particle communication; global
  estimate combined from per-shard aggregate weights (one tiny psum).
* **RNA**  — fixed per-shard particle count, local resampling, static ring
  exchange of a fixed fraction of particles (paper's 10%–50%) via
  ``ppermute`` — the direct TPU translation of the MPI ring.
* **ARNA** — RNA with the exchange ratio adapted from the *effective number
  of processes* P_eff = (Σ W_i)²/Σ W_i², and maximal re-mixing (fused
  ``all_to_all`` shuffle) when the target is lost (the paper randomizes the
  ring order; a static-shape SPMD program cannot re-wire ``ppermute`` at
  runtime, so we substitute the strictly-stronger full shuffle — DESIGN.md §2).
* **RPA**  — stratified resampling with proportional allocation across
  shards, followed by DLB routing (GS/SGS/LGS from ``repro.core.dlb``) of
  compressed particles.
* **BUTTERFLY** — log2(P) distance-doubling pairwise mix stages
  (``runtime.butterfly_schedule``); each stage ships one
  ``butterfly_cap``-slot slab of compressed (state, count, log-weight)
  triples to the stage partner via ``ppermute`` — O(log P) collective
  rounds and a statically bounded comm volume per step (DESIGN.md §14).

All functions here are *per-shard* ensemble transformers: they take the
shard's ``ParticleEnsemble`` and return the resampled one (DESIGN.md §9),
use collectives with an ``axis_name`` (always through the
``repro.core.runtime`` facade), and are meant to be called inside
``shard_map`` (see ``repro.core.filters`` for the user-facing driver).
RPA and butterfly stay in the compressed (counts) representation
end-to-end: local resample → routing → merge all move multiplicities and
per-replica log-weights, and replicas are only materialized afterwards
(paper §V.B).

Every DRA also returns **comm-volume accounting** in its diagnostics
(DESIGN.md §14.3): ``comm_bytes`` — the payload bytes this shard injects
into collectives per frame (logical message size, not algorithm wire
traffic) — and ``comm_stages`` — sequential collective rounds on the
critical path.  Shapes are static, so both are trace-time constants.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import dlb
from repro.core import particles
from repro.core import resampling
from repro.core import runtime
from repro.core.particles import ParticleEnsemble, log_sum_weights
from repro.kernels import resample as resample_kernel

Array = jax.Array

RESAMPLE_BACKENDS = ("auto", "pallas", "jnp")


@dataclasses.dataclass(frozen=True)
class DRAConfig:
    """Distributed-resampling configuration (paper §III–§V knobs)."""

    kind: str = "rna"               # mpf | rna | arna | rpa | butterfly
    resampler: str = "systematic"
    ess_frac: float = 0.5            # N_threshold = ess_frac * N (Alg. 1)
    # local-resampling backend: "pallas" = fused CDF+bisection kernel
    # (interpret mode; it does not lower for TPU, so it raises there),
    # "jnp" = pure-XLA comb, "auto" = the jnp comb on every platform.
    # Only the systematic scheme has a kernel.
    resample_backend: str = "auto"
    # RNA / ARNA
    exchange_ratio: float = 0.10     # paper's 10%–50%
    q_min: float = 0.05              # ARNA adaptive range
    q_max: float = 0.50
    lost_log_lik: float = -1e4       # "target lost" likelihood floor (ARNA)
    # RPA
    scheduler: str = "lgs"           # gs | sgs | lgs
    k_cap: int = 64                  # routing window (unique particles/dest)
    slack: float = 2.0               # per-shard allocation cap = slack * C
    # BUTTERFLY: slab slots shipped to the stage partner per mix stage.
    # Compression makes the slot budget go far (a slot carries an arbitrary
    # multiplicity); units that do not fit stay local with exact weights
    # (DESIGN.md §14.2), so this bounds comm volume, not correctness.
    butterfly_cap: int = 32

    def __post_init__(self):
        assert self.kind in ("mpf", "rna", "arna", "rpa", "butterfly"), \
            self.kind
        assert self.scheduler in dlb.SCHEDULERS, self.scheduler
        assert self.resampler in resampling.RESAMPLERS, self.resampler
        assert self.resample_backend in RESAMPLE_BACKENDS, self.resample_backend
        assert self.butterfly_cap >= 1, self.butterfly_cap
        # an explicit kernel request must not silently fall back: only the
        # systematic scheme has a kernel
        if self.resample_backend == "pallas":
            assert self.resampler == "systematic", (
                f"resample_backend='pallas' requires resampler='systematic', "
                f"got {self.resampler!r}")


def use_pallas_resample(cfg: DRAConfig, n_out) -> bool:
    """Whether the local resample runs through the Pallas kernel.

    The kernel covers the static-shape systematic path (MPF/RNA/ARNA local
    step, where ``n_out`` is the python-int slot count C); RPA's traced
    per-shard allocation stays on the jnp comb (DESIGN.md §2.1).
    """
    # "auto" never picks the kernel: Mosaic refuses it on TPU
    # (resample_kernel.CUMSUM_REFUSED) and off the chip it only runs in
    # interpret mode
    return (cfg.resample_backend == "pallas"
            and cfg.resampler == "systematic"
            and isinstance(n_out, int)
            and resample_kernel.kernel_applicable(n_out))


def _per_particle_bytes(state: Any) -> int:
    """Payload bytes of one particle's state (static under tracing)."""
    return runtime.tree_bytes(
        jax.tree_util.tree_map(lambda x: x[:1], state))


def _comm_diag(bytes_per_frame: int, stages: int) -> dict:
    """Comm-volume accounting entries for a DRA diag dict (DESIGN.md §14.3).

    ``bytes_per_frame`` — payload bytes this shard injects into collectives
    during the resample phase of one frame (logical message size; int32 is
    ample for any per-shard configuration this library runs).
    ``stages`` — sequential collective rounds on the critical path
    (leaf-parallel launches of one logical exchange count once).
    """
    return {"comm_bytes": jnp.asarray(bytes_per_frame, jnp.int32),
            "comm_stages": jnp.asarray(stages, jnp.int32)}


def _shard_log_z(log_weights: Array, axis_name: str) -> tuple[Array, Array]:
    """(local logZ, gathered (P,) vector of all shards' logZ)."""
    local = log_sum_weights(log_weights)
    return local, runtime.all_gather(local, axis_name)


def global_log_z(log_weights: Array, axis_name: str) -> Array:
    """logsumexp of ALL shards' weights — the global normalizer (one
    all_gather of per-shard scalars, paper §III)."""
    _, gathered = _shard_log_z(log_weights, axis_name)
    return jax.scipy.special.logsumexp(gathered)


def global_ess(log_weights: Array, axis_name: str) -> Array:
    """Global N_eff (Alg. 1 line 15) with one psum."""
    glz = global_log_z(log_weights, axis_name)
    sq = jnp.sum(jnp.exp(2.0 * (log_weights - glz)), where=jnp.isfinite(log_weights))
    return 1.0 / jnp.maximum(runtime.psum(sq, axis_name), 1e-38)


def effective_processes(log_weights: Array, axis_name: str) -> Array:
    """P_eff = (Σ_i W_i)² / Σ_i W_i² over shard aggregate weights (ARNA)."""
    local, gathered = _shard_log_z(log_weights, axis_name)
    del local
    lw = gathered - jax.scipy.special.logsumexp(gathered)
    w = jnp.exp(lw)
    return 1.0 / jnp.maximum(jnp.sum(jnp.square(w)), 1e-38)


# ---------------------------------------------------------------------------
# Local resample (shared by all DRAs)
# ---------------------------------------------------------------------------

def _local_resample_materialize(key: Array, state: Any, log_weights: Array,
                                n_out, cfg: DRAConfig) -> tuple[Any, Array]:
    """Resample ``n_out`` offspring locally and materialize ``C`` slots.

    Returns (state, counts).  Offspring counts follow the configured local
    scheme; materialization (counts → replicas) is the paper's deferred
    expansion, done here because no routing follows (MPF/RNA path).

    When ``cfg.resample_backend`` selects the Pallas kernel (and the
    scheme/shape qualify, see ``use_pallas_resample``) ancestors come from
    the fused CDF+bisection kernel on the same shared-uniform comb, so the
    offspring distribution is identical to the jnp comb up to 1-ulp CDF
    ties.
    """
    c = log_weights.shape[0]
    # the kernel materializes exactly n_out rows while the jnp path pads to
    # C, so the kernel only serves the full-ensemble case (all callers today)
    if n_out == c and use_pallas_resample(cfg, n_out):
        u = jax.random.uniform(key, ())
        ancestors = resample_kernel.systematic_ancestors_auto(
            log_weights, u, n_out=n_out)
        counts = resampling.ancestors_to_counts(ancestors, c)
    else:
        counts_fn = resampling.RESAMPLERS[cfg.resampler]
        counts = counts_fn(key, log_weights, n_out, capacity=c)
        ancestors = resampling.counts_to_ancestors(counts, c)
    new_state = jax.tree_util.tree_map(lambda x: x[ancestors], state)
    return new_state, counts


def _local_resample_ensemble(key: Array, ensemble: ParticleEnsemble,
                             log_weight: Array,
                             cfg: DRAConfig) -> ParticleEnsemble:
    """Full-capacity local resample to a materialized ensemble whose every
    slot carries ``log_weight`` (the MPF/RNA/ARNA post-resample weight).

    Counts are folded into the sampling weights (§9 rule 3), so compressed
    and materialized input ensembles draw the same offspring distribution.
    """
    c = ensemble.capacity
    eff_lw = particles.effective_log_weights(ensemble.log_weights,
                                             ensemble.counts)
    state, _ = _local_resample_materialize(key, ensemble.state, eff_lw, c,
                                           cfg)
    return ParticleEnsemble(state=state,
                            log_weights=jnp.full((c,), log_weight),
                            counts=jnp.ones((c,), jnp.int32))


# ---------------------------------------------------------------------------
# The five DRA resample+rebalance programs
# ---------------------------------------------------------------------------

def mpf_resample(key: Array, ensemble: ParticleEnsemble, cfg: DRAConfig,
                 axis_name: str) -> tuple[ParticleEnsemble, dict]:
    """Independent local resampling; shard keeps its aggregate weight."""
    c = ensemble.capacity
    local_lz, gathered = _shard_log_z(
        particles.effective_log_weights(ensemble.log_weights,
                                        ensemble.counts), axis_name)
    glz = jax.scipy.special.logsumexp(gathered)
    # each offspring carries Ŵ_i / C of the global posterior mass
    out = _local_resample_ensemble(key, ensemble,
                                   local_lz - glz - jnp.log(c), cfg)
    return out, {"exchanged": jnp.zeros((), jnp.int32),
                 # one scalar all_gather of the shard logZ
                 **_comm_diag(4, 1)}


def _ring_exchange(state: Any, log_weights: Array, m_buf: int, m_valid: Array,
                   axis_name: str, shuffle: Array | None = None):
    """Exchange the first ``m_buf`` slots with the ring neighbor; only the
    first ``m_valid``(≤ m_buf, global scalar) received slots are accepted.

    If ``shuffle`` is true (ARNA lost-mode), use a fused all_to_all perfect
    shuffle instead of the ring (maximal information mixing).
    """
    p = runtime.axis_size(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]

    def take(x):
        return x[:m_buf]

    send_state = jax.tree_util.tree_map(take, state)
    send_lw = log_weights[:m_buf]

    def ring(args):
        s, lw = args
        r_s = jax.tree_util.tree_map(
            lambda x: runtime.ppermute(x, axis_name, perm), s)
        r_lw = runtime.ppermute(lw, axis_name, perm)
        return r_s, r_lw

    def mix(args):
        s, lw = args
        b = m_buf // p

        def a2a(x):
            y = x[: b * p].reshape((p, b) + x.shape[1:])
            y = runtime.all_to_all(y, axis_name, split_axis=0, concat_axis=0)
            y = y.reshape((b * p,) + x.shape[1:])
            return jnp.concatenate([y, x[b * p:]], axis=0)

        return jax.tree_util.tree_map(a2a, s), a2a(lw)

    if shuffle is None:
        recv_state, recv_lw = ring((send_state, send_lw))
    else:
        recv_state, recv_lw = jax.lax.cond(shuffle, mix, ring,
                                           (send_state, send_lw))

    keep = jnp.arange(m_buf) < m_valid

    def splice(orig, recv):
        head = jnp.where(
            keep.reshape((-1,) + (1,) * (recv.ndim - 1)), recv, orig[:m_buf])
        return jnp.concatenate([head, orig[m_buf:]], axis=0)

    out_state = jax.tree_util.tree_map(splice, state, recv_state)
    out_lw = splice(log_weights, recv_lw)
    return out_state, out_lw


def _permute_ensemble(key: Array, ensemble: ParticleEnsemble) -> ParticleEnsemble:
    """Randomize slot order (systematic ancestors are sorted, so the ring
    head would otherwise always ship the lowest-index ancestors)."""
    return particles.permute(ensemble,
                             jax.random.permutation(key, ensemble.capacity))


def rna_resample(key: Array, ensemble: ParticleEnsemble, cfg: DRAConfig,
                 axis_name: str) -> tuple[ParticleEnsemble, dict]:
    """RNA: local resample to C, then static ring exchange of a fixed
    fraction (paper §III / §VII.D)."""
    c = ensemble.capacity
    local_lz, gathered = _shard_log_z(
        particles.effective_log_weights(ensemble.log_weights,
                                        ensemble.counts), axis_name)
    glz = jax.scipy.special.logsumexp(gathered)
    k_res, k_perm = jax.random.split(key)
    ens = _local_resample_ensemble(k_res, ensemble,
                                   local_lz - glz - jnp.log(c), cfg)
    # randomize which particles travel (systematic ancestors are ordered)
    ens = _permute_ensemble(k_perm, ens)
    m = max(int(round(cfg.exchange_ratio * c)), 1)
    state, lw = _ring_exchange(ens.state, ens.log_weights, m,
                               jnp.asarray(m), axis_name)
    ens = ens.replace(state=state, log_weights=lw)
    return ens, {"exchanged": jnp.asarray(m, jnp.int32),
                 # logZ gather + ring ppermute of m (state, log-weight) rows
                 **_comm_diag(4 + m * (_per_particle_bytes(ens.state) + 4),
                              2)}


def arna_resample(key: Array, ensemble: ParticleEnsemble, cfg: DRAConfig,
                  axis_name: str,
                  max_log_lik: Array) -> tuple[ParticleEnsemble, dict]:
    """ARNA: RNA with P_eff-adaptive exchange ratio and lost-mode shuffle."""
    c = ensemble.capacity
    p = runtime.axis_size(axis_name)
    eff_lw = particles.effective_log_weights(ensemble.log_weights,
                                             ensemble.counts)
    p_eff = effective_processes(eff_lw, axis_name)
    local_lz, gathered = _shard_log_z(eff_lw, axis_name)
    glz = jax.scipy.special.logsumexp(gathered)

    k_res, k_perm = jax.random.split(key)
    ens = _local_resample_ensemble(k_res, ensemble,
                                   local_lz - glz - jnp.log(c), cfg)
    ens = _permute_ensemble(k_perm, ens)

    # adaptive ratio: all shards tracking (P_eff≈P) → q_min; collapsed → q_max
    frac_eff = jnp.clip(p_eff / p, 0.0, 1.0)
    q = cfg.q_min + (cfg.q_max - cfg.q_min) * (1.0 - frac_eff)
    m_buf = max(int(round(cfg.q_max * c)) // p * p, p)  # static buffer, P-divisible
    m_valid = jnp.ceil(q * c).astype(jnp.int32)
    m_valid = jnp.minimum(m_valid, m_buf)

    lost = runtime.pmax(max_log_lik, axis_name) < cfg.lost_log_lik
    state, lw = _ring_exchange(ens.state, ens.log_weights, m_buf, m_valid,
                               axis_name, shuffle=lost)
    ens = ens.replace(state=state, log_weights=lw)
    return ens, {
        "exchanged": m_valid,
        "p_eff": p_eff,
        "q": q,
        "lost": lost.astype(jnp.int32),
        # P_eff gather + logZ gather + lost-mode pmax + exchange of the
        # full m_buf buffer (ring and shuffle ship the same slab)
        **_comm_diag(12 + m_buf * (_per_particle_bytes(ens.state) + 4), 4),
    }


def rpa_resample(key: Array, ensemble: ParticleEnsemble, cfg: DRAConfig,
                 axis_name: str) -> tuple[ParticleEnsemble, dict]:
    """RPA: proportional allocation across shards + DLB routing of
    compressed particles (paper §III–§V).

    The compressed representation is carried end-to-end: the local
    resample produces (counts, per-replica log-weights), routing ships
    exactly those, and replicas are materialized only after the merge —
    no placeholder weight vectors anywhere (DESIGN.md §9).
    """
    c = ensemble.capacity
    p = runtime.axis_size(axis_name)
    my = runtime.axis_index(axis_name)
    n_total = c * p
    cap_units = int(round(cfg.slack * c))

    # --- stratified proportional allocation over shards (identical everywhere)
    _, gathered_lz = _shard_log_z(
        particles.effective_log_weights(ensemble.log_weights,
                                        ensemble.counts), axis_name)
    alloc = dlb.proportional_allocation(gathered_lz, n_total, cap_units)  # (P,)

    # --- local resampling of my allocation, in compressed (counts) form;
    # post-resample every offspring unit carries 1/N of the posterior
    comp = particles.resample_compressed(
        key, ensemble, alloc[my], scheme=cfg.resampler, capacity=cap_units,
        fill_log_weight=-jnp.log(float(n_total)))

    # --- DLB schedule from the globally known allocation vector
    targets = dlb.balanced_targets(jnp.asarray(n_total), p)
    schedule = dlb.SCHEDULERS[cfg.scheduler](alloc, targets)  # (P, P)
    row_send = schedule[my]

    # --- route compressed particles, merge, then expand locally
    # (deferred replica creation, paper §V.B)
    route = dlb.route_compressed(comp, row_send, k_cap=cfg.k_cap,
                                 axis_name=axis_name)
    merged = dlb.merge_routed(comp, route)
    out = particles.materialize(merged, c)
    stats = dlb.schedule_stats(schedule)
    return out, {
        "overflow": runtime.psum(route.overflow_units, axis_name),
        "links": stats["links"],
        "units_moved": stats["units_moved"],
        "max_message_units": stats["max_message_units"],
        # logZ gather + fused all_to_all of P×K (state, count, log-weight)
        # window triples
        **_comm_diag(
            4 + p * cfg.k_cap * (_per_particle_bytes(ensemble.state) + 8),
            2),
    }


def butterfly_resample(key: Array, ensemble: ParticleEnsemble, cfg: DRAConfig,
                       axis_name: str) -> tuple[ParticleEnsemble, dict]:
    """Butterfly DRA: log2(P) pairwise mix stages with exact bookkeeping
    (Heine–Whiteley–Cemgil, arXiv:1812.01502; DESIGN.md §14).

    Stage ``s`` pairs shard ``i`` with ``i XOR 2^s``
    (``runtime.butterfly_schedule``).  Within a pair holding aggregate
    weights (W_i, W_j), each shard draws

        n_i = (C − m_i←j) + m_i→j   offspring from its local ensemble,

    where ``m_i→j = min(round(C · W_i/(W_i+W_j)), butterfly_cap)`` is the
    number of offspring *units* the partner takes from shard ``i``'s
    distribution (both shards compute identical splits from one scalar
    log-total exchange, because logaddexp is symmetric).  Capping the
    units at the slab's slot budget makes the exchange structurally
    overflow-free — a window of m units on the cumulative unit line
    overlaps at most m ≤ cap slots (``dlb.pack_slab``) — and conserves
    the per-shard unit count *exactly*: every stage ends with C logical
    units on every shard, so the final materialize never truncates.

    Every unit shard ``i`` draws — kept or shipped — carries per-unit
    weight ``W_i / n_i`` (uniform within the draw), so the pair's total
    bookkeeping weight is conserved exactly for any kept/shipped split
    and the estimates stay unbiased under the cap (DESIGN.md §14.2).
    The capped totals no longer equalize exactly across shards, so the
    global normalizer is carried by a parallel *scalar* butterfly — the
    hypercube all-reduce average ``lz_run ← logaddexp(lz_run, partner) −
    log 2`` rides the same ppermute and ends as ``log(W_global / P)`` on
    every shard — no all_gather anywhere in this DRA.  Capacity grows by
    ``butterfly_cap`` slots per stage; one materialize restores C slots.
    """
    c = ensemble.capacity
    p = runtime.axis_size(axis_name)
    schedule = runtime.butterfly_schedule(p)
    cap = cfg.butterfly_cap
    zero = jnp.zeros((), jnp.int32)
    pp_bytes = _per_particle_bytes(ensemble.state)
    # per stage: one ppermute of the two scalars (lz, lz_run) + one slab
    # ppermute of (state, count, log-weight) triples → 2 rounds,
    # 8 + cap·(pp+8) bytes
    comm = _comm_diag(len(schedule) * (8 + cap * (pp_bytes + 8)),
                      2 * len(schedule))

    if not schedule:                 # P == 1: plain local resample
        out = _local_resample_ensemble(key, ensemble, -jnp.log(float(c)), cfg)
        return out, {"exchanged": zero, "overflow": zero,
                     "truncated": zero, **comm}

    ens = ensemble
    keys = jax.random.split(key, len(schedule))
    lz_run = particles.log_sum_weights(ens.log_weights, ens.counts)
    shipped_total = zero
    overflow_total = zero
    for k_s, perm in zip(keys, schedule):
        eff = particles.effective_log_weights(ens.log_weights, ens.counts)
        lz = jax.scipy.special.logsumexp(eff)
        lz_p, lzr_p = runtime.grouped_ppermute((lz, lz_run), axis_name, perm)
        lz_run = jnp.logaddexp(lz_run, lzr_p) - jnp.log(2.0)
        pair = jnp.logaddexp(lz, lz_p)
        # dead-pair guard: both totals -inf → no units move either way
        frac_own = jnp.where(jnp.isfinite(pair), jnp.exp(lz - pair), 0.0)
        frac_partner = jnp.where(jnp.isfinite(pair), jnp.exp(lz_p - pair), 0.0)
        m_send = jnp.minimum(jnp.round(c * frac_own), cap).astype(jnp.int32)
        m_recv = jnp.minimum(jnp.round(c * frac_partner), cap).astype(jnp.int32)
        n_tot = c - m_recv + m_send
        # every unit of this draw carries W_i / n_i — exact for any split
        fill = lz - jnp.log(jnp.maximum(n_tot, 1).astype(jnp.float32))
        # comb teeth must cover n_tot ≤ C + cap (a comb only emits
        # `capacity` points, so an undersized one would silently truncate
        # the draw whenever this shard sends more than it receives)
        comp = particles.resample_compressed(
            k_s, ens, n_tot, scheme=cfg.resampler,
            capacity=ens.capacity + cap, fill_log_weight=fill)
        pack = dlb.pack_slab(comp, m_send, k_cap=cap)
        recv_state, recv_counts, recv_lw = runtime.grouped_ppermute(
            (pack.slab_state, pack.slab_counts, pack.slab_log_weights),
            axis_name, perm)

        def cat(a, b):
            return jnp.concatenate([a, b], axis=0)

        ens = ParticleEnsemble(
            state=jax.tree_util.tree_map(cat, comp.state, recv_state),
            log_weights=cat(comp.log_weights, recv_lw),
            counts=cat(pack.kept_counts, recv_counts))
        shipped_total = shipped_total + pack.shipped_units
        overflow_total = overflow_total + pack.overflow_units

    # scalar butterfly == hypercube all-reduce: lz_run is log(W_global/P)
    glz = lz_run + jnp.log(float(p))
    truncated = jnp.maximum(particles.logical_size(ens) - c, 0)
    out = particles.materialize(
        ens.replace(log_weights=ens.log_weights - glz), c)
    return out, {"exchanged": shipped_total,
                 "overflow": runtime.psum(overflow_total, axis_name),
                 "truncated": runtime.psum(truncated, axis_name),
                 **comm}
