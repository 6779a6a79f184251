"""Resident FilterBank sessions: streaming serving with dynamic membership.

``ParticleSessionServer`` holds a fixed-capacity ``B_max``-slot
``FilterBank`` alive and steps it **one frame at a time** under churn —
the serving shape the ROADMAP's "heavy traffic from millions of users"
needs and ``FilterBank.run`` cannot provide (it demands every member's
full observation stack up front and recompiles when the bank size
changes).  The engine keeps **one** jitted step program across
``attach``/``detach`` (DESIGN.md §11): a slot allocator hands out slots
of a statically shaped bank, and a per-slot ``active`` mask makes
detached slots run masked no-op math — shapes never change, so
membership churn causes **zero retraces** (asserted by tests and
``benchmarks/bench_serve.py``).

Lifecycle::

    server = ParticleSessionServer(model=model, sir=SIRConfig(...),
                                   capacity=8)
    h = server.attach(jax.random.key(1))     # allocate a slot
    server.submit(h, frame)                  # enqueue frames as they arrive
    res = server.result(h)                   # drain → FilterResult so far
    ckpt = server.suspend(h, directory=...)  # host-side carry, slot freed
    h2 = server.resume(ckpt)                 # continue — bitwise identical
    server.detach(h2)                        # slot returns to the pool

A session stepped through the server reproduces the standalone
``ParallelParticleFilter.run`` trajectory **bitwise**, regardless of what
the other slots do (golden + property tests in ``tests/test_sessions.py``).
Suspension round-trips the session's ``ParticleEnsemble`` + PRNG carry
through ``repro.checkpoint.store`` as host-side full arrays, so a
suspended session is mesh-elastic: it can resume on a server with a
different capacity, a different mesh, or in a different process.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import itertools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.checkpoint import store
from repro.core import filters, particles, runtime, smc
from repro.models.ssm import base as ssm_base

Array = jax.Array


def _untimed(name: str, **args):
    """The ``span`` of an untimed ``step``: brackets nothing."""
    return contextlib.nullcontext()


@dataclasses.dataclass(frozen=True)
class SessionHandle:
    """Opaque ticket for one attached session.

    Attributes:
      uid: server-unique session id (survives nothing — handles from a
        dead server or a detached session are rejected).
      slot: the bank slot currently hosting the session (informational;
        the server validates by ``uid``).
    """

    uid: int
    slot: int


@dataclasses.dataclass
class SuspendedSession:
    """Host-side snapshot of one session (mesh- and capacity-elastic).

    Everything is a NumPy array (PRNG key as ``key_data``), so the
    payload can be checkpointed by ``repro.checkpoint.store``, shipped
    across processes, and resumed on a server with any capacity/mesh.

    Attributes:
      key_data: ``jax.random.key_data`` of the carry PRNG key.
      state: ensemble state pytree, full ``(N, ...)`` arrays.
      log_weights: ``(N,)`` ensemble log-weights.
      counts: ``(N,)`` ensemble multiplicities.
      frames_done: frames filtered before suspension.
      estimates / ess / log_marginal / resampled / ancestors: the
        per-frame output trajectory so far (leading dim ``frames_done``),
        so ``result`` after resume returns the full history
        (``ancestors`` has trailing width 0 unless the server's
        ``SIRConfig.record_ancestry`` is set).
    """

    key_data: np.ndarray
    state: Any
    log_weights: np.ndarray
    counts: np.ndarray
    frames_done: int
    estimates: Any
    ess: np.ndarray
    log_marginal: np.ndarray
    resampled: np.ndarray
    ancestors: np.ndarray

    def as_tree(self) -> dict:
        """The checkpointable pytree (what ``save``/``load`` round-trip)."""
        return {
            "key_data": self.key_data, "state": self.state,
            "log_weights": self.log_weights, "counts": self.counts,
            "frames_done": np.asarray(self.frames_done),
            "estimates": self.estimates, "ess": self.ess,
            "log_marginal": self.log_marginal, "resampled": self.resampled,
            "ancestors": self.ancestors,
        }

    def save(self, directory: str) -> str:
        """Persist atomically via ``repro.checkpoint.store.save_checkpoint``
        (checkpoint step = ``frames_done``).  Returns the final path.

        ``directory`` must be dedicated to this one session (the store
        keys checkpoints by step and GCs old ones): one directory per
        session, exactly like one directory per training run."""
        return store.save_checkpoint(directory, self.frames_done,
                                     self.as_tree())

    @classmethod
    def load(cls, directory: str, like: "SuspendedSession",
             step: int | None = None) -> "SuspendedSession":
        """Restore from ``save``'s directory.

        ``like`` supplies the pytree *structure* (shapes come from disk);
        use ``ParticleSessionServer.blank_suspended()`` for it.  ``step``
        defaults to the latest checkpoint in the directory.
        """
        if step is None:
            step = store.latest_step(directory)
            if step is None:
                raise FileNotFoundError(f"no checkpoints under {directory}")
        tree = store.load_checkpoint(directory, step, like.as_tree())
        tree = jax.tree_util.tree_map(np.asarray, tree)
        return cls(key_data=tree["key_data"], state=tree["state"],
                   log_weights=tree["log_weights"], counts=tree["counts"],
                   frames_done=int(tree["frames_done"]),
                   estimates=tree["estimates"], ess=tree["ess"],
                   log_marginal=tree["log_marginal"],
                   resampled=tree["resampled"],
                   ancestors=tree["ancestors"])


class _Session:
    """Server-internal per-session bookkeeping (host side)."""

    def __init__(self, uid: int, slot: int):
        self.uid = uid
        self.slot = slot
        self.queue: list[Any] = []       # frames not yet stepped (FIFO)
        self.pending: list[tuple] = []   # (outs, row) refs not yet folded
        self.stacked: dict | None = None  # ...into this host-side cache
        self.last: tuple | None = None   # most recent (outs, row) ref
        self.frames_done = 0


class ParticleSessionServer:
    """A resident ``B_max``-slot filter bank stepped under churn.

    One jitted single-frame ``bank_step`` (``repro.core.filters``) stays
    compiled for the server's lifetime; ``attach``/``detach`` only flip
    host-side slot bookkeeping and write/free slot carries, so membership
    changes never retrace (``step_traces`` stays 1 — DESIGN.md §11.3).

    Args:
      model: any ``repro.models.ssm.StateSpaceModel`` — every
        session filters with it.
      sir: per-session SIR configuration (``n_particles`` per slot).
        ``sir.step_backend="fused"`` serves every slot with the fused
        step (DESIGN.md §13.1) — the server adds no backend logic of
        its own, it inherits whatever ``filters.make_bank_step`` builds.
      capacity: ``B_max`` — the static slot count of the resident bank.
      mesh: optional device mesh; slots are sharded over ``bank_axis``
        (each session lives wholly on one device — sessions are the unit
        of data parallelism; particle-sharding a single session remains
        ``ParallelParticleFilter``'s job).
      bank_axis: mesh axis name the slot dimension shards over.

    Sessions are driven by ``submit`` (enqueue one frame) and ``step``
    (advance every slot that has a pending frame by one frame);
    ``result`` drains and returns the ``FilterResult`` trajectory so far.

    Occupancy tiers (DESIGN.md §15.2): on the single-device path each
    tick gathers only the ready slots into the smallest power-of-two
    bucket ≥ their count, steps that compact bank, and scatters the
    carries back — so a sparse bank runs a small program instead of
    paying for all ``B_max`` slots (the BENCH_serve.json zero-churn
    0.3× tax).  One jitted tier program exists per distinct bucket
    size, so ``step_traces`` is bounded by ``len(tiers)`` rather than
    staying at exactly 1; it is still churn-invariant (re-visiting a
    tier never retraces).  Mesh-sharded banks keep the single
    full-capacity program — a cross-shard gather would turn a local
    reindex into a collective.
    """

    def __init__(self, model: ssm_base.StateSpaceModel, sir: smc.SIRConfig,
                 capacity: int = 8, mesh: Mesh | None = None,
                 bank_axis: str = "bank"):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if mesh is not None and mesh.devices.size > 1:
            if bank_axis not in mesh.shape:
                raise ValueError(f"bank_axis={bank_axis!r} not in mesh "
                                 f"axes {tuple(mesh.shape)}")
            if capacity % mesh.shape[bank_axis]:
                raise ValueError(
                    f"capacity {capacity} not divisible by "
                    f"{mesh.shape[bank_axis]} {bank_axis!r}-axis shards")
        else:
            mesh = None
        self.model = model
        self.sir = sir
        self.capacity = capacity
        self.mesh = mesh
        self.bank_axis = bank_axis
        self._uids = itertools.count()
        self._free: list[int] = list(range(capacity))   # min-heap of slots
        self._sessions: dict[int, _Session] = {}
        self._by_slot: dict[int, int] = {}              # slot -> uid
        self._frame_spec: tuple | None = None           # (shape, dtype)
        self._step_traces = 0
        # occupancy tiers: powers of two up to capacity (always including
        # capacity itself).  Mesh banks run the one full-capacity program
        # — tier-gathering across bank shards would need a collective.
        if self.mesh is None:
            self.tiers = tuple(sorted(
                {min(1 << i, capacity) for i in
                 range(capacity.bit_length() + 1)} | {capacity}))
        else:
            self.tiers = (capacity,)
        self.tier_hits: dict[int, int] = {t: 0 for t in self.tiers}
        # device-resident (idx, active) routing arrays per recurring ready
        # set: steady-state traffic re-steps the same slots every tick, so
        # re-uploading an identical route each step is pure overhead
        self._route_cache: dict[tuple, tuple] = {}
        # one canonical carry sharding (slots over bank_axis): the init
        # and slot-write programs emit it via out_shardings, so the
        # resident step only ever sees ONE input sharding+layout —
        # otherwise jit compiles a fresh executable per carry provenance
        self._bank_sharding = (jax.sharding.NamedSharding(
            self.mesh, P(self.bank_axis)) if self.mesh is not None else None)
        self._build_programs()
        # all slots start detached: placeholder carries, all-False mask
        keys = jnp.stack([jax.random.key(0)] * capacity)
        self._carry = self._init_fn(keys)

    # -- compiled programs (each traced once per server) -------------------
    def _build_programs(self) -> None:
        bank_step = filters.make_bank_step(self.model, self.sir)

        def step_fn(carry, frames, active):
            self._step_traces += 1      # trace-time side effect only
            return bank_step(carry, (frames, active))

        if self.mesh is not None:
            spec = P(self.bank_axis)
            step_fn = runtime.shard_map(
                step_fn, self.mesh, in_specs=(spec, spec, spec),
                out_specs=(spec, spec))
        self._step_fn = jax.jit(step_fn, donate_argnums=(0,))

        def tier_fn(carry, idx, frames, active):
            # gather the ready slots into a (T,)-slot compact bank, run
            # the T-sized step, scatter the carries back.  ``idx`` holds
            # DISTINCT slot ids (ready first, masked-off padding after),
            # so the scatter is collision-free; padded lanes carry their
            # slot's frozen state bitwise through the masked step, making
            # their write-back a value-level no-op.  jit keys its cache
            # by the (T,) shape: one trace + executable per tier ever.
            self._step_traces += 1      # trace-time side effect only
            with jax.named_scope("ppf.tier.gather"):
                sub = jax.tree_util.tree_map(lambda c: c[idx], carry)
            sub, outs = bank_step(sub, (frames, active))
            with jax.named_scope("ppf.tier.scatter"):
                carry = jax.tree_util.tree_map(
                    lambda c, x: c.at[idx].set(x), carry, sub)
            return carry, outs

        self._tier_fn = jax.jit(tier_fn, donate_argnums=(0,))
        # carry-producing helpers emit the canonical bank sharding, so an
        # attach never hands the step a differently-sharded bank (which
        # would cost a reshard + an executable per provenance)
        shard_kw = ({} if self._bank_sharding is None
                    else {"out_shardings": self._bank_sharding})

        def write_fn(carry, slot, new):
            return jax.tree_util.tree_map(
                lambda c, x: c.at[slot].set(x), carry, new)

        self._write_fn = jax.jit(write_fn, donate_argnums=(0,), **shard_kw)
        self._init_fn = jax.jit(jax.vmap(
            lambda k: filters.member_carry(k, self.model, self.sir)),
            **shard_kw)
        self._fresh_fn = jax.jit(
            lambda k: filters.member_carry(k, self.model, self.sir))

    # -- introspection ------------------------------------------------------
    @property
    def step_traces(self) -> int:
        """Times a resident step program was traced.  Bounded by
        ``len(self.tiers)`` after ANY churn pattern (the tiered
        zero-retrace contract, DESIGN.md §15.2): each occupancy tier
        compiles once ever, and membership churn inside a tier never
        retraces.  Mesh servers have a single full-capacity tier, so the
        bound degenerates to the original ``== 1`` contract."""
        return self._step_traces

    def jit_cache_size(self) -> int | None:
        """The jit executable-cache size of the resident step (None when
        the running JAX version does not expose ``_cache_size``).

        Single-device servers hold at most one executable per occupancy
        tier for life (``<= len(self.tiers)``).  On a mesh the count
        stabilizes at ≤ 2 — attach-written and step-produced carries
        carry different *layout metadata* (None vs concrete, same
        physical row-major layout) in current JAX, so the executable
        cache keys them separately once — and, the part that matters, it
        never grows with churn (pinned by the mesh test in
        ``tests/test_sessions.py``)."""
        fn = self._step_fn if self.mesh is not None else self._tier_fn
        size = getattr(fn, "_cache_size", None)
        return size() if callable(size) else None

    @property
    def occupancy(self) -> int:
        """Number of currently attached sessions (≤ ``capacity``)."""
        return len(self._sessions)

    # -- membership ---------------------------------------------------------
    def attach(self, key: Array) -> SessionHandle:
        """Allocate a slot and start a fresh session from ``key``.

        The slot's carry is initialized exactly as ``smc.run_sir`` would
        (same key split, same initial ensemble), so the session's
        trajectory equals ``ParallelParticleFilter.run(key, frames)``
        bitwise.  Raises ``RuntimeError`` when the bank is full.
        """
        slot = self._take_slot()
        self._carry = self._write_fn(self._carry, jnp.asarray(slot),
                                     self._fresh_fn(key))
        return self._register(slot)

    def detach(self, handle: SessionHandle) -> None:
        """Release the session's slot back to the pool.

        Pending (unstepped) frames are discarded; call ``result`` or
        ``suspend`` first to keep them.  The slot's carry stays in place
        as masked dead weight until the next ``attach`` overwrites it.
        """
        sess = self._lookup(handle)
        del self._sessions[sess.uid]
        del self._by_slot[sess.slot]
        heapq.heappush(self._free, sess.slot)

    # -- streaming ----------------------------------------------------------
    def submit(self, handle: SessionHandle, frame: Any) -> None:
        """Enqueue one observation frame for the session (FIFO).

        The frame is COPIED at enqueue: clients that reuse one capture
        buffer across submissions (the streaming norm) must not have
        pending frames silently alias the latest write.
        """
        sess = self._lookup(handle)
        frame = np.array(frame)          # owned copy, never a view
        if self._frame_spec is None:
            self._frame_spec = (frame.shape, frame.dtype)
        elif self._frame_spec != (frame.shape, frame.dtype):
            raise ValueError(
                f"frame {frame.shape}/{frame.dtype} does not match the "
                f"server's {self._frame_spec} (one program = one frame "
                f"shape; start another server for a second observation "
                f"space)")
        sess.queue.append(frame)

    def tier_for(self, ready: int) -> int:
        """The slot count of the program a tick with ``ready`` sessions
        runs: the smallest occupancy tier covering them (the full
        capacity on a mesh server)."""
        return next(t for t in self.tiers if t >= ready)

    def step(self, span=None) -> int:
        """Advance every slot with a pending frame by ONE frame.

        Single-device servers run the smallest occupancy-tier program
        covering this tick's ready count (gather → T-slot step → scatter,
        DESIGN.md §15.2); mesh servers run the one full-capacity program.
        Either way it is one program launch per tick.  Returns the number
        of sessions stepped (0 = nothing pending, no launch).

        ``span``, when given, is a context-manager factory
        ``span(name, **args)`` (the request plane passes its
        ``Metrics.span``) that brackets the tick's two phases:
        ``"bank.stage"`` (the frame batch, the route, the upload) and
        ``"bank.dispatch"`` (the jitted call until it returns, with the
        ``tier`` it runs).
        """
        span = span or _untimed
        with span("bank.stage"):
            ready = sorted((s for s in self._sessions.values() if s.queue),
                           key=lambda s: s.slot)
            if not ready:
                return 0
            tier = self.tier_for(len(ready))
            shape, dtype = self._frame_spec
            frames = np.zeros((tier,) + shape, dtype)
            if self.mesh is None:
                # gather the ready slots into the tier's compact bank
                rows = range(len(ready))
                idx, active = self._route(tier, tuple(s.slot for s in ready))
            else:
                # slot order is the shard layout: slots stay in place and
                # inactivity is a mask
                rows = [s.slot for s in ready]
                mask = np.zeros((tier,), bool)
                mask[rows] = True
                active = jnp.asarray(mask)
            for row, sess in zip(rows, ready):
                frames[row] = sess.queue.pop(0)
            frames = jnp.asarray(frames)
        self.tier_hits[tier] += 1
        with span("bank.dispatch", tier=tier):
            if self.mesh is None:
                self._carry, outs = self._tier_fn(self._carry, idx, frames,
                                                  active)
            else:
                self._carry, outs = self._step_fn(self._carry, frames,
                                                  active)
        self._record_outputs(ready, rows, outs)
        return len(ready)

    def _route(self, tier: int, slots: tuple) -> tuple:
        """Device-resident ``(idx, active)`` for this tick's ready set.

        Padding rows use DISTINCT idle slots (``capacity - ready >=
        tier - ready``, so there are always enough): their masked lanes
        freeze the carry bitwise, making the scatter write-back a no-op.
        Routes recur tick after tick in steady traffic, so the arrays are
        cached on device instead of re-uploaded per step.
        """
        cached = self._route_cache.get((tier, slots))
        if cached is None:
            active = np.zeros((tier,), bool)
            active[:len(slots)] = True
            idx = np.zeros((tier,), np.int32)
            idx[:len(slots)] = slots
            pad = (s for s in range(self.capacity) if s not in set(slots))
            for row in range(len(slots), tier):
                idx[row] = next(pad)
            if len(self._route_cache) >= 256:    # bounded under any churn
                self._route_cache.clear()
            cached = (jnp.asarray(idx), jnp.asarray(active))
            self._route_cache[(tier, slots)] = cached
        return cached

    def _record_outputs(self, ready: list[_Session], rows, outs) -> None:
        # reference the batched outs + row index; slicing happens lazily
        # at read time (``latest`` / ``_stack_rows``) — per-step device
        # indexing would cost ~4 dispatches per ready session per tick,
        # which dominated the serving tick before the tiered rework
        for sess, i in zip(ready, rows):
            ref = (outs, i)
            sess.pending.append(ref)
            sess.last = ref
            sess.frames_done += 1

    @staticmethod
    def _materialize_row(ref: tuple) -> tuple:
        """Resolve one ``(outs, row)`` reference to host-side
        ``(estimate, ess, log_marginal, resampled, ancestors)`` NumPy
        values (``ancestors`` has width 0 unless
        ``SIRConfig.record_ancestry``)."""
        outs, i = ref
        return tuple(jax.tree_util.tree_map(
            lambda x: np.asarray(x[i]),
            (outs.estimate, outs.ess, outs.log_marginal, outs.resampled,
             outs.ancestors)))

    def warm_tiers(self, example_frame: Any) -> None:
        """Compile every occupancy-tier step program ahead of traffic.

        Runs each tier once with an all-inactive mask (every carry is
        frozen bitwise by the mask, so this is a value-level no-op) —
        after it, no client ever pays a compile on the serving hot path.
        ``example_frame`` fixes the server's frame shape/dtype the same
        way a first ``submit`` would.
        """
        frame = np.array(example_frame)
        spec = (frame.shape, frame.dtype)
        if self._frame_spec is None:
            self._frame_spec = spec
        elif self._frame_spec != spec:
            raise ValueError(f"frame {spec} does not match the server's "
                             f"{self._frame_spec}")
        shape, dtype = self._frame_spec
        # compile the attach path too (fresh-carry + slot-write): the
        # request plane attaches streams lazily, so an unwarmed first
        # attach would land its compile in some client's frame latency.
        # Writing into a FREE slot is harmless — its carry is masked
        # dead weight until an attach overwrites it anyway.
        if self._free:
            self._carry = self._write_fn(
                self._carry, jnp.asarray(self._free[0]),
                self._fresh_fn(jax.random.key(0)))
        if self.mesh is not None:
            self._carry, outs = self._step_fn(
                self._carry, jnp.zeros((self.capacity,) + shape, dtype),
                jnp.zeros((self.capacity,), bool))
            self._materialize_row((outs, 0))
            return
        for tier in self.tiers:
            self._carry, outs = self._tier_fn(
                self._carry, jnp.arange(tier, dtype=jnp.int32),
                jnp.zeros((tier,) + shape, dtype),
                jnp.zeros((tier,), bool))
            # also warm the output-read path: row indexing compiles one
            # gather executable per outs shape (i.e. per tier) on first
            # use — ~200ms that would otherwise hit the first frames
            self._materialize_row((outs, 0))

    def latest(self, handle: SessionHandle) -> tuple | None:
        """The most recent stepped frame's ``(estimate, ess,
        log_marginal, resampled, ancestors)`` for the session (host
        NumPy values),
        or ``None`` if no frame has been stepped since attach/resume.

        This is the streaming accessor the request plane
        (``repro.serve.frontend``) resolves per-frame futures from: it
        reads the last row without draining the queue or stacking the
        whole history the way ``result`` does.
        """
        last = self._lookup(handle).last
        return None if last is None else self._materialize_row(last)

    def result(self, handle: SessionHandle) -> filters.FilterResult:
        """Drain the session's queue and return its trajectory so far.

        The returned ``FilterResult`` has leading dim ``frames_done`` and
        is bitwise what ``ParallelParticleFilter.run`` returns over the
        same frames (``diag`` is empty on the serving path — DRA
        diagnostics belong to particle-sharded offline runs).
        """
        sess = self._lookup(handle)
        while sess.queue:
            self.step()
        stacked = self._stack_rows(sess)
        if stacked is None:
            raise ValueError("session has no filtered frames yet")
        return filters.FilterResult(
            estimates=stacked["estimates"],
            ess=stacked["ess"],
            log_marginal=stacked["log_marginal"],
            resampled=stacked["resampled"],
            ancestors=stacked["ancestors"],
            diag={},
            final=self._slot_ensemble(sess.slot))

    # -- suspension (mesh-elastic, DESIGN.md §11.4) -------------------------
    def suspend(self, handle: SessionHandle,
                directory: str | None = None) -> SuspendedSession:
        """Drain, snapshot to host, and free the slot.

        The snapshot (carry + output history) is full-array NumPy — no
        mesh layout leaks into it — so it resumes on any server with the
        same model/``n_particles``, whatever its capacity or mesh.  With
        ``directory`` it is also persisted via ``checkpoint.store``.

        ``directory`` is ONE session's checkpoint stream (steps keyed by
        ``frames_done``, oldest GC'd like a training run's) — give each
        session its own directory; two sessions sharing one would
        overwrite each other's snapshots.
        """
        sess = self._lookup(handle)
        while sess.queue:
            self.step()
        carry = jax.tree_util.tree_map(lambda x: x[sess.slot], self._carry)
        stacked = self._stack_rows(sess)
        if stacked is None:
            blank = self.blank_suspended()
            stacked = {"estimates": blank.estimates, "ess": blank.ess,
                       "log_marginal": blank.log_marginal,
                       "resampled": blank.resampled,
                       "ancestors": blank.ancestors}
        sus = SuspendedSession(
            key_data=np.asarray(jax.random.key_data(carry.key)),
            state=jax.tree_util.tree_map(np.asarray, carry.ensemble.state),
            log_weights=np.asarray(carry.ensemble.log_weights),
            counts=np.asarray(carry.ensemble.counts),
            frames_done=sess.frames_done,
            estimates=stacked["estimates"],
            ess=stacked["ess"],                 # native dtypes: the round
            log_marginal=stacked["log_marginal"],  # -trip stays bitwise
            resampled=stacked["resampled"],        # under x64 too
            ancestors=stacked["ancestors"],
        )
        self.detach(handle)
        if directory is not None:
            sus.save(directory)
        return sus

    def resume(self, suspended: SuspendedSession) -> SessionHandle:
        """Attach a suspended session into a free slot and continue it.

        The carry is restored bit-for-bit (PRNG key from ``key_data``,
        ensemble from the full host arrays), so the continuation matches
        an uninterrupted run bitwise; the output history is restored so
        ``result`` spans the whole stream.
        """
        n = suspended.log_weights.shape[0]
        if n != self.sir.n_particles:
            raise ValueError(
                f"suspended session has {n} particles, server runs "
                f"{self.sir.n_particles}")
        slot = self._take_slot()
        carry = smc.SIRCarry(
            key=jax.random.wrap_key_data(jnp.asarray(suspended.key_data)),
            ensemble=particles.ParticleEnsemble(
                state=jax.tree_util.tree_map(jnp.asarray, suspended.state),
                log_weights=jnp.asarray(suspended.log_weights),
                counts=jnp.asarray(suspended.counts)))
        self._carry = self._write_fn(self._carry, jnp.asarray(slot), carry)
        handle = self._register(slot)
        sess = self._sessions[handle.uid]
        sess.frames_done = suspended.frames_done
        if suspended.frames_done:
            # seed the host cache with the restored arrays directly —
            # no per-frame unstack/restack round-trip
            sess.stacked = {
                "estimates": suspended.estimates, "ess": suspended.ess,
                "log_marginal": suspended.log_marginal,
                "resampled": suspended.resampled,
                "ancestors": suspended.ancestors,
            }
        return handle

    def resume_from(self, directory: str,
                    step: int | None = None) -> SessionHandle:
        """``resume(SuspendedSession.load(directory))`` — restore straight
        from a checkpoint directory written by ``suspend``."""
        return self.resume(SuspendedSession.load(
            directory, self.blank_suspended(), step=step))

    def blank_suspended(self) -> SuspendedSession:
        """A zero-frame ``SuspendedSession`` with this server's pytree
        structure — the ``like`` template ``SuspendedSession.load`` needs
        to reassemble a checkpoint (structure from the model, shapes from
        disk)."""
        carry = jax.eval_shape(
            lambda k: filters.member_carry(k, self.model, self.sir),
            jax.random.key(0))
        zeros = lambda sh: jax.tree_util.tree_map(      # noqa: E731
            lambda l: np.zeros(l.shape, l.dtype), sh)
        est = jax.tree_util.tree_map(
            lambda l: np.zeros((0,) + l.shape[1:], l.dtype),
            carry.ensemble.state)
        return SuspendedSession(
            key_data=np.zeros(
                jax.eval_shape(jax.random.key_data, carry.key).shape,
                jnp.uint32),
            state=zeros(carry.ensemble.state),
            log_weights=zeros(carry.ensemble.log_weights),
            counts=zeros(carry.ensemble.counts),
            frames_done=0, estimates=est, ess=np.zeros((0,), np.float32),
            log_marginal=np.zeros((0,), np.float32),
            resampled=np.zeros((0,), bool),
            ancestors=np.zeros(
                (0, self.sir.n_particles if self.sir.record_ancestry else 0),
                np.int32))

    # -- internals ----------------------------------------------------------
    def _take_slot(self) -> int:
        if not self._free:
            raise RuntimeError(
                f"server full: all {self.capacity} slots attached "
                f"(detach or suspend a session, or start a server with a "
                f"larger capacity)")
        return heapq.heappop(self._free)

    def _register(self, slot: int) -> SessionHandle:
        uid = next(self._uids)
        self._sessions[uid] = _Session(uid, slot)
        self._by_slot[slot] = uid
        return SessionHandle(uid=uid, slot=slot)

    def _lookup(self, handle: SessionHandle) -> _Session:
        sess = self._sessions.get(handle.uid)
        if sess is None:
            raise KeyError(f"unknown or detached session {handle}")
        return sess

    def _slot_ensemble(self, slot: int) -> particles.ParticleEnsemble:
        return jax.tree_util.tree_map(lambda x: x[slot],
                                      self._carry.ensemble)

    def _stack_rows(self, sess: _Session) -> dict | None:
        """Fold pending rows into the host-side history cache and return
        it (None = no frames filtered yet).  Only rows appended since the
        last call are device→host converted, so per-frame ``result``
        polling costs O(new frames) in transfers (the returned
        full-history arrays are still O(T) memcpy)."""
        if sess.pending:
            est, ess, log_z, res, anc = zip(*(self._materialize_row(r)
                                              for r in sess.pending))
            fresh = {
                "estimates": jax.tree_util.tree_map(
                    lambda *xs: np.stack(xs), *est),
                "ess": np.stack(ess),
                "log_marginal": np.stack(log_z),
                "resampled": np.stack(res),
                "ancestors": np.stack(anc),
            }
            sess.pending = []
            sess.stacked = fresh if sess.stacked is None else \
                jax.tree_util.tree_map(
                    lambda a, b: np.concatenate([a, b]), sess.stacked,
                    fresh)
        return sess.stacked
