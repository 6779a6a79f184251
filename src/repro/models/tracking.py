"""The paper's example application (§VII): fluorescence-microscopy object
tracking with a near-constant-velocity dynamics model and a Gaussian-PSF
observation model.

State vector x = (x̂, ŷ, v_x, v_y, I_0)  (paper §VII.A).
Observation model:  I(x,y) = I_0 · exp(−((x−x0)² + (y−y0)²) / 2σ_PSF²) + I_bg
with Gaussian read-out noise of scale σ_ξ (paper Eqs. 3–4); likelihood is
evaluated on the patch S_x = ±3σ_PSF around the particle (paper §VI.E —
image patches reduce O(N·N_pix) to O(N)).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.domain import DomainSpec

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TrackingConfig:
    """Paper §VII.C defaults: 512×512 frames, σ_PSF = 1.16 px, SNR 2."""

    img_size: tuple[int, int] = (512, 512)
    sigma_psf: float = 1.16
    sigma_noise: float = 1.0        # image noise σ (movie synthesis)
    sigma_like: float = 2.0         # σ_ξ — likelihood peakiness (paper Eq. 4)
    i_peak: float = 2.0             # SNR 2 ⇒ peak = 2 σ_noise
    i_bg: float = 0.0
    # "eq4"    — paper Eq. 4 verbatim: −Σ(Z−I)²/2σ_ξ²  (includes the ΣZ²
    #            patch-energy term, which at SNR 2 lets single-frame noise
    #            outweigh the true spot for large N).
    # "matched"— equivalent matched-filter form (ΣZ·I − ½ΣI²)/σ_ξ²: drops
    #            the particle-location noise-energy term. Beyond-paper
    #            robustness fix, recorded in DESIGN.md §8.
    likelihood_form: str = "matched"
    # near-constant-velocity dynamics noise
    sigma_pos: float = 0.5
    sigma_vel: float = 0.5
    sigma_int: float = 0.05
    v_init: float = 2.0             # px/frame scale for initialization
    patch_radius: int = 4           # ⌈3·σ_PSF⌉ + margin  (S_x support)


def psf_patch_offsets(radius: int) -> tuple[Array, Array]:
    r = jnp.arange(-radius, radius + 1)
    dy, dx = jnp.meshgrid(r, r, indexing="ij")
    return dy, dx


def render_spot(yx: Array, intensity: Array, cfg: TrackingConfig,
                shape: tuple[int, int]) -> Array:
    """Render one Gaussian-PSF spot into a full frame (movie synthesis)."""
    h, w = shape
    yy = jnp.arange(h, dtype=jnp.float32)[:, None]
    xx = jnp.arange(w, dtype=jnp.float32)[None, :]
    d2 = (yy - yx[0]) ** 2 + (xx - yx[1]) ** 2
    return intensity * jnp.exp(-d2 / (2.0 * cfg.sigma_psf ** 2))


def patch_table(frame: Array, radius: int) -> Array:
    """Every ±``radius`` patch that fits in ``frame``, one per row.

    Row ``sy * tw + sx`` holds ``frame[sy:sy+k, sx:sx+k].ravel()`` with
    ``k = 2·radius + 1`` and ``tw = W − 2·radius``; the shape is
    ``((H − 2·radius)·(W − 2·radius), k²)``.  The rows are copies of
    pixels, so a patch read from the table is exactly the patch sliced
    from the frame.

    The table is built as ``(k², rows)``, ``k`` row shifts and then ``k``
    column shifts, and transposed once: stacking the ``k²`` shifted
    frames on the minor axis instead makes XLA lay out each of them with
    its one column padded to the TPU's 128 lanes (10.7 GB of temporaries
    at 512², compiled for a TPU v5e).
    """
    k = 2 * radius + 1
    h, w = frame.shape
    th, tw = h - 2 * radius, w - 2 * radius
    rows = jnp.stack([frame[i:i + th] for i in range(k)])           # (k, th, w)
    shifts = jnp.stack([rows[:, :, j:j + tw] for j in range(k)], axis=1)
    return shifts.reshape(k * k, th * tw).T                         # (th·tw, k²)


def patch_log_likelihood(state: Array, frame: Array, cfg: TrackingConfig, *,
                         center_bounds: tuple | None = None,
                         frame_origin: tuple | None = None) -> Array:
    """Log-likelihood (paper Eq. 4) for a batch of particles against one
    frame, each evaluated on its own ±R patch.

    state: (N, 5) [y, x, vy, vx, I0];  frame: (H, W).

    The patches come from one table of every patch of the frame
    (``patch_table``, built once per frame) by one gather of one whole
    row per particle; the model and the sum then run on the
    ``(N, 2R+1, 2R+1)`` patches.  The table holds copies of pixels, so
    every particle reads the bits a per-particle ``dynamic_slice`` of the
    frame reads, and the result is that form's, bit for bit.  A per-particle window gather, that vmapped
    ``dynamic_slice`` included, runs on the TPU as a serial loop over
    particles; a row gather does not.

    The two keyword extras are the domain-decomposition hooks
    (DESIGN.md §10.2); ``frame`` may then be a halo *slab* of the full
    frame rather than the frame itself:

    center_bounds: (lo_y, hi_y, lo_x, hi_x) clamp for the patch-center
        pixel in FRAME coordinates, overriding the default frame interior
        ``[R, dim-1-R]``.  The bounds keep each patch inside ``frame`` (the
        default and ``tile_patch_log_likelihood``'s do); a patch they let
        overhang reads the nearest one that fits.
    frame_origin: frame coordinates (oy, ox) of ``frame[0, 0]``.  Only
        the integer patch *gather* is offset by the origin — positions,
        centers, and the PSF model all stay in frame coordinates, so a
        slab evaluation is bit-identical to the full-frame one (a
        coordinate rebase would round: float32 ``y - oy`` loses a ulp
        when it crosses a binade).
    """
    r = cfg.patch_radius
    k = 2 * r + 1
    dy, dx = psf_patch_offsets(r)                       # (2R+1, 2R+1)
    h, w = frame.shape
    if center_bounds is None:
        lo_y, hi_y, lo_x, hi_x = r, h - 1 - r, r, w - 1 - r
    else:
        lo_y, hi_y, lo_x, hi_x = center_bounds
    oy, ox = (0, 0) if frame_origin is None else frame_origin

    cy = jnp.clip(jnp.round(state[:, 0]).astype(jnp.int32), lo_y, hi_y)
    cx = jnp.clip(jnp.round(state[:, 1]).astype(jnp.int32), lo_x, hi_x)
    th, tw = h - 2 * r, w - 2 * r
    row = (jnp.clip(cy - r - oy, 0, th - 1) * tw
           + jnp.clip(cx - r - ox, 0, tw - 1))
    patches = jnp.take(patch_table(frame, r), row, axis=0,
                       mode="clip").reshape(-1, k, k)

    def one(s, cy, cx, patch):
        y, x, i0 = s[0], s[1], s[4]
        py = cy + dy
        px = cx + dx
        model = i0 * jnp.exp(-((py - y) ** 2 + (px - x) ** 2)
                             / (2.0 * cfg.sigma_psf ** 2)) + cfg.i_bg
        if cfg.likelihood_form == "eq4":
            resid = patch - model
            return -0.5 * jnp.sum(resid * resid) / (cfg.sigma_like ** 2)
        # matched-filter form: −½Σ(Z−I)² + ½ΣZ² = ΣZ·I − ½ΣI²
        return (jnp.sum(patch * model) - 0.5 * jnp.sum(model * model)) / (
            cfg.sigma_like ** 2)

    return jax.vmap(one)(state, cy, cx, patches)


def tile_patch_log_likelihood(state: Array, slab: Array, origin_yx,
                              cfg: TrackingConfig) -> Array:
    """Tile-local likelihood against one halo slab (DESIGN.md §10.2).

    ``slab`` is the ``(tile_h + 2R, tile_w + 2R)`` halo slab whose
    ``[0, 0]`` pixel sits at frame coordinates ``origin_yx`` (integers,
    possibly negative at frame edges).  All float arithmetic stays in
    frame coordinates (see ``patch_log_likelihood``); the patch-center
    clamp is the frame interior intersected with "the patch fits in the
    slab".  For particles owned by the slab's tile
    (``repro.core.domain.owner_of``) the slab constraint is a no-op —
    ownership derives from the clipped center, so every owned particle is
    interior to its slab — and the result is bitwise the full-frame
    ``patch_log_likelihood``.
    """
    oy, ox = origin_yx
    h, w = cfg.img_size
    r = cfg.patch_radius
    sh, sw = slab.shape
    bounds = (jnp.maximum(r, oy + r), jnp.minimum(h - 1 - r, oy + sh - 1 - r),
              jnp.maximum(r, ox + r), jnp.minimum(w - 1 - r, ox + sw - 1 - r))
    return patch_log_likelihood(state, slab, cfg, center_bounds=bounds,
                                frame_origin=origin_yx)


def make_domain_spec(cfg: TrackingConfig, tiles: int, *,
                     k_cap: int | None = None) -> DomainSpec:
    """Domain decomposition for this imaging model: halo = patch radius,
    squarest tile grid that divides the frame (DESIGN.md §10.1)."""
    return DomainSpec.for_mesh(cfg.img_size, tiles, cfg.patch_radius,
                               k_cap=k_cap)


@dataclasses.dataclass(frozen=True)
class TrackingSSM:
    """The paper's tracking application as a
    ``repro.models.ssm.StateSpaceModel`` adapter (DESIGN.md §12).

    What used to be the hard-wired likelihood of the whole filter stack
    is now just one protocol implementation among the generic families
    in ``repro.models.ssm`` — state ``(N, 5)`` = (y, x, v_y, v_x, I_0),
    near-constant-velocity dynamics, Gaussian-PSF patch likelihood.  It
    additionally implements the spatial hooks (``positions`` /
    ``tile_observation_log_prob``) that enable input-space domain
    decomposition (DESIGN.md §10), which the generic families do not
    have.  Numerics are bitwise those of the pre-protocol closure model
    (pinned by ``tests/golden/sir_parity.json`` and
    ``session_parity.json``).
    """

    cfg: TrackingConfig

    @property
    def state_dim(self) -> int:
        """Length of the (y, x, v_y, v_x, I_0) state vector."""
        return 5

    def init(self, key: Array, n: int) -> Array:
        """Uniform positions over the frame, Gaussian velocities and
        intensities around the configured SNR."""
        cfg = self.cfg
        h, w = cfg.img_size
        k1, k2, k3 = jax.random.split(key, 3)
        pos = jax.random.uniform(k1, (n, 2)) * jnp.asarray([h, w], jnp.float32)
        vel = jax.random.normal(k2, (n, 2)) * cfg.v_init
        inten = jnp.abs(cfg.i_peak + 0.5 * jax.random.normal(k3, (n, 1)))
        return jnp.concatenate([pos, vel, inten], axis=-1)

    def transition_sample(self, key: Array, state: Array) -> Array:
        """Near-constant-velocity: pos += vel + ε_p;  vel += ε_v."""
        cfg = self.cfg
        h, w = cfg.img_size
        n = state.shape[0]
        eps = jax.random.normal(key, (n, 5))
        pos = state[:, 0:2] + state[:, 2:4] + cfg.sigma_pos * eps[:, 0:2]
        vel = state[:, 2:4] + cfg.sigma_vel * eps[:, 2:4]
        inten = jnp.abs(state[:, 4:5] + cfg.sigma_int * eps[:, 4:5])
        pos = jnp.clip(pos, 0.0, jnp.asarray([h - 1.0, w - 1.0]))
        return jnp.concatenate([pos, vel, inten], axis=-1)

    def observation_log_prob(self, state: Array, frame: Array) -> Array:
        """Per-particle patch likelihood against one full frame."""
        return patch_log_likelihood(state, frame, self.cfg)

    def positions(self, state: Array) -> Array:
        """Frame-coordinate (y, x) of every particle (domain hook)."""
        return state[:, 0:2]

    def tile_observation_log_prob(self, state: Array, slab: Array,
                                  origin_yx) -> Array:
        """Tile-local patch likelihood against one halo slab (domain
        hook, DESIGN.md §10.2)."""
        return tile_patch_log_likelihood(state, slab, origin_yx, self.cfg)

    def observation_sample(self, key: Array, state: Array) -> Array:
        """Per-particle noisy frames ``(n, H, W)`` — one rendered spot
        plus read-out noise (powers ``repro.models.ssm.base.simulate``;
        movie synthesis proper lives in ``repro.data.synthetic_movie``)."""
        cfg = self.cfg
        clean = jax.vmap(
            lambda s: render_spot(s[0:2], s[4], cfg, cfg.img_size))(state)
        noise = cfg.sigma_noise * jax.random.normal(
            key, (state.shape[0],) + cfg.img_size)
        return clean + cfg.i_bg + noise


def make_tracking_model(cfg: TrackingConfig) -> TrackingSSM:
    """Build the tracking model (kept as the stable constructor name;
    returns the ``TrackingSSM`` protocol adapter)."""
    return TrackingSSM(cfg)
