"""Tracking model + synthetic movie tests (paper §VII)."""
import dataclasses

import jax
import jax.extend.core as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SIRConfig
from repro.core import domain as D
from repro.core.smc import run_sir
from repro.data.synthetic_movie import generate_movie, tracking_rmse
from repro.models.tracking import (TrackingConfig, make_domain_spec,
                                   make_tracking_model, patch_log_likelihood,
                                   psf_patch_offsets,
                                   tile_patch_log_likelihood)


def test_noiseless_tracking_subpixel():
    """Near-noiseless movie → sub-0.1px tracking (mechanics correctness)."""
    cfg = TrackingConfig(img_size=(64, 64), sigma_noise=0.05,
                         sigma_like=0.5, v_init=1.5)
    model = make_tracking_model(cfg)
    movie = generate_movie(jax.random.key(0), cfg, n_frames=25)
    _, outs = run_sir(jax.random.key(1), model,
                      SIRConfig(n_particles=8192, ess_frac=0.5),
                      movie.frames)
    rmse = float(tracking_rmse(outs.estimate, movie.trajectories[:, 0]))
    assert rmse < 0.1, rmse


def test_snr2_tracking_converges():
    """The paper's SNR-2 regime tracks to ~sub-pixel accuracy."""
    cfg = TrackingConfig(img_size=(64, 64), v_init=1.5)
    model = make_tracking_model(cfg)
    movie = generate_movie(jax.random.key(0), cfg, n_frames=40)
    _, outs = run_sir(jax.random.key(1), model,
                      SIRConfig(n_particles=8192, ess_frac=0.5),
                      movie.frames)
    rmse = float(tracking_rmse(outs.estimate, movie.trajectories[:, 0],
                               warmup=10))
    assert rmse < 1.5, rmse


def test_likelihood_peaks_at_truth():
    cfg = TrackingConfig(img_size=(64, 64))
    movie = generate_movie(jax.random.key(3), cfg, n_frames=1)
    gt = movie.trajectories[0, 0]
    offsets = jnp.asarray([[0, 0], [4, 0], [0, 4], [8, 8], [-6, 2]],
                          jnp.float32)
    states = jnp.concatenate([
        gt[None] + offsets,
        jnp.zeros((5, 2)),
        jnp.full((5, 1), cfg.i_peak)], axis=-1)
    ll = patch_log_likelihood(states, movie.frames[0], cfg)
    assert int(jnp.argmax(ll)) == 0


def test_movie_trajectories_stay_in_frame():
    cfg = TrackingConfig(img_size=(128, 128), v_init=2.0)
    movie = generate_movie(jax.random.key(7), cfg, n_frames=60, n_spots=3)
    t = np.asarray(movie.trajectories)
    assert (t >= 0).all() and (t <= 128).all()
    assert movie.frames.shape == (60, 128, 128)


def test_eq4_and_matched_forms_agree_on_ordering():
    """Both likelihood forms prefer the true location (they differ by the
    patch energy term, not the argmax near truth)."""
    for form in ("eq4", "matched"):
        cfg = TrackingConfig(img_size=(64, 64), likelihood_form=form,
                             sigma_noise=0.1, sigma_like=1.0)
        movie = generate_movie(jax.random.key(5), cfg, n_frames=1)
        gt = movie.trajectories[0, 0]
        states = jnp.stack([
            jnp.concatenate([gt, jnp.zeros(2), jnp.ones(1) * cfg.i_peak]),
            jnp.concatenate([gt + 5, jnp.zeros(2),
                             jnp.ones(1) * cfg.i_peak]),
        ])
        ll = patch_log_likelihood(states, movie.frames[0], cfg)
        assert float(ll[0]) > float(ll[1]), form


def _dynamic_slice_likelihood(state, frame, cfg, center_bounds=None,
                              frame_origin=None, fit_start=False):
    """The per-particle form the patch table replaced, kept as the oracle:
    each particle slices its own patch out of the frame.  With
    ``fit_start`` the slice starts at the nearest patch that fits in the
    frame (``dynamic_slice`` alone wraps a negative start first)."""
    r = cfg.patch_radius
    dy, dx = psf_patch_offsets(r)
    h, w = frame.shape
    if center_bounds is None:
        lo_y, hi_y, lo_x, hi_x = r, h - 1 - r, r, w - 1 - r
    else:
        lo_y, hi_y, lo_x, hi_x = center_bounds
    oy, ox = (0, 0) if frame_origin is None else frame_origin

    def one(s):
        y, x, i0 = s[0], s[1], s[4]
        cy = jnp.clip(jnp.round(y).astype(jnp.int32), lo_y, hi_y)
        cx = jnp.clip(jnp.round(x).astype(jnp.int32), lo_x, hi_x)
        sy, sx = cy - r - oy, cx - r - ox
        if fit_start:
            sy = jnp.clip(sy, 0, h - 2 * r - 1)
            sx = jnp.clip(sx, 0, w - 2 * r - 1)
        patch = jax.lax.dynamic_slice(frame, (sy, sx), (2 * r + 1, 2 * r + 1))
        py = cy + dy
        px = cx + dx
        model = i0 * jnp.exp(-((py - y) ** 2 + (px - x) ** 2)
                             / (2.0 * cfg.sigma_psf ** 2)) + cfg.i_bg
        if cfg.likelihood_form == "eq4":
            resid = patch - model
            return -0.5 * jnp.sum(resid * resid) / (cfg.sigma_like ** 2)
        return (jnp.sum(patch * model) - 0.5 * jnp.sum(model * model)) / (
            cfg.sigma_like ** 2)

    return jax.vmap(one)(state)


def _edge_states(key, n, h, w):
    """``n`` particles over the frame and up to 6 px beyond it, the first
    ones pinned to the clamped edges, the rounding boundaries and off the
    frame."""
    ks = jax.random.split(key, 3)
    y = jax.random.uniform(ks[0], (n,), minval=-6.0, maxval=h + 6.0)
    x = jax.random.uniform(ks[1], (n,), minval=-6.0, maxval=w + 6.0)
    edges = jnp.asarray([0.0, 0.49, 3.5, 4.49, h - 4.5, h - 1.0, h - 0.5,
                         -3.0, h + 2.0, 17.5])
    y = y.at[:10].set(edges)
    x = x.at[:10].set(edges[::-1] * (w / h))
    i0 = jax.random.uniform(ks[2], (n,)) * 3.0
    return jnp.stack([y, x, jnp.zeros(n), jnp.zeros(n), i0], axis=1)


def _case(kind, size, form):
    """(table form, dynamic_slice oracle) of one case, evaluated."""
    cfg = TrackingConfig(img_size=(size, size), likelihood_form=form)
    key = jax.random.key(size)
    frame = jax.random.normal(key, (size, size)) + 0.5
    n = 4096
    if kind == "frame":
        state = _edge_states(key, n, size, size)
        return (patch_log_likelihood(state, frame, cfg),
                _dynamic_slice_likelihood(state, frame, cfg))
    if kind == "slab":
        # the halo slabs of 4 tiles against every particle of the frame,
        # owned or not: with the tile's center bounds, and with the frame
        # interior's, where a patch centered outside the slab reads the
        # nearest one that fits
        spec = make_domain_spec(cfg, 4)
        r = cfg.patch_radius
        interior = (r, size - 1 - r, r, size - 1 - r)
        state = _edge_states(key, n, size, size)
        got, want = [], []
        for t in range(spec.tiles):
            slab = D.extract_slab(spec, frame, t)
            oy, ox = spec.slab_origin(t)
            sh, sw = slab.shape
            bounds = (max(r, oy + r), min(size - 1 - r, oy + sh - 1 - r),
                      max(r, ox + r), min(size - 1 - r, ox + sw - 1 - r))
            got.append(tile_patch_log_likelihood(state, slab, (oy, ox), cfg))
            want.append(_dynamic_slice_likelihood(
                state, slab, cfg, center_bounds=bounds, frame_origin=(oy, ox)))
            got.append(patch_log_likelihood(
                state, slab, cfg, center_bounds=interior,
                frame_origin=(oy, ox)))
            want.append(_dynamic_slice_likelihood(
                state, slab, cfg, center_bounds=interior,
                frame_origin=(oy, ox), fit_start=True))
        return jnp.stack(got), jnp.stack(want)
    # four sessions' frames under vmap, as a tier program steps them
    frames = jax.random.normal(key, (4, size, size))
    state = jax.vmap(lambda k: _edge_states(k, n, size, size))(
        jax.random.split(key, 4))
    return (jax.vmap(lambda s, f: patch_log_likelihood(s, f, cfg))(
                state, frames),
            jax.vmap(lambda s, f: _dynamic_slice_likelihood(s, f, cfg))(
                state, frames))


@pytest.mark.parametrize("form", ["matched", "eq4"])
@pytest.mark.parametrize("kind,size", [
    ("frame", 48), ("frame", 64), ("frame", 512), ("slab", 64),
    ("sessions", 64)])
def test_patch_table_matches_dynamic_slice_bitwise(kind, size, form):
    """The table-and-row-gather likelihood returns the per-particle
    ``dynamic_slice`` likelihood's bits: on full frames at 48², 64² and
    512² with particles at the clamped edges and off the frame, on halo
    slabs with ``center_bounds`` and ``frame_origin`` (with the frame
    interior's bounds a patch centered outside the slab reads the nearest
    one that fits), and vmapped over four sessions' frames."""
    got, want = _case(kind, size, form)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _gathers(jaxpr):
    """Every ``gather`` equation of ``jaxpr`` and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if isinstance(sub, jex.ClosedJaxpr):
                    yield from _gathers(sub.jaxpr)
                elif isinstance(sub, jex.Jaxpr):
                    yield from _gathers(sub)


def test_patch_likelihood_gathers_whole_table_rows():
    """Every gather of the likelihood takes whole rows of a 2-D table: a
    per-particle window of the frame (a ``(2R+1, 2R+1)`` gather or a
    ``dynamic_slice``) runs as a serial loop over particles on the TPU."""
    cfg = TrackingConfig(img_size=(64, 64))
    state = jnp.zeros((16, 5))
    jaxpr = jax.make_jaxpr(
        lambda s, f: patch_log_likelihood(s, f, cfg))(state,
                                                      jnp.zeros((64, 64)))
    gathers = list(_gathers(jaxpr.jaxpr))
    assert gathers
    k2 = (2 * cfg.patch_radius + 1) ** 2
    for eqn in gathers:
        operand = eqn.invars[0].aval
        assert operand.shape[1:] == (k2,), operand.shape
        assert tuple(eqn.params["slice_sizes"]) == (1, k2)
