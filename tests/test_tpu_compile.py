"""Compiles for a described TPU v5e (no chip needed): the Pallas kernels
that chip paths select, the refusals that keep the others off them, and
the memory of one full-size tracking SIR step.

The topology is described inside a module fixture, never at import, and
the file skips where it cannot be described.  Interpret mode hides
lowering failures, so every kernel here compiles with
``interpret=False``; code that asks ``jax.default_backend()`` still sees
the CPU, so kernels and steps are compiled directly.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import SIRConfig, particles, smc
from repro.kernels import patch_likelihood as patch_kernels
from repro.kernels import resample as resample_kernels
from repro.kernels import sir_fused
from repro.kernels.flash_attention import flash_attention
from repro.models.tracking import TrackingConfig, make_tracking_model

N = 1 << 20            # the filter's per-chip ensemble on the main path
V5E_HBM_BYTES = 15.75e9


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off (described-device entries cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 — any failure means no chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_attention_kernel_compiles_for_v5e(one_chip):
    """``repro.kernels.ops.attention`` picks the flash kernel on TPU, so
    it must lower (an LM prefill block: 32 heads x 2048 tokens x head
    dim 128, bf16)."""
    q = _spec(one_chip, (1, 32, 2048, 128), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v: flash_attention(q, k, v)).lower(
        q, q, q).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1


# (kernel, argument shapes, the refusal its selection rule cites; the
# patch kernel's is an assertion without a message)
REFUSED = {
    "systematic": (
        lambda lw, u: resample_kernels.systematic_ancestors_kernel(
            lw, u, n_out=N, block=1024),
        [((N,), jnp.float32), ((), jnp.float32)], "cumsum"),
    "fused": (
        lambda lw, ll, x, u: sir_fused.fused_weight_step_kernel(
            lw, ll, x, u, block=1024),
        [((N,), jnp.float32), ((N,), jnp.float32), ((N, 5), jnp.float32),
         ((), jnp.float32)], "cumsum"),
    "metropolis": (
        lambda lw, p, lu: resample_kernels.metropolis_ancestors_kernel(
            lw, p, lu),
        [((N,), jnp.float32), ((N, 32), jnp.int32), ((N, 32), jnp.float32)],
        "Only 2D gather"),
    "rejection": (
        lambda lw, p, lu: resample_kernels.rejection_ancestors_kernel(
            lw, p, lu),
        [((N,), jnp.float32), ((N, 32), jnp.int32), ((N, 32), jnp.float32)],
        "Only 2D gather"),
    "patch_likelihood": (
        lambda y, x, i0, img: patch_kernels.patch_log_likelihood_kernel(
            y, x, i0, img, block_n=1024),
        [((N,), jnp.float32)] * 3 + [((512, 512), jnp.float32)], None),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_unselected_kernel_is_still_refused(one_chip, name):
    """The kernels no TPU path selects still fail to lower, with the
    message their selection rule cites; when one starts to lower, this
    fails and the rule can send the chip to it."""
    fn, shapes, message = REFUSED[name]
    args = [_spec(one_chip, s, d) for s, d in shapes]
    with pytest.raises((NotImplementedError, AssertionError),
                       match=message):
        jax.jit(fn).lower(*args).compile()


def test_tracking_sir_step_fits_one_chip(one_chip):
    """One composed tracking SIR step at N = 2^20 on 512x512 frames fits
    a v5e's HBM and holds no Pallas kernel (every slot takes XLA)."""
    model = make_tracking_model(TrackingConfig())
    step = smc.make_sir_step(model, SIRConfig(n_particles=N))
    carry = jax.eval_shape(lambda k: smc.SIRCarry(
        k, particles.init_ensemble(k, model.init, N)), jax.random.key(0))
    carry = jax.tree_util.tree_map(
        lambda s: _spec(one_chip, s.shape, s.dtype), carry)
    compiled = jax.jit(step).lower(
        carry, _spec(one_chip, (512, 512))).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB"
    assert "tpu_custom_call" not in compiled.as_text()
