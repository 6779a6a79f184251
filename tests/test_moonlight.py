"""Moonlight-16B-A3B against its plain float32 reference at the smoke
size, on seeded random weights (``tests/lm_reference.py``).

* prefill followed by cached, absorbed decode gives the reference's
  full-forward logits at every position;
* ``smc_decode``'s per-row log p equals the reference's teacher-forced sum
  over the row's tokens;
* the four expert shares' outputs, the shared experts counted once, add
  up to the uncut layer, in the dense form of a decode step and in the
  routed form of a longer call;
* a routing that sends every token to one expert drops nothing;
* with ``dispatch="ep_shardmap"`` on a mesh of simulated devices the
  serving layer gives the one-device layer's output;
* the selection bias changes which experts are chosen, not their gates.
"""
import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.lm import model as M
from repro.models.lm import moe as MOE
from repro.serve import SMCDecodeConfig, smc_decode

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
import lm_reference as ref  # noqa: E402

KEY = jax.random.key(15)


@pytest.fixture(scope="module")
def cfg():
    c = get_config("moonlight-16b-a3b", smoke=True)
    # capacity raised so that the training dispatch, checked beside the
    # serving path, drops nothing either
    return dataclasses.replace(
        c, compute_dtype="float32",
        moe=dataclasses.replace(c.moe, capacity_factor=8.0))


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights with every RMSNorm gain away from 1 (stored as
    1 + w), so that a gain left out or misapplied shows."""
    def gains(path, a):
        if str(getattr(path[-1], "key", "")).endswith("norm"):
            return jax.random.uniform(jax.random.fold_in(KEY, a.size),
                                      a.shape, a.dtype, -0.5, 0.5)
        return a
    return jax.tree_util.tree_map_with_path(gains,
                                            M.init_params(KEY, cfg))


def test_smoke_config_is_the_published_block(cfg):
    full = get_config("moonlight-16b-a3b")
    assert (full.n_layers, full.d_model, full.n_heads, full.vocab_size) \
        == (27, 2048, 16, 163840)
    assert (full.mla.kv_lora_rank, full.mla.q_lora_rank,
            full.mla.qk_nope_dim, full.mla.qk_rope_dim,
            full.mla.v_head_dim) == (512, 0, 128, 64, 128)
    m = full.moe
    assert (m.n_experts, m.top_k, m.d_ff_expert, m.n_shared_experts,
            m.first_dense_layers, m.scoring, m.selection_bias,
            m.routed_scale) == (64, 6, 1408, 2, 1, "sigmoid", True, 2.446)
    assert cfg.mla.q_lora_rank == 0 and cfg.moe.selection_bias


def test_prefill_then_cached_decode_gives_the_full_forward_logits(cfg,
                                                                   params):
    b, t0, extra = 2, 9, 5
    tokens = jax.random.randint(KEY, (b, t0 + extra), 0, cfg.vocab_size)
    want = ref.logits(params, cfg, tokens)                 # (B, T, V)
    hidden, _ = M.forward_train(params, cfg, tokens)
    full = M.unembed(M.cast_params(params, cfg), cfg, hidden)
    np.testing.assert_allclose(full, want, rtol=2e-4, atol=2e-4)

    h_last, caches, _ = M.forward_prefill(params, cfg, tokens[:, :t0],
                                          max_len=t0 + extra + 1)
    got = [M.unembed(M.cast_params(params, cfg), cfg, h_last)[:, 0]]
    for i in range(t0, t0 + extra - 1):
        step, caches = M.forward_decode(params, cfg, tokens[:, i:i + 1], i,
                                        caches)
        got.append(step[:, 0])
    np.testing.assert_allclose(np.stack(got, 1), want[:, t0 - 1:-1],
                               rtol=2e-4, atol=2e-4)


def test_smc_decode_logp_is_the_teacher_forced_sum(cfg, params):
    prompt = jax.random.randint(jax.random.key(3), (2, 7), 0,
                                cfg.vocab_size)
    dcfg = SMCDecodeConfig(n_particles=4, steps=6, proposal_temperature=1.5)
    res = smc_decode(params, cfg, prompt, dcfg, key=jax.random.key(4))
    for b in range(2):
        seqs = np.concatenate([np.broadcast_to(prompt[b], (4, 7)),
                               np.asarray(res.sequences[b])], 1)
        lp = jax.nn.log_softmax(ref.logits(params, cfg, seqs[:, :-1]), -1)
        picked = np.take_along_axis(np.asarray(lp[:, 6:]),
                                    seqs[:, 7:, None], -1)[..., 0]
        np.testing.assert_allclose(res.logp[b], picked.sum(1), rtol=1e-4,
                                   atol=1e-4)


def _moe_layer(params):
    return jax.tree_util.tree_map(lambda a: a[0],
                                  params["blocks"])["l0_M_moe"]["moe"]


@pytest.mark.parametrize("tokens", [16, 2 * MOE.DENSE_TOKENS])
def test_expert_shares_add_up_to_the_uncut_layer(cfg, params, tokens):
    """Per row of 2: 16 tokens run the dense form, 512 the routed one."""
    p = _moe_layer(params)
    x = jax.random.normal(jax.random.key(5), (2, tokens, cfg.d_model))
    uncut = ref.experts(p, x.reshape(-1, cfg.d_model), cfg)
    shared = ref.swiglu(p["shared"], x.reshape(-1, cfg.d_model))
    shares, total = 4, shared
    held = cfg.moe.n_experts // shares
    for s in range(shares):
        mcfg = dataclasses.replace(cfg.moe, expert_shares=shares,
                                   expert_share=s)
        part = {**p, **{k: p[k][s * held:(s + 1) * held]
                        for k in ("we_gate", "we_up", "we_down")}}
        out = MOE.apply_expert_share(part, x, mcfg).reshape(-1, cfg.d_model)
        total = total + (out - shared)
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-4)
    whole = MOE.apply_expert_share(p, x, cfg.moe).reshape(-1, cfg.d_model)
    np.testing.assert_allclose(whole, uncut, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tokens", [32, 2 * MOE.DENSE_TOKENS])
def test_every_token_routed_to_one_expert_drops_nothing(cfg, params,
                                                         tokens):
    p = dict(_moe_layer(params))
    p["router_bias"] = p["router_bias"].at[3].set(100.0)
    x = jax.random.normal(jax.random.key(6), (2, tokens, cfg.d_model))
    gate, eid, _ = MOE.route(x.reshape(-1, cfg.d_model), p["router"],
                             p["router_bias"], cfg.moe)
    assert bool((eid == 3).any(-1).all())
    got = MOE.apply_expert_share(p, x, cfg.moe).reshape(-1, cfg.d_model)
    np.testing.assert_allclose(
        got, ref.experts(p, x.reshape(-1, cfg.d_model), cfg), rtol=1e-4,
        atol=1e-4)
    # the capacity dispatch of training would have dropped most of them
    _, aux = MOE.apply_moe(p, x, dataclasses.replace(cfg.moe,
                                                      capacity_factor=1.25))
    assert float(aux["moe_drop_frac"]) > 0.2


def test_selection_bias_picks_the_experts_not_their_gates(cfg, params):
    p = _moe_layer(params)
    x = jax.random.normal(jax.random.key(7), (64, cfg.d_model))
    gate, eid, scores = MOE.route(x, p["router"], p["router_bias"], cfg.moe)
    _, eid0, _ = MOE.route(x, p["router"], None, cfg.moe)
    assert bool((jnp.sort(eid, -1) != jnp.sort(eid0, -1)).any())
    picked = jnp.take_along_axis(scores, eid, -1)
    np.testing.assert_allclose(
        gate, picked / picked.sum(-1, keepdims=True) * 2.446, rtol=1e-6)
    assert bool((scores > 0).all() & (scores < 1).all())


EP_CHECK = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.core import runtime
from repro.launch.sharding import mesh_context
from repro.models.lm import moe as MOE
cfg = get_config("moonlight-16b-a3b", smoke=True)
ep = dataclasses.replace(cfg.moe, dispatch="ep_shardmap")
p = MOE.moe_params(jax.random.key(1), cfg.d_model, ep, jnp.float32)
mesh = runtime.make_mesh((2, 2), ("data", "model"))
for t in (4, MOE.DENSE_TOKENS):
    x = jax.random.normal(jax.random.key(t), (4, t, cfg.d_model))
    want = MOE.apply_expert_share(p, x, cfg.moe)
    with mesh_context(mesh):
        f = jax.jit(lambda p, x: MOE.apply_expert_share(p, x, ep))
        got = f(p, x)
        assert "all_gather" in f.lower(p, x).as_text()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
print("EP_OK")
"""


def test_expert_parallel_serving_layer_matches_one_device():
    """``dispatch="ep_shardmap"`` on a 2 x 2 mesh of simulated devices
    (each ``data`` shard one share of the experts): the same output as
    the one-device layer, at a decode size (dense) and a prefill size
    (routed)."""
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(
        pathlib.Path(__file__).resolve().parent.parent / "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", EP_CHECK],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "EP_OK" in out.stdout
