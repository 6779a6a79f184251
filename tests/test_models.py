"""Per-architecture smoke tests (reduced same-family configs): one forward
/ train step on CPU, asserting shapes and finiteness; decode-vs-train
consistency in f32."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.data.tokens import make_batch
from repro.models.lm import model as M
from repro.optim import OptConfig, init_opt_state
from repro.train import TrainConfig, make_train_step

# Tier-1 runtime budget audit (DESIGN.md §12.3): the two heaviest smoke
# configs dominate this file's wall-clock (measured with --durations:
# together they were ~60% of it), so they run in the slow lane.  Every
# architecture family keeps a tier-1 representative: attention/GQA →
# stablelm-3b, qwen3-32b, granite-34b; MLA + MoE → moonlight-16b-a3b;
# RG-LRU → recurrentgemma-2b; SSM → mamba2-1.3b; vision cross-attn →
# llama-3.2-vision-11b; multi-codebook → musicgen-medium.
HEAVY_ARCHS = {"gemma3-27b", "deepseek-v2-236b"}
ARCHS = [pytest.param(a, marks=pytest.mark.slow) if a in HEAVY_ARCHS else a
         for a in list_archs()]
KEY = jax.random.key(0)


def _batch_for(cfg, b, t):
    return make_batch(0, 0, cfg, b, t)


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finiteness(arch):
    cfg = get_config(arch, smoke=True)
    params = M.init_params(KEY, cfg)
    batch = _batch_for(cfg, 2, 64)
    x, aux = M.forward_train(params, cfg, batch["tokens"],
                             batch.get("image_embeds"))
    assert x.shape == (2, 64, cfg.d_model)
    logits = M.unembed(M.cast_params(params, cfg), cfg, x)
    expect = ((2, 64, cfg.n_codebooks, cfg.vocab_size)
              if cfg.n_codebooks > 1 else (2, 64, cfg.vocab_size))
    assert logits.shape == expect
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch):
    cfg = get_config(arch, smoke=True)
    params = M.init_params(KEY, cfg)
    opt_state = init_opt_state(params)
    step = jax.jit(make_train_step(cfg, OptConfig(lr=1e-3, warmup_steps=1),
                                   TrainConfig(xent_chunk=32)))
    batch = _batch_for(cfg, 2, 64)
    params2, opt_state, metrics = step(params, opt_state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    # parameters actually moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(params2)))
    assert moved


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_consistency_f32(arch):
    """Prefill+decode must reproduce the full-forward logits exactly in
    f32 (MoE capacity raised to avoid drop artifacts)."""
    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    if cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = M.init_params(KEY, cfg)
    b, t = 2, 31
    shape = (b, t + 1, cfg.n_codebooks) if cfg.n_codebooks > 1 else (b, t + 1)
    tokens = jax.random.randint(KEY, shape, 0, cfg.vocab_size)
    img = (jax.random.normal(KEY, (b, cfg.n_image_tokens, cfg.d_image))
           if cfg.cross_attn_every else None)
    x_full, _ = M.forward_train(params, cfg, tokens, img)
    logits_full = M.unembed(M.cast_params(params, cfg), cfg, x_full)[:, -1]
    _, caches, _ = M.forward_prefill(params, cfg, tokens[:, :t],
                                     max_len=t + 8, img=img)
    logits_dec, _ = M.forward_decode(params, cfg, tokens[:, t:t + 1], t,
                                     caches)
    np.testing.assert_allclose(np.asarray(logits_full),
                               np.asarray(logits_dec[:, 0]),
                               rtol=1e-4, atol=1e-4)


def test_layer_plan_counts():
    """head + groups·unit + tail == n_layers for every arch (full config)."""
    for arch in list_archs():      # plain names: ARCHS carries slow marks
        cfg = get_config(arch)
        plan = M.make_plan(cfg)
        total = (len(plan.head) + plan.n_groups * len(plan.unit)
                 + len(plan.tail))
        assert total == cfg.n_layers, (arch, plan)


def test_moe_load_diagnostics():
    cfg = get_config("moonlight-16b-a3b", smoke=True)
    params = M.init_params(KEY, cfg)
    batch = _batch_for(cfg, 2, 64)
    _, aux = M.forward_train(params, cfg, batch["tokens"])
    assert 0.0 <= float(aux["moe_drop_frac"]) < 1.0
    assert float(aux["moe_aux_loss"]) >= 0.0
