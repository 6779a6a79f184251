"""``correct`` of the decode cell is decided by a comparison shown to
fail: the harness runs here past its look for a chip, at a size a test
run holds, first sound and then with the timed path broken underneath,
and each fault makes ``correct`` come out false: the selection bias left
out, the 2.446 gate scaling left out, the shared experts left out, a
cache position off by one, the importance increments left out (which
only ``logw_gap`` sees), and the float8 control in the program's
place.  Also the decode step's work by hand, and the scopes the trace
readers find in the compiled scan.
"""
import collections
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from perf_faultkit import control_checks, over, run_cell  # noqa: E402
from bench import harness, scopes  # noqa: E402
from bench.window import Window  # noqa: E402
from repro.models.lm import model as M  # noqa: E402
from repro.models.lm import moe as MOE  # noqa: E402

# the cell at a CPU size: every published ratio kept (a quarter of the
# router's experts held, top-6, 2 shared experts, the dense layer first,
# four MoE layers after it), widths and vocabulary small, every prompt's
# rows compared.  The program runs in float32 here: at
# widths of 64, rounding to bfloat16 flips near-tied expert choices about
# as often as a fault changes them (a sound bfloat16 run read logp_gap
# 0.013-0.037 where the scaling fault read 0.026 on the CPU), while the
# chip's sound bfloat16 runs are what set the limits (PERF.md section 4)
DECODE = ("decode-moonlight-b16k8-p2k",
          {"prompts": 4, "particles": 4, "prompt_len": 32, "prefix_len": 24,
           "steps": 16, "compared_prompts": 4},
          {"num_hidden_layers": 5, "hidden_size": 64,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "intermediate_size": 128, "vocab_size": 512, "kv_lora_rank": 32,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
           "moe_intermediate_size": 32, "n_routed_experts": 4,
           "router_experts": 16, "dtype": "float32"}, 0.5)


@pytest.fixture(autouse=True)
def fresh_traces():
    """The decode scan is a module-level ``jax.jit``: a fault planted in
    what it calls shows only in a fresh trace."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def sound_decode():
    return run_cell(DECODE)


def test_sound_decode_run_is_correct(sound_decode):
    correct, checks = sound_decode
    assert correct, checks


def _route_with(change):
    original = MOE.route

    def route(x, router, bias, cfg):
        return change(original, x, router, bias, cfg)
    return route


def test_selection_bias_left_out_is_not_correct(monkeypatch):
    monkeypatch.setattr(MOE, "route", _route_with(
        lambda f, x, r, b, cfg: f(x, r, None, cfg)))
    correct, checks = run_cell(DECODE)
    assert not correct and over(checks, "logp_gap"), checks


def test_gate_scaling_left_out_is_not_correct(monkeypatch):
    def unscaled(f, x, r, b, cfg):
        gate, eid, scores = f(x, r, b, cfg)
        return gate / cfg.routed_scale, eid, scores
    monkeypatch.setattr(MOE, "route", _route_with(unscaled))
    correct, checks = run_cell(DECODE)
    assert not correct and over(checks, "logp_gap"), checks


def test_shared_experts_left_out_is_not_correct(monkeypatch):
    monkeypatch.setattr(MOE, "apply_mlp", lambda p, x: jnp.zeros_like(x))
    correct, checks = run_cell(DECODE)
    assert not correct and over(checks, "logp_gap"), checks


def test_cache_position_off_by_one_is_not_correct(monkeypatch):
    """Decode writes and reads the latent cache one position late."""
    block = M._mla_block

    def late(p, h, cfg, mode, cache, positions, pos):
        if mode == "decode":
            positions, pos = positions + 1, pos + 1
        return block(p, h, cfg, mode, cache, positions, pos)
    monkeypatch.setattr(M, "_mla_block", late)
    correct, checks = run_cell(DECODE)
    assert not correct and over(checks, "logp_gap"), checks


def test_importance_increments_left_out_is_not_correct(monkeypatch):
    """Every step reweights by zero in place of log p - log q: the tokens
    and their log p are the sound run's, so only ``logw_gap`` sees it."""
    from repro.models.lm import decode_ssm
    monkeypatch.setattr(decode_ssm.LMDecodeSSM, "observation_log_prob",
                        lambda self, state, obs: jnp.zeros_like(state["inc"]))
    correct, checks = run_cell(DECODE)
    assert not correct and over(checks, "logw_gap"), checks
    assert not over(checks, "logp_gap"), checks


def test_float8_control_is_not_correct():
    correct, checks = control_checks(DECODE)
    assert not correct, checks


def test_published_weights_load_into_the_program_tree():
    """The weights drawn by published name fill the program's parameter
    tree leaf for leaf, in its shapes and dtypes (the selection bias in
    float32, as published), with every RMSNorm gain away from 1."""
    name, traffic, config, _ = DECODE
    cell = harness.load_cell(name, traffic, config)
    got = cell.model.make_weights(cell.config, 3)
    arch = cell.model.arch_config(cell.config)
    want = jax.eval_shape(lambda k: M.init_params(k, arch),
                          jax.random.key(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        bias = getattr(path[-1], "key", None) == "router_bias"
        assert a.shape == b.shape, path
        assert a.dtype == (jnp.float32 if bias else b.dtype), path
    norms = [a for path, a in jax.tree_util.tree_leaves_with_path(got)
             if str(getattr(path[-1], "key", "")).endswith("norm")]
    assert norms and all(float(jnp.abs(a).min()) > 0 for a in norms)
    w = cell.model.weights(cell.config, 3)
    lm = w()["lm_head.weight"]
    assert lm.shape == (cell.config["vocab_size"],
                        cell.config["hidden_size"])
    assert bool((got["lm_head"] == lm.T).all())


def test_decode_work_by_hand():
    """The decode step's counted work at the cell's own sizes."""
    cell = harness.load_cell(DECODE[0])
    cfg, t = cell.config, cell.traffic
    rows = t["prompts"] * t["particles"]
    d, v = 2048, 163840
    mla = d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d
    assert mla == 13_762_560
    pos = 2048 + 64
    f, b = cell.model.mla_work(cfg, t, rows)
    assert b == 9 * (mla * 2 + rows * (pos + 1) * 576 * 2)
    assert f == 9 * rows * (2 * mla + 2 * 16 * pos * (2 * 512 + 64))
    expert = 3 * d * 1408
    f, b = cell.model.moe_work(cfg, t, rows)
    assert b == 8 * (16 * expert + 2 * expert + d * 64) * 2
    assert f == 8 * rows * 2 * (d * 64 + 2 * expert + 1.5 * expert)
    flops, nbytes = cell.model.work(cfg, t, rows, 0.5)
    f_mla, b_mla = cell.model.mla_work(cfg, t, rows)
    f_moe, b_moe = cell.model.moe_work(cfg, t, rows)
    state = 9 * 2177 * 576 * 2 + 128 * 4 + 5 * 4
    assert nbytes == (b_mla + b_moe + (3 * d * 11264 + d * v) * 2
                      + rows * d * 2 + 2 * rows * v * 4
                      + 0.5 * 2 * rows * state)
    assert flops == f_mla + f_moe + rows * 2 * (3 * d * 11264 + d * v)
    # 3.55 GB of held weights outside the embedding (PERF.md section 4)
    held = mla + 3 * d * 11264 \
        + 8 * (16 * expert + 2 * expert + d * 64 + mla) + d * v
    assert abs(held * 2 / 1e9 - 3.55) < 0.01


def test_scope_map_finds_the_model_scopes_in_the_compiled_scan():
    """The decode scan compiled at the test size maps instructions to
    ``ppf.lm.mla``, ``ppf.lm.moe`` and ``ppf.lm.head``, and ``per_step``
    reads a trace of two steps of it."""
    name, traffic, config, _ = DECODE
    cell = harness.load_cell(name, traffic, config)
    d = harness.make_driver(cell, 3, 0.1)
    d.setup()
    scope_of, loops = d.counters["scope_of"], d.counters["loops"]
    found = collections.Counter(scope_of.values())
    assert {"ppf.lm.mla", "ppf.lm.moe", "ppf.lm.head",
            "ppf.advance"} <= set(found), found
    assert loops and all(i not in loops or scope_of.get(i) != "ppf.lm.head"
                         for i in scope_of)
    mla = next(i for i, s in scope_of.items()
               if s == "ppf.lm.mla" and i not in loops)
    head = next(i for i, s in scope_of.items()
                if s == "ppf.lm.head" and i not in loops)
    module = d.counters["module"]
    events = [(0.0, 1000.0, module + "(1)")]
    for step in range(2):
        t = 100.0 + 300.0 * step
        events += [(t, t + 50.0, f"%{mla} = bf16[4] fusion(%p)"),
                   (t + 60.0, t + 70.0, f"%{head} = f32[4] fusion(%q)")]
    win = Window({}, 1, 0, 2, 1.0, counters=d.counters)
    ctx = {"window": win, "busy": {"dev": events}, "lo": 0.0, "hi": 1000.0}
    assert scopes.per_step(ctx, "ppf.lm.mla", "ppf.lm.head") == \
        pytest.approx(50e-9)
    assert scopes.per_step(ctx, "ppf.lm.moe", "ppf.lm.head") == 0.0
    assert scopes.per_step({**ctx, "window": Window({}, 1, 0, 2, 1.0)},
                           "ppf.lm.mla", "ppf.lm.head") is None
    d.release()
