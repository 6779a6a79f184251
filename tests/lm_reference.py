"""Plain float32 reference of the DeepSeek-V3 decoder block, for the CPU
tests of Moonlight-16B-A3B (``tests/test_moonlight.py``).

Written from the published description and not from ``models/lm``: the
full teacher-forced forward pass of each sequence, in float32 under
``jax.default_matmul_precision("highest")``, with no cache and no
batching of sequences.  The weights are the program's (seeded data read
by its layout).  Per block, RMSNorm gains stored as 1 + w:

    x += W_O attn(RMSNorm(x))    query W_Q x (no low rank); latent
                                 c = RMSNorm(W_DKV x); one rotary key
                                 RoPE(W_KR x) for all heads; keys W_UK c,
                                 values W_UV c; scores / sqrt(nope + rope)
    x += FFN(RMSNorm(x))         a SwiGLU in the leading dense layers,
                                 then s = sigmoid(x W_r), top-k of s + b
                                 chosen, g_i = s_i / sum s_j x scale, the
                                 held experts' part of sum g_i E_i(x) plus
                                 the shared SwiGLU

The rotary dims rotate as two halves in stored order (the published code
de-interleaves them first, a fixed permutation of random weights).  The
benchmark's copy of this reference is
``bench/configs/moonlight-16b-a3b.ref.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(F32) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, tree)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def rotate(x, pos, theta):
    """x (T, ..., d): the two halves of the last axis rotated by pos x
    theta^(-i / (d/2))."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * freq[None, :]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention(p, x, cfg):
    """Causal latent attention of one sequence x (T, D)."""
    t, m = x.shape[0], cfg.mla
    h, dn, dr, r = cfg.n_heads, m.qk_nope_dim, m.qk_rope_dim, m.kv_lora_rank
    pos = jnp.arange(t)
    q = (x @ p["wq"]).reshape(t, h, dn + dr)
    q_nope, q_rope = q[..., :dn], rotate(q[..., dn:], pos, cfg.rope_theta)
    kv = x @ p["wkv_a"]
    c = rms(kv[:, :r], p["kv_norm"], cfg.norm_eps)
    k_rope = rotate(kv[:, r:], pos, cfg.rope_theta)
    k_nope = (c @ p["wk_b"]).reshape(t, h, dn)
    v = (c @ p["wv_b"]).reshape(t, h, m.v_head_dim)
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
         + jnp.einsum("thd,sd->hts", q_rope, k_rope)) / np.sqrt(dn + dr)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    return o.reshape(t, -1) @ p["wo"]


def swiglu(p, x):
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def experts(p, x, cfg, first=0):
    """The expert layer on tokens x (N, D): the experts ``p`` holds, ids
    ``first`` on, each computed on every token with its gate (0 where
    it was not chosen), plus the shared experts."""
    moe = cfg.moe
    p = _f32(p)
    with jax.default_matmul_precision("highest"):
        logits = x @ p["router"]
        s = (jax.nn.sigmoid(logits) if moe.scoring == "sigmoid"
             else jax.nn.softmax(logits, -1))
        b = p.get("router_bias", jnp.zeros_like(s[0]))
        _, chosen = jax.lax.top_k(s + b, moe.top_k)
        g = jnp.take_along_axis(s, chosen, -1)
        g = g / jnp.sum(g, -1, keepdims=True)
        gates = jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None],
                                     chosen].set(g * moe.routed_scale)
        y = swiglu(p["shared"], x) if "shared" in p else jnp.zeros_like(x)
        for e in range(p["we_gate"].shape[0]):
            y = y + gates[:, first + e, None] * swiglu(
                {"w_gate": p["we_gate"][e], "w_up": p["we_up"][e],
                 "w_down": p["we_down"][e]}, x)
    return y


def block(p, x, cfg):
    x = x + attention(p["mla"], rms(x, p["pre_norm"], cfg.norm_eps), cfg)
    hf = rms(x, p["ffn_norm"], cfg.norm_eps)
    if "moe" in p:
        return x + experts(p["moe"], hf, cfg,
                           cfg.moe.expert_share * p["moe"]["we_gate"].shape[0])
    return x + swiglu(p["mlp"], hf)


def blocks(params):
    """Each block's weights in order: the leading dense layers, each
    stacked group's layers, the tail."""
    out = list(params.get("head_blocks", []))
    if "blocks" in params:
        n = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
        for g in range(n):
            group = jax.tree_util.tree_map(lambda a: a[g], params["blocks"])
            out.extend(group[k] for k in sorted(group))
    return out + list(params.get("tail_blocks", []))


def logits(params, cfg, tokens):
    """Float32 next-token logits at every position of each row of
    ``tokens`` (R, T): (R, T, V)."""
    params = _f32(params)
    with jax.default_matmul_precision("highest"):
        rows = []
        for seq in np.asarray(tokens):
            x = params["embed"][seq]
            for p in blocks(params):
                x = block(p, x, cfg)
            x = rms(x, params["final_norm"], cfg.norm_eps)
            rows.append(x @ params["lm_head"])
    return jnp.stack(rows)
