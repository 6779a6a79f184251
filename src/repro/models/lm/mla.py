"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV is compressed to a ``kv_lora_rank`` latent (512) plus a shared
``qk_rope_dim`` (64) rotary key — the cache is 576 floats/token regardless
of head count (vs 2·H·hd = 32768 for vanilla MHA at H=128, hd=128).

Two apply paths:

* ``mla_attention`` (train/prefill): decompress K/V per head and run
  chunked attention — decompression is einsum-fused by XLA.
* ``mla_decode_absorbed``: the W^UK/W^UV *absorption* trick — score and
  value computations run directly in the 512-dim latent space, so decode
  never materializes per-head K/V.  This is the TPU-native formulation of
  MLA serving (bandwidth-bound by the 576-wide cache stream).

The layer, per token x (D) at position t, H heads (DeepSeek-V2/V3):

    q_h        = W^Q_h x                      (no query low rank, V3's
                 or W^QB_h RMSNorm(W^QA x)     ``q_lora_rank`` null)
    [q^C_h ; q^R_h] = q_h                      (nope 128, rope 64)
    c_t        = RMSNorm(W^DKV x)              (latent, kv_lora_rank 512)
    k^R_t      = RoPE(W^KR x, t)               (one 64-wide key, all heads)
    k^C_{t,h}  = W^UK_h c_t,   v_{t,h} = W^UV_h c_t
    a_h(s)     = softmax_s((q^C_h · k^C_{s,h} + RoPE(q^R_h, t) · k^R_s)
                           / sqrt(nope + rope)),  s <= t
    out        = W^O [Σ_s a_h(s) v_{s,h}]_h

The cache holds c_t and k^R_t: 576 numbers a token, whatever H is.
Departures from the published checkpoints, each a fixed relabelling of
random weights: the 64 rotary dims are rotated as two halves
(``rotate_half``) in their stored order, where the Hugging Face DeepSeek
code first de-interleaves them (a permutation of W^Q's and W^KR's
rotary columns); RMSNorm gains are stored as ``1 + w`` (``rms_norm``);
W^DKV and W^KR are one matrix ``wkv_a``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MLAConfig
from repro.launch.sharding import constrain
from repro.models.lm.layers import chunked_causal_attention, rms_norm, rope

Array = jax.Array


def mla_params(key: Array, d_model: int, n_heads: int, cfg: MLAConfig,
               dtype) -> dict:
    ks = jax.random.split(key, 8)
    s = d_model ** -0.5
    r = cfg.kv_lora_rank
    qr = cfg.q_lora_rank
    dqk = cfg.qk_nope_dim
    drope = cfg.qk_rope_dim
    dv = cfg.v_head_dim
    if qr:
        # query low-rank path: D → qr → H·(dqk + drope)
        query = {
            "wq_a": jax.random.normal(ks[0], (d_model, qr), dtype) * s,
            "q_norm": jnp.zeros((qr,), dtype),
            "wq_b": jax.random.normal(ks[1], (qr, n_heads * (dqk + drope)),
                                      dtype) * qr ** -0.5,
        }
    else:
        # direct query projection: D → H·(dqk + drope)
        query = {"wq": jax.random.normal(
            ks[0], (d_model, n_heads * (dqk + drope)), dtype) * s}
    return {
        **query,
        # kv low-rank: D → (r latent + drope shared rotary key)
        "wkv_a": jax.random.normal(ks[2], (d_model, r + drope), dtype) * s,
        "kv_norm": jnp.zeros((r,), dtype),
        # decompression: latent → per-head nope-key / value
        "wk_b": jax.random.normal(ks[3], (r, n_heads * dqk), dtype) * r ** -0.5,
        "wv_b": jax.random.normal(ks[4], (r, n_heads * dv), dtype) * r ** -0.5,
        "wo": jax.random.normal(ks[5], (n_heads * dv, d_model), dtype)
              * (n_heads * dv) ** -0.5,
    }


def mla_compress(p: dict, x: Array, positions: Array, theta: float,
                 eps: float) -> tuple[Array, Array]:
    """x: (B,T,D) → (c_kv: (B,T,r) normalized latent, k_rope: (B,T,drope))."""
    r = p["kv_norm"].shape[0]
    kv = x @ p["wkv_a"]
    c_kv = rms_norm(kv[..., :r], p["kv_norm"], eps)
    k_pe = kv[..., r:]
    k_pe = rope(k_pe[:, :, None, :], positions, theta)[:, :, 0, :]
    return c_kv, k_pe


def _queries(p: dict, x: Array, n_heads: int, cfg: MLAConfig,
             positions: Array, theta: float, eps: float):
    b, t, _ = x.shape
    dqk, drope = cfg.qk_nope_dim, cfg.qk_rope_dim
    if "wq" in p:
        q = x @ p["wq"]
    else:
        q = rms_norm(x @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]
    q = q.reshape(b, t, n_heads, dqk + drope)
    q_nope, q_pe = q[..., :dqk], q[..., dqk:]
    q_pe = rope(q_pe, positions, theta)
    return q_nope, q_pe


def mla_attention(p: dict, x: Array, n_heads: int, cfg: MLAConfig, *,
                  positions: Array, theta: float, eps: float,
                  chunk: int = 512, unroll: bool = False,
                  scores_dtype=jnp.float32) -> Array:
    """Training / prefill path: decompress and run chunked attention."""
    b, t, _ = x.shape
    dqk, drope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_pe = _queries(p, x, n_heads, cfg, positions, theta, eps)
    c_kv, k_pe = mla_compress(p, x, positions, theta, eps)

    k_nope = (c_kv @ p["wk_b"]).reshape(b, t, n_heads, dqk)
    v = (c_kv @ p["wv_b"]).reshape(b, t, n_heads, dv)
    # concatenate nope+rope so one attention call handles both terms
    q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (b, t, n_heads, drope))],
        axis=-1)
    scale = (dqk + drope) ** -0.5
    # TP the decompressed heads — otherwise every device materializes all
    # 128 heads' scores (the dominant memory-roofline term, EXPERIMENTS §Perf)
    qh = constrain(jnp.moveaxis(q_full, 1, 2), "act_heads")
    kh = constrain(jnp.moveaxis(k_full, 1, 2), "act_heads")
    vh = constrain(jnp.moveaxis(v, 1, 2), "act_heads")
    o = chunked_causal_attention(qh, kh, vh, chunk=chunk, scale=scale,
                                 unroll=unroll, scores_dtype=scores_dtype)
    o = jnp.moveaxis(o, 1, 2).reshape(b, t, n_heads * dv)
    return o @ p["wo"]


def mla_decode_absorbed(p: dict, x: Array, n_heads: int, cfg: MLAConfig, *,
                        c_cache: Array, pe_cache: Array, pos, theta: float,
                        eps: float) -> Array:
    """Absorbed decode: x (B,1,D); caches (B,L,r) / (B,L,drope).

    score_h(t) = q_nope_h · (W^UK_h c_t)  +  q_pe_h · k_pe_t
               = (W^UK_hᵀ q_nope_h) · c_t +  q_pe_h · k_pe_t
    out_h      = W^UV_h Σ_t a_t c_t
    """
    b, _, _ = x.shape
    r = c_cache.shape[-1]
    dqk, drope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    l = c_cache.shape[1]
    positions = jnp.full((1,), pos, jnp.int32)
    q_nope, q_pe = _queries(p, x, n_heads, cfg, positions, theta, eps)
    # absorb W^UK into the query: (B,1,H,dqk) → (B,H,r)
    wk = p["wk_b"].reshape(r, n_heads, dqk)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wk)
    s = (jnp.einsum("bhr,blr->bhl", q_lat, c_cache)
         + jnp.einsum("bhd,bld->bhl", q_pe[:, 0], pe_cache)
         ).astype(jnp.float32) * (dqk + drope) ** -0.5
    mask = jnp.arange(l) <= pos
    s = jnp.where(mask[None, None, :], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1).astype(c_cache.dtype)
    o_lat = jnp.einsum("bhl,blr->bhr", a, c_cache)
    wv = p["wv_b"].reshape(r, n_heads, dv)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, wv).reshape(b, 1, n_heads * dv)
    return o @ p["wo"]
