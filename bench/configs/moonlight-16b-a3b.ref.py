"""Plain reference for the Moonlight-16B-A3B decode cell, and the
comparison that decides ``correct``.

Written from the DeepSeek-V3 description and its published tensor names,
not from the program: a teacher-forced full forward pass of each
sequence, prompt and sampled tokens together, in float32 under
``jax.default_matmul_precision("highest")``, with no cache and no
batching of sequences (one row at a time, ``lax.map``).  Per layer i,
with g the RMSNorm gains as published and Linear weights (out, in):

    x += W_O attn(RMSNorm_g(x)),  the query W_Q x (``q_proj``, no low
         rank); ``kv_a_proj_with_mqa`` x split into the latent
         c = RMSNorm_g(.) (512) and one rotary key (64) shared by the 16
         heads; ``kv_b_proj`` c split per head into keys (128) and values
         (128); the 64 rotary dims de-interleaved (even, then odd) and
         rotated as halves; scores over sqrt(128 + 64), causal softmax;
    x += FFN(RMSNorm_g(x)),  a SwiGLU of width 11264 in layer 0, then the
         expert layer: s = sigmoid(x W_r) over all 64 experts, top-6 of
         s + b chosen (b the ``e_score_correction_bias``), gates
         s_i / sum s_j x 2.446, the held experts' part of sum g_i E_i(x)
         plus the shared SwiGLU of width 2 x 1408.

The held experts (``mlp.experts.<j>`` for j below ``n_routed_experts``,
of ``router_experts``) are computed densely for every token, a token not
routed to one getting gate 0; the rest are absent here as in the program.
The weights are the configuration's seeded data by published name
(``published`` in ``moonlight-16b-a3b.py``), drawn one layer at a time
and cast to float32, or in the control first rounded to ``CONTROL``.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
CONTROL = "float8_e4m3fn"


def _cast(tree, dtype):
    """Every floating leaf in float32, rounded first to ``dtype``."""
    def one(a):
        if not jnp.issubdtype(a.dtype, jnp.floating):
            return a
        return a.astype(dtype).astype(F32)
    return jax.tree_util.tree_map(one, tree)


def _rms(x, g, eps):
    return g * (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps))


def _rope(x, pos, theta):
    """x (T, ..., d): the published rotary embedding, the dims first
    de-interleaved, then ``x cos + rotate_half(x) sin`` at angles
    pos x theta^(-2i / d)."""
    d = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + half * jnp.sin(ang)


def _attention(w, a, x, cfg):
    """Causal latent attention of layer prefix ``a`` on one sequence x
    (T, D)."""
    t = x.shape[0]
    h, dn = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    dr, dv, r = cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    pos = jnp.arange(t)
    q = (x @ w[a + "q_proj.weight"].T).reshape(t, h, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, cfg["rope_theta"])
    kv = x @ w[a + "kv_a_proj_with_mqa.weight"].T
    c = _rms(kv[:, :r], w[a + "kv_a_layernorm.weight"], cfg["rms_norm_eps"])
    k_rope = _rope(kv[:, r:], pos, cfg["rope_theta"])
    kvb = (c @ w[a + "kv_b_proj.weight"].T).reshape(t, h, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
         + jnp.einsum("thd,sd->hts", q_rope, k_rope)) / np.sqrt(dn + dr)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shd->thd", p, v).reshape(t, h * dv)
    return o @ w[a + "o_proj.weight"].T


def _swiglu(w, a, x):
    return (jax.nn.silu(x @ w[a + "gate_proj.weight"].T)
            * (x @ w[a + "up_proj.weight"].T)) @ w[a + "down_proj.weight"].T


def _experts(w, a, x, cfg):
    """The expert layer of prefix ``a`` on one sequence x (T, D): the
    held experts and the shared experts."""
    t = x.shape[0]
    s = jax.nn.sigmoid(x @ w[a + "gate.weight"].T)
    _, chosen = jax.lax.top_k(s + w[a + "gate.e_score_correction_bias"],
                              cfg["num_experts_per_tok"])
    g = jnp.take_along_axis(s, chosen, -1)
    g = g / jnp.sum(g, -1, keepdims=True) * cfg["routed_scaling_factor"]
    gates = jnp.zeros_like(s).at[jnp.arange(t)[:, None], chosen].set(g)
    y = _swiglu(w, a + "shared_experts.", x)
    for j in range(cfg["n_routed_experts"]):
        y = y + gates[:, j, None] * _swiglu(w, a + f"experts.{j}.", x)
    return y


def _layer(w, dense, x, cfg):
    """One layer on one sequence x (T, D), ``w`` its tensors by name
    within the layer (``self_attn.q_proj.weight``, ...)."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(w, "self_attn.",
                       _rms(x, w["input_layernorm.weight"], eps), cfg)
    hf = _rms(x, w["post_attention_layernorm.weight"], eps)
    if dense:
        return x + _swiglu(w, "mlp.", hf)
    return x + _experts(w, "mlp.", hf, cfg)


_BLOCKS: dict = {}


def _block(cfg: dict, dense: bool, dtype):
    """One layer over every row, jitted once per configuration, layer
    kind and precision."""
    key = (json.dumps(cfg, sort_keys=True), dense, str(dtype))
    if key not in _BLOCKS:
        _BLOCKS[key] = jax.jit(lambda w, x: jax.lax.map(
            lambda row: _layer(_cast(w, dtype), dense, row, cfg), x))
    return _BLOCKS[key]


def token_log_probs(cfg: dict, weights, seqs, wanted, temperature: float,
                    dtype=F32):
    """Teacher-forced ``(log p, log q)`` of ``seqs[:, i + 1]`` at each
    position ``i`` of ``wanted``: the full forward pass of every row of
    ``seqs`` (R, T), p the model's next-token distribution and q the
    same at ``temperature``.  ``weights(i)`` gives layer i's published
    tensors, ``weights()`` the embedding, final norm and head.  Each
    (R, len(wanted)) float32."""
    seqs = jnp.asarray(seqs, jnp.int32)
    wanted = jnp.asarray(wanted, jnp.int32)
    eps = cfg["rms_norm_eps"]

    @jax.jit
    def head(w, x, seq):
        w = _cast(w, dtype)
        h = _rms(x[wanted], w["model.norm.weight"], eps)
        logits = h @ w["lm_head.weight"].T
        nxt = seq[wanted + 1][:, None]
        lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1), nxt, -1)
        lq = jnp.take_along_axis(
            jax.nn.log_softmax(logits / temperature, -1), nxt, -1)
        return lp[:, 0], lq[:, 0]

    with jax.default_matmul_precision("highest"):
        top = weights()
        x = _cast(top["model.embed_tokens.weight"][seqs], dtype)
        del top["model.embed_tokens.weight"]
        for i in range(cfg["num_hidden_layers"]):
            a = f"model.layers.{i}."
            w = {k[len(a):]: v for k, v in weights(i).items()}
            dense = i < cfg["first_k_dense_replace"]
            x = _block(cfg, dense, dtype)(w, x)
        out = [head(top, x[r], seqs[r]) for r in range(seqs.shape[0])]
    return (np.stack([np.asarray(o[0]) for o in out]),
            np.stack([np.asarray(o[1]) for o in out]))


def reference(cfg: dict, traffic: dict, weights, prompt, tokens, resampled,
              dtype=F32) -> dict:
    """The reference's numbers for one prompt's K rows: ``logp``, each
    row's summed log p of its ``tokens`` (K, S) after ``prompt`` (T0,),
    and ``log_weights``, the rows' normalised sums of log p - log q over
    the steps after the last one at which ``resampled`` (S,) is true (the
    SIR step reweights, then resamples and resets the weights to uniform;
    step 0 is the prefill's draw)."""
    tokens = np.asarray(tokens)
    k, steps = tokens.shape
    t0 = len(prompt)
    seqs = np.concatenate([np.broadcast_to(np.asarray(prompt), (k, t0)),
                           tokens], 1)[:, :t0 + steps]
    lp, lq = token_log_probs(cfg, weights, seqs, np.arange(t0 - 1,
                                                          t0 + steps - 1),
                             traffic["proposal_temperature"],
                             jnp.dtype(dtype))
    lp, lq = lp.astype(np.float64), lq.astype(np.float64)
    hits = np.flatnonzero(np.asarray(resampled))
    since = hits[-1] + 1 if hits.size else 0
    lw = np.sum(lp[:, since:] - lq[:, since:], axis=1)
    lw = lw - np.logaddexp.reduce(lw)
    return {"logp": lp.sum(axis=1), "log_weights": lw}


def compare(cfg: dict, traffic: dict, got: dict, ref: dict) -> dict:
    """The compared numbers: ``logp_gap``, the widest per-row gap of the
    summed log p over the decoded tokens, in nats a token; ``logw_gap``,
    the median over rows of the gap of the final log-weights (the widest
    row's is set by the few rows where rounding flipped a near-tied
    expert choice)."""
    steps = traffic["steps"]
    logp = np.abs(np.asarray(got["logp"], np.float64) - ref["logp"]) / steps
    logw = np.abs(np.asarray(got["log_weights"], np.float64)
                  - ref["log_weights"])
    return {"logp_gap": float(np.max(logp)),
            "logw_gap": float(np.median(logw))}
