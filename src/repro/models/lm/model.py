"""Composable decoder assembly covering the whole assigned pool.

Layer kinds:
  G — global causal attention            L — sliding-window attention
  M — multi-head latent attention        R — RG-LRU recurrent block
  X — cross-attention to image tokens    D — Mamba-2 SSD block

The repeating unit of ``cfg.layer_pattern`` is scanned with stacked
parameters, so HLO size is O(pattern) not O(depth) — this is what keeps
the 512-device dry-run compile times sane (DESIGN.md §6).  Leading
"exception" layers (MoE archs with dense first layers) and the pattern
remainder (gemma3's 62 = 10·6 + 2) are unrolled around the scan.

Three entry points: ``forward_train`` (hidden states — the loss is chunked
over vocab in ``repro.train``), ``forward_prefill`` (hiddens + caches),
``forward_decode`` (one token against caches).  Expert layers train
through the capacity dispatch (``moe.apply_moe`` / ``apply_moe_ep``) and
serve (prefill and decode) through the dropless ``moe.apply_expert_share``.

Named scopes put the latent attention (``ppf.lm.mla``), the expert layer
(``ppf.lm.moe``) and the unembedding (``ppf.lm.head``) into each
operation's ``op_name``, under the SIR step's own scopes when a decode
step runs inside one (``repro.models.lm.decode_ssm``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.launch.sharding import constrain
from repro.models.lm import layers as L
from repro.models.lm import mla as MLA
from repro.models.lm import moe as MOE
from repro.models.lm import rglru as RG
from repro.models.lm import ssm as SSM

Array = jax.Array


# ---------------------------------------------------------------------------
# Layer plan: head (unrolled) + scanned groups + tail (unrolled)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerPlan:
    head: tuple[tuple[str, str], ...]       # (kind, ffn) per unrolled layer
    unit: tuple[tuple[str, str], ...]       # repeating group
    n_groups: int
    tail: tuple[tuple[str, str], ...]


def make_plan(cfg: ArchConfig) -> LayerPlan:
    kinds = cfg.pattern_for(cfg.n_layers)
    first_dense = cfg.moe.first_dense_layers if cfg.moe else 0

    def ffn_of(i: int) -> str:
        if kinds[i] == "D":
            return "none"
        if cfg.moe and i >= first_dense:
            return "moe"
        return "dense"

    per_layer = tuple((kinds[i], ffn_of(i)) for i in range(cfg.n_layers))
    head = per_layer[:first_dense]
    rest = per_layer[first_dense:]
    unit_len = max(len(cfg.layer_pattern), 1)
    n_groups = len(rest) // unit_len
    tail = rest[n_groups * unit_len:]
    unit = rest[:unit_len] if n_groups else ()
    return LayerPlan(head=head, unit=unit, n_groups=n_groups, tail=tail)


# ---------------------------------------------------------------------------
# Per-layer params
# ---------------------------------------------------------------------------

def _layer_params(key: Array, cfg: ArchConfig, kind: str, ffn: str,
                  dtype) -> dict:
    hd = cfg.resolved_head_dim
    k_mix, k_ffn = jax.random.split(key)
    p: dict[str, Any] = {"pre_norm": jnp.zeros((cfg.d_model,), dtype)}
    if kind in ("G", "L"):
        p["attn"] = L.attn_params(k_mix, cfg.d_model, cfg.n_heads,
                                  cfg.n_kv_heads, hd, cfg.qk_norm, dtype)
    elif kind == "M":
        p["mla"] = MLA.mla_params(k_mix, cfg.d_model, cfg.n_heads,
                                  cfg.mla, dtype)
    elif kind == "X":
        p["xattn"] = L.attn_params(k_mix, cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, hd, cfg.qk_norm, dtype)
        p["xattn_gate"] = jnp.zeros((), dtype)
    elif kind == "R":
        p["rglru"] = RG.rglru_params(k_mix, cfg.d_model, cfg.rglru, dtype)
    elif kind == "D":
        p["ssm"] = SSM.ssm_params(k_mix, cfg.d_model, cfg.ssm, dtype)
    else:
        raise ValueError(kind)
    if ffn == "dense":
        p["ffn_norm"] = jnp.zeros((cfg.d_model,), dtype)
        p["mlp"] = L.mlp_params(k_ffn, cfg.d_model, cfg.d_ff, dtype)
    elif ffn == "moe":
        p["ffn_norm"] = jnp.zeros((cfg.d_model,), dtype)
        p["moe"] = MOE.moe_params(k_ffn, cfg.d_model, cfg.moe, dtype)
    return p


def _group_params(key: Array, cfg: ArchConfig,
                  unit: tuple[tuple[str, str], ...], dtype) -> dict:
    keys = jax.random.split(key, len(unit))
    return {f"l{i}_{kind}_{ffn}": _layer_params(keys[i], cfg, kind, ffn, dtype)
            for i, (kind, ffn) in enumerate(unit)}


def init_params(key: Array, cfg: ArchConfig) -> dict:
    dtype = L.dtype_of(cfg.param_dtype)
    plan = make_plan(cfg)
    k_emb, k_head, k_groups, k_tail, k_lm, k_img = jax.random.split(key, 6)
    d = cfg.d_model
    params: dict[str, Any] = {}
    if cfg.n_codebooks > 1:
        params["embed"] = jax.random.normal(
            k_emb, (cfg.n_codebooks, cfg.vocab_size, d), dtype) * d ** -0.5
    else:
        params["embed"] = jax.random.normal(
            k_emb, (cfg.vocab_size, d), dtype) * d ** -0.5
    if plan.head:
        hk = jax.random.split(k_head, len(plan.head))
        params["head_blocks"] = [
            _layer_params(hk[i], cfg, kind, ffn, dtype)
            for i, (kind, ffn) in enumerate(plan.head)]
    if plan.n_groups:
        gk = jax.random.split(k_groups, plan.n_groups)
        params["blocks"] = jax.vmap(
            lambda kk: _group_params(kk, cfg, plan.unit, dtype))(gk)
    if plan.tail:
        tk = jax.random.split(k_tail, len(plan.tail))
        params["tail_blocks"] = [
            _layer_params(tk[i], cfg, kind, ffn, dtype)
            for i, (kind, ffn) in enumerate(plan.tail)]
    params["final_norm"] = jnp.zeros((d,), dtype)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks > 1:
            params["lm_head"] = jax.random.normal(
                k_lm, (cfg.n_codebooks, d, cfg.vocab_size), dtype) * d ** -0.5
        else:
            params["lm_head"] = jax.random.normal(
                k_lm, (d, cfg.vocab_size), dtype) * d ** -0.5
    if cfg.cross_attn_every:
        params["img_proj"] = jax.random.normal(
            k_img, (cfg.d_image, d), dtype) * cfg.d_image ** -0.5
    return params


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def _layer_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                 dtype) -> dict:
    hd = cfg.resolved_head_dim
    if kind in ("G", "L"):
        shape = (batch, cfg.n_kv_heads, max_len, hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if kind == "M":
        return {"c": jnp.zeros((batch, max_len, cfg.mla.kv_lora_rank), dtype),
                "pe": jnp.zeros((batch, max_len, cfg.mla.qk_rope_dim), dtype)}
    if kind == "X":
        shape = (batch, cfg.n_kv_heads, cfg.n_image_tokens, hd)
        return {"xk": jnp.zeros(shape, dtype), "xv": jnp.zeros(shape, dtype)}
    if kind == "R":
        w = cfg.rglru.lru_width
        return {"rec": jnp.zeros((batch, w), jnp.float32),
                "conv": jnp.zeros((batch, cfg.rglru.conv_width - 1, w), dtype)}
    if kind == "D":
        s = cfg.ssm
        d_inner = s.expand * cfg.d_model
        h = d_inner // s.head_dim
        c_ch = d_inner + 2 * s.n_groups * s.state_dim
        return {"ssm": jnp.zeros((batch, h, s.head_dim, s.state_dim), dtype),
                "conv": jnp.zeros((batch, s.conv_width - 1, c_ch), dtype)}
    raise ValueError(kind)


def init_caches(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    dtype = L.dtype_of(cfg.compute_dtype)
    plan = make_plan(cfg)
    caches: dict[str, Any] = {}
    if plan.head:
        caches["head_blocks"] = [
            _layer_cache(cfg, kind, batch, max_len, dtype)
            for kind, _ in plan.head]
    if plan.n_groups:
        def one_group(_):
            return {f"l{i}_{kind}_{ffn}":
                    _layer_cache(cfg, kind, batch, max_len, dtype)
                    for i, (kind, ffn) in enumerate(plan.unit)}
        caches["blocks"] = jax.vmap(one_group)(jnp.arange(plan.n_groups))
    if plan.tail:
        caches["tail_blocks"] = [
            _layer_cache(cfg, kind, batch, max_len, dtype)
            for kind, _ in plan.tail]
    return caches


# ---------------------------------------------------------------------------
# Block forward
# ---------------------------------------------------------------------------

def _theta_window(cfg: ArchConfig, kind: str):
    if kind == "L":
        window = cfg.sliding_window or (cfg.rglru.attn_window if cfg.rglru
                                        else 0)
        return cfg.rope_theta, window
    theta = cfg.rope_theta_global or cfg.rope_theta
    return theta, 0


def _block_forward(bp: dict, x: Array, kind: str, ffn: str, cfg: ArchConfig,
                   mode: str, cache: Optional[dict], positions: Array,
                   pos, img: Optional[Array], aux: dict):
    """One decoder block.  Returns (x, new_cache)."""
    eps = cfg.norm_eps
    hd = cfg.resolved_head_dim
    h = L.rms_norm(x, bp["pre_norm"], eps)
    new_cache = cache

    if kind in ("G", "L"):
        theta, window = _theta_window(cfg, kind)
        q, k, v = L.apply_qkv(bp["attn"], h, cfg.n_heads, cfg.n_kv_heads, hd,
                              positions, theta, cfg.qk_norm, eps)
        q = constrain(q, "act_heads")
        if mode == "decode":
            k_c = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, pos, 2)
            v_c = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, pos, 2)
            o = L.decode_attention(q, k_c, v_c, pos, window=window,
                                   softcap=cfg.logit_softcap)
            new_cache = {"k": k_c, "v": v_c}
        else:
            o = L.chunked_causal_attention(
                q, k, v, window=window, chunk=cfg.attn_chunk,
                softcap=cfg.logit_softcap, unroll=cfg.scan_unroll,
                scores_dtype=L.dtype_of(cfg.attn_scores_dtype))
            if mode == "prefill":
                new_cache = {
                    "k": _pad_cache(k, cache["k"]),
                    "v": _pad_cache(v, cache["v"]),
                }
        b, t = x.shape[:2]
        o = jnp.moveaxis(o, 1, 2).reshape(b, t, cfg.n_heads * hd)
        x = x + o @ bp["attn"]["wo"]

    elif kind == "M":
        o, new_cache = _mla_block(bp["mla"], h, cfg, mode, cache, positions,
                                  pos)
        x = x + o

    elif kind == "X":
        b, t = x.shape[:2]
        q = (h @ bp["xattn"]["wq"]).reshape(b, t, cfg.n_heads, hd)
        q = jnp.moveaxis(q, 1, 2)
        if mode == "decode":
            xk, xv = cache["xk"], cache["xv"]
        else:
            n_img = img.shape[1]
            xk = jnp.moveaxis((img @ bp["xattn"]["wk"]).reshape(
                b, n_img, cfg.n_kv_heads, hd), 1, 2)
            xv = jnp.moveaxis((img @ bp["xattn"]["wv"]).reshape(
                b, n_img, cfg.n_kv_heads, hd), 1, 2)
        o = L.chunked_causal_attention(q, xk, xv, chunk=cfg.attn_chunk,
                                       causal=False, unroll=cfg.scan_unroll)
        o = jnp.moveaxis(o, 1, 2).reshape(b, t, cfg.n_heads * hd)
        gate = jnp.tanh(bp["xattn_gate"].astype(jnp.float32)).astype(x.dtype)
        x = x + gate * (o @ bp["xattn"]["wo"])
        if mode == "prefill":
            new_cache = {"xk": xk, "xv": xv}

    elif kind == "R":
        if mode == "decode":
            o, rec, conv = RG.rglru_decode_step(
                bp["rglru"], h, cfg.rglru,
                rec_state=cache["rec"], conv_state=cache["conv"])
            new_cache = {"rec": rec, "conv": conv.astype(cache["conv"].dtype)}
        elif mode == "prefill":
            o, rec, conv = RG.rglru_forward(bp["rglru"], h, cfg.rglru,
                                            return_state=True)
            new_cache = {"rec": rec, "conv": conv.astype(cache["conv"].dtype)}
        else:
            o = RG.rglru_forward(bp["rglru"], h, cfg.rglru)
        x = x + o.astype(x.dtype)

    elif kind == "D":
        if mode == "decode":
            o, ssm_s, conv_s = SSM.ssd_decode_step(
                bp["ssm"], h, cfg.ssm, cfg.d_model, eps,
                ssm_state=cache["ssm"], conv_state=cache["conv"])
            new_cache = {"ssm": ssm_s.astype(cache["ssm"].dtype),
                         "conv": conv_s.astype(cache["conv"].dtype)}
        elif mode == "prefill":
            o, ssm_s, conv_s = SSM.ssd_forward(bp["ssm"], h, cfg.ssm,
                                               cfg.d_model, eps,
                                               return_state=True)
            new_cache = {"ssm": ssm_s.astype(cache["ssm"].dtype),
                         "conv": conv_s.astype(cache["conv"].dtype)}
        else:
            o = SSM.ssd_forward(bp["ssm"], h, cfg.ssm, cfg.d_model, eps,
                                unroll=cfg.scan_unroll)
        x = x + o.astype(x.dtype)

    else:
        raise ValueError(kind)

    if ffn == "dense":
        hf = L.rms_norm(x, bp["ffn_norm"], eps)
        x = x + L.apply_mlp(bp["mlp"], hf)
    elif ffn == "moe":
        hf = L.rms_norm(x, bp["ffn_norm"], eps)
        with jax.named_scope("ppf.lm.moe"):
            if mode == "train":
                moe_fn = (MOE.apply_moe_ep
                          if cfg.moe.dispatch == "ep_shardmap"
                          else MOE.apply_moe)
                o, moe_aux = moe_fn(bp["moe"], hf, cfg.moe)
                for k_, v_ in moe_aux.items():
                    aux[k_] = (aux[k_] + v_) if k_ in aux else v_
            else:
                o = MOE.apply_expert_share(bp["moe"], hf, cfg.moe)
        x = x + o

    x = constrain(x, "act_sp" if (cfg.seq_parallel and mode != "decode")
                  else "act")
    return x, new_cache


def _mla_block(p: dict, h: Array, cfg: ArchConfig, mode: str,
               cache: Optional[dict], positions: Array, pos):
    """Latent attention of one block under ``ppf.lm.mla``: its output and
    the new cache (``cache`` itself in training)."""
    eps = cfg.norm_eps
    new_cache = cache
    with jax.named_scope("ppf.lm.mla"):
        if mode == "decode":
            c_new, pe_new = MLA.mla_compress(
                p, h, jnp.full((1,), pos, jnp.int32),
                cfg.rope_theta, eps)
            c_c = jax.lax.dynamic_update_slice_in_dim(cache["c"], c_new,
                                                      pos, 1)
            pe_c = jax.lax.dynamic_update_slice_in_dim(cache["pe"], pe_new,
                                                       pos, 1)
            o = MLA.mla_decode_absorbed(p, h, cfg.n_heads, cfg.mla,
                                        c_cache=c_c, pe_cache=pe_c, pos=pos,
                                        theta=cfg.rope_theta, eps=eps)
            new_cache = {"c": c_c, "pe": pe_c}
        else:
            o = MLA.mla_attention(p, h, cfg.n_heads, cfg.mla,
                                  positions=positions, theta=cfg.rope_theta,
                                  eps=eps, chunk=cfg.attn_chunk,
                                  unroll=cfg.scan_unroll,
                                  scores_dtype=L.dtype_of(
                                      cfg.attn_scores_dtype))
            if mode == "prefill":
                c_new, pe_new = MLA.mla_compress(p, h, positions,
                                                 cfg.rope_theta, eps)
                new_cache = {"c": _pad_cache(c_new, cache["c"], axis=1),
                             "pe": _pad_cache(pe_new, cache["pe"], axis=1)}
    return o, new_cache


def _pad_cache(fresh: Array, template: Array, axis: int = 2) -> Array:
    """Place freshly computed K/V (length T) into a max_len cache buffer."""
    if fresh.shape[axis] == template.shape[axis]:
        return fresh.astype(template.dtype)
    return jax.lax.dynamic_update_slice_in_dim(
        template, fresh.astype(template.dtype), 0, axis)


# ---------------------------------------------------------------------------
# Full forward passes
# ---------------------------------------------------------------------------

def _embed(params: dict, cfg: ArchConfig, tokens: Array) -> Array:
    if cfg.n_codebooks > 1:
        # tokens: (B, T, K) — sum codebook embeddings (musicgen)
        parts = [params["embed"][k][tokens[..., k]]
                 for k in range(cfg.n_codebooks)]
        x = sum(parts)
    else:
        x = params["embed"][tokens]
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x.astype(L.dtype_of(cfg.compute_dtype))


def unembed(params: dict, cfg: ArchConfig, x: Array) -> Array:
    """Hidden states → logits.  (B, T, D) → (B, T, V[, K])."""
    if cfg.n_codebooks > 1:
        head = (params["lm_head"] if not cfg.tie_embeddings
                else jnp.moveaxis(params["embed"], -1, -2))
        logits = jnp.einsum("btd,kdv->btkv", x, head.astype(x.dtype))
    else:
        head = (params["lm_head"] if not cfg.tie_embeddings
                else params["embed"].T)
        logits = x @ head.astype(x.dtype)
    return constrain(logits, "logits")


def _run_blocks(params: dict, cfg: ArchConfig, x: Array, mode: str,
                caches: Optional[dict], positions: Array, pos,
                img: Optional[Array]):
    plan = make_plan(cfg)
    aux: dict[str, Any] = {}
    new_caches: dict[str, Any] = {}

    def run_unrolled(x, blocks, cache_list, specs):
        outs = []
        for i, (kind, ffn) in enumerate(specs):
            c = cache_list[i] if cache_list is not None else None
            x, nc = _block_forward(blocks[i], x, kind, ffn, cfg, mode, c,
                                   positions, pos, img, aux)
            outs.append(nc)
        return x, outs

    if plan.head:
        x, nc = run_unrolled(x, params["head_blocks"],
                             caches.get("head_blocks") if caches else None,
                             plan.head)
        new_caches["head_blocks"] = nc

    if plan.n_groups:
        cache_stack = caches.get("blocks") if caches else None
        acc0: dict[str, Any] = {}
        if cfg.moe and mode == "train":
            acc0 = {"moe_aux_loss": jnp.zeros((), jnp.float32),
                    "moe_drop_frac": jnp.zeros((), jnp.float32),
                    "moe_max_load": jnp.zeros((), jnp.int32)}

        def run_group(x, acc, gp, gc):
            aux_step: dict[str, Any] = {}
            gnew = {}
            for i, (kind, ffn) in enumerate(plan.unit):
                name = f"l{i}_{kind}_{ffn}"
                c = gc[name] if gc is not None else None
                x, nc = _block_forward(gp[name], x, kind, ffn, cfg, mode, c,
                                       positions, pos, img, aux_step)
                gnew[name] = nc
            if acc:
                acc = {k_: acc[k_] + aux_step[k_] for k_ in acc}
            return x, acc, gnew

        if cache_stack is None:
            def body(carry, gp):
                x, acc = carry
                x, acc, _ = run_group(x, acc, gp, None)
                return (x, acc), None

            if cfg.remat and mode == "train":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            (x, acc), _ = jax.lax.scan(body, (x, acc0), params["blocks"],
                                       unroll=cfg.scan_unroll)
        else:
            # caches ride the CARRY with in-place slice updates: XLA
            # aliases loop-carried buffers, so serve steps never pay the
            # xs→ys stacked-copy of the whole cache (EXPERIMENTS §Perf)
            def body(carry, scanned):
                x, acc, stack = carry
                gp, gi = scanned
                gc = jax.tree_util.tree_map(
                    lambda st: jax.lax.dynamic_index_in_dim(
                        st, gi, 0, keepdims=False), stack)
                x, acc, gnew = run_group(x, acc, gp, gc)
                stack = jax.tree_util.tree_map(
                    lambda st, n: jax.lax.dynamic_update_index_in_dim(
                        st, n.astype(st.dtype), gi, 0), stack, gnew)
                return (x, acc, stack), None

            (x, acc, new_stack), _ = jax.lax.scan(
                body, (x, acc0, cache_stack),
                (params["blocks"], jnp.arange(plan.n_groups)),
                unroll=cfg.scan_unroll)
            new_caches["blocks"] = new_stack
        for k_, v_ in acc.items():
            aux[k_] = (aux[k_] + v_) if k_ in aux else v_

    if plan.tail:
        x, nc = run_unrolled(x, params["tail_blocks"],
                             caches.get("tail_blocks") if caches else None,
                             plan.tail)
        new_caches["tail_blocks"] = nc

    return x, new_caches, aux


def forward_train(params: dict, cfg: ArchConfig, tokens: Array,
                  img: Optional[Array] = None):
    """tokens (B, T[, K]) → hidden states (B, T, D), aux."""
    params = cast_params(params, cfg)
    x = _embed(params, cfg, tokens)
    x = constrain(x, "act")
    t = tokens.shape[1]
    positions = jnp.arange(t)
    if img is not None:
        img = (img.astype(x.dtype) @ params["img_proj"].astype(x.dtype))
    x, _, aux = _run_blocks(params, cfg, x, "train", None, positions, None,
                            img)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def forward_prefill(params: dict, cfg: ArchConfig, tokens: Array,
                    max_len: int, img: Optional[Array] = None):
    params = cast_params(params, cfg)
    b, t = tokens.shape[:2]
    caches = init_caches(cfg, b, max_len)
    x = _embed(params, cfg, tokens)
    positions = jnp.arange(t)
    if img is not None:
        img = (img.astype(x.dtype) @ params["img_proj"].astype(x.dtype))
    x, new_caches, aux = _run_blocks(params, cfg, x, "prefill", caches,
                                     positions, None, img)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x[:, -1:], new_caches, aux


def forward_decode(params: dict, cfg: ArchConfig, tokens: Array, pos,
                   caches: dict):
    """tokens (B, 1[, K]) + caches → (logits (B,1,V[,K]), caches)."""
    params = cast_params(params, cfg)
    x = _embed(params, cfg, tokens)
    positions = jnp.full((1,), pos, jnp.int32)
    x, new_caches, _ = _run_blocks(params, cfg, x, "decode", caches,
                                   positions, pos, None)
    with jax.named_scope("ppf.lm.head"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(params, cfg, x)
    return logits, new_caches


def cast_params(params: dict, cfg: ArchConfig) -> dict:
    """Cast float params to compute dtype (bf16 matmuls, f32 master copy);
    the experts' selection bias keeps its own dtype, since the router
    chooses in float32 (``moe.route``)."""
    ct = L.dtype_of(cfg.compute_dtype)

    def cast(path, x):
        if getattr(path[-1], "key", None) == "router_bias":
            return x
        if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(ct)
        return x

    return jax.tree_util.tree_map_with_path(cast, params)
