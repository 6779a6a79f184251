"""Input ShapeDtypeStructs + shardings for every (arch × shape) dry-run cell.

``input_specs`` follows the shannon/kernels pattern: weak-type-correct,
shardable stand-ins; nothing is allocated.  Per-shape sharding strategy:

* train_4k / prefill_32k / decode_32k — batch over (pod, data), TP over
  model, params FSDP×TP (launch/sharding.py rules).
* long_500k (batch=1) — batch unshardable, so the KV cache / recurrent
  state shards its OWN parallel axis: cache length over ``data`` (sequence
  parallelism for decode), heads/state width over ``model``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.launch.sharding import make_param_shardings
from repro.models.lm import model as M

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

# microbatch counts chosen so per-device activations fit 16 GB (v5e)
TRAIN_MICROBATCHES = {
    "gemma3-27b": 8, "granite-34b": 16, "stablelm-3b": 4, "qwen3-32b": 16,
    "deepseek-v2-236b": 16, "moonlight-16b-a3b": 8,
    "recurrentgemma-2b": 4, "mamba2-1.3b": 4,
    "llama-3.2-vision-11b": 8, "musicgen-medium": 4,
}


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def token_shape(cfg: ArchConfig, batch: int, seq: int):
    if cfg.n_codebooks > 1:
        return (batch, seq, cfg.n_codebooks)
    return (batch, seq)


def batch_specs(cfg: ArchConfig, shape_name: str) -> dict:
    """ShapeDtypeStructs for the data batch of a cell."""
    info = SHAPES[shape_name]
    b, t = info["batch"], info["seq"]
    if info["kind"] == "train":
        out = {"tokens": sds(token_shape(cfg, b, t), jnp.int32),
               "targets": sds(token_shape(cfg, b, t), jnp.int32)}
        if cfg.cross_attn_every:
            out["image_embeds"] = sds((b, cfg.n_image_tokens, cfg.d_image),
                                      jnp.float32)
        return out
    if info["kind"] == "prefill":
        out = {"tokens": sds(token_shape(cfg, b, t), jnp.int32)}
        if cfg.cross_attn_every:
            out["image_embeds"] = sds((b, cfg.n_image_tokens, cfg.d_image),
                                      jnp.float32)
        return out
    # decode: one new token against a seq-long cache
    caches = jax.eval_shape(lambda: M.init_caches(cfg, b, t))
    return {"tokens": sds(token_shape(cfg, b, 1), jnp.int32),
            "pos": sds((), jnp.int32),
            "caches": caches}


def param_and_opt_specs(cfg: ArchConfig, with_opt: bool,
                        moments_bf16: bool = False) -> tuple[Any, Any]:
    params = jax.eval_shape(
        lambda: M.init_params(jax.random.key(0), cfg))
    if not with_opt:
        return params, None
    from repro.optim import init_opt_state
    mdt = "bfloat16" if moments_bf16 else "float32"
    opt = jax.eval_shape(lambda: init_opt_state(params, mdt))
    return params, opt


# ---------------------------------------------------------------------------
# Shardings
# ---------------------------------------------------------------------------

def _batch_axes(mesh: Mesh):
    names = mesh.axis_names
    ax = tuple(n for n in names if n in ("pod", "data"))
    return ax if len(ax) > 1 else ax[0]


def _n_batch_shards(mesh: Mesh) -> int:
    n = 1
    for name in ("pod", "data"):
        if name in mesh.axis_names:
            n *= mesh.shape[name]
    return n


def _cache_leaf_spec(name: str, shape, cfg: ArchConfig, mesh: Mesh):
    """Spec for a cache leaf.  Cache leaves under the scanned block stack
    carry a leading (n_groups,) dim — rules address the TRAILING dims and
    are left-padded with None."""
    bsh = _n_batch_shards(mesh)
    ba = _batch_axes(mesh)
    model_n = mesh.shape["model"]

    def model_if(dim: int):
        return "model" if dim % model_n == 0 else None

    rank = {"k": 4, "v": 4, "xk": 4, "xv": 4, "c": 3, "pe": 3,
            "ssm": 4, "rec": 2, "conv": 3}.get(name)
    if rank is None or len(shape) < rank:
        return P(*([None] * len(shape)))
    ts = shape[-rank:]                           # trailing (true) dims
    batch_ok = ts[0] % bsh == 0

    if name in ("k", "v", "xk", "xv"):           # (B, Hkv, L, hd)
        # TP the cache: heads over model when divisible; otherwise shard
        # the cache LENGTH over model — flash-decode sequence parallelism:
        # scores arrive L-sharded with only tiny stats/output all-reduces
        # (EXPERIMENTS §Perf cell 3).
        h_ax = model_if(ts[1])
        l_ax = model_if(ts[2]) if (h_ax is None and name in ("k", "v")) \
            else None
        if batch_ok:
            tail = P(ba, h_ax, l_ax, None)
        else:
            both = tuple(a for a in ("data", "model")
                         if a in mesh.axis_names)
            l_axes = both if (h_ax is None and
                              ts[2] % (mesh.shape["data"] * model_n) == 0) \
                else "data"
            tail = P(None, h_ax, l_axes, None)
    elif name in ("c", "pe"):                    # (B, L, r)
        # MLA latent cache: same sequence-parallel treatment
        tail = (P(ba, model_if(ts[1]), None) if batch_ok
                else P(None, "data", None))
    elif name == "ssm":                          # (B, H, P, N)
        tail = (P(ba, model_if(ts[1]), None, None) if batch_ok
                else P(None, model_if(ts[1]), None, None))
    elif name == "rec":                          # (B, W)
        tail = P(ba, model_if(ts[1])) if batch_ok else P(None, model_if(ts[1]))
    else:                                        # conv: (B, K-1, C)
        tail = (P(ba, None, None) if batch_ok
                else P(None, None, model_if(ts[2])))
    pad = [None] * (len(shape) - rank)
    from repro.launch.sharding import fit_spec
    return fit_spec(P(*(pad + list(tail))), shape, mesh)


def make_batch_shardings(batch_spec: dict, cfg: ArchConfig, mesh: Mesh):
    ba = _batch_axes(mesh)
    bsh = _n_batch_shards(mesh)

    def leaf(path, s):
        name = str(getattr(path[-1], "key", path[-1]))
        if "caches" in [str(getattr(k, "key", "")) for k in path]:
            return NamedSharding(mesh, _cache_leaf_spec(name, s.shape, cfg,
                                                        mesh))
        if name == "pos":
            return NamedSharding(mesh, P())
        # tokens / targets / image_embeds: batch-shard when divisible
        if s.shape[0] % bsh == 0:
            return NamedSharding(
                mesh, P(ba, *([None] * (len(s.shape) - 1))))
        return NamedSharding(mesh, P(*([None] * len(s.shape))))

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch_spec)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf(p, s) for p, s in flat])


def make_opt_shardings(mesh: Mesh, opt_spec: Any, param_shardings: Any):
    """m/v mirror the param shardings; step is replicated."""
    return {
        "m": param_shardings,
        "v": param_shardings,
        "step": NamedSharding(mesh, P()),
    }


def cell_shardings(cfg: ArchConfig, shape_name: str, mesh: Mesh,
                   moments_bf16: bool = False):
    """(in_shardings, specs) for the jit of a cell's step function."""
    info = SHAPES[shape_name]
    with_opt = info["kind"] == "train"
    params, opt = param_and_opt_specs(cfg, with_opt, moments_bf16)
    p_sh = make_param_shardings(mesh, params)
    batch = batch_specs(cfg, shape_name)
    b_sh = make_batch_shardings(batch, cfg, mesh)
    if with_opt:
        o_sh = make_opt_shardings(mesh, opt, p_sh)
        return (p_sh, o_sh, b_sh), (params, opt, batch)
    return (p_sh, b_sh), (params, batch)
