"""The Moonlight-16B-A3B configuration: one chip's share of the model
built through the program's own constructors, the seeded weights and
prompts it is fed, and the work one SMC decode step needs.

The configuration file holds the published ``config.json`` with the two
cuts listed under ``reduced``: nine of the 27 layers (stage 1 of a
three-stage pipeline) and 16 of each layer's 64 routed experts
(``n_routed_experts``, the experts held here; the router keeps its
``router_experts`` outputs).  Weights are data, like the tracking movies:
``published`` draws each tensor of the seed under its published name and
shape (``model.layers.<i>.self_attn.q_proj.weight``, Linear weights as
(out, in)), the reference reads those, and ``make_weights`` loads the
same tensors into the program's parameter tree by name.
"""
from __future__ import annotations

import dataclasses
import json
import math

import jax
import jax.numpy as jnp

BF16 = 2               # bytes
F32 = 4
I32 = 4


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for this configuration's share.

    The layer loop is unrolled (``scan_unroll``): under the decode step's
    ``vmap`` over prompts the stacked caches lead with the prompt axis,
    and a scanned layer loop that indexes their layer axis makes the
    compiler lay the whole stack out again, one 2.3 GB copy each way per
    step at 16 prompts; unrolled, each layer's cache is a static slice
    (v5e compile, PERF.md section 4)."""
    from repro.configs import get_config
    from repro.configs.base import MLAConfig, MoEConfig
    base = get_config("moonlight-16b-a3b")
    if cfg["q_lora_rank"] is not None or not cfg["norm_topk_prob"]:
        raise ValueError("the program runs a direct query projection and "
                         "normalised top-k gates")
    shares = cfg["router_experts"] // cfg["n_routed_experts"]
    return dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], param_dtype=cfg["dtype"],
        compute_dtype=cfg["dtype"], scan_unroll=True,
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=0,
                      qk_nope_dim=cfg["qk_nope_head_dim"],
                      qk_rope_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        moe=MoEConfig(n_experts=cfg["router_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      n_shared_experts=cfg["n_shared_experts"],
                      first_dense_layers=cfg["first_k_dense_replace"],
                      scoring=cfg["scoring_func"],
                      selection_bias=cfg["topk_method"] == "noaux_tc",
                      routed_scale=cfg["routed_scaling_factor"],
                      expert_shares=shares, expert_share=0))


# ---------------------------------------------------------------------------
# Weights: drawn by published name and shape, then loaded by name
# ---------------------------------------------------------------------------

def _layer_spec(cfg: dict, i: int) -> list:
    """``(names, shape, kind)`` of layer ``i``'s published tensors, each
    entry one draw of ``len(names)`` tensors of ``shape``: kind ``w`` (a
    Linear weight, sd one over the root of its fan-in), ``gain`` (an
    RMSNorm gain, on [0.5, 1.5]) or ``bias`` (the selection bias, sd
    ``BIAS_SD``), each uniform."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    a = f"model.layers.{i}."
    spec = [((a + "input_layernorm.weight",), (d,), "gain"),
            ((a + "self_attn.q_proj.weight",), (h * (dn + dr), d), "w"),
            ((a + "self_attn.kv_a_proj_with_mqa.weight",), (r + dr, d), "w"),
            ((a + "self_attn.kv_a_layernorm.weight",), (r,), "gain"),
            ((a + "self_attn.kv_b_proj.weight",), (h * (dn + dv), r), "w"),
            ((a + "self_attn.o_proj.weight",), (d, h * dv), "w"),
            ((a + "post_attention_layernorm.weight",), (d,), "gain")]

    def swiglu(prefixes, f):
        return [(tuple(x + "gate_proj.weight" for x in prefixes), (f, d), "w"),
                (tuple(x + "up_proj.weight" for x in prefixes), (f, d), "w"),
                (tuple(x + "down_proj.weight" for x in prefixes), (d, f),
                 "w")]
    if i < cfg["first_k_dense_replace"]:
        return spec + swiglu([a + "mlp."], cfg["intermediate_size"])
    f, e = cfg["moe_intermediate_size"], cfg["router_experts"]
    spec += [((a + "mlp.gate.weight",), (e, d), "w"),
             ((a + "mlp.gate.e_score_correction_bias",), (e,), "bias")]
    spec += swiglu([a + f"mlp.experts.{j}."
                    for j in range(cfg["n_routed_experts"])], f)
    return spec + swiglu([a + "mlp.shared_experts."],
                         cfg["n_shared_experts"] * f)


def _top_spec(cfg: dict) -> list:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return [(("model.embed_tokens.weight",), (v, d), "w"),
            (("model.norm.weight",), (d,), "gain"),
            (("lm_head.weight",), (v, d), "w")]


def _draw(spec: list, key, dtype) -> dict:
    """Every entry of ``spec`` cut in order from one uniform draw on
    [-1, 1) and given its spread, held in ``dtype``; the selection bias
    stays float32, as published.  One draw compiles in seconds, where a
    draw per entry took minutes for a v5e (``published``)."""
    sizes = [len(names) * math.prod(shape) for names, shape, _ in spec]
    flat = jax.random.uniform(key, (sum(sizes),), jnp.float32, -1.0, 1.0)
    out, at = {}, 0
    for (names, shape, kind), size in zip(spec, sizes):
        a = flat[at:at + size].reshape((len(names),) + shape)
        at += size
        if kind == "gain":
            a = (1.0 + 0.5 * a).astype(dtype)
        elif kind == "bias":
            a = a * (BIAS_SD * 3 ** 0.5)
        else:
            a = (a * (3.0 / shape[1]) ** 0.5).astype(dtype)
        out.update(zip(names, a))
    return out


# the selection bias's spread, an assumption (``assumed`` in the file)
BIAS_SD = 0.1
TOP = 1 << 20          # key of the tensors outside the layers


def published(cfg: dict, seed, layer: int | None = None) -> dict:
    """The published tensors of ``seed`` by name: one layer's, or with
    ``layer`` None the embedding, final norm and head.  The bits come
    from XLA's own generator (``rbg``), one uniform draw a layer: for a
    v5e a normal draw per tensor from the default generator compiled in
    207 s for the whole model."""
    key = jax.random.key(seed, impl="rbg")
    dtype = jnp.dtype(cfg["dtype"])
    if layer is None:
        return _draw(_top_spec(cfg), jax.random.fold_in(key, TOP), dtype)
    return _draw(_layer_spec(cfg, layer), jax.random.fold_in(key, layer),
                 dtype)


def _deinterleave(n: int):
    """The published code's order of the rotary dims before its
    ``rotate_half``: even dims, then odd ones."""
    return jnp.concatenate([jnp.arange(0, n, 2), jnp.arange(1, n, 2)])


def _program_layer(cfg: dict, w: dict, i: int) -> dict:
    """Layer ``i``'s published tensors as the program's block: Linear
    weights transposed to (in, out), RMSNorm gains g stored as g - 1,
    the rotary columns in the order the program rotates them, ``kv_b``
    split into W_UK and W_UV, the held experts stacked."""
    h, dn = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    dr, dv, r = cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"]
    a = f"model.layers.{i}."
    perm = _deinterleave(dr)

    def gain(name):
        g = w[a + name]
        return (g.astype(jnp.float32) - 1.0).astype(g.dtype)

    q = w[a + "self_attn.q_proj.weight"].reshape(h, dn + dr, -1)
    q = jnp.concatenate([q[:, :dn], q[:, dn:][:, perm]], 1)
    kva = w[a + "self_attn.kv_a_proj_with_mqa.weight"]
    kva = jnp.concatenate([kva[:r], kva[r:][perm]], 0)
    kvb = w[a + "self_attn.kv_b_proj.weight"].reshape(h, dn + dv, r)
    block = {
        "pre_norm": gain("input_layernorm.weight"),
        "mla": {"wq": q.reshape(h * (dn + dr), -1).T, "wkv_a": kva.T,
                "kv_norm": gain("self_attn.kv_a_layernorm.weight"),
                "wk_b": kvb[:, :dn].reshape(h * dn, r).T,
                "wv_b": kvb[:, dn:].reshape(h * dv, r).T,
                "wo": w[a + "self_attn.o_proj.weight"].T},
        "ffn_norm": gain("post_attention_layernorm.weight")}

    def swiglu(prefix):
        return {"w_gate": w[prefix + "gate_proj.weight"].T,
                "w_up": w[prefix + "up_proj.weight"].T,
                "w_down": w[prefix + "down_proj.weight"].T}
    if i < cfg["first_k_dense_replace"]:
        block["mlp"] = swiglu(a + "mlp.")
        return block
    experts = [swiglu(a + f"mlp.experts.{j}.")
               for j in range(cfg["n_routed_experts"])]
    stack = {k: jnp.stack([x[k] for x in experts]) for k in experts[0]}
    block["moe"] = {
        "router": w[a + "mlp.gate.weight"].T,
        "router_bias": w[a + "mlp.gate.e_score_correction_bias"],
        "we_gate": stack["w_gate"], "we_up": stack["w_up"],
        "we_down": stack["w_down"],
        "shared": swiglu(a + "mlp.shared_experts.")}
    return block


def _program(cfg: dict, seed) -> dict:
    """The program's parameter tree holding ``published``'s tensors."""
    from repro.models.lm import model as M
    plan = M.make_plan(arch_config(cfg))
    top = published(cfg, seed)
    params = {"embed": top["model.embed_tokens.weight"],
              "final_norm": (top["model.norm.weight"].astype(jnp.float32)
                             - 1.0).astype(jnp.dtype(cfg["dtype"])),
              "lm_head": top["lm_head.weight"].T}
    layers = [_program_layer(cfg, published(cfg, seed, i), i)
              for i in range(cfg["num_hidden_layers"])]
    first, unit = len(plan.head), len(plan.unit)
    if plan.head:
        params["head_blocks"] = layers[:first]
    if plan.n_groups:
        params["blocks"] = {
            f"l{j}_{kind}_{ffn}": jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *layers[first + j::unit][:plan.n_groups])
            for j, (kind, ffn) in enumerate(plan.unit)}
    tail = layers[first + plan.n_groups * unit:]
    if tail:
        params["tail_blocks"] = tail
    return params


_JITTED: dict = {}


def _jitted(cfg: dict, fn, *static):
    """``fn(cfg, seed, *static)`` jitted once per configuration, the seed
    traced."""
    key = (fn.__name__, json.dumps(cfg, sort_keys=True), static)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(lambda seed: fn(cfg, seed, *static))
    return _JITTED[key]


def make_weights(cfg: dict, seed: int):
    """The program's parameter tree of ``seed``, made on the device in one
    call."""
    return _jitted(cfg, _program)(seed)


def weights(cfg: dict, seed: int):
    """``published(cfg, seed, layer)`` as a function of ``layer`` alone,
    each layer's draw made on the device in one call."""
    return lambda layer=None: _jitted(cfg, published, layer)(seed)


def make_inputs(cfg: dict, traffic: dict, key, count: int):
    """``count`` prompts of the mix's ``prompt_len`` token ids, uniform
    over the vocabulary: a ``prefix_len`` system prefix shared by all,
    then each prompt's own tokens.  ``(count, prompt_len)`` int32."""
    k_pre, k_own = jax.random.split(key)
    v, t, pre = cfg["vocab_size"], traffic["prompt_len"], traffic["prefix_len"]
    prefix = jax.random.randint(k_pre, (1, pre), 0, v, jnp.int32)
    own = jax.random.randint(k_own, (count, t - pre), 0, v, jnp.int32)
    return jnp.concatenate([jnp.broadcast_to(prefix, (count, pre)), own], 1)


# ---------------------------------------------------------------------------
# Work of one decode step, counted from shapes (PERF.md section 3)
# ---------------------------------------------------------------------------

def _sizes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    f, e = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    mla = d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d
    expert = 3 * d * f
    return {"d": d, "h": h, "r": r, "dr": dr, "dn": dn, "dv": dv,
            "mla": mla, "expert": expert,
            "shared": cfg["n_shared_experts"] * expert,
            "router": d * cfg["router_experts"], "held": e * expert,
            "dense": 3 * d * cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"],
            "moe_layers": cfg["num_hidden_layers"]
            - cfg["first_k_dense_replace"]}


def mean_position(traffic: dict) -> float:
    """The cache length a decode step reads, averaged over a call."""
    return traffic["prompt_len"] + traffic["steps"] / 2.0


def mla_work(cfg: dict, traffic: dict, rows: int) -> tuple[float, float]:
    """FLOPs and HBM bytes of the latent attention of one decode step over
    ``rows`` sequences, all layers: the MLA weights read once; per row
    and layer the latent cache (``kv_lora_rank`` + rope dims, bfloat16)
    read up to the position and one entry written.  FLOPs per row and
    layer: 2 per weight (the projections, with the absorbed ``W^UK`` /
    ``W^UV`` applied once each), and per head and cached position 2 x
    (r + rope) for the score and 2 x r for the value sum."""
    s = _sizes(cfg)
    pos = mean_position(traffic)
    entry = (s["r"] + s["dr"]) * BF16
    nbytes = s["layers"] * (s["mla"] * BF16 + rows * (pos + 1) * entry)
    flops = s["layers"] * rows * (
        2 * s["mla"] + 2 * s["h"] * pos * (2 * s["r"] + s["dr"]))
    return float(flops), float(nbytes)


def moe_work(cfg: dict, traffic: dict, rows: int) -> tuple[float, float]:
    """FLOPs and HBM bytes of the expert layers of one decode step over
    ``rows`` tokens: the held experts', shared experts' and router's
    weights read once; FLOPs 2 per weight for the router and the shared
    experts, and for the routed pairs that land on held experts at their
    expected share (top-k x held / router width per token)."""
    s = _sizes(cfg)
    pairs = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]
    nbytes = s["moe_layers"] * (s["held"] + s["shared"] + s["router"]) * BF16
    flops = s["moe_layers"] * rows * 2 * (
        s["router"] + s["shared"] + pairs * s["expert"])
    return float(flops), float(nbytes)


def work(cfg: dict, traffic: dict, rows: int,
         resample_share: float) -> tuple[float, float]:
    """FLOPs and HBM bytes one SMC decode step over ``rows`` sequences
    needs at least, whatever the implementation: the latent attention
    and the expert layers above; the dense layer's and the head's weights
    read once and the embedding by rows; the logits (float32) written
    and read once; 2 FLOPs per weight per row outside the expert layers;
    and, where the step resamples (share ``resample_share``), each
    row's decode state (the whole latent cache and the token history)
    read and written by the gather."""
    s = _sizes(cfg)
    v = cfg["vocab_size"]
    f_mla, b_mla = mla_work(cfg, traffic, rows)
    f_moe, b_moe = moe_work(cfg, traffic, rows)
    max_len = traffic["prompt_len"] + traffic["steps"] + 1
    state = (s["layers"] * max_len * (s["r"] + s["dr"]) * BF16
             + traffic["steps"] * I32 + 5 * F32)
    nbytes = (b_mla + b_moe + (s["dense"] + s["d"] * v) * BF16
              + rows * s["d"] * BF16 + 2 * rows * v * F32
              + resample_share * 2 * rows * state)
    flops = f_mla + f_moe + rows * 2 * (s["dense"] + s["d"] * v)
    return float(flops), float(nbytes)
