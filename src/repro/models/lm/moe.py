"""Mixture-of-Experts layers: the router, the dropless expert layer of
the serving side, and the capacity dispatch of training.

Routing, per token x (D), E routed experts, top k (``route``):

    s      = softmax(x W_r)  or  sigmoid(x W_r)         (scores, float32)
    chosen = top-k of (s + b)                           (b: selection bias)
    g_i    = s_i / Σ_{j in chosen} s_j × scale          (i in chosen)
    out    = Σ_{i in chosen} g_i E_i(x) + S(x)

with each E_i a SwiGLU of width ``d_ff_expert`` and S the shared experts,
one SwiGLU of width ``n_shared_experts × d_ff_expert``.  DeepSeek-V3
(``noaux_tc``, as Moonlight-16B-A3B runs it with one group): sigmoid
scores, a per-expert bias b that picks the experts and does not weight
them, gates normalised then scaled by ``routed_scaling_factor`` (2.446).
The softmax router without a bias, normalised, is DeepSeek-V2's as this
repo runs it.

``apply_expert_share`` is the serving layer (prefill and decode): it is
told which experts it holds (share ``expert_share`` of ``expert_shares``
contiguous shares), routes over all ``n_experts``, and returns its held
experts' part of the sum plus S(x).  It drops nothing.  A call of at most
``DENSE_TOKENS`` tokens (decode) runs every held expert on every token, a
token not routed to one getting gate 0: below ~240 tokens a bfloat16
expert matmul on a v5e is bound by reading its weights, so the unrouted
pairs cost no time, and the weights are read once for all tokens, also
under ``vmap`` over prompts.  A longer call (prefill) runs only the
routed pairs: sorted by expert and multiplied group by group
(``lax.ragged_dot``), in chunks of ``ROUTED_CHUNK`` tokens.  With
``dispatch="ep_shardmap"`` on a mesh each ``data`` shard is one share:
its tokens' routes and rows are gathered over ``data``, every shard runs
its own experts on all of them, and the sums are reduce-scattered back.
On one chip the layer runs without the exchange that would bring other
chips' tokens.

``apply_moe`` / ``apply_moe_ep`` are the training dispatch: sort-based
fixed-capacity buffers (GShard-style dense dispatch, T × E × C, does not
fit this pool's scale), with deterministic dropping at overflow
(``capacity_factor``).  They hold every expert.

**PPF tie-in (DESIGN.md §5):** expert overload here is the same
senders/receivers imbalance as the paper's §IV particle routing; the aux
metrics exported per layer (tokens dropped, per-expert load) are the MoE
analogue of the DLB diagnostics, and the auxiliary load-balancing loss
plays the role of the paper's balancing objective.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core import runtime
from repro.launch.sharding import constrain
from repro.models.lm.layers import apply_mlp, mlp_params

Array = jax.Array

# the selection bias of a randomly initialised router: DeepSeek-V3 learns
# it during training and publishes no values, so one is drawn
BIAS_SCALE = 0.1


def held_experts(cfg: MoEConfig) -> int:
    """How many routed experts one share holds."""
    if cfg.n_experts % cfg.expert_shares:
        raise ValueError(f"{cfg.n_experts} experts do not split into "
                         f"{cfg.expert_shares} equal shares")
    return cfg.n_experts // cfg.expert_shares


def moe_params(key: Array, d_model: int, cfg: MoEConfig, dtype) -> dict:
    k_r, k_in, k_gate, k_out, k_sh, k_b = jax.random.split(key, 6)
    e, f = held_experts(cfg), cfg.d_ff_expert
    s_in = d_model ** -0.5
    s_out = f ** -0.5
    p = {
        "router": jax.random.normal(k_r, (d_model, cfg.n_experts),
                                    dtype) * s_in,
        "we_gate": jax.random.normal(k_gate, (e, d_model, f), dtype) * s_in,
        "we_up": jax.random.normal(k_in, (e, d_model, f), dtype) * s_in,
        "we_down": jax.random.normal(k_out, (e, f, d_model), dtype) * s_out,
    }
    if cfg.selection_bias:
        p["router_bias"] = jax.random.normal(
            k_b, (cfg.n_experts,), dtype) * BIAS_SCALE
    if cfg.n_shared_experts:
        p["shared"] = mlp_params(k_sh, d_model,
                                 cfg.n_shared_experts * f, dtype)
    return p


def route(x: Array, router: Array, bias: Array | None,
          cfg: MoEConfig) -> tuple[Array, Array, Array]:
    """x (N, D) → ``(gate, eid, scores)``: the (N, k) float32 gates and
    int32 expert ids of the chosen experts, and the (N, E) scores.  The
    router runs in float32 at full precision, as the published model's
    gate does."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if cfg.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif cfg.scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"unknown MoE scoring {cfg.scoring!r}")
    choice = scores if bias is None else scores + bias.astype(jnp.float32)
    _, eid = jax.lax.top_k(choice, cfg.top_k)
    gate = jnp.take_along_axis(scores, eid, axis=-1)
    gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
    return gate * cfg.routed_scale, eid, scores


# the most tokens a serving call runs through the dense form: a bfloat16
# matmul on a v5e is bound by reading its weights below ~240 rows (197
# TFLOP/s over 819 GB/s), so there the unrouted pairs cost no time
DENSE_TOKENS = 256
# tokens per sorted dispatch of a longer call: bounds its (chunk x top_k)
# row buffers, and the expert weights are read once a chunk
ROUTED_CHUNK = 4096


def apply_expert_share(p: dict, x: Array, cfg: MoEConfig) -> Array:
    """The serving expert layer, dropless: x (B, T, D) → the held
    experts' part of the routed sum plus the shared experts' output."""
    from repro.launch.sharding import _state
    b, t, d = x.shape
    st = _state()
    mesh = st.mesh
    if (cfg.dispatch == "ep_shardmap" and cfg.expert_shares == 1
            and mesh is not None and "data" in mesh.axis_names
            and cfg.n_experts % mesh.shape["data"] == 0):
        out = _expert_share_ep(p, x, cfg, mesh, st.batch_axes)
    else:
        xf = x.reshape(b * t, d)
        gate, eid, _ = route(xf, p["router"], p.get("router_bias"), cfg)
        out = _held_part(p, xf, gate, eid, cfg.expert_share)
        out = out.reshape(b, t, d)
    if "shared" in p:
        out = out + apply_mlp(p["shared"], x.reshape(b * t, d)).reshape(
            b, t, d)
    return out


def _held_part(p: dict, xf: Array, gate: Array, eid: Array,
               share) -> Array:
    """The held experts' part of the routed sum for the tokens xf (N, D)
    routed to ``eid`` (N, k) with ``gate``: ``p`` holds the experts
    ``share`` × held onwards (``share`` may be traced)."""
    e = p["we_gate"].shape[0]
    local = eid - share * e
    if xf.shape[0] > DENSE_TOKENS:
        return _routed_part(p, xf, gate, local)
    # each held expert's gate for each token, 0 where it was not chosen
    w = jnp.sum(jnp.where(local[:, :, None] == jnp.arange(e),
                          gate[:, :, None], 0.0), axis=1)      # (N, e)
    h = jnp.einsum("nd,edf->nef", xf, p["we_gate"])
    u = jnp.einsum("nd,edf->nef", xf, p["we_up"])
    a = jax.nn.silu(h) * u * w[:, :, None].astype(xf.dtype)
    return jnp.einsum("nef,efd->nd", a, p["we_down"])


def _routed_part(p: dict, xf: Array, gate: Array, local: Array) -> Array:
    """``_held_part`` over the routed pairs alone: per chunk of tokens the
    (token, choice) pairs on held experts sorted by expert, each expert's
    rows through its SwiGLU (``lax.ragged_dot``), the gated rows added
    back to their tokens; pairs on other shares' experts sort last and
    count for nothing."""
    n, d = xf.shape
    e, k = p["we_gate"].shape[0], local.shape[1]
    c = min(n, ROUTED_CHUNK)
    pad = -n % c
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        gate = jnp.pad(gate, ((0, pad), (0, 0)))
        local = jnp.pad(local, ((0, pad), (0, 0)), constant_values=e)

    def chunk(args):
        x, g, l = args
        held = (l >= 0) & (l < e)
        group = jnp.where(held, l, e).reshape(-1)            # (c·k,)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((e + 1,), jnp.int32).at[group].add(1)[:e]
        valid = (jnp.arange(c * k) < jnp.sum(sizes))[:, None]
        tok = order // k
        xs = x[tok]
        gs = jnp.where(held, g, 0.0).reshape(-1)[order][:, None]
        h = jax.lax.ragged_dot(xs, p["we_gate"], sizes)
        u = jax.lax.ragged_dot(xs, p["we_up"], sizes)
        a = jnp.where(valid, jax.nn.silu(h) * u * gs.astype(x.dtype), 0)
        y = jnp.where(valid, jax.lax.ragged_dot(a, p["we_down"], sizes), 0)
        return jnp.zeros((c, d), jnp.float32).at[tok].add(
            y.astype(jnp.float32)).astype(x.dtype)

    out = jax.lax.map(chunk, (xf.reshape(-1, c, d),
                              gate.reshape(-1, c, k),
                              local.reshape(-1, c, k)))
    return out.reshape(-1, d)[:n]


def _expert_share_ep(p: dict, x: Array, cfg: MoEConfig, mesh,
                     batch_axes) -> Array:
    """``apply_expert_share``'s routed part on a mesh, without the shared
    experts: experts over ``data`` (each shard one share), their widths
    over ``model``, tokens over the batch axes.  Each shard routes its own
    tokens; per chunk of them the rows, gates and expert ids of the whole
    ``data`` group are gathered, every shard runs its experts on all of
    them, and the partial sums are reduced over ``model`` and scattered
    back over ``data``."""
    from jax.sharding import PartitionSpec as P
    b, t, d = x.shape
    bias = p.get("router_bias")
    if bias is None:
        bias = jnp.zeros((cfg.n_experts,), jnp.float32)
    has_model = "model" in mesh.axis_names

    def shard_fn(xb, router, bias, wg, wu, wd):
        bl, tl, _ = xb.shape
        n = bl * tl
        xf = xb.reshape(n, d)
        gate, eid, _ = route(xf, router, bias, cfg)
        c = min(n, max(1, ROUTED_CHUNK // mesh.shape["data"]))
        pad = -n % c
        if pad:
            xf = jnp.pad(xf, ((0, pad), (0, 0)))
            gate = jnp.pad(gate, ((0, pad), (0, 0)))
            eid = jnp.pad(eid, ((0, pad), (0, 0)), constant_values=-1)
        share = runtime.axis_index("data")
        local = {"we_gate": wg, "we_up": wu, "we_down": wd}

        def chunk(args):
            xc, gc, ec = (runtime.all_gather(a, "data", axis=0, tiled=True)
                          for a in args)
            y = _held_part(local, xc, gc, ec, share)
            if has_model:
                y = runtime.psum(y, "model")
            return runtime.psum_scatter(y, "data", scatter_dimension=0,
                                        tiled=True)

        k = cfg.top_k
        out = jax.lax.map(chunk, (xf.reshape(-1, c, d),
                                  gate.reshape(-1, c, k),
                                  eid.reshape(-1, c, k)))
        return out.reshape(-1, d)[:n].reshape(bl, tl, d)

    return runtime.shard_map(
        shard_fn, mesh,
        in_specs=(P(batch_axes, None, None),        # x (B, T, D)
                  P(None, None),                    # router (replicated)
                  P(None),                          # selection bias
                  P("data", None, "model"),         # we_gate (E, D, F)
                  P("data", None, "model"),         # we_up
                  P("data", "model", None)),        # we_down (E, F, D)
        out_specs=P(batch_axes, None, None),
    )(x, p["router"], bias, p["we_gate"], p["we_up"], p["we_down"])


def _holds_all(cfg: MoEConfig) -> None:
    if cfg.expert_shares != 1:
        raise ValueError("the capacity dispatch holds every expert; an "
                         "expert share runs through apply_expert_share")


def capacity_for(n_tokens: int, cfg: MoEConfig) -> int:
    c = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(8, -(-c // 8) * 8)    # round up to 8


def _rank_within_expert(flat_e: Array, n_entries: int, e: int) -> Array:
    """Position of each (token, k) assignment within its expert's queue —
    the sort-based slotting shared by both dispatch paths."""
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    group_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank_sorted = jnp.arange(n_entries) - group_start[sorted_e]
    return jnp.zeros((n_entries,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))


def apply_moe(p: dict, x: Array, cfg: MoEConfig) -> tuple[Array, dict]:
    """x: (B, T, D) → (B, T, D), aux {load, drop_frac, aux_loss}."""
    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    _holds_all(cfg)
    n = b * t
    xf = x.reshape(n, d)
    cap = capacity_for(n, cfg)

    gate, eid, probs = route(xf, p["router"], p.get("router_bias"), cfg)

    # ---- sort-based slotting: rank of each (token, k) within its expert ---
    flat_e = eid.reshape(-1)                               # (N*k,)
    order = jnp.argsort(flat_e, stable=True)               # tokens grouped by expert
    sorted_e = flat_e[order]
    # position within expert group = index - start_of_group
    group_start = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank_sorted = jnp.arange(n * k) - group_start[sorted_e]
    rank = jnp.zeros((n * k,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))

    keep = rank < cap                                      # dropped at overflow
    slot = jnp.where(keep, rank, cap)                      # cap = trash slot

    # ---- dispatch: scatter tokens into (E, cap+1, D) buffers ---------------
    # expert-parallel layout: E over `data`, model dims over `model`
    # (the PPF DLB analogue — tokens route to expert-owning shards)
    xf = constrain(xf, "tokens_flat")
    buf = jnp.zeros((e, cap + 1, d), xf.dtype)
    tok_idx = jnp.repeat(jnp.arange(n), k)
    dispatch_src = constrain(xf[tok_idx], "tokens_flat")    # (N·k, D)
    buf = buf.at[flat_e, slot].set(dispatch_src, mode="drop")
    buf = constrain(buf[:, :cap], "moe_buf_d")               # (E, C, D)

    # ---- expert computation (dense batched einsum over experts) -----------
    h = constrain(jnp.einsum("ecd,edf->ecf", buf, p["we_gate"]), "moe_buf_f")
    u = constrain(jnp.einsum("ecd,edf->ecf", buf, p["we_up"]), "moe_buf_f")
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, p["we_down"])
    y = constrain(y, "moe_buf_d")

    # ---- combine: gather back and weight by gates --------------------------
    y_flat = y.reshape(e * cap, d)
    gathered = y_flat[jnp.clip(flat_e * cap + slot, 0, e * cap - 1)]
    gathered = constrain(jnp.where(keep[:, None], gathered, 0.0),
                         "tokens_flat")
    w = gate.reshape(-1)[:, None].astype(gathered.dtype)
    out = jnp.zeros((n, d), gathered.dtype).at[tok_idx].add(gathered * w)
    out = constrain(out, "tokens_flat")

    # ---- shared experts (always-on residual experts) -----------------------
    if "shared" in p:
        out = out + apply_mlp(p["shared"], xf)

    # ---- aux: load-balance loss + DLB-style diagnostics --------------------
    me = jnp.mean(probs, axis=0)                           # router prob mass
    ce = jnp.zeros((e,), jnp.float32).at[eid[:, 0]].add(1.0) / n  # top-1 load
    aux_loss = cfg.router_aux_loss * e * jnp.sum(me * ce)
    load = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    aux = {
        "moe_aux_loss": aux_loss,
        "moe_drop_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),
        "moe_max_load": jnp.max(load),
    }
    return out.reshape(b, t, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map): the paper's DLB routing executor
# applied to MoE tokens — §Perf optimization for the collective-bound cells.
# ---------------------------------------------------------------------------

def apply_moe_ep(p: dict, x: Array, cfg: MoEConfig) -> tuple[Array, dict]:
    """Expert-parallel MoE: tokens route to expert-owning data shards via
    ONE fused all_to_all of fixed-capacity buffers (cf. paper §IV latency
    criterion: one collective launch; §V bandwidth criterion: capacity ×
    payload, compressed to exactly the routed tokens).

    Layout: experts over ``data`` (E_loc = E/P per shard), expert FFN
    column/row-split over ``model``; tokens batch-sharded over
    (pod·)data.  Traffic per device ≈ tokens_loc·top_k·cf·D — the EP lower
    bound — versus the XLA dense path's replicated token buffers.
    """
    from repro.launch.sharding import _state
    from jax.sharding import PartitionSpec as P
    st = _state()
    mesh = st.mesh
    if mesh is None or "data" not in mesh.axis_names:
        return apply_moe(p, x, cfg)                 # single-device fallback

    b, t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    _holds_all(cfg)
    bias = p.get("router_bias")
    if bias is None:
        bias = jnp.zeros((e,), jnp.float32)
    p_data = mesh.shape["data"]
    has_model = "model" in mesh.axis_names
    ba = st.batch_axes
    if e % p_data:
        return apply_moe(p, x, cfg)                 # EP needs E % data == 0
    e_loc = e // p_data

    def shard_fn(xb, router, bias, wg, wu, wd):
        # xb: (B_loc, T, D) full-D tokens; wg/wu: (E_loc, D, F_loc);
        # wd: (E_loc, F_loc, D); router: (D, E) replicated.
        bl, tl, _ = xb.shape
        n_loc = bl * tl
        xf = xb.reshape(n_loc, d)
        cap = capacity_for(n_loc, cfg)              # per (src, expert)

        gate, eid, probs = route(xf, router, bias, cfg)
        flat_e = eid.reshape(-1)
        rank = _rank_within_expert(flat_e, n_loc * k, e)
        keep = rank < cap
        slot = jnp.where(keep, rank, cap)

        # pack per-expert send buffers: (E, cap+1, D) → (P, E_loc, cap, D)
        tok_idx = jnp.repeat(jnp.arange(n_loc), k)
        buf = jnp.zeros((e, cap + 1, d), xf.dtype)
        buf = buf.at[flat_e, slot].set(xf[tok_idx], mode="drop")
        send = buf[:, :cap].reshape(p_data, e_loc, cap, d)

        # ---- ONE fused all_to_all over the data axis (latency criterion)
        recv = runtime.all_to_all(send, "data", split_axis=0, concat_axis=0)
        # recv: (P_src, E_loc, cap, D) → (E_loc, P_src·cap, D)
        hbuf = jnp.moveaxis(recv, 0, 1).reshape(e_loc, p_data * cap, d)

        # ---- local expert FFN (col/row split over model) ----------------
        hg = jnp.einsum("esd,edf->esf", hbuf, wg)
        hu = jnp.einsum("esd,edf->esf", hbuf, wu)
        y = jnp.einsum("esf,efd->esd", jax.nn.silu(hg) * hu, wd)

        model_n = mesh.shape.get("model", 1)
        use_rs = (has_model and cfg.ep_reduce == "rs_ag"
                  and d % model_n == 0)
        if has_model and not use_rs:
            y = runtime.psum(y, "model")            # row-parallel reduce
        elif use_rs:
            # reduce-scatter the partial sums along D: the return route and
            # the combine then carry only D/TP per device.
            y = runtime.psum_scatter(y, "model", scatter_dimension=2,
                                     tiled=True)    # (E_loc, S, D/TP)
        d_eff = y.shape[-1]

        # ---- route results back (second all_to_all) ---------------------
        yb = jnp.moveaxis(y.reshape(e_loc, p_data, cap, d_eff), 1, 0)
        back = runtime.all_to_all(yb, "data", split_axis=0, concat_axis=0)
        y_flat = back.reshape(e * cap, d_eff)       # same layout as `buf`

        idx = jnp.clip(flat_e * cap + slot, 0, e * cap - 1)
        gathered = jnp.where(keep[:, None], y_flat[idx], 0.0)
        w = gate.reshape(-1)[:, None].astype(gathered.dtype)
        out = jnp.zeros((n_loc, d_eff), gathered.dtype).at[tok_idx].add(
            gathered * w)
        if use_rs:
            out = runtime.all_gather(out, "model", axis=1, tiled=True)

        # aux (psum'd to replicated scalars)
        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[eid[:, 0]].add(1.0) / n_loc
        aux_l = cfg.router_aux_loss * e * jnp.sum(me * ce)
        naxes = tuple(a for a in ("pod", "data", "model")
                      if a in mesh.axis_names)
        aux_l = runtime.pmean(aux_l, naxes)
        drop = runtime.pmean(1.0 - jnp.mean(keep.astype(jnp.float32)), naxes)
        load = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
        maxl = runtime.pmax(jnp.max(load), naxes)
        return out.reshape(bl, tl, d), aux_l, drop, maxl

    out, aux_l, drop, maxl = runtime.shard_map(
        shard_fn,
        mesh,
        in_specs=(P(ba, None, None),                # x (B, T, D)
                  P(None, None),                    # router (replicated)
                  P(None),                          # selection bias
                  P("data", None, "model"),         # we_gate (E, D, F)
                  P("data", None, "model"),         # we_up
                  P("data", "model", None)),        # we_down (E, F, D)
        out_specs=(P(ba, None, None), P(), P(), P()),
    )(x, p["router"], bias, p["we_gate"], p["we_up"], p["we_down"])

    if "shared" in p:
        out = out + apply_mlp(p["shared"], x.reshape(b * t, d)).reshape(
            b, t, d)
    aux = {"moe_aux_loss": aux_l, "moe_drop_frac": drop,
           "moe_max_load": maxl}
    return out, aux
