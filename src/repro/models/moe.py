"""Fine-grained MoE (DeepSeekMoE / Kimi-K2 style: shared + routed top-k).

Parallelism design (see DESIGN.md §5): experts are sharded over the 'model'
mesh axis (EP); expert weights are additionally ZeRO-3 sharded over 'data' and
all-gathered per layer inside the shard_map (FSDP semantics, overlappable by
the scheduler). Token dispatch is a *local* sort + capacity-gather per
(data, model) shard — each model shard selects the tokens routed to its own
expert range — and the only cross-shard collective on the critical path is a
single psum of the combined output over 'model', i.e. exactly the collective
cost of a dense TP MLP. No global sort, no all-to-all, no [T, E, C] one-hot.

Router/top-k runs outside the shard_map under plain GSPMD (it is tiny), which
also yields the load-balance auxiliary loss.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.layers import decl_mlp, mlp
from repro.models.params import ParamDecl
from repro.types import ModelConfig


def decl_moe(cfg: ModelConfig) -> dict:
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    decls = {
        "router": ParamDecl((d, E), P(None, None), scale=0.02, dtype="float32"),
        "w_gate": ParamDecl((E, d, f), P("model", "data", None)),
        "w_up": ParamDecl((E, d, f), P("model", "data", None)),
        "w_down": ParamDecl((E, f, d), P("model", None, "data")),
    }
    if cfg.n_shared_experts:
        decls["shared"] = decl_mlp(d, cfg.moe_d_ff * cfg.n_shared_experts)
    return decls


def router_topk(cfg: ModelConfig, params: dict, x: jax.Array):
    """Returns (weights [B,S,k], expert ids [B,S,k], aux_loss scalar)."""
    logits = x.astype(jnp.float32) @ params["router"]  # [B,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)  # renormalize over selected
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    E = cfg.n_experts
    me = jnp.mean(probs, axis=(0, 1))  # mean router prob per expert
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=2), axis=(0, 1)
    ) / cfg.top_k  # fraction of tokens per expert
    aux = E * jnp.sum(me * ce)
    return w.astype(x.dtype), idx.astype(jnp.int32), aux


def _expert_shard_body(
    x: jax.Array,  # [T_loc, d] tokens for this (pod,data) shard, replicated over model
    idx: jax.Array,  # [T_loc, k] global expert ids
    w: jax.Array,  # [T_loc, k] combine weights
    w_gate: jax.Array,  # [E_loc, d_loc, f]
    w_up: jax.Array,  # [E_loc, d_loc, f]
    w_down: jax.Array,  # [E_loc, f, d_loc]
    *,
    cfg: ModelConfig,
    tp_axis: str,
    fsdp_axis: str,
    capacity: int,
):
    E_loc = w_gate.shape[0]
    my = jax.lax.axis_index(tp_axis)
    e0 = my * E_loc
    T, k = idx.shape
    N = T * k

    # FSDP all-gather of this layer's expert weights over 'data'
    wg = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)  # [E_loc, d, f]
    wu = jax.lax.all_gather(w_up, fsdp_axis, axis=1, tiled=True)
    wd = jax.lax.all_gather(w_down, fsdp_axis, axis=2, tiled=True)  # [E_loc, f, d]

    flat_e = idx.reshape(N)
    flat_t = jnp.arange(N, dtype=jnp.int32) // k
    flat_w = w.reshape(N)
    local_e = flat_e - e0
    mine = (local_e >= 0) & (local_e < E_loc)
    key = jnp.where(mine, local_e, E_loc)  # sentinel sorts last
    order = jnp.argsort(key)
    s_key = key[order]
    s_t = flat_t[order]
    s_w = flat_w[order]
    starts = jnp.searchsorted(s_key, jnp.arange(E_loc, dtype=s_key.dtype))
    ends = jnp.searchsorted(s_key, jnp.arange(1, E_loc + 1, dtype=s_key.dtype))
    slots = starts[:, None] + jnp.arange(capacity, dtype=jnp.int32)[None, :]
    valid = slots < ends[:, None]  # [E_loc, C]
    slots_c = jnp.minimum(slots, N - 1)
    tok = jnp.take(s_t, slots_c)  # [E_loc, C] token index per slot
    cw = jnp.take(s_w, slots_c) * valid.astype(s_w.dtype)  # [E_loc, C]

    xg = jnp.take(x, tok.reshape(-1), axis=0).reshape(E_loc, capacity, -1)
    g = jnp.einsum("ecd,edf->ecf", xg, wg)
    u = jnp.einsum("ecd,edf->ecf", xg, wu)
    h = jax.nn.silu(g) * u
    y = jnp.einsum("ecf,efd->ecd", h, wd)  # [E_loc, C, d]
    y = y * cw[..., None].astype(y.dtype)

    out = jnp.zeros_like(x).at[tok.reshape(-1)].add(y.reshape(N if False else E_loc * capacity, -1))
    out = jax.lax.psum(out, tp_axis)
    return out


def moe_block(
    cfg: ModelConfig,
    params: dict,
    x: jax.Array,
    mesh,
    *,
    capacity: int | None = None,
):
    """Returns (y [B,S,d], aux_loss). x must be replicated over 'model'."""
    B, S, d = x.shape
    w, idx, aux = router_topk(cfg, params, x)

    axes = mesh.axis_names
    has_pod = "pod" in axes
    batch_ax = ("pod", "data") if has_pod else ("data",)
    n_tp = mesh.shape["model"]
    n_dp = mesh.shape["data"] * (mesh.shape["pod"] if has_pod else 1)
    T_loc = max(1, (B * S) // n_dp)
    E_loc = cfg.n_experts // n_tp if cfg.n_experts % n_tp == 0 else cfg.n_experts
    if cfg.n_experts % n_tp != 0:
        # fall back: replicate experts over model (small smoke configs)
        n_tp_eff = 1
        E_loc = cfg.n_experts
    else:
        n_tp_eff = n_tp
    if capacity is None:
        capacity = int(np.ceil(T_loc * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
        capacity = max(capacity, 8)

    xf = x.reshape(B * S, d)
    idxf = idx.reshape(B * S, cfg.top_k)
    wf = w.reshape(B * S, cfg.top_k)

    expert_spec = (
        P("model", "data", None) if n_tp_eff > 1 else P(None, "data", None)
    )
    expert_spec_d = (
        P("model", None, "data") if n_tp_eff > 1 else P(None, None, "data")
    )
    tp_axis = "model"

    body = partial(
        _expert_shard_body,
        cfg=cfg,
        tp_axis=tp_axis,
        fsdp_axis="data",
        capacity=capacity,
    )
    token_spec = P(batch_ax, None)
    if n_tp_eff == 1:
        # experts replicated over model: run the same body with a 1-wide psum
        # by mapping over 'model' too (each shard computes the full answer,
        # psum then divides). Simpler: compute without model mapping.
        def body_nomodel(xb, ib, wb, g_, u_, d_):
            return _expert_shard_body(
                xb, ib, wb, g_, u_, d_,
                cfg=cfg, tp_axis="model", fsdp_axis="data", capacity=capacity,
            )
        # check_vma off: the expert body psums over replicated axes
        yf = jax.shard_map(
            body_nomodel,
            mesh=mesh,
            in_specs=(token_spec, token_spec, token_spec, expert_spec, expert_spec, expert_spec_d),
            out_specs=token_spec,
            check_vma=False,
        )(xf, idxf, wf, params["w_gate"], params["w_up"], params["w_down"])
        yf = yf / n_tp  # psum over replicated model shards overcounts
    else:
        # check_vma off: the expert body psums over replicated axes
        yf = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(token_spec, token_spec, token_spec, expert_spec, expert_spec, expert_spec_d),
            out_specs=token_spec,
            check_vma=False,
        )(xf, idxf, wf, params["w_gate"], params["w_up"], params["w_down"])

    y = yf.reshape(B, S, d)
    if cfg.n_shared_experts:
        y = y + mlp(params["shared"], x)
    return y, aux
