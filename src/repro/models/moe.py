"""Mixture-of-Experts FFN (DeepSeek-V2-lite / Moonlight style).

Shared experts + routed experts with top-k softmax gating (DeepSeek-V2's
``MoEGate``: softmax over every routed expert in f32, greedy top-k, the
gates renormalised only under ``norm_topk_prob``, else scaled by
``routed_scaling_factor``). Two execution paths:

  * **held experts, dropless** (no mesh): the layer holds the routed experts
    ``0 .. experts_held - 1`` (all of them by default; another device's
    share is the same layer over its own experts' weights, with the
    router's columns ordered so that its experts come first). It routes over all ``n_experts``; the rows routed to
    its held experts are sorted by expert and run as one grouped product
    (``lax.ragged_dot``). No capacity applies and nothing is dropped, so a
    token's output does not depend on what else is in the batch. With fewer
    experts held than routed, the layer computes its share of the result:
    what the other shares' experts would add is not computed here.
  * **expert-parallel** (ambient mesh with a "model" axis, every expert
    held): tokens are sub-sharded along the model axis, dispatched into
    per-destination capacity slots, exchanged with ``lax.all_to_all``,
    FFN'd by the local expert shard, exchanged back and combined. Dropless
    up to the capacity factor; overflow tokens are dropped (standard GShard
    semantics) and counted in the aux metrics.

The shared experts run *outside* shard_map as a fused dense FFN so they keep
ordinary tensor parallelism over the model axis.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import dense_init
from repro.models.ffn import ffn, init_ffn
from repro.parallel import meshctx


def init_moe(key, cfg, dtype=None) -> dict:
    """Router over all ``n_experts``; weights of the held experts only."""
    dtype = dtype or cfg.param_dtype
    d, f, E = cfg.d_model, cfg.d_ff, cfg.experts_held
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, cfg.n_experts), jnp.float32, fan_in=d),
        "wi": dense_init(ks[1], (E, d, f), dtype, fan_in=d),
        "wg": dense_init(ks[2], (E, d, f), dtype, fan_in=d),
        "wo": dense_init(ks[3], (E, f, d), dtype, fan_in=f),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_ffn(ks[4], d, cfg.n_shared_experts * f, "swiglu", dtype)
    return p


def _route(params, cfg, x_flat):
    """x (N, d) -> (expert_ids (N,k), gates (N,k), aux_loss scalar)."""
    # the router is f32 in the published model: a TPU's default f32 matmul
    # would round both operands to bf16 and move near-tied experts
    logits = jnp.einsum("nd,de->ne", x_flat.astype(jnp.float32), params["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    elif cfg.routed_scaling_factor != 1.0:
        gates = gates * cfg.routed_scaling_factor
    # load-balance aux (Switch-style): E * Σ_e fraction_e · prob_e
    E = cfg.n_experts
    frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=1), axis=0
    )
    pmean = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * pmean)
    return ids, gates, aux


def _dispatch_indices(ids, E: int, capacity: int):
    """Sort-based capacity slotting. ids (N, k) -> (flat_e, slot, keep)."""
    N, k = ids.shape
    flat_e = ids.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos_sorted = jnp.arange(N * k) - seg_start[sorted_e]
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
    keep = pos < capacity
    slot = jnp.where(keep, pos, capacity)  # overflow -> spill row (sliced off)
    return flat_e, slot, keep


def _expert_ffn(params, x, dtype):
    """x (E, C, d) with per-expert weights (E, d, f)."""
    h = jnp.einsum("ecd,edf->ecf", x, params["wi"].astype(dtype))
    g = jnp.einsum("ecd,edf->ecf", x, params["wg"].astype(dtype))
    h = jax.nn.silu(g) * h
    return jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(dtype))


def moe_held(params, cfg, x_flat, valid=None):
    """Dropless routed part of the layer over the held experts.

    x_flat (N, d); ``valid`` (N,) bool leaves padding rows out of the expert
    products. Returns (out (N, d) of the held experts only, aux scalar,
    rows (experts_held,) int32: the rows each held expert received)."""
    N, d = x_flat.shape
    k, E_h, dt = cfg.top_k, cfg.experts_held, cfg.dtype
    ids, gates, aux = _route(params, cfg, x_flat)
    held = ids < E_h
    if valid is not None:
        held &= valid[:, None]
    # rows of held experts sorted by expert; the rest sort last, past every
    # group, and ragged_dot leaves them out
    key = jnp.where(held, ids, E_h).reshape(-1)
    order = jnp.argsort(key, stable=True)
    rows = jnp.zeros((E_h + 1,), jnp.int32).at[key].add(1)[:E_h]
    xs = x_flat.astype(dt)[order // k]
    with jax.named_scope("moe_experts"):
        h = jax.lax.ragged_dot(xs, params["wi"].astype(dt), rows)
        g = jax.lax.ragged_dot(xs, params["wg"].astype(dt), rows)
        y = jax.lax.ragged_dot(jax.nn.silu(g) * h, params["wo"].astype(dt), rows)
    y = jnp.zeros_like(y).at[order].set(y).reshape(N, k, d)
    y = jnp.where(held[..., None], y, 0)
    w = jnp.where(held, gates, 0.0).astype(dt)
    out = jnp.einsum("nkd,nk->nd", y, w, preferred_element_type=jnp.float32)
    return out.astype(dt), aux, rows


def _moe_local(params, cfg, x_flat, n_local_experts: int, a2a_axis: str | None):
    """Per-shard MoE: route -> dispatch -> (a2a) -> expert FFN -> (a2a) -> combine."""
    N, d = x_flat.shape
    E, k = cfg.n_experts, cfg.top_k
    C = max(8, int(math.ceil(N * k / E * cfg.capacity_factor)))
    ids, gates, aux = _route(params, cfg, x_flat)
    flat_e, slot, keep = _dispatch_indices(ids, E, C)

    tok = jnp.repeat(jnp.arange(N), k)
    buf = jnp.zeros((E, C + 1, d), cfg.dtype)
    buf = buf.at[flat_e, slot].add(x_flat[tok].astype(cfg.dtype))
    buf = buf[:, :C]

    if a2a_axis is not None:
        # (E = M·E_loc, C, d) -> exchange so this shard holds its E_loc experts'
        # tokens from every peer: -> (E_loc, M·C, d); inverse on the way back.
        recv = jax.lax.all_to_all(buf, a2a_axis, split_axis=0, concat_axis=1, tiled=True)
        out = _expert_ffn(params, recv, cfg.dtype)  # local expert weights
        buf_out = jax.lax.all_to_all(out, a2a_axis, split_axis=1, concat_axis=0, tiled=True)
    else:
        buf_out = _expert_ffn(params, buf, cfg.dtype)

    slot_safe = jnp.minimum(slot, C - 1)
    vals = buf_out[flat_e, slot_safe] * keep[:, None].astype(cfg.dtype)
    w = gates.reshape(-1).astype(cfg.dtype)
    out = jnp.sum((vals * w[:, None]).reshape(N, k, d), axis=1)
    drop_frac = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return out, aux, drop_frac


def moe_block(params, cfg, x, valid=None):
    """x (B, S, d) -> (B, S, d), plus metrics dict.

    Uses expert-parallel all_to_all when an ambient mesh with a "model" axis
    exists, every expert is held here and E divides evenly; otherwise the
    held-expert dropless path, whose metrics carry ``moe_rows`` (rows each
    held expert received; ``valid`` (B, S) leaves padding out).
    """
    B, S, d = x.shape
    mesh = meshctx.get_mesh()
    batch_axes: tuple[str, ...] = ()
    dp = 1
    if mesh is not None:
        for name in ("pod", "data"):  # maximal DP prefix dividing B
            if name in mesh.axis_names and B % (dp * mesh.shape[name]) == 0:
                batch_axes += (name,)
                dp *= mesh.shape[name]
    M = mesh.shape.get("model", 1) if mesh is not None else 1
    n_local_tokens = (B // dp) * S
    use_ep = (
        M > 1
        and cfg.experts_held == cfg.n_experts
        and cfg.n_experts % M == 0
        and n_local_tokens % M == 0
        and n_local_tokens >= M
    )

    if use_ep:
        E_loc = cfg.n_experts // M
        P = jax.sharding.PartitionSpec

        def inner(x_in, router, wi, wg, wo):
            Bl, Sl, _ = x_in.shape
            flat = x_in.reshape(Bl * Sl, d)
            # sub-shard tokens along the model axis (sequence-parallel dispatch)
            m_idx = jax.lax.axis_index("model")
            n_m = (Bl * Sl) // M
            flat_m = jax.lax.dynamic_slice_in_dim(flat, m_idx * n_m, n_m, axis=0)
            p_local = {"router": router, "wi": wi, "wg": wg, "wo": wo}
            out_m, aux, drop = _moe_local(p_local, cfg, flat_m, E_loc, "model")
            out = jax.lax.all_gather(out_m, "model", axis=0, tiled=True)
            aux = jax.lax.pmean(aux, ("model",) + batch_axes)
            drop = jax.lax.pmean(drop, ("model",) + batch_axes)
            return out.reshape(Bl, Sl, d), aux, drop

        inner_sm = meshctx.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(batch_axes, None, None), P(), P("model"), P("model"), P("model")),
            out_specs=(P(batch_axes, None, None), P(), P()),
            check_vma=False,
        )
        out, aux, drop = inner_sm(x, params["router"], params["wi"], params["wg"], params["wo"])
        metrics = {"moe_aux": aux, "moe_drop": drop}
    else:
        flat = x.reshape(B * S, d)
        out, aux, rows = moe_held(params, cfg, flat,
                                  None if valid is None else valid.reshape(-1))
        out = out.reshape(B, S, d)
        metrics = {"moe_aux": aux, "moe_drop": jnp.zeros((), jnp.float32),
                   "moe_rows": rows}

    if cfg.n_shared_experts:
        out = out + ffn(params["shared"], x, "swiglu", cfg.dtype)
    return out, metrics
