"""Mixture-of-Experts with top-k routing.

Two interchangeable implementations (cfg.moe.impl):

* ``scatter`` — production path: capacity-bounded token dispatch into an
  (E, C, D) buffer via scatter-add, batched expert GEMMs, gather-combine.
  Dropped tokens (over capacity) contribute zero, matching Switch/GShard
  semantics [arXiv:2101.03961, arXiv:2006.16668].
* ``dense`` — oracle: every expert runs on every token, outputs weighted by
  the (renormalised) top-k gates.  O(E) FLOPs — smoke tests only, and the
  correctness reference for the scatter path when nothing is dropped.

* ``ep_a2a`` — expert-parallel shard_map path: tokens stay on their
  (data x model) shard, routing is local, and dispatch/combine move through
  ``jax.lax.all_to_all`` over the model(=expert) axis — the collective is
  O(tokens x D / chips) instead of the all-reduce of the full (E, C, D)
  buffer XLA emits for the cross-shard scatter (measured 4.9 TB/chip on
  dbrx train_4k; see EXPERIMENTS.md §Perf iteration 2).

Returns (y, aux_loss): aux is the Switch load-balance loss
``E * sum_e f_e * P_e`` (fraction-dispatched x mean router prob).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config.core import ModelConfig
from repro.distributed.sharding import active_mesh, constrain
from repro.utils import Params, split_keys, truncated_normal_init


def init_moe(key: jax.Array, cfg: ModelConfig) -> Params:
    moe = cfg.moe
    keys = split_keys(key, ["router", "gate", "up", "down"])
    e, d, f = moe.num_experts, cfg.d_model, cfg.d_ff
    return {
        "router": truncated_normal_init(keys["router"], (d, e), fan_in=d),
        "gate": truncated_normal_init(keys["gate"], (e, d, f), fan_in=d),
        "up": truncated_normal_init(keys["up"], (e, d, f), fan_in=d),
        "down": truncated_normal_init(keys["down"], (e, f, d), fan_in=f),
    }


def moe_specs(cfg: ModelConfig) -> Params:
    return {
        "router": (None, None),
        "gate": ("expert", "fsdp", None),
        "up": ("expert", "fsdp", None),
        "down": ("expert", None, "fsdp"),
    }


def _router(params: Params, x: jnp.ndarray, top_k: int):
    """x: (N, D) -> (weights (N,k) f32, indices (N,k) i32, probs (N,E) f32)."""
    logits = (x.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, indices = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    return weights, indices, probs


def _aux_loss(probs: jnp.ndarray, indices: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """Switch/GShard load-balance loss, normalised so that perfectly uniform
    dispatch + uniform router probs give exactly 1.0 (f_e is the fraction of
    the N*k dispatch slots assigned to expert e)."""
    dispatch = jax.nn.one_hot(indices, num_experts, dtype=jnp.float32)  # (N,k,E)
    k = indices.shape[-1]
    frac_dispatched = jnp.mean(jnp.sum(dispatch, axis=1), axis=0) / k   # (E,)
    mean_prob = jnp.mean(probs, axis=0)                                 # (E,)
    return num_experts * jnp.sum(frac_dispatched * mean_prob)


def _expert_ffn(params: Params, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Batched per-expert SwiGLU: h (E, C, D) -> (E, C, D)."""
    dt = h.dtype
    g = jnp.einsum("ecd,edf->ecf", h, params["gate"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", h, params["up"].astype(dt))
    a = jax.nn.silu(g) * u
    # experts already occupy the model axis; hidden dim stays local
    a = constrain(a, ("expert", None, None))
    return jnp.einsum("ecf,efd->ecd", a, params["down"].astype(dt))


def capacity(num_tokens: int, cfg: ModelConfig) -> int:
    moe = cfg.moe
    c = math.ceil(num_tokens * moe.top_k / moe.num_experts * moe.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8 for layout friendliness


def apply_moe(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (B, S, D), aux loss (scalar f32)."""
    moe = cfg.moe
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    xf = constrain(xf, ("tokens", None))
    weights, indices, probs = _router(params, xf, moe.top_k)
    aux = _aux_loss(probs, indices, moe.num_experts)

    if moe.impl == "dense":
        y = _dense_combine(params, xf, weights, indices, cfg)
    else:
        y = _scatter_combine(params, xf, weights, indices, cfg)
    return y.reshape(b, s, d), aux


def apply_moe_ep(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE via shard_map + all_to_all (the §Perf fix).

    Layout: x (B, S, D) with B over batch axes and S over the model axis
    (sequence-parallel residual); experts over the model axis; expert
    weights FSDP-sharded over "data" (all-gathered locally per layer).
    Requires an active mesh — callers fall back to :func:`apply_moe`
    otherwise (CPU tests).
    """
    mesh = active_mesh()
    if mesh is None:
        return apply_moe(params, x, cfg)

    from repro.distributed.sharding import active_rules
    rules = active_rules()
    moe = cfg.moe
    batch_axes = rules.batch if isinstance(rules.batch, tuple) else (rules.batch,)
    model_axis = rules.tp
    n_exp_shards = mesh.shape[model_axis]
    assert moe.num_experts % n_exp_shards == 0
    e_loc = moe.num_experts // n_exp_shards

    if x.shape[1] % n_exp_shards != 0:
        # decode shapes (S=1): too few tokens to amortise the EP exchange +
        # per-layer weight gathers (measured REGRESSION on moonshot/dbrx
        # decode_32k — §Perf cell 3 iteration 2, refuted hypothesis); the
        # scatter path's small (E, C, D) buffer is the better trade here.
        return apply_moe(params, x, cfg)

    def local_moe(router_w, gate_w, up_w, down_w, x_loc):
        # x_loc: (B_loc, S_loc, D); weights: router (D, E) replicated,
        # gate/up/down (E_loc, D_loc, F)/(E_loc, F, D_loc) — fsdp-sharded
        b_loc, s_loc, d = x_loc.shape
        n_loc = b_loc * s_loc
        xf = x_loc.reshape(n_loc, d)
        weights, indices, probs = _router({"router": router_w}, xf, moe.top_k)
        # aux from GLOBAL sufficient statistics (pmean the per-expert
        # fractions first; pmean of local products would differ)
        disp = jax.nn.one_hot(indices, moe.num_experts, dtype=jnp.float32)
        f_e = jnp.mean(jnp.sum(disp, axis=1), axis=0) / moe.top_k
        p_e = jnp.mean(probs, axis=0)
        for ax in (model_axis,) + tuple(batch_axes):
            f_e = jax.lax.pmean(f_e, ax)
            p_e = jax.lax.pmean(p_e, ax)
        aux = moe.num_experts * jnp.sum(f_e * p_e)

        # capacity per (source shard, expert)
        cap = max(8, -(-math.ceil(n_loc * moe.top_k / moe.num_experts
                                  * moe.capacity_factor) // 8) * 8)

        # local dispatch into a per-expert send buffer (E, cap, D)
        flat_e = indices.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, moe.num_experts, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        flat_p = jnp.sum(pos * onehot, axis=-1)
        dropped = flat_p >= cap
        flat_p = jnp.where(dropped, cap, flat_p)
        upd = jnp.repeat(xf, moe.top_k, axis=0)
        send = jnp.zeros((moe.num_experts, cap + 1, d), xf.dtype)
        send = send.at[flat_e, flat_p].add(upd)[:, :cap]      # (E, cap, D)

        # exchange: expert-major blocks to their owning shard
        # (E, cap, D) -> (n_shards, E_loc, cap, D) -> a2a -> recv blocks
        send = send.reshape(n_exp_shards, e_loc, cap, d)
        recv = jax.lax.all_to_all(send, model_axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        # recv: (n_shards, E_loc, cap, D) — tokens from every source shard
        recv = recv.transpose(1, 0, 2, 3).reshape(e_loc, n_exp_shards * cap, d)

        # expert FFN with fsdp all-gathered weights
        gather = lambda w, ax: jax.lax.all_gather(w, "data", axis=ax, tiled=True)
        g_w = gather(gate_w, 1)
        u_w = gather(up_w, 1)
        d_w = gather(down_w, 2)
        dt = recv.dtype
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, g_w.astype(dt))) * jnp.einsum(
            "ecd,edf->ecf", recv, u_w.astype(dt))
        out = jnp.einsum("ecf,efd->ecd", h, d_w.astype(dt))

        # return path: reverse the exchange
        out = out.reshape(e_loc, n_exp_shards, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(out, model_axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        back = back.reshape(moe.num_experts, cap, d)
        back = jnp.concatenate([back, jnp.zeros((moe.num_experts, 1, d), dt)], axis=1)

        gathered = back[flat_e, flat_p].reshape(n_loc, moe.top_k, d)
        w_mask = jnp.where(dropped.reshape(n_loc, moe.top_k), 0.0, weights)
        y = jnp.einsum("nkd,nk->nd", gathered.astype(jnp.float32),
                       w_mask.astype(jnp.float32))
        return y.astype(x_loc.dtype).reshape(b_loc, s_loc, d), aux

    x_spec = P(batch_axes, model_axis, None)
    fn = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(
            P(None, None),                      # router replicated
            P(model_axis, "data", None),        # gate (E, D, F)
            P(model_axis, "data", None),        # up
            P(model_axis, None, "data"),        # down (E, F, D)
            x_spec,
        ),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(params["router"], params["gate"], params["up"], params["down"], x)


def _apply_moe_ep_replicated(params, x, cfg: ModelConfig, mesh, rules):
    """EP for token counts too small to shard over the model axis (decode):
    tokens replicated over model; each shard computes its local experts and
    the outputs psum-combine.  Collective = one psum of (N, D).

    STATUS: kept as the measured-REFUTED §Perf cell-3 iteration-1 variant
    (per-layer weight gathers + capacity padding dominate at decode token
    counts; see EXPERIMENTS.md).  Production decode uses the scatter path;
    this function remains test-covered reference material."""
    moe = cfg.moe
    batch_axes = rules.batch if isinstance(rules.batch, tuple) else (rules.batch,)
    model_axis = rules.tp
    n_exp_shards = mesh.shape[model_axis]
    e_loc = moe.num_experts // n_exp_shards

    def local_moe(router_w, gate_w, up_w, down_w, x_loc):
        b_loc, s_loc, d = x_loc.shape
        n_loc = b_loc * s_loc
        xf = x_loc.reshape(n_loc, d)
        weights, indices, probs = _router({"router": router_w}, xf, moe.top_k)
        disp = jax.nn.one_hot(indices, moe.num_experts, dtype=jnp.float32)
        f_e = jnp.mean(jnp.sum(disp, axis=1), axis=0) / moe.top_k
        p_e = jnp.mean(probs, axis=0)
        for ax in tuple(batch_axes):
            f_e = jax.lax.pmean(f_e, ax)
            p_e = jax.lax.pmean(p_e, ax)
        aux = moe.num_experts * jnp.sum(f_e * p_e)

        sid = jax.lax.axis_index(model_axis)
        local = (indices // e_loc) == sid                      # (N, k) mine?
        local_idx = jnp.where(local, indices % e_loc, e_loc)   # park others
        cap = max(8, -(-math.ceil(n_loc * moe.top_k / moe.num_experts
                                  * moe.capacity_factor) // 8) * 8)
        flat_e = local_idx.reshape(-1)
        onehot = jax.nn.one_hot(flat_e, e_loc + 1, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) - onehot
        flat_p = jnp.sum(pos * onehot, axis=-1)
        dropped = (flat_p >= cap) | (flat_e == e_loc)
        flat_p = jnp.where(dropped, cap, flat_p)
        flat_e = jnp.where(flat_e == e_loc, 0, flat_e)

        upd = jnp.repeat(xf, moe.top_k, axis=0)
        upd = jnp.where(dropped[:, None], 0.0, upd)
        buf = jnp.zeros((e_loc, cap + 1, d), xf.dtype)
        buf = buf.at[flat_e, flat_p].add(upd)[:, :cap]

        gather = lambda w, ax: jax.lax.all_gather(w, "data", axis=ax, tiled=True)
        g_w, u_w, d_w = gather(gate_w, 1), gather(up_w, 1), gather(down_w, 2)
        dt = buf.dtype
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, g_w.astype(dt))) * jnp.einsum(
            "ecd,edf->ecf", buf, u_w.astype(dt))
        out = jnp.einsum("ecf,efd->ecd", h, d_w.astype(dt))
        out = jnp.concatenate([out, jnp.zeros((e_loc, 1, d), dt)], axis=1)

        gathered = out[flat_e, jnp.where(dropped, cap, flat_p)].reshape(
            n_loc, moe.top_k, d)
        w_mask = jnp.where(dropped.reshape(n_loc, moe.top_k), 0.0, weights)
        y = jnp.einsum("nkd,nk->nd", gathered.astype(jnp.float32),
                       w_mask.astype(jnp.float32))
        y = jax.lax.psum(y, model_axis)                        # combine experts
        return y.astype(x_loc.dtype).reshape(b_loc, s_loc, d), aux

    x_spec = P(batch_axes, None, None)
    fn = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(
            P(None, None),
            P(model_axis, "data", None),
            P(model_axis, "data", None),
            P(model_axis, None, "data"),
            x_spec,
        ),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(params["router"], params["gate"], params["up"], params["down"], x)


def _dense_combine(params, xf, weights, indices, cfg: ModelConfig) -> jnp.ndarray:
    moe = cfg.moe
    n, d = xf.shape
    # every expert on every token: (E, N, D)
    h = jnp.broadcast_to(xf[None], (moe.num_experts, n, d))
    out = _expert_ffn(params, h, cfg)                        # (E, N, D)
    gates = jnp.zeros((n, moe.num_experts), jnp.float32)
    gates = gates.at[jnp.arange(n)[:, None], indices].add(weights)
    y = jnp.einsum("end,ne->nd", out.astype(jnp.float32), gates)
    return y.astype(xf.dtype)


def _scatter_combine(params, xf, weights, indices, cfg: ModelConfig) -> jnp.ndarray:
    moe = cfg.moe
    n, d = xf.shape
    e, k = moe.num_experts, moe.top_k
    cap = capacity(n, cfg)

    # position of each (token, choice) within its expert, in flat order
    flat_e = indices.reshape(-1)                                  # (N*k,)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)           # (N*k, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot                     # exclusive cumsum
    flat_p = jnp.sum(pos * onehot, axis=-1)                       # (N*k,)
    dropped = flat_p >= cap
    flat_p = jnp.where(dropped, cap, flat_p)                      # park dropped in slot `cap`

    # dispatch: (E, cap+1, D) buffer; slot `cap` is the drop bin
    upd = jnp.repeat(xf, k, axis=0)                               # (N*k, D)
    buf = jnp.zeros((e, cap + 1, d), xf.dtype)
    buf = buf.at[flat_e, flat_p].add(upd)
    buf = constrain(buf, ("expert", None, None))

    out = _expert_ffn(params, buf[:, :cap], cfg)                  # (E, cap, D)
    out = jnp.concatenate([out, jnp.zeros((e, 1, d), out.dtype)], axis=1)
    out = constrain(out, ("expert", None, None))

    # combine: gather each (token, choice) result, weight, sum over k
    gathered = out[flat_e, flat_p].reshape(n, k, d)               # dropped -> zeros
    w = jnp.where(dropped.reshape(n, k), 0.0, weights).astype(jnp.float32)
    y = jnp.einsum("nkd,nk->nd", gathered.astype(jnp.float32), w)
    return y.astype(xf.dtype)
