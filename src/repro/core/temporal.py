"""Temporal parallelism (paper Section 3): wavefront execution of a
multi-layer recurrent stack.

Two executors over the (layer x time) iteration grid:

* :func:`wavefront_forward` — single-device skewed scan.  At wavefront step
  k every layer fires concurrently (one vmapped fused cell over the layer
  stack), layer i processing timestep ``k - i``.  This is the paper's
  dataflow schedule expressed as data parallelism over layers; it is
  bit-exact against :func:`repro.core.lstm.lstm_ae_sequential`.

* :func:`pipelined_forward` — multi-device pipeline via ``shard_map`` over a
  stage mesh axis.  Each stage owns a contiguous group of layers (chosen by
  the Eq-8-analogue DP in core/balancing.py); inter-stage activations move
  through ``jax.lax.ppermute`` — the depth-1 FIFO of the paper's
  architecture.  Batch is sharded over the data axis at the same time.

Latency semantics match Eq (1): K = T + S - 1 wavefront steps, each costing
the bottleneck stage's per-timestep latency.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config.core import ModelConfig
from repro.core.balancing import stage_assignment_for
from repro.core.lstm import lstm_cell, stacked_cell_params
from repro.utils import Params


def schedule_table(num_layers: int, timesteps: int) -> list[list[tuple[int, int]]]:
    """Which (layer, timestep) pairs execute at each wavefront step —
    documentation/test helper mirroring Fig. 2's staggered execution."""
    steps = []
    for k in range(timesteps + num_layers - 1):
        active = [(i, k - i) for i in range(num_layers) if 0 <= k - i < timesteps]
        steps.append(active)
    return steps


def wavefront_forward(params: Params, xs: jnp.ndarray, pwl: bool = False) -> jnp.ndarray:
    """Single-device wavefront execution.  xs: (T, B, F) -> (T, B, F).

    All N layers execute in ONE vmapped cell per wavefront step — the
    software rendering of "all modules operate concurrently" (paper §3.2).
    """
    layers = params["layers"]
    n = len(layers)
    t_len, b, f = xs.shape
    stacked, in_sizes, hid_sizes = stacked_cell_params(layers)
    in_max = stacked["wx"].shape[1]
    h_max = stacked["wh"].shape[1]

    k_total = t_len + n - 1
    xs_ext = jnp.pad(xs, ((0, n - 1), (0, 0), (0, in_max - f)))  # drain steps: zeros

    cell = functools.partial(lstm_cell, pwl=pwl)
    vcell = jax.vmap(cell)  # over the layer stack

    h0 = jnp.zeros((n, b, h_max), xs.dtype)
    c0 = jnp.zeros((n, b, h_max), jnp.float32)
    layer_ids = jnp.arange(n)

    def step(carry, inp):
        h, c = carry
        x_k, k = inp
        # layer 0 reads the fresh input; layer i reads layer i-1's carry h
        upstream = jnp.pad(h[:-1], ((0, 0), (0, 0), (0, in_max - h_max)))
        in_buf = jnp.concatenate([x_k[None], upstream], axis=0)   # (N, B, in_max)
        h_new, c_new = vcell(stacked, in_buf, h, c)
        t_for_layer = k - layer_ids
        valid = (t_for_layer >= 0) & (t_for_layer < t_len)        # (N,)
        vmask = valid[:, None, None]
        h = jnp.where(vmask, h_new, h)
        c = jnp.where(vmask, c_new, c)
        return (h, c), h[-1]

    (_, _), ys = jax.lax.scan(step, (h0, c0), (xs_ext, jnp.arange(k_total)))
    return ys[n - 1 :, :, :f]


# ---------------------------------------------------------------------------
# Multi-device pipeline (shard_map over the stage axis)
# ---------------------------------------------------------------------------

def build_stage_params(
    params: Params, cfg: ModelConfig, n_stages: int
) -> tuple[Params, jnp.ndarray, list[int]]:
    """Group layers into stages (balanced DP) and stack padded cells into
    (S, max_layers_per_stage, ...) arrays shardable over the stage axis.

    Returns (stage_params, per-stage layer counts (S,), assignment list).
    """
    layers = params["layers"]
    assignment, _ = stage_assignment_for(cfg.lstm_ae, n_stages)
    n_used = max(assignment) + 1
    groups: list[list] = [[] for _ in range(n_stages)]
    for layer, sid in zip(layers, assignment):
        groups[sid].append(layer)
    max_per = max(len(g) for g in groups)

    stacked_all, _, _ = stacked_cell_params(list(layers))
    in_max = stacked_all["wx"].shape[1]
    h_max = stacked_all["wh"].shape[1]

    def pad_group(group):
        # pad cells to the GLOBAL dims (gate-aligned) before stacking, then
        # pad the layer-count dim up to max_per with zero cells
        if group:
            g_stacked, _, _ = stacked_cell_params(group, in_max=in_max, h_max=h_max)
        else:
            g_stacked = {
                "wx": jnp.zeros((0, in_max, 4 * h_max), jnp.float32),
                "wh": jnp.zeros((0, h_max, 4 * h_max), jnp.float32),
                "b": jnp.zeros((0, 4 * h_max), jnp.float32),
            }
        def pad_leaf(leaf):
            pads = [(0, max_per - leaf.shape[0])] + [(0, 0)] * (leaf.ndim - 1)
            return jnp.pad(leaf, pads)
        return jax.tree.map(pad_leaf, g_stacked)

    stage_params = jax.tree.map(lambda *xs: jnp.stack(xs), *[pad_group(g) for g in groups])
    counts = jnp.array([len(g) for g in groups], jnp.int32)
    return stage_params, counts, assignment


def pipelined_forward(
    stage_params: Params,
    counts: jnp.ndarray,
    xs: jnp.ndarray,
    *,
    mesh: Mesh,
    cfg: ModelConfig,
    stage_axis: str = "model",
    batch_axes: tuple[str, ...] = ("data",),
    pwl: bool = False,
) -> jnp.ndarray:
    """Pipelined wavefront over ``stage_axis``.  xs: (T, B, F) -> (T, B, F).

    stage_params: (S, max_per, ...) stacked padded cells (stage-sharded);
    counts: (S,) layers per stage.  Stages beyond the model depth idle and
    pass activations through — utilisation is reported by the balancing
    module, mirroring the paper's Table-1 discussion.
    """
    n_stages = counts.shape[0]
    t_len, b, f = xs.shape
    in_max = stage_params["wx"].shape[2]
    h_max = stage_params["wh"].shape[2]
    max_per = stage_params["wx"].shape[1]
    total_layers = len(cfg.lstm_ae.layer_sizes())
    k_total = t_len + n_stages - 1

    xs_ext = jnp.pad(xs, ((0, n_stages - 1), (0, 0), (0, in_max - f)))

    def stage_fn(sp, cnt, xs_loc):
        sid = jax.lax.axis_index(stage_axis)
        b_loc = xs_loc.shape[1]
        cnt = cnt[0]  # my layer count
        cell = functools.partial(lstm_cell, pwl=pwl)

        h0 = jnp.zeros((max_per, b_loc, h_max), xs_loc.dtype)
        c0 = jnp.zeros((max_per, b_loc, h_max), jnp.float32)
        fifo0 = jnp.zeros((b_loc, in_max), xs_loc.dtype)

        def step(carry, inp):
            h, c, fifo = carry
            x_k, k = inp
            t_mine = k - sid
            active_t = (t_mine >= 0) & (t_mine < t_len)
            cur = jnp.where(sid == 0, x_k, fifo)  # stage input (B, in_max)

            def run_layer(j, acc):
                cur_j, h, c = acc
                pj = jax.tree.map(lambda a: a[0, j], sp)
                h_j, c_j = cell(pj, cur_j, h[j], c[j])
                is_active = (j < cnt) & active_t
                h = h.at[j].set(jnp.where(is_active, h_j, h[j]))
                c = c.at[j].set(jnp.where(is_active, c_j, c[j]))
                nxt = jnp.pad(h_j, ((0, 0), (0, in_max - h_max)))
                cur_j = jnp.where(j < cnt, nxt, cur_j)  # inactive slot: pass through
                return (cur_j, h, c)

            cur_out, h, c = jax.lax.fori_loop(0, max_per, run_layer, (cur, h, c))
            # FIFO hop to the next stage (paper's inter-module queue)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            fifo = jax.lax.ppermute(cur_out, stage_axis, perm)
            return (h, c, fifo), cur_out

        (_, _, _), ys = jax.lax.scan(step, (h0, c0, fifo0), (xs_loc, jnp.arange(k_total)))
        return ys[None]  # (1, K, B_loc, in_max): stage-major for out_specs

    in_specs = (
        P(stage_axis),                 # stage_params stacked on dim 0
        P(stage_axis),                 # counts
        P(None, batch_axes, None),     # xs (K, B, F)
    )
    # out: (S, K, B, in_max) — stage-major stack of every stage's stream
    out_specs = P(stage_axis, None, batch_axes, None)

    fn = jax.shard_map(
        stage_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    all_streams = fn(stage_params, counts, xs_ext)
    # Stages with zero layers pass activations through, so the final stage's
    # stream is always the model output, delayed by (n_stages - 1) fill steps.
    ys = all_streams[-1, n_stages - 1 :, :, :f]
    return ys
