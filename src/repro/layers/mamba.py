"""Mamba selective-SSM block [arXiv:2312.00752] (jamba's recurrent mixer).

State h in R^{d_inner x d_state} per batch element:

    h_t = exp(dt_t * A) . h_{t-1} + dt_t * B_t * x_t     (A diagonal, <0)
    y_t = C_t . h_t + D * x_t

with data-dependent (dt_t, B_t, C_t) — the "selective" part.  As in every
Jamba, dt, B and C are RMS-normalised after ``x_proj`` (``dt_norm``,
``b_norm``, ``c_norm``).  Projections round their input to the weight's
dtype and accumulate in float32; the SSM recurrence and its state are
float32 whatever the weights, the conv state keeps the dtype it is given.
Sequence form
uses a chunked nested scan (outer over S/chunk, inner over steps) so nothing
of shape (S, d_inner, d_state) is ever materialised; d_inner is
tensor-parallel (the recurrence is diagonal, so the scan stays local to each
shard — the TPU analogue of the paper's per-module locality).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config.core import ModelConfig
from repro.distributed.sharding import constrain
from repro.layers.linear import apply_linear_f32, init_linear, linear_specs
from repro.layers.norms import apply_norm, init_norm, norm_specs
from repro.utils import Params, split_keys, truncated_normal_init


def mamba_dims(cfg: ModelConfig) -> tuple[int, int, int]:
    ssm = cfg.ssm
    d_inner = ssm.expand * cfg.d_model
    dt_rank = ssm.dt_rank or -(-cfg.d_model // 16)
    return d_inner, ssm.d_state, dt_rank


def init_mamba(key: jax.Array, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    d_inner, d_state, dt_rank = mamba_dims(cfg)
    k = split_keys(key, ["in_x", "in_z", "conv", "x_bc_dt", "dt_up", "out", "a"])
    return {
        "in_x": init_linear(k["in_x"], d, d_inner),
        "in_z": init_linear(k["in_z"], d, d_inner),  # gate branch
        "conv_w": truncated_normal_init(k["conv"], (cfg.ssm.d_conv, d_inner), fan_in=cfg.ssm.d_conv),
        "conv_b": jnp.zeros((d_inner,), jnp.float32),
        # x -> (dt_rank + 2*d_state): dt low-rank + B + C
        "x_proj": init_linear(k["x_bc_dt"], d_inner, dt_rank + 2 * d_state),
        "dt_norm": init_norm("rmsnorm", dt_rank),
        "b_norm": init_norm("rmsnorm", d_state),
        "c_norm": init_norm("rmsnorm", d_state),
        "dt_proj": init_linear(k["dt_up"], dt_rank, d_inner, bias=True),
        "a_log": jnp.log(jnp.tile(jnp.arange(1, d_state + 1, dtype=jnp.float32), (d_inner, 1))),
        "d_skip": jnp.ones((d_inner,), jnp.float32),
        "out": init_linear(k["out"], d_inner, d),
    }


def mamba_specs(cfg: ModelConfig) -> Params:
    return {
        "in_x": linear_specs("fsdp", "tp"),
        "in_z": linear_specs("fsdp", "tp"),
        "conv_w": (None, "tp"),
        "conv_b": ("tp",),
        "x_proj": linear_specs("tp", None),
        "dt_norm": norm_specs("rmsnorm"),
        "b_norm": norm_specs("rmsnorm"),
        "c_norm": norm_specs("rmsnorm"),
        "dt_proj": linear_specs(None, "tp", bias=True),
        "a_log": ("tp", None),
        "d_skip": ("tp",),
        "out": linear_specs("tp", "fsdp"),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 conv_state: jnp.ndarray | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv1d.  x: (B, S, C); w: (K, C).

    conv_state: (B, K-1, C) history for decode; returns (y, new_state).
    """
    k = w.shape[0]
    if conv_state is None:
        conv_state = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)  # (B, S+K-1, C)
    # sum_j w[j] * x[t + j - (K-1)]  via K shifted adds (K=4: cheap, fusion-friendly)
    y = sum(w[j].astype(x.dtype) * xp[:, j : j + x.shape[1], :] for j in range(k))
    y = y + b.astype(x.dtype)
    new_state = xp[:, -(k - 1):, :] if k > 1 else conv_state
    return y, new_state.astype(conv_state.dtype)


def _ssm_inputs(params: Params, xc: jnp.ndarray, cfg: ModelConfig):
    """xc: (B, S, d_inner) post-conv activations -> dt, B_t, C_t."""
    d_inner, d_state, dt_rank = mamba_dims(cfg)
    proj = apply_linear_f32(params["x_proj"], xc)
    dt_lr, b_t, c_t = jnp.split(proj, [dt_rank, dt_rank + d_state], axis=-1)
    eps = cfg.norm_eps
    dt_lr = apply_norm(params["dt_norm"], dt_lr, "rmsnorm", eps)
    b_t = apply_norm(params["b_norm"], b_t, "rmsnorm", eps)
    c_t = apply_norm(params["c_norm"], c_t, "rmsnorm", eps)
    dt = jax.nn.softplus(apply_linear_f32(params["dt_proj"], dt_lr))
    return dt, b_t, c_t


def ssm_scan(dt, b_t, c_t, xc, a, state, chunk: int = 256):
    """Selective scan.  dt/xc: (B,S,d_inner); b_t/c_t: (B,S,d_state);
    a: (d_inner, d_state) (negative); state: (B,d_inner,d_state) f32.
    Returns (y (B,S,d_inner) f32, final state)."""
    bsz, s, d_inner = xc.shape
    d_state = b_t.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        xc = jnp.pad(xc, ((0, 0), (0, pad), (0, 0)))
        b_t = jnp.pad(b_t, ((0, 0), (0, pad), (0, 0)))
        c_t = jnp.pad(c_t, ((0, 0), (0, pad), (0, 0)))
    n = (s + pad) // chunk

    def chunks(t):  # (B, S, F) -> (n, chunk, B, F)
        return jnp.moveaxis(t.reshape(bsz, n, chunk, t.shape[-1]), (1, 2), (0, 1))

    dtc, xcc, btc, ctc = map(chunks, (dt, xc, b_t, c_t))

    def inner(h, step):
        dt_t, x_t, bt_t, ct_t = step          # (B,di) (B,di) (B,ds) (B,ds)
        da = jnp.exp(dt_t[..., None] * a[None])            # (B,di,ds)
        db = dt_t[..., None] * bt_t[:, None, :]            # (B,di,ds)
        h = da * h + db * x_t.astype(jnp.float32)[..., None]
        y_t = jnp.sum(h * ct_t[:, None, :], axis=-1)
        return h, y_t

    def outer(h, blk):
        h, y_blk = jax.lax.scan(inner, h, blk)
        return h, y_blk

    state, y = jax.lax.scan(outer, state, (dtc, xcc, btc, ctc))
    y = y.reshape(n * chunk, bsz, d_inner)[:s]
    return jnp.moveaxis(y, 0, 1), state


def ssm_step(dt, b_t, c_t, xc, a, state):
    """One decode step: dt/xc (B,d_inner); b_t/c_t (B,d_state)."""
    da = jnp.exp(dt[..., None] * a[None])
    db = dt[..., None] * b_t[:, None, :]
    state = da * state + db * xc.astype(jnp.float32)[..., None]
    y = jnp.sum(state * c_t[:, None, :], axis=-1)  # a reduction: float32 on any backend
    return y, state


def _gated_out(params: Params, y: jnp.ndarray, xc: jnp.ndarray,
               z: jnp.ndarray) -> jnp.ndarray:
    y = (y + params["d_skip"].astype(jnp.float32) * xc) * jax.nn.silu(z)
    return apply_linear_f32(params["out"], y)


def apply_mamba(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                state: Params | None = None, chunk: int = 256):
    """Sequence form.  x: (B,S,D) -> (y in x's dtype, new_state dict)."""
    bsz, s, _ = x.shape
    if state is None:
        state = init_mamba_state(cfg, bsz, x.dtype)
    xz = apply_linear_f32(params["in_x"], x)
    z = apply_linear_f32(params["in_z"], x)
    xz = constrain(xz, ("batch", None, "tp"))
    xc, conv_state = _causal_conv(xz, params["conv_w"], params["conv_b"], state["conv"])
    xc = jax.nn.silu(xc)
    dt, b_t, c_t = _ssm_inputs(params, xc, cfg)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    y, ssm_state = ssm_scan(dt, b_t, c_t, xc, a, state["ssm"], chunk=chunk)
    out = _gated_out(params, y, xc, z).astype(x.dtype)
    sp = "sp" if s > 1 else None
    return constrain(out, ("batch", sp, None)), {"ssm": ssm_state, "conv": conv_state}


def apply_mamba_step(params: Params, x: jnp.ndarray, cfg: ModelConfig, state: Params):
    """Decode step.  x: (B, D) -> (y (B,D) in x's dtype, new_state)."""
    xz = apply_linear_f32(params["in_x"], x)
    z = apply_linear_f32(params["in_z"], x)
    xc, conv_state = _causal_conv(xz[:, None, :], params["conv_w"], params["conv_b"],
                                  state["conv"])
    xc = jax.nn.silu(xc[:, 0, :])
    dt, b_t, c_t = _ssm_inputs(params, xc, cfg)
    a = -jnp.exp(params["a_log"].astype(jnp.float32))
    y, ssm_state = ssm_step(dt, b_t, c_t, xc, a, state["ssm"])
    out = _gated_out(params, y, xc, z).astype(x.dtype)
    return out, {"ssm": ssm_state, "conv": conv_state}


def init_mamba_state(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16) -> Params:
    d_inner, d_state, _ = mamba_dims(cfg)
    return {
        "ssm": jnp.zeros((batch, d_inner, d_state), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm.d_conv - 1, d_inner), dtype),
    }


def mamba_state_specs() -> Params:
    return {"ssm": ("batch", "tp", None), "conv": ("batch", None, "tp")}
