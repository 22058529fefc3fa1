"""Plain linear layers with logical sharding specs."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.utils import Params, truncated_normal_init


def init_linear(key: jax.Array, d_in: int, d_out: int, bias: bool = False) -> Params:
    p = {"w": truncated_normal_init(key, (d_in, d_out), fan_in=d_in)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def linear_specs(in_axis: Optional[str], out_axis: Optional[str], bias: bool = False) -> Params:
    s = {"w": (in_axis, out_axis)}
    if bias:
        s["b"] = (out_axis,)
    return s


def apply_linear(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ params["w"].astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


def apply_linear_f32(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    """``x @ w`` with ``x`` rounded to the weight's dtype and the products
    summed in float32, returned in float32: bf16 weights give bf16 matmul
    inputs with float32 accumulation, float32 weights a float32 product."""
    w = params["w"]
    y = jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)
    if "b" in params:
        y = y + params["b"].astype(jnp.float32)
    return y
