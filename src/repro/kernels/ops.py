"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to :func:`pallas_interpret` of the default backend
(True on CPU, False on TPU, an error anywhere else), overridable per call.
The wrappers adapt framework-native layouts (e.g. core/lstm.py param dicts,
(B,S,H,d) attention tensors) to kernel layouts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lstm_cell import lstm_cell_pallas, pack_weights
from repro.kernels.wkv6 import wkv6_pallas


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode on the default backend.

    Only the CPU interprets (tests and rehearsals); the TPU compiles them.
    Any other platform is refused: emulating there would hide that the
    kernels never reached a device that can run them."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels compile for TPU and are interpreted on CPU; "
        f"no path for platform {platform!r}"
    )


@functools.partial(jax.jit, static_argnames=("block_b", "block_h", "pwl", "interpret"))
def lstm_cell_op(params, x, h, c, *, block_b: int = 128, block_h: int = 128,
                 pwl: bool = False, interpret: bool | None = None):
    """Fused LSTM cell using core/lstm.py param layout {wx, wh, b}."""
    if interpret is None:
        interpret = pallas_interpret()
    wx, wh, b = pack_weights(params)
    return lstm_cell_pallas(
        x, h, c, wx, wh, b, block_b=block_b, block_h=block_h, pwl=pwl,
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6_op(r, k, v, w, u, s0, *, interpret: bool | None = None):
    """WKV6 recurrence: r/k/v/w (B,T,H,hd), u (H,hd), s0 (B,H,hd,hd)."""
    if interpret is None:
        interpret = pallas_interpret()
    return wkv6_pallas(r, k, v, w, u, s0, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_b", "pwl", "interpret"))
def lstm_seq_op(params, xs, h0=None, c0=None, *, block_b: int = 256,
                pwl: bool = False, interpret: bool | None = None):
    """Sequence-streaming LSTM layer (state VMEM-resident across T).

    params: core/lstm.py layout; xs (T, B, In) -> (ys (T,B,H), (h, c))."""
    from repro.kernels.lstm_seq import lstm_seq_pallas

    if interpret is None:
        interpret = pallas_interpret()
    wx, wh, b = pack_weights(params)
    bsz = xs.shape[1]
    hidden = wh.shape[1]
    if h0 is None:
        h0 = jnp.zeros((bsz, hidden), xs.dtype)
    if c0 is None:
        c0 = jnp.zeros((bsz, hidden), jnp.float32)
    return lstm_seq_pallas(
        xs, h0, c0, wx, wh, b, block_b=block_b, pwl=pwl, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention_op(q, k, v, *, causal: bool = True, block_q: int = 512,
                       block_k: int = 512, interpret: bool | None = None):
    """Flash attention over (B, S, H, d) layout (framework-native)."""
    if interpret is None:
        interpret = pallas_interpret()
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_pallas(
        qt, kt, vt, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return jnp.swapaxes(out, 1, 2)
