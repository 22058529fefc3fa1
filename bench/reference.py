"""The benchmark's plain LSTM autoencoder: weights from the seed, and the
forward pass every served answer is compared with.

Nothing here imports the program.  The model is the paper's (§2, Fig. 1):
a stack of LSTM layers whose hidden sizes halve from the input width to
the bottleneck and double back, the last layer's hidden state being the
reconstruction of the input.  Gates are ordered (i, f, g, o):

    gates = x Wx + h Wh + b
    c' = sigmoid(f) c + sigmoid(i) tanh(g)      h' = sigmoid(o) tanh(c')

A window's score is the mean over its timesteps of the mean squared
reconstruction error; a stream's running error after ``t`` samples is
the same mean over its first ``t`` samples.

``precision="highest"`` is the reference (float32, every dot at full
precision).  ``precision="default"`` is the same pass at the precision the
configurations state for the served model: float32, dots at the chip's
default precision.  ``precision="bfloat16"`` is the control: the same pass
with weights, activations and state in bfloat16, the error taken in
float32 against the float32 input.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from flops import layer_shapes


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed (64-bit seeds included)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def init_params(key: jax.Array, features: int, depth: int) -> dict:
    """Uniform(-1/sqrt(H), 1/sqrt(H)) for every weight and bias, in the
    layout the served model takes: ``{"layers": ({"wx", "wh", "b"}, ...)}``
    with ``wx`` (In, 4H), ``wh`` (H, 4H), ``b`` (4H,)."""
    layers = []
    for k, (n_in, h) in zip(jax.random.split(key, depth),
                            layer_shapes(features, depth)):
        kx, kh, kb = jax.random.split(k, 3)
        s = 1.0 / math.sqrt(h)
        layers.append({
            "wx": jax.random.uniform(kx, (n_in, 4 * h), jnp.float32, -s, s),
            "wh": jax.random.uniform(kh, (h, 4 * h), jnp.float32, -s, s),
            "b": jax.random.uniform(kb, (4 * h,), jnp.float32, -s, s),
        })
    return {"layers": tuple(layers)}


def make_params(seed: int, features: int, depth: int) -> dict:
    """The weights of a run, made on the default device in one jitted call."""
    fn = jax.jit(partial(init_params, features=features, depth=depth))
    return jax.block_until_ready(fn(seed_key(seed)))


def _forward(params: dict, xs: jnp.ndarray, precision: str) -> jnp.ndarray:
    """xs (T, B, F) float32 -> per-step squared error (T, B) float32."""
    if precision == "highest":
        dtype, dot_precision = jnp.float32, jax.lax.Precision.HIGHEST
    elif precision == "default":
        dtype, dot_precision = jnp.float32, jax.lax.Precision.DEFAULT
    elif precision == "bfloat16":
        dtype, dot_precision = jnp.bfloat16, None
    else:
        raise ValueError(f"unknown precision {precision!r}")
    ys = xs.astype(dtype)
    for layer in params["layers"]:
        wx, wh, b = (layer[k].astype(dtype) for k in ("wx", "wh", "b"))
        hidden = wh.shape[0]
        batch = xs.shape[1]

        def cell(carry, x_t, wx=wx, wh=wh, b=b):
            h, c = carry
            gates = (jnp.dot(x_t, wx, precision=dot_precision)
                     + jnp.dot(h, wh, precision=dot_precision) + b)
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h

        zeros = jnp.zeros((batch, hidden), dtype)
        _, ys = jax.lax.scan(cell, (zeros, zeros), ys)
    return jnp.mean(jnp.square(ys.astype(jnp.float32) - xs), axis=-1)


_sq_err = jax.jit(_forward, static_argnames="precision")


def _pad_len(t: int) -> int:
    """Pad a block's length to a power of two (at least 64), so a few
    programs serve every run."""
    return max(64, 1 << (int(t) - 1).bit_length())


def running_errors(params: dict, samples: list, precision: str = "highest",
                   block: int = 512) -> list:
    """Running mean error after each sample, per stream.  ``samples`` is a
    list of (n_i, F) arrays; returns a list of (n_i,) float64 arrays.  The
    LSTM is causal, so zero samples padded after a stream's last one do not
    change its earlier errors."""
    out = []
    for lo in range(0, len(samples), block):
        part = samples[lo:lo + block]
        t_pad = _pad_len(max(len(s) for s in part))
        xs = np.zeros((t_pad, block, part[0].shape[1]), np.float32)
        for j, s in enumerate(part):
            xs[:len(s), j] = s
        sq = np.asarray(_sq_err(params, jnp.asarray(xs), precision), np.float64)
        for j, s in enumerate(part):
            n = len(s)
            out.append(np.cumsum(sq[:n, j]) / np.arange(1, n + 1))
    return out


def window_scores(params: dict, windows: list, precision: str = "highest",
                  block: int = 256) -> np.ndarray:
    """Score of each window (a (T_i, F) array): float64 (n,).  Windows are
    scored in blocks, each zero-padded at the end to the longest window of
    all (one program for every block)."""
    order = sorted(range(len(windows)), key=lambda i: len(windows[i]))
    scores = np.zeros(len(windows), np.float64)
    t_pad = _pad_len(len(windows[order[-1]]))
    for lo in range(0, len(order), block):
        idx = order[lo:lo + block]
        xs = np.zeros((t_pad, block, windows[idx[0]].shape[1]), np.float32)
        for j, i in enumerate(idx):
            xs[:len(windows[i]), j] = windows[i]
        sq = np.asarray(_sq_err(params, jnp.asarray(xs), precision), np.float64)
        for j, i in enumerate(idx):
            scores[i] = sq[:len(windows[i]), j].mean()
    return scores
