"""The benchmark's plain Jamba: the forward pass every served Jamba answer is
compared with, run on the chip in blocks of streams after the window.

Nothing here imports the program.  The model is HF ``modeling_jamba.py``'s
(see ``configs/jamba2-3b.json``), over each stream's whole token sequence:
no cache, no slots, layer by layer.

* Layer ``i`` is attention iff ``i % attn_layer_period ==
  attn_layer_offset``, else a Mamba-1 mixer; every layer has a dense
  SiLU-gated MLP (``num_experts`` 1).  Pre-norm residual blocks, RMSNorm.
* Mamba: ``x, z`` from the input projection, a causal depthwise conv of
  ``mamba_d_conv`` taps with bias, SiLU, ``x_proj`` into ``(dt, B, C)``,
  each RMS-normalised, ``dt = softplus(dt_proj(dt))``, the selective scan
  run sequentially ``h_t = exp(dt A) h_{t-1} + dt B x_t``,
  ``y = C.h + D x``, gated by ``SiLU(z)``, then ``out_proj``.
* Attention: full causal softmax attention, the one KV head shared by
  every query head, no positional encoding.
* A stream's answer after token ``t`` is the running mean of the surprisal
  ``-ln p(x_s | x_<s)`` over ``s <= t``, the prediction of ``x_s`` read from
  the final-normed hidden state after ``x_{s-1}`` through the tied
  unembedding; token 0 is predicted from a zero hidden state (all-zero
  logits, surprisal ``ln V``).

Departures from ``modeling_jamba.py``: HF scores tokens 1..T only; here
token 0 gets the uniform prediction a stream admitted with no history is
told.  ``JambaRMSNorm`` casts back to its input dtype before its weight;
here every norm is float32 throughout.

``precision``:

* ``highest``: the reference.  Float32, every product at full precision.
* ``default``: the precision the configuration states for the served
  model: every matrix product's inputs rounded to bfloat16 and summed in
  float32 (the TPU's default for float32 products, written out so that it
  holds on any backend), the conv history and the KV cache rounded to
  bfloat16; norms, softmax and the SSM float32.
* ``bfloat16``: the control, the precision next below the stated one.
  ``default`` with every float32 part in bfloat16: the SSM recurrence and
  its state, each product's result, each norm's output and the residual
  stream after each block.

The weights are those ``families/jamba.py`` makes: the program's layout,
``embed.table``, ``ln_f.scale`` and ``positions[j]`` for each position of a
period of ``attn_layer_period`` layers, each leaf stacked over periods.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "default", "bfloat16")
#: streams a block of the forward takes at once
BLOCK = 16


class _Precision:
    """Where a precision rounds: ``inputs`` every product's inputs (and the
    conv history and KV cache), ``outputs`` every activation as well;
    ``ssm`` the dtype of the SSM recurrence and state."""

    def __init__(self, name: str):
        self.inputs = name != "highest"
        self.outputs = name == "bfloat16"
        self.ssm = jnp.bfloat16 if self.outputs else jnp.float32


def _round(x, on: bool):
    """``x`` as bfloat16 values where ``on``."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if on else x


def _lin(p, x, pr):
    y = _round(x, pr.inputs) @ p["w"].astype(jnp.float32)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return _round(y, pr.outputs)


def _rms(x, scale, eps, pr=None):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)
    return _round(y, pr is not None and pr.outputs)


def _mamba(p, x, cfg, pr):
    """x (B, T, D) -> (B, T, D)."""
    n, k, r = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    eps = cfg["rms_norm_eps"]
    xs, z = _lin(p["in_x"], x, pr), _lin(p["in_z"], x, pr)
    # the conv reads the current token as computed and its history as kept
    hist = jnp.pad(_round(xs, pr.inputs), ((0, 0), (k - 1, 0), (0, 0)))
    w = p["conv_w"].astype(jnp.float32)
    t = xs.shape[1]
    xc = sum(w[j] * hist[:, j:j + t] for j in range(k - 1)) + w[k - 1] * xs
    xc = _round(jax.nn.silu(xc + p["conv_b"].astype(jnp.float32)), pr.outputs)
    proj = _lin(p["x_proj"], xc, pr)
    dt = _rms(proj[..., :r], p["dt_norm"]["scale"], eps, pr)
    b = _rms(proj[..., r:r + n], p["b_norm"]["scale"], eps, pr)
    c = _rms(proj[..., r + n:], p["c_norm"]["scale"], eps, pr)
    dt = jax.nn.softplus(_lin(p["dt_proj"], dt, pr))
    ssm = pr.ssm
    a = (-jnp.exp(p["a_log"].astype(jnp.float32))).astype(ssm)

    def scan(h, inp):
        dt_t, b_t, c_t, x_t = inp
        d = dt_t.astype(ssm)[..., None]
        h = jnp.exp(d * a) * h \
            + d * b_t.astype(ssm)[:, None, :] * x_t.astype(ssm)[..., None]
        return h, jnp.sum(h.astype(jnp.float32) * c_t[:, None, :], axis=-1)

    h0 = jnp.zeros((x.shape[0], xc.shape[-1], n), ssm)
    _, y = jax.lax.scan(scan, h0, tuple(jnp.swapaxes(v, 0, 1) for v in (dt, b, c, xc)))
    y = jnp.swapaxes(y, 0, 1)
    y = _round((y + p["d_skip"].astype(jnp.float32) * xc) * jax.nn.silu(z), pr.outputs)
    return _lin(p["out"], y, pr)


def _attention(p, x, cfg, pr):
    bsz, t, _ = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // heads
    q = _lin(p["q"], x, pr).reshape(bsz, t, heads, hd)
    k = _lin(p["k"], x, pr).reshape(bsz, t, kv, hd)
    v = _lin(p["v"], x, pr).reshape(bsz, t, kv, hd)
    k = jnp.repeat(_round(k, pr.inputs), heads // kv, axis=2)
    v = jnp.repeat(_round(v, pr.inputs), heads // kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", _round(q, pr.inputs), k) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", _round(jax.nn.softmax(s, axis=-1), pr.inputs), v)
    return _lin(p["o"], _round(o, pr.outputs).reshape(bsz, t, heads * hd), pr)


def _mlp(p, x, pr):
    g, u = _lin(p["gate"], x, pr), _lin(p["up"], x, pr)
    return _lin(p["down"], _round(jax.nn.silu(g) * u, pr.outputs), pr)


@partial(jax.jit, static_argnames=("cfg", "attn", "precision"))
def _layer(stacked, q, h, cfg, attn, precision):
    """One layer, ``q``-th of its period position's stack, on h (B, T, D)."""
    cfg = dict(cfg)
    pr = _Precision(precision)
    lp = jax.tree.map(lambda a: a[q], stacked)
    eps = cfg["rms_norm_eps"]
    hn = _rms(h, lp["ln1"]["scale"], eps, pr)
    h = _round(h + (_attention(lp["mixer"], hn, cfg, pr) if attn
                    else _mamba(lp["mixer"], hn, cfg, pr)), pr.outputs)
    return _round(h + _mlp(lp["ffn"], _rms(h, lp["ln2"]["scale"], eps, pr), pr),
                  pr.outputs)


@partial(jax.jit, static_argnames=("cfg", "precision"))
def _surprisal(table, ln_f, h, tokens, cfg, precision):
    """Each token's surprisal (B, T) from the hidden states before it, the
    logits made 64 positions at a time."""
    pr = _Precision(precision)
    eps = dict(cfg)["rms_norm_eps"]
    bsz, t, d = h.shape
    # position s is predicted from the hidden state after s - 1; position 0
    # from a zero state, whose logits are all zero (ln_f(0) = 0)
    prev = jnp.pad(h[:, :-1], ((0, 0), (1, 0), (0, 0)))
    w = table.astype(jnp.float32).T

    def chunk(inp):
        hc, tc = inp                                   # (B, 64, D), (B, 64)
        logits = _round(_rms(hc, ln_f, eps, pr), pr.inputs) @ w
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(logits, axis=-1) - gold

    hs = jnp.swapaxes(prev.reshape(bsz, t // 64, 64, d), 0, 1)
    ts = jnp.swapaxes(tokens.reshape(bsz, t // 64, 64), 0, 1)
    out = jax.lax.map(chunk, (hs, ts))                 # (T/64, B, 64)
    return jnp.swapaxes(out, 0, 1).reshape(bsz, t)


def surprisals(params, tokens: np.ndarray, cfg: dict, precision: str) -> np.ndarray:
    """tokens (B, T) int -> the surprisal of each token (B, T), float64."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    key = tuple(sorted(cfg.items()))
    period = cfg["attn_layer_period"]
    with jax.default_matmul_precision("highest"):
        table = params["embed"]["table"]
        toks = jnp.asarray(tokens, jnp.int32)
        h = table[toks].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            attn = i % period == cfg["attn_layer_offset"]
            h = _layer(params["positions"][i % period], i // period, h, cfg=key,
                       attn=attn, precision=precision)
        out = _surprisal(table, params["ln_f"]["scale"], h, toks, cfg=key,
                         precision=precision)
    return np.asarray(out, np.float64)


def running_answers(params, samples: list, cfg: dict, precision: str) -> list:
    """The answer after each token of each stream: its running mean
    surprisal.  Streams run in blocks of ``BLOCK``, padded at the end to a
    length of 64 tokens times a power of two (causal: the padding changes
    nothing before it)."""
    out = [None] * len(samples)
    order = sorted(range(len(samples)), key=lambda i: len(samples[i]))
    for start in range(0, len(order), BLOCK):
        idx = order[start:start + BLOCK]
        longest = max(len(samples[i]) for i in idx)
        t = 64
        while t < longest:
            t *= 2
        toks = np.zeros((BLOCK, t), np.int32)
        for row, i in enumerate(idx):
            toks[row, :len(samples[i])] = samples[i]
        s = surprisals(params, toks, cfg, precision)
        for row, i in enumerate(idx):
            n = len(samples[i])
            out[i] = np.cumsum(s[row, :n]) / np.arange(1, n + 1)
    return out
