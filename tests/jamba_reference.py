"""A plain float32 Jamba: the forward pass the served Jamba stream is tested
against.  It imports nothing of the program.

The model is HF ``modeling_jamba.py``'s, layer by layer over a whole token
sequence: no cache, no batching across streams, no masked slots, every
matrix product at ``jax.default_matmul_precision("highest")``.

* Layer ``i`` is attention iff ``i % attn_layer_period ==
  attn_layer_offset``, else a Mamba-1 mixer; every layer has a dense
  SiLU-gated MLP (``num_experts`` 1).  Pre-norm residual blocks, RMSNorm.
* Mamba: ``x, z`` from the input projection, a causal depthwise conv of
  ``d_conv`` taps with bias, SiLU, ``x_proj`` into ``(dt, B, C)``, each
  RMS-normalised, ``dt = softplus(dt_proj(dt))``, the selective scan run
  sequentially ``h_t = exp(dt A) h_{t-1} + dt B x_t``, ``y = C.h + D x``,
  gated by ``SiLU(z)``, then ``out_proj``.
* Attention: full causal softmax attention, one KV head shared by every
  query head, no positional encoding.
* The answer after token ``t`` is the running mean of the surprisal
  ``-ln p(x_s | x_<s)`` over ``s <= t``, the prediction of ``x_s`` read
  from the final-normed hidden state after ``x_{s-1}`` through the tied
  unembedding.  Token 0 is predicted from a zero hidden state, so its
  logits are all zero and its surprisal is ``ln V``.

Departures from ``modeling_jamba.py``: HF has no prediction for token 0
(it scores tokens 1..T); here token 0 gets the uniform prediction, which is
what a stream admitted with no history is told.  ``JambaRMSNorm`` casts back
to the input dtype before its weight; here everything is float32, so the
two agree.  ``ssm_dtype`` (default float32) sets the dtype of the SSM
recurrence and state: ``bfloat16`` is the planted fault the tests use.

The weights are read from the program's layout (``models/jamba.py``):
``embed.table``, ``ln_f.scale`` and ``positions[j]``, one entry per
position of a period of ``period`` layers, each leaf stacked over periods
(``ln1``, ``ln2``, ``mixer`` and ``ffn`` with ``{"w"[, "b"]}`` linears).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _lin(p, x):
    y = x @ p["w"].astype(jnp.float32)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _mamba(p, x, cfg, ssm_dtype):
    """x (T, D) -> (T, D)."""
    n, k = cfg["mamba_d_state"], cfg["mamba_d_conv"]
    r = cfg["mamba_dt_rank"]
    xs, z = _lin(p["in_x"], x), _lin(p["in_z"], x)
    pad = jnp.concatenate([jnp.zeros((k - 1, xs.shape[1])), xs])
    xc = sum(p["conv_w"][j] * pad[j:j + xs.shape[0]] for j in range(k)) + p["conv_b"]
    xc = jax.nn.silu(xc)
    proj = _lin(p["x_proj"], xc)
    eps = cfg["rms_norm_eps"]
    dt = _rms(proj[:, :r], p["dt_norm"]["scale"], eps)
    b = _rms(proj[:, r:r + n], p["b_norm"]["scale"], eps)
    c = _rms(proj[:, r + n:], p["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(_lin(p["dt_proj"], dt))
    a = -jnp.exp(p["a_log"])

    def scan(h, inp):
        dt_t, b_t, c_t, x_t = (v.astype(ssm_dtype) for v in inp)
        h = jnp.exp(dt_t[:, None] * a.astype(ssm_dtype)) * h \
            + dt_t[:, None] * b_t[None, :] * x_t[:, None]
        return h, jnp.sum(h.astype(jnp.float32) * c_t.astype(jnp.float32), axis=-1)

    h0 = jnp.zeros((xc.shape[1], n), ssm_dtype)
    _, y = jax.lax.scan(scan, h0, (dt, b, c, xc))
    y = (y + p["d_skip"] * xc) * jax.nn.silu(z)
    return _lin(p["out"], y)


def _attention(p, x, cfg):
    t = x.shape[0]
    heads = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // heads
    q = _lin(p["q"], x).reshape(t, heads, hd)
    k = jnp.repeat(_lin(p["k"], x).reshape(t, kv, hd), heads // kv, axis=1)
    v = jnp.repeat(_lin(p["v"], x).reshape(t, kv, hd), heads // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return _lin(p["o"], o.reshape(t, heads * hd))


def _mlp(p, x):
    return _lin(p["down"], jax.nn.silu(_lin(p["gate"], x)) * _lin(p["up"], x))


def surprisals(params, tokens, cfg: dict, ssm_dtype=jnp.float32) -> np.ndarray:
    """tokens (T,) -> the surprisal of each token (T,), float64."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        eps = cfg["rms_norm_eps"]
        period = cfg["period"]
        table = params["embed"]["table"]
        tokens = jnp.asarray(tokens, jnp.int32)
        h = table[tokens]
        for i in range(cfg["num_hidden_layers"]):
            lp = jax.tree.map(lambda a, q=i // period: a[q], params["positions"][i % period])
            hn = _rms(h, lp["ln1"]["scale"], eps)
            if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]:
                h = h + _attention(lp["mixer"], hn, cfg)
            else:
                h = h + _mamba(lp["mixer"], hn, cfg, ssm_dtype)
            h = h + _mlp(lp["ffn"], _rms(h, lp["ln2"]["scale"], eps))
        logits = _rms(h, params["ln_f"]["scale"], eps) @ table.T
        # the prediction of token s comes from the hidden state after s - 1;
        # token 0's from a zero state, whose logits are all zero
        logits = jnp.concatenate([jnp.zeros((1, table.shape[0])), logits[:-1]])
        gold = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
        return np.asarray(jax.nn.logsumexp(logits, axis=-1) - gold, np.float64)


def running_surprisal(params, tokens, cfg: dict, ssm_dtype=jnp.float32) -> np.ndarray:
    """The answer after each token: the running mean surprisal (T,)."""
    s = surprisals(params, tokens, cfg, ssm_dtype)
    return np.cumsum(s) / np.arange(1, len(s) + 1)


def config_dict(cfg) -> dict:
    """The reference's view of a program ``ModelConfig`` (HF key names)."""
    return {
        "num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
        "attn_layer_period": cfg.attn_every, "attn_layer_offset": cfg.attn_offset,
        "mamba_d_state": cfg.ssm.d_state, "mamba_d_conv": cfg.ssm.d_conv,
        "mamba_dt_rank": cfg.ssm.dt_rank, "rms_norm_eps": cfg.norm_eps,
        "period": cfg.attn_every,
    }
